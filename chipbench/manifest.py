"""BENCHMARK.json and the data files it names: the only place that knows
where a cell's configuration, traffic, job kind, adapter, layer metrics and
reducers live. Everything is found by name under ``root``, so a later PR adds a cell
by adding files (chipbench/README.md) and a test can do so in a temporary
directory."""

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
DEFAULT_ADAPTER = "llama"  # a configuration file without the key ``adapter``


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(root: str, kind: str, name: str):
    """``<root>/chipbench/<kind>/<name>.py`` as a module, by file path."""
    path = os.path.join(root, "chipbench", kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def adapter_for(config_path: str, cfg: dict):
    """The adapter the configuration file names under ``adapter`` (absent:
    ``llama``): what is one architecture's own, chipbench/adapters/<name>.py.
    Found under the root the file lives in (<root>/chipbench/configs/), so a
    temporary root brings its own; a lone file elsewhere gets chipbench's."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(config_path))))
    if not os.path.isdir(os.path.join(root, "chipbench", "adapters")):
        root = ROOT
    name = cfg.get("adapter", DEFAULT_ADAPTER)
    try:
        return load_module(root, "adapters", name)
    except FileNotFoundError as e:
        raise ValueError(f"{config_path}: key 'adapter': {e}") from None


class Cell:
    """One entry of ``workloads`` with every file it names loaded."""

    def __init__(self, root: str, bench: dict, name: str) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.root, self.name, self.workload = root, name, cells[name]
        self.chips = int(self.workload["chips"])
        entry = {c["name"]: c for c in bench["configs"]}[self.workload["config"]]
        self.config_path = os.path.join(root, entry["file"])
        self.config = _json(self.config_path)
        self.traffic_name = self.workload["traffic"]
        self.traffic = _json(os.path.join(
            root, "chipbench", "traffic", self.traffic_name + ".json"))
        here = lambda m: name in m.get("workloads", [name])  # noqa: E731
        self.end_to_end = [m for m in bench["end_to_end"] if here(m)]
        self.per_layer = [m for m in bench["per_layer"] if here(m)]
        self._adapter = None

    def job(self):
        return load_module(self.root, "jobs", self.traffic["job"])

    def adapter(self):
        """What is the configuration's architecture's own, loaded once."""
        if self._adapter is None:
            self._adapter = adapter_for(self.config_path, self.config)
        return self._adapter

    def layer_metric(self, name: str) -> dict:
        return _json(os.path.join(
            self.root, "chipbench", "layer_metrics", name + ".json"))

    def reducer(self, name: str):
        return load_module(self.root, "reducers", name)


def load(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def problems(root: str = ROOT) -> "list[str]":
    """Everything wrong with the manifest that can be seen without a chip:
    missing files, names and units outside the contract's alphabet, more
    four-chip cells than a quarter, a metric no cell reports, a configuration
    its adapter cannot express."""
    bench, out = load(root), []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[key]]
        out += [f"{key}: bad name {n!r}" for n in names if not NAME.match(n)]
        out += [f"{key}: duplicate {n!r}" for n in set(names) if names.count(n) > 1]
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    if len(four) > max(1, len(bench["workloads"]) // 4):
        out.append(f"too many four-chip cells: {four}")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher") \
                or m["source"] not in SOURCES:
            out.append(f"metric {m['name']}: bad unit, better or source")
    for w in bench["workloads"]:
        try:
            cell = Cell(root, bench, w["name"])
            cell.job()
            cell.adapter().config(cell.config)
        except (OSError, KeyError, ValueError) as e:
            out.append(f"{w['name']}: {e}")
            continue
        got = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in got or len(got) < 2 or not cell.per_layer:
            out.append(f"{w['name']}: needs setup_s, one more end-to-end "
                       "metric and one per-layer metric")
        for m in cell.per_layer:
            if m["moves"] not in got:
                out.append(f"{w['name']}: {m['name']} moves {m['moves']}, "
                           "which this cell does not report")
            try:
                cell.reducer(cell.layer_metric(m["name"])["reducer"])
            except (OSError, KeyError, ValueError) as e:
                out.append(f"{w['name']}: {m['name']}: {e}")
    for m in bench["per_layer"]:
        if m["moves"] not in e2e:
            out.append(f"{m['name']} moves unknown metric {m['moves']}")
    return out
