"""What the traced workers left under the trace directory, per process: the
device trace, the worker's stamps of the trainer's loop and the Manager's
span ring, all put on the trace's clock (xplane.py)."""

import glob
import json
import os

from chipbench import xplane


def _json(path: str):
    with open(path) as f:
        return json.load(f)


def collect(trace_dir: str) -> "list[dict]":
    """One entry per worker that stopped its trace: {"replica", "pid",
    "spans": [(name, t0_ns, t1_ns, step)], "window": (t0, t1), "trace"}."""
    out = []
    for host in sorted(glob.glob(os.path.join(trace_dir, "*.host.json"))):
        meta = _json(host)
        tag = os.path.basename(host)[: -len(".host.json")]
        path = xplane.find(os.path.join(trace_dir, tag))
        if path is None:
            continue
        trace = xplane.read(path)
        if trace["anchor_ns"] is None:
            raise ValueError(f"{path}: no {xplane.ANCHOR} annotation")
        off = trace["anchor_ns"] - meta["anchor_epoch_ns"]
        spans = [(n, a + off, b + off, None) for n, a, b in meta["spans"]]
        ring = os.path.join(trace_dir, tag + ".spans.json")
        if os.path.exists(ring):
            for s in _json(ring)["spans"]:
                a = s["ts_us"] * 1000 + off
                spans.append((f"manager.{s['cat']}.{s['name']}", a,
                              a + s["dur_us"] * 1000, s.get("step")))
        out.append({"replica": meta["replica"], "pid": meta["pid"],
                    "spans": spans, "trace": trace,
                    "window": (trace["anchor_ns"],  # written as tracing starts
                               meta["stop_epoch_ns"] + off)})
    return out


def reduce(procs: "list[dict]") -> dict:
    """All traced processes -> the merged device reduction."""
    return xplane.merge([
        xplane.reduce(p["trace"], [s[:3] for s in p["spans"]], p["window"])
        for p in procs])
