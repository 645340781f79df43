"""The manifest loader, and that a configuration, a traffic mix, a job kind,
a layer metric and a reducer can each be added as files of their own."""

import re

import pytest

from chipbench_helpers import ROOT, add_cell, copy_root, read, write

from chipbench import manifest, run

CELLS = ["mistral-7b.bare", "mistral-7b.managed-1g",
         "internlm2-1.8b.managed-1g", "internlm2-1.8b.kill-rejoin-4g"]


def test_manifest_has_no_problems():
    assert manifest.problems(ROOT) == []


def test_contract_keys_and_cells():
    bench = manifest.load(ROOT)
    assert sorted(bench) == sorted(["command", "paths", "run_seconds", "configs",
                                    "workloads", "end_to_end", "per_layer"])
    assert [w["name"] for w in bench["workloads"]] == CELLS
    assert [w["chips"] for w in bench["workloads"]] == [1, 1, 1, 4]
    assert sorted(m["name"] for m in bench["end_to_end"]) == [
        "bare_tok_s_chip", "peak_hbm_gib", "setup_s", "tok_s_chip"]
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    # the bare cell repeats to 0.001%: its own metric, so that the managed
    # cells' run-to-run noise does not set the bound of the one cell built to
    # show model, remat and kernel work
    assert bounds["bare_tok_s_chip"] == 0.01 and bounds["tok_s_chip"] == 0.1
    assert 1 <= bench["run_seconds"] <= 51
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
        assert sorted(set(m) - {"workloads"}) == ["better", "bound", "name", "source", "unit"]
    for m in bench["per_layer"]:
        assert sorted(set(m) - {"workloads"}) == [
            "better", "layer", "moves", "name", "source", "unit"]
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_its_files(cell):
    c = manifest.Cell(ROOT, manifest.load(ROOT), cell)
    assert callable(c.job().run)
    names = {m["name"] for m in c.end_to_end}
    # the failure cell has no deciding time but set-up (ISSUE 23 rule 3):
    # its recovery phases, rejoin.work_s among them, are per-layer
    failure = cell.endswith("kill-rejoin-4g")
    assert "setup_s" in names and "peak_hbm_gib" in names
    assert len(names) == (2 if failure else 3)
    assert ("rejoin.work_s" in {m["name"] for m in c.per_layer}) == failure
    for m in c.per_layer:
        spec = c.layer_metric(m["name"])
        assert callable(c.reducer(spec["reducer"]).reduce)
        assert m["moves"] in names
        assert re.fullmatch(r"[A-Za-z0-9_/%.\-]{1,16}", m["unit"])


def test_config_files_keep_published_widths():
    m = read(f"{ROOT}/chipbench/configs/mistral-7b.json")
    assert (m["hidden_size"], m["intermediate_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["vocab_size"]) == (
        4096, 14336, 32, 8, 128, 32768)
    i = read(f"{ROOT}/chipbench/configs/internlm2-1.8b.json")
    assert (i["hidden_size"], i["intermediate_size"], i["num_attention_heads"],
            i["num_key_value_heads"], i["head_dim"], i["vocab_size"]) == (
        2048, 8192, 16, 8, 128, 92544)
    for c in (m, i):
        assert c["reduced"] == ["num_hidden_layers"]
        assert c["num_hidden_layers"] < c["published"]["num_hidden_layers"]


def test_problems_are_found(tmp_path):
    root = copy_root(tmp_path)
    bench = read(f"{root}/BENCHMARK.json")
    bench["workloads"][0]["chips"] = 4          # two four-chip cells in four
    bench["per_layer"][0]["unit"] = "tokens per second"
    bench["workloads"][1]["traffic"] = "nowhere"
    write(f"{root}/BENCHMARK.json", bench)
    got = "\n".join(manifest.problems(root))
    assert "too many four-chip cells" in got
    assert "bad unit" in got and "nowhere" in got


def test_one_of_each_can_be_added_as_new_files(tmp_path):
    """A later PR's cell: new configuration, traffic mix, job kind, layer
    metric and reducer, all as files beside the ones that are there."""
    root = copy_root(tmp_path)
    before = {p: open(p, "rb").read() for p in _files(root)}
    cfg = read(f"{root}/chipbench/configs/mistral-7b.json")
    cfg["name"] = "other-model"
    write(f"{root}/chipbench/configs/other-model.json", cfg)
    write(f"{root}/chipbench/traffic/burst.json", {"job": "echo", "rate": 7})
    with open(f"{root}/chipbench/jobs/echo.py", "w") as f:
        f.write("def run(cell, seed, seconds, trace, out_dir, cache_dir, t_start):\n"
                "    return {'device': {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1},\n"
                "            'memory_peak_bytes': 5, 'correct': True, 'attempted': seed,\n"
                "            'failed': 0, 'e2e': {'setup_s': 1.0, 'peak_hbm_gib': 2.0,\n"
                "            'tok_s_chip': 3.0}, 'counted': cell.traffic['rate'],\n"
                "            'trace': {'busy_s': 1.0, 'window_s': 2.0, 'device_ops': [],\n"
                "                      'idle_gaps': []}}\n")
    with open(f"{root}/chipbench/reducers/counted.py", "w") as f:
        f.write("def reduce(obs, cell, times):\n    return obs['counted'] * times\n")
    write(f"{root}/chipbench/layer_metrics/burst.count.json", {
        "reducer": "counted", "args": {"times": 3}, "what": "test"})
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "other-model", "source": "x", "reduced": [],
                             "file": "chipbench/configs/other-model.json", "why": "x"})
    add_cell(root, bench, "other-model.burst", "other-model", "burst",
             "mistral-7b.managed-1g")
    for m in bench["per_layer"]:  # the new cell has its own layer metric only
        if "other-model.burst" in m.get("workloads", []):
            m["workloads"].remove("other-model.burst")
    bench["per_layer"].append({"name": "burst.count", "unit": "n", "better": "higher",
                               "source": "program_counter", "layer": "new layer",
                               "moves": "tok_s_chip", "workloads": ["other-model.burst"]})
    write(f"{root}/BENCHMARK.json", bench)
    assert manifest.problems(root) == []
    cell = manifest.Cell(root, bench, "other-model.burst")
    obs = cell.job().run(cell, 11, 1.0, True, "", "", 0.0)
    assert run.layer_values(cell, obs) == {"burst.count": 21}
    # nothing that was there was edited (BENCHMARK.json gains entries only)
    for p, was in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert open(p, "rb").read() == was, p


def test_a_layer_metric_file_holds_only_what_BENCHMARK_json_does_not():
    """Layer, unit, better, moves, source and the cells that report a metric
    are BENCHMARK.json's; the file says how to read it."""
    import os

    names = {m["name"] for m in manifest.load(ROOT)["per_layer"]}
    d = f"{ROOT}/chipbench/layer_metrics"
    assert {f[:-5] for f in os.listdir(d)} == names
    for n in names:
        assert sorted(read(f"{d}/{n}.json")) == ["args", "reducer", "what"], n


def test_no_traffic_file_knows_a_configuration():
    """A traffic mix pairs with any configuration: nothing in it is keyed by
    a configuration's name (a first-run guess of the step time was)."""
    import os

    bench = manifest.load(ROOT)
    d = f"{ROOT}/chipbench/traffic"
    for f in os.listdir(d):
        text = open(f"{d}/{f}").read()
        for c in bench["configs"]:
            assert c["name"] not in text, (f, c["name"])


def _files(root):
    import os

    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]
