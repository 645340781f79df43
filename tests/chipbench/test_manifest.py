"""The manifest loader, and that a configuration, a traffic mix, a job kind,
an adapter, a layer metric and a reducer can each be added as files of their
own. The cells are read from BENCHMARK.json when this file is collected: a PR
that adds a cell edits no test. What the manifest must hold stands in
chipbench_helpers (``check_contract``, ``check_cell``, ``check_config_files``,
functions of a root), so a root with cells added is held to it too."""

import json

import pytest

from chipbench_helpers import (ACCEPTED_CELLS, ROOT, add_cell, add_toy, check_cell,
                               check_config_files, check_contract, copy_root,
                               files_of, only_appended, read, write)

from chipbench import manifest, run

CELLS = [w["name"] for w in manifest.load(ROOT)["workloads"]]


def test_manifest_has_no_problems():
    assert manifest.problems(ROOT) == []


def test_contract_keys_and_cells():
    """The accepted cells and end-to-end metrics stand unchanged at the head
    of their lists (names, configurations, traffic, chips, ``why``; bounds
    and units); whatever a later PR appended is held to the same shape."""
    bench = check_contract(ROOT)
    assert CELLS[:4] == [w["name"] for w in ACCEPTED_CELLS]
    assert [w["chips"] for w in bench["workloads"][:4]] == [1, 1, 1, 4]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_loads_its_files(cell):
    c = check_cell(ROOT, cell)
    if cell in CELLS[:4]:  # the accepted cells report what they did when accepted
        failure = cell == CELLS[3]
        assert (c.traffic["job"] == "kill_rejoin") == failure
        assert len(c.end_to_end) == (2 if failure else 3)


@pytest.mark.parametrize("edit", [
    lambda b: b["workloads"][2].update(why="another reason"),
    lambda b: b["workloads"][0].update(traffic="managed-1g", name="mistral-7b.bare"),
    lambda b: b["workloads"].insert(0, b["workloads"].pop(3)),
    lambda b: b["end_to_end"][0].update(bound=0.05),
    lambda b: b["end_to_end"][2].update(workloads=["mistral-7b.bare"]),
    lambda b: b["end_to_end"].pop(1),
], ids=["why", "traffic", "order", "bound", "hbm-not-everywhere", "metric-gone"])
def test_an_edit_to_what_was_accepted_is_seen(tmp_path, edit):
    root = copy_root(tmp_path)
    check_contract(root)
    bench = read(f"{root}/BENCHMARK.json")
    edit(bench)
    write(f"{root}/BENCHMARK.json", bench)
    with pytest.raises((AssertionError, KeyError)):
        check_contract(root)
        for w in bench["workloads"]:
            check_cell(root, w["name"])


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
        assert "adapter" not in c  # absent means llama: the files stand as accepted
    check_config_files(ROOT)


@pytest.mark.parametrize("edit", [
    lambda c: c.update(reduced=["num_hidden_layers", "vocab_size"]),
    lambda c: c["published"].update(hidden_size=8192),
    lambda c: c["published"].update(num_hidden_layers=c["num_hidden_layers"]),
], ids=["reduced-not-published", "published-not-reduced", "same-value"])
def test_a_configuration_file_that_hides_a_cut_is_seen(tmp_path, edit):
    root = copy_root(tmp_path)
    check_config_files(root)
    cfg = read(f"{root}/chipbench/configs/mistral-7b.json")
    edit(cfg)
    write(f"{root}/chipbench/configs/mistral-7b.json", cfg)
    with pytest.raises(AssertionError):
        check_config_files(root)


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
    """A later PR's cells: new configuration, traffic mix, job kind, layer
    metric and reducer, and an adapter that is not ``llama`` with its own
    reference, a configuration that names it, a bare-kind and a managed-1g
    cell: all as files beside the ones that are there."""
    root = copy_root(tmp_path)
    before = files_of(root)
    was = read(f"{root}/BENCHMARK.json")
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
    bench["configs"].append({"name": "other-model", "source": "x",
                             "reduced": ["num_hidden_layers"],
                             "file": "chipbench/configs/other-model.json", "why": "x"})
    add_cell(root, bench, "other-model.burst", "other-model", "burst",
             "mistral-7b.managed-1g")
    for m in bench["per_layer"]:  # the new cell has its own layer metric only
        if "other-model.burst" in m.get("workloads", []):
            m["workloads"].remove("other-model.burst")
    bench["per_layer"].append({"name": "burst.count", "unit": "n", "better": "higher",
                               "source": "program_counter", "layer": "new layer",
                               "moves": "tok_s_chip", "workloads": ["other-model.burst"]})
    toy = add_toy(root, bench)  # writes BENCHMARK.json
    assert manifest.problems(root) == []
    cell = manifest.Cell(root, bench, "other-model.burst")
    obs = cell.job().run(cell, 11, 1.0, True, "", "", 0.0)
    assert run.layer_values(cell, obs) == {"burst.count": 21}
    # nothing that was there was edited: every file byte for byte, and
    # BENCHMARK.json gains entries and members of ``workloads`` lists only
    now = files_of(root)
    for p, bytes_ in before.items():
        if not p.endswith("BENCHMARK.json"):
            assert now[p] == bytes_, p
    assert sorted(set(now) - set(before)) == sorted(f"{root}/chipbench/{f}" for f in (
        "adapters/toy.py", "toy_reference.py", "configs/toy-model.json",
        "configs/other-model.json", "traffic/burst.json", "jobs/echo.py",
        "reducers/counted.py", "layer_metrics/burst.count.json"))
    got = read(f"{root}/BENCHMARK.json")
    assert only_appended(was, got) and not only_appended(got, was)
    assert len(got["workloads"]) == len(was["workloads"]) + 3
    # the new cells find what is their architecture's own by the file's key
    assert "hidden_size" not in toy and toy["adapter"] == "toy"
    for name in ("toy-model.bare", "toy-model.managed-1g"):
        c = check_cell(root, name)
        assert c.adapter().__file__ == f"{root}/chipbench/adapters/toy.py"
        assert c.adapter().reference.__file__ == f"{root}/chipbench/toy_reference.py"
    for w in was["workloads"]:  # and the accepted ones still get llama
        assert manifest.Cell(root, got, w["name"]).adapter().__file__ == \
            f"{root}/chipbench/adapters/llama.py"
    check_contract(root)
    check_config_files(root)


def test_only_appended_tells_an_append_from_an_edit():
    was = manifest.load(ROOT)
    now = json.loads(json.dumps(was))
    add_cell(ROOT, now, "x.y", "mistral-7b", "bare", "mistral-7b.bare")
    now["per_layer"].append(dict(now["per_layer"][0], name="new.metric"))
    assert only_appended(was, now)
    for edit in (lambda b: b["per_layer"][3].update(moves="setup_s"),
                 lambda b: b["workloads"][1].update(chips=4),
                 lambda b: b["end_to_end"][0]["workloads"].remove("mistral-7b.managed-1g"),
                 lambda b: b.update(run_seconds=10),
                 lambda b: b["configs"][0]["reduced"].insert(0, "vocab_size")):
        bad = json.loads(json.dumps(now))
        edit(bad)
        assert not only_appended(was, bad)


def test_an_adapter_that_is_not_there_is_refused_with_the_key_named(tmp_path):
    root = copy_root(tmp_path)
    cfg = read(f"{root}/chipbench/configs/mistral-7b.json")
    cfg.update(name="lost", adapter="nowhere")
    write(f"{root}/chipbench/configs/lost.json", cfg)
    with pytest.raises(ValueError, match=r"key 'adapter'.*'nowhere'"):
        manifest.adapter_for(f"{root}/chipbench/configs/lost.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "lost", "source": "x", "reduced": [],
                             "file": "chipbench/configs/lost.json", "why": "x"})
    add_cell(root, bench, "lost.bare", "lost", "bare", "mistral-7b.bare")
    write(f"{root}/BENCHMARK.json", bench)
    assert ["lost.bare" in p and "'adapter'" in p and "nowhere" in p
            for p in manifest.problems(root)] == [True]


@pytest.mark.parametrize("key,value", [
    ("num_experts", 64), ("num_experts_per_tok", 8), ("norm_topk_prob", False),
    ("kv_lora_rank", 512), ("layer_types", ["full_attention"]), ("hidden_act", "gelu")])
def test_llama_refuses_a_key_it_would_drop_in_silence(tmp_path, key, value):
    """MoE, latent-attention and layer-mix keys handed to the default
    adapter: refused with the key named, in the loader's ``problems`` too
    (``num_experts`` used to be dropped without a word)."""
    root = copy_root(tmp_path)
    cfg = read(f"{root}/chipbench/configs/mistral-7b.json")
    llama = manifest.adapter_for(f"{root}/chipbench/configs/mistral-7b.json", cfg)
    llama.config(cfg)
    with pytest.raises(ValueError, match=f"key '{key}'"):
        llama.config({**cfg, key: value})
    write(f"{root}/chipbench/configs/mistral-7b.json", {**cfg, key: value})
    got = manifest.problems(root)
    assert len(got) == 2 and all(f"'{key}'" in p for p in got)  # both Mistral cells


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


def test_no_job_kind_or_reducer_names_an_architecture():
    """What is one architecture's own is found through the configuration's
    adapter: no job kind, reducer, launcher or shared trainer code imports a
    model, tests ``model_type`` or says ``llama`` but as the default
    adapter's name; worker.py names models/llama.py in ``llama_config`` only,
    where the default adapter finds it."""
    import glob
    import re

    files = [f"{ROOT}/chipbench/{f}" for f in ("trainer_job.py", "launch.py")]
    for d in ("jobs", "reducers"):
        files += glob.glob(f"{ROOT}/chipbench/{d}/*.py")
    assert len(files) >= 13
    for path in files:
        text = open(path).read()
        assert "model_type" not in text and "torchft_tpu.models" not in text, path
        assert not re.findall(r"llama(?!``)", text), path
    worker = open(f"{ROOT}/chipbench/worker.py").read()
    body = worker.split("def llama_config")[1].split("\nclass _Tracer")[0]
    assert worker.count("torchft_tpu.models") == body.count("torchft_tpu.models") == 1
