"""CPU rehearsal of ``ouro-2.6b.bare-loop-4k`` and ``ouro-2.6b.managed-1g``,
for tests only: the configuration at tiny widths as new files in a temporary
root (nothing that is there edited), the bare cell's path through
``jobs/bare.py`` up to where it finds no TPU, the managed cell through
``launch.Launch``, ``worker.py``, the ``ouro`` adapter and the one trainer as
they stand, and the three new per-layer metrics on made-up observations.
Refused as a measurement like every CPU run."""

import json
import os
import re
import subprocess
import time

import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               check_contract, copy_root, files_of, only_appended,
                               read, write)

from chipbench import manifest, result  # noqa: I001

BARE, MANAGED = "ouro-2.6b.bare-loop-4k", "ouro-2.6b.managed-1g"
CONFIG = f"{ROOT}/chipbench/configs/ouro-2.6b.json"
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, vocab_size=512, num_hidden_layers=3,
            layer_types=["full_attention"] * 3)
LOOP = ("loop.pass_s", "loop.exit_s", "loop.exit_step_mean")


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as f:
        return next(r for r in map(json.loads, f) if r["name"] == "Ouro-2.6B")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ouro")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = read(CONFIG)
    cfg.update(TINY, name="tiny-ouro")
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 64, "loss_chunk": 16}
    write(f"{root}/chipbench/configs/tiny-ouro.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-ouro", "source": "x",
                             "reduced": ["num_hidden_layers", "layer_types"],
                             "file": "chipbench/configs/tiny-ouro.json", "why": "x"})
    add_cell(root, bench, "tiny-ouro.bare-loop-4k", "tiny-ouro", "bare-loop-4k", BARE)
    add_cell(root, bench, "tiny-ouro.managed-1g", "tiny-ouro", "managed-1g", MANAGED)
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


@pytest.fixture(scope="module")
def managed(tiny_root):
    root, bench, tmp = tiny_root
    cell = manifest.Cell(root, bench, "tiny-ouro.managed-1g")
    obs = cell.job().run(cell, seed=2147485049, seconds=1.0, trace=False,
                         out_dir=str(tmp / "out"), cache_dir=str(tmp / "cache"),
                         t_start=time.monotonic())
    return cell, obs


def test_the_repos_own_manifest_holds_both_cells_as_appended_entries():
    """By name, not by place: a later PR appends after these."""
    bench = check_contract(ROOT)
    assert manifest.problems(ROOT) == []
    check_config_files(ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    entry = {c["name"]: c for c in bench["configs"]}["ouro-2.6b"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    bare, man = check_cell(ROOT, BARE), check_cell(ROOT, MANAGED)
    assert cells[BARE]["traffic"] == "bare-loop-4k" and cells[MANAGED]["traffic"] == "managed-1g"
    assert bare.chips == man.chips == 1 and bare.config["adapter"] == "ouro"
    assert bare.traffic["job"] == "bare" and man.traffic["job"] == "managed"
    assert {m["name"] for m in bare.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in man.end_to_end} == {"tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in bare.per_layer} == {
        "model.step_device_s", "model.mfu", "kernel.flash_s", "kernel.flash_roofline",
        "loop.pass_s", "loop.exit_s"}
    # every metric the dense managed cell reports, with the flash kernel's
    # for the splash kernel's (the dispatcher gives multi-head attention
    # flash), but `allreduce.d2h_concurrency` (its test pins the metric's
    # cells by hand), and the gate's counter
    mistral = manifest.Cell(ROOT, bench, "mistral-7b.managed-1g")
    assert {m["name"] for m in man.per_layer} == \
        {m["name"] for m in mistral.per_layer
         if "splash" not in m["name"] and m["name"] != "allreduce.d2h_concurrency"} \
        | {"kernel.flash_1g_roofline", "loop.exit_step_mean"}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, cell, moves in zip(LOOP, (BARE, BARE, MANAGED),
                                 ("bare_tok_s_chip", "bare_tok_s_chip", "tok_s_chip")):
        assert by_name[name]["workloads"] == [cell] and by_name[name]["moves"] == moves
        assert by_name[name]["layer"] == "looped stack and exits (models/ouro.py)"
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


def test_the_configuration_is_the_catalogs_row_but_for_its_depth():
    cfg, row = read(CONFIG), _catalog()
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert cfg["source"] == row["source_url"]
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    depth = cfg["num_hidden_layers"]
    assert depth in (8, 12, 16) and cfg["layer_types"] == row["config"]["layer_types"][:depth]
    assert cfg["total_ut_steps"] == 4 and cfg["early_exit_threshold"] == 1
    pc = manifest.adapter_for(CONFIG, cfg).config(cfg)
    assert (pc.total_ut_steps, pc.exit_beta, pc.n_layers) == (4, 0.05, depth)
    assert pc.loss_chunk == cfg["recipe"]["loss_chunk"] and 4096 % pc.loss_chunk == 0
    assert (cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]) == (2, 4096)
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 5
    said = " ".join(cfg["assumed"])
    assert all(word in said for word in ("bias", "inside the loop", "beta", "4,096",
                                         "initialisation"))


def test_the_new_metrics_read_scopes_and_the_gates_counter(tiny_root):
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-ouro.bare-loop-4k")
    fake = {"trace": {"ops": {"fusion.1": 1.0, "fusion.2": 0.5, "fusion.3": 0.25,
                              "splash_mha_fwd_residuals": 2.0, "fusion.9": 8.0},
                      "chips_traced": 1},
            "steps_in_window": 2, "device": {"kind": "TPU v5 lite"},
            # what the reducer makes of the compiled step, handed over ready
            "scopes": {"fusion.1": "jit(step)/jit(main)/while/body/loop/pass/while/body/dot",
                       "fusion.2": "jit(step)/jit(main)/transpose(jvp(loop/pass))/while/mul",
                       "fusion.3": "jit(step)/jit(main)/while/body/loop/exit/rsqrt",
                       "fusion.9": "jit(step)/jit(main)/add"}}
    spec = cell.layer_metric("loop.pass_s")
    assert spec["reducer"] == "device_scope_step"
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == 1.75
    spec = cell.layer_metric("loop.exit_s")
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == 0.125
    # a program without the scopes: nothing to read, no raise; an untraced run too
    old = {**fake, "trace": {"ops": {"fusion.1": 1.0}, "chips_traced": 1},
           "scopes": {"fusion.1": "jit(step)/jit(main)/add"}}
    for name in LOOP[:2]:
        spec = cell.layer_metric(name)
        assert cell.reducer(spec["reducer"]).reduce(old, cell, **spec["args"]) is None
        assert cell.reducer(spec["reducer"]).reduce({}, cell, **spec["args"]) is None
    spec = cell.layer_metric("loop.exit_step_mean")
    obs = {"summaries": {0: [{"model_stats": {"loop_exit_step_mean": [2.4, 2.6, 2.5]}}]}}
    assert cell.reducer(spec["reducer"]).reduce(obs, cell, **spec["args"]) == 2.5
    assert cell.reducer(spec["reducer"]).reduce(
        {"summaries": {0: [{"model_stats": {}}]}}, cell, **spec["args"]) is None
    # 64 calls of each kernel pass a step at the published depth, not 16
    full = manifest.Cell(ROOT, manifest.load(ROOT), BARE)
    assert full.adapter().layers_with(full.config, "attention") \
        == 4 * full.config["num_hidden_layers"]


def test_the_scopes_are_read_from_the_step_the_bare_job_builds(tiny_root):
    """``device_scope_step`` on a run whose job handed out no scopes: it
    compiles the cell's fused step (here for the CPU, at the tiny size) and
    finds every traced instruction's scope path in its text, once a run."""
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-ouro.bare-loop-4k")
    reducer = cell.reducer("device_scope_step")
    text = reducer._step_text(cell)
    text_names = manifest.load_module(root, "jobs", "bare_routed").scopes_of(
        text, re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", text, re.M))
    passes = [n for n, s in text_names.items() if "loop/pass" in s]
    exits = [n for n, s in text_names.items() if "loop/exit" in s]
    assert passes and exits
    obs = {"trace": {"ops": {passes[0]: 3.0, exits[0]: 1.0, "no.such.op": 5.0},
                     "chips_traced": 1}, "steps_in_window": 2}
    spec = cell.layer_metric("loop.pass_s")
    assert reducer.reduce(obs, cell, **spec["args"]) == 1.5
    assert set(obs["scopes"]) == {passes[0], exits[0]}  # kept for the cell's other metrics
    assert reducer.reduce(obs, cell, **cell.layer_metric("loop.exit_s")["args"]) == 0.5


def test_the_bare_cell_stops_where_it_finds_no_tpu(tiny_root, tmp_path):
    """Its reference is a child that gives no CPU answers: the job ends
    there, with the child's exit, before this process would touch JAX."""
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-ouro.bare-loop-4k")
    assert cell.job().check_sample_of(cell, cell.adapter())["grad_leaves"] == [
        "embed", "lm_head", "final_norm", "exit_gate.w", "layers.wq",
        "layers.attn_post_norm", "layers.w_down"]
    with pytest.raises(subprocess.CalledProcessError):
        cell.job().run(cell, seed=2147485001, seconds=1.0, trace=False,
                       out_dir=str(tmp_path), cache_dir=str(tmp_path / "cache"),
                       t_start=time.monotonic())
    assert not [f for f in os.listdir(tmp_path / "cache") if f.startswith("reference_")]


def test_the_configuration_runs_through_the_one_trainer_under_the_manager(managed):
    cell, obs = managed
    assert obs["e2e"]["tok_s_chip"] > 0 and obs["failed"] == 0
    summary = obs["summaries"][0][-1]
    assert summary["config"] == "tiny-ouro"
    steps = cell.traffic["warmup_steps"] + cell.traffic["min_steps"]
    assert summary["committed"] == steps and summary["discarded"] == 0
    stats = summary["model_stats"]
    assert sorted(stats) == ["loop_ce_1", "loop_ce_2", "loop_ce_3", "loop_ce_4",
                             "loop_exit_entropy", "loop_exit_step_mean", "loop_p_last"]
    assert all(len(v) == steps for v in stats.values())
    assert all(2.0 < x < 3.0 for x in stats["loop_exit_step_mean"])
    assert not any("first loss" in b for b in obs["notes"]["bad"])
    # the staged chain: the head's part, then the one segment of three tiny
    # layers with the embedding and the norm between the passes
    assert summary["timings"]["allreduce_ops"] == 2
    spec = cell.layer_metric("loop.exit_step_mean")
    got = cell.reducer(spec["reducer"]).reduce(obs, cell, **spec["args"])
    assert 2.0 < got < 3.0
    with pytest.raises(RuntimeError, match="no result"):
        result.build(cell, obs, obs["e2e"], trace=False)
