"""CPU rehearsal of ``jamba2-3b.bare-scan``, for tests only: the
configuration at tiny widths as new files in a temporary root (nothing that
is there edited), the cell's path through ``jobs/bare.py`` up to where it
finds no TPU, and, though the benchmark holds no managed cell for it (one
period does not fit a chip under the Manager), the same configuration
through ``launch.Launch``, ``worker.py``, the ``jamba`` adapter and the one
trainer as they stand. Refused as a measurement like every CPU run."""

import os
import subprocess
import time

import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               check_contract, copy_root, files_of, only_appended,
                               read, write)

from chipbench import manifest, result  # noqa: I001

CELL, CONFIG = "jamba2-3b.bare-scan", f"{ROOT}/chipbench/configs/jamba2-3b.json"
TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=1, vocab_size=512, num_hidden_layers=4,
            attn_layer_period=4, attn_layer_offset=2, mamba_dt_rank=8)
# the catalog row of /opt/skills/guides/model-configs/architectures.jsonl
CATALOG = {
    "attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
    "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
    "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1,
    "rms_norm_eps": 1e-06, "sliding_window": None, "tie_word_embeddings": True,
    "use_mamba_kernels": True, "vocab_size": 65536}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("jamba")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = read(CONFIG)
    cfg.update(TINY, name="tiny-jamba")
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 128}
    write(f"{root}/chipbench/configs/tiny-jamba.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-jamba", "source": "x",
                             "reduced": ["num_hidden_layers"],
                             "file": "chipbench/configs/tiny-jamba.json", "why": "x"})
    add_cell(root, bench, "tiny-jamba.bare-scan", "tiny-jamba", "bare-scan", CELL)
    add_cell(root, bench, "tiny-jamba.managed-1g", "tiny-jamba", "managed-1g",
             "internlm2-1.8b.managed-1g")
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


@pytest.fixture(scope="module")
def managed(tiny_root):
    root, bench, tmp = tiny_root
    cell = manifest.Cell(root, bench, "tiny-jamba.managed-1g")
    obs = cell.job().run(cell, seed=2147485033, seconds=1.0, trace=False,
                         out_dir=str(tmp / "out"), cache_dir=str(tmp / "cache"),
                         t_start=time.monotonic())
    return cell, obs


def test_the_repos_own_manifest_holds_the_cell_as_appended_entries():
    bench = check_contract(ROOT)
    assert manifest.problems(ROOT) == []
    assert bench["workloads"][-1]["name"] == CELL and bench["configs"][-1]["name"] == "jamba2-3b"
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "jamba" and c.traffic["job"] == "bare"
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        "model.step_device_s", "model.mfu", "kernel.splash_s", "kernel.splash_roofline",
        "kernel.sscan_s", "kernel.sscan_roofline"}
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "kernel.sscan_s", "kernel.sscan_roofline"]
    for m in bench["per_layer"][-2:]:
        assert m["workloads"] == [CELL] and m["moves"] == "bare_tok_s_chip"
        assert m["layer"] == "kernels (ops/attention.py)"  # the layer's name as it stands
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])


def test_the_configuration_is_the_catalogs_row_but_for_its_depth():
    cfg = read(CONFIG)
    differ = sorted(k for k, v in CATALOG.items() if cfg.get(k, "absent") != v)
    assert differ == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 28} and cfg["num_hidden_layers"] == 14
    adapter = manifest.adapter_for(CONFIG, cfg)
    pc = adapter.config(cfg)
    assert pc.layers_block_type == ["mamba"] * 7 + ["attention"] + ["mamba"] * 6
    assert pc.tie_word_embeddings and pc.loss_chunk == cfg["recipe"]["loss_chunk"] == 2048
    assert (cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]) == (1, 8192)
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 6


@pytest.mark.parametrize("key,value", [
    ("num_experts", 16), ("num_experts_per_tok", 2), ("sliding_window", 4096),
    ("hidden_act", "gelu"), ("mamba_proj_bias", True), ("rope_theta", 10000.0)])
def test_the_adapter_refuses_what_the_hybrid_cannot_express(key, value):
    cfg = read(CONFIG)
    with pytest.raises(ValueError, match=key):
        manifest.adapter_for(CONFIG, cfg).config({**cfg, key: value})


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    assert adapter.num_params(cfg) == 1_598_556_096 == adapter.config(cfg).num_params()
    assert adapter.num_params({**cfg, "num_hidden_layers": 28}) == 3_029_337_472
    assert adapter.layers_with(cfg, "selective_scan") == 13
    assert adapter.layers_with(cfg, "attention") == 1
    # 6 FLOP a non-embedding parameter and token, the head three times, the
    # one attention layer's causal products and the scans' own operations
    per_token = adapter.train_flops_per_token(cfg, 8192)
    matrices = 6 * (1_598_556_096 - 65536 * 2560) + 6 * 65536 * 2560
    assert matrices < per_token < 1.03 * matrices
    scans = 3 * 13 * adapter.SCAN_OPS * 5120 * 16
    assert 0.002 < scans / per_token < 0.004
    fwd = adapter.KERNEL_COSTS["selective_scan"](cfg, 1, 8192, "fwd")
    bwd = adapter.KERNEL_COSTS["selective_scan"](cfg, 1, 8192, "bwd")
    assert fwd["flops"] == 9 * 8192 * 5120 * 16
    assert fwd["bytes"] == 4 * 2 * 8192 * 5120 + 2 * 4 * 8192 * 16 + 4 * (5120 * 16 + 5120)
    assert bwd["bytes"] == 2 * fwd["bytes"]  # read again, and one cotangent an operand
    assert fwd["bytes"] / 819e9 > fwd["flops"] / 197e12  # the HBM bound, on a v5e
    att = adapter.KERNEL_COSTS["attention"](cfg, 1, 8192, "fwd")
    assert att["flops"] == 2 * 2 * (20 * 8192 * 8193 / 2) * 128
    with pytest.raises(KeyError):
        adapter.KERNEL_COSTS["selective_scan"](cfg, 1, 8192, "drhs")


def test_the_new_metrics_read_the_kernels_by_name_and_nothing_from_a_parent(tiny_root):
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-jamba.bare-scan")
    fake = {"trace": {"ops": {"selective_scan_fwd": 1.0, "selective_scan_fwd.3": 0.5,
                              "selective_scan_bwd.19": 2.5, "fusion.selective": 9.0,
                              "splash_mha_fwd_residuals": 0.25}, "chips_traced": 1},
            "steps_in_window": 2, "device": {"kind": "TPU v5 lite"}}
    spec = cell.layer_metric("kernel.sscan_s")
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == 2.0
    spec = cell.layer_metric("kernel.sscan_roofline")
    cfg, r = cell.config, cell.config["recipe"]
    cost = cell.adapter().KERNEL_COSTS["selective_scan"]
    floor = sum(calls * 3 * cost(cfg, r["batch_size"], r["seq_len"], p)["bytes"] / 819e9
                for p, calls in spec["args"]["roofline"]["calls_per_layer"].items())
    assert spec["args"]["roofline"]["calls_per_layer"] == {"fwd": 2, "bwd": 1}
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == \
        pytest.approx(100 * floor / 2.0, rel=1e-9)
    # a program without the kernels (the parent): nothing to read, no raise
    old = {**fake, "trace": {"ops": {"fusion.1": 1.0}, "chips_traced": 1}}
    for name in ("kernel.sscan_s", "kernel.sscan_roofline"):
        spec = cell.layer_metric(name)
        assert cell.reducer(spec["reducer"]).reduce(old, cell, **spec["args"]) is None
        assert cell.reducer(spec["reducer"]).reduce({}, cell, **spec["args"]) is None
    spec = cell.layer_metric("kernel.splash_roofline")  # one layer of four has attention
    one = cell.adapter().KERNEL_COSTS["attention"]
    floor = sum(calls * max(one(cfg, 1, 128, p)["flops"] / 197e12,
                            one(cfg, 1, 128, p)["bytes"] / 819e9)
                for p, calls in spec["args"]["roofline"]["calls_per_layer"].items())
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == \
        pytest.approx(100 * floor / 0.125, rel=1e-9)


def test_the_bare_cell_stops_where_it_finds_no_tpu(tiny_root, tmp_path):
    """Its reference is a child that gives no CPU answers: the job ends
    there, with the child's exit, before this process would touch JAX."""
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-jamba.bare-scan")
    assert cell.traffic["job"] == "bare"
    assert cell.job().check_sample_of(cell, cell.adapter())["grad_leaves"] == [
        "embed", "layers.00_mamba.x_proj", "layers.00_mamba.w_down"]
    with pytest.raises(subprocess.CalledProcessError):
        cell.job().run(cell, seed=2147485001, seconds=1.0, trace=False,
                       out_dir=str(tmp_path), cache_dir=str(tmp_path / "cache"),
                       t_start=time.monotonic())
    assert not [f for f in os.listdir(tmp_path / "cache") if f.startswith("reference_")]


def test_the_configuration_runs_through_the_one_trainer_under_the_manager(managed):
    cell, obs = managed
    assert obs["e2e"]["tok_s_chip"] > 0 and obs["failed"] == 0
    summary = obs["summaries"][0][-1]
    assert summary["config"] == "tiny-jamba"
    steps = cell.traffic["warmup_steps"] + cell.traffic["min_steps"]
    assert summary["committed"] == steps and summary["discarded"] == 0
    stats = summary["model_stats"]
    assert sorted(stats) == ["ssm_dt_max", "ssm_y_absmax"]
    assert all(len(v) == steps and all(x > 0 for x in v) for v in stats.values())
    assert not any("first loss" in b for b in obs["notes"]["bad"])
    # float32 leaves (A_log, D) beside the bf16 ones: a bucket of their own
    assert summary["timings"]["allreduce_buckets"] >= 2
    with pytest.raises(RuntimeError, match="no result"):
        result.build(cell, obs, obs["e2e"], trace=False)
