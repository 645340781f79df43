"""CPU rehearsal of ``lfm2-8b-a1b.bare-routed-8k``, for tests only: the
configuration at tiny widths as new files in a temporary root (nothing that
is there edited), the cell's path through ``jobs/bare_routed.py`` up to
where it finds no TPU, the adapter's counts against the program's and
against FLOPs counted from the compiled forward pass, and, though the
benchmark holds no managed cell for it (one period does not fit a chip under
the Manager), the same configuration through ``launch.Launch``,
``worker.py``, the ``lfm2`` adapter and the one trainer as they stand.
Refused as a measurement like every CPU run."""

import os
import subprocess
import time

import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               copy_root, files_of, only_appended, read, write)

from chipbench import manifest, result  # noqa: I001

CELL, CONFIG = "lfm2-8b-a1b.bare-routed-8k", f"{ROOT}/chipbench/configs/lfm2-8b-a1b.json"
TINY = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
            num_experts=16, num_experts_per_tok=4)
# the catalog row of /opt/skills/guides/model-configs/architectures.jsonl
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv", "full_attention", "conv",
                    "conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
STANDING = ["model.step_device_s", "model.mfu", "kernel.splash_s", "kernel.splash_roofline",
            "kernel.gmm_s", "kernel.gmm_roofline", "moe.block_s", "moe.route_s"]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("lfm2")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = read(CONFIG)
    cfg.update(TINY, name="tiny-lfm2")
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 128}
    write(f"{root}/chipbench/configs/tiny-lfm2.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-lfm2", "source": "x",
                             "reduced": ["num_hidden_layers", "num_dense_layers",
                                         "layer_types"],
                             "file": "chipbench/configs/tiny-lfm2.json", "why": "x"})
    add_cell(root, bench, "tiny-lfm2.bare-routed-8k", "tiny-lfm2", "bare-routed-8k", CELL)
    add_cell(root, bench, "tiny-lfm2.managed-1g", "tiny-lfm2", "managed-1g",
             "internlm2-1.8b.managed-1g")
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


@pytest.fixture(scope="module")
def managed(tiny_root):
    root, bench, tmp = tiny_root
    cell = manifest.Cell(root, bench, "tiny-lfm2.managed-1g")
    obs = cell.job().run(cell, seed=2147485035, seconds=1.0, trace=False,
                         out_dir=str(tmp / "out"), cache_dir=str(tmp / "cache"),
                         t_start=time.monotonic())
    return cell, obs


def test_the_repos_own_manifest_holds_the_cell_as_appended_entries():
    """By name and by place after what stood at PR 34 (seven cells, four
    configurations, 66 per-layer metrics), not by "last": a later PR appends
    behind these and this test still holds."""
    bench = manifest.load(ROOT)
    assert manifest.problems(ROOT) == []
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 7
    assert [c["name"] for c in bench["configs"]].index("lfm2-8b-a1b") == 4
    assert [w["name"] for w in bench["workloads"][:8] if w["chips"] == 4] == [
        "internlm2-1.8b.kill-rejoin-4g"]
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "lfm2" and c.traffic["job"] == "bare_routed"
    assert (c.workload["config"], c.workload["traffic"]) == ("lfm2-8b-a1b", "bare-routed-8k")
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in c.per_layer} >= set(STANDING) | {"conv.mixer_s", "attn.mixer_s"}
    # the two new metrics behind the 66 that stood, the standing ones with
    # the cell behind the cells that stood
    assert [m["name"] for m in bench["per_layer"][66:68]] == ["conv.mixer_s", "attn.mixer_s"]
    for m in bench["per_layer"][66:68]:
        assert m["workloads"][0] == CELL and m["moves"] == "bare_tok_s_chip"
        assert m["source"] == "device_trace" and m["unit"] == "s"
        assert m["layer"] in {x["layer"] for x in bench["per_layer"][:66]}
    stood = {w["name"] for w in bench["workloads"][:7]}
    for m in bench["per_layer"][:66]:
        assert (CELL in m["workloads"]) == (m["name"] in STANDING), m["name"]
        if m["name"] in STANDING:
            at = m["workloads"].index(CELL)
            assert set(m["workloads"][:at]) <= stood and at >= 1
    assert all(len(e["why"]) <= 200 for e in bench["workloads"][:8] + bench["configs"][:5])
    assert bench["run_seconds"] == 48


def test_the_configuration_is_the_catalogs_row_but_for_its_cut():
    cfg = read(CONFIG)
    differ = sorted(k for k, v in CATALOG.items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == [
        "layer_types", "num_dense_layers", "num_hidden_layers"]
    assert cfg["published"] == {k: CATALOG[k] for k in cfg["reduced"]}
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    # published layers 1 to 5: one leading dense layer, one whole period
    assert cfg["layer_types"] == CATALOG["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    adapter = manifest.adapter_for(CONFIG, cfg)
    pc = adapter.config(cfg)
    assert [k for k in pc.kinds()] == [("conv", "dense"), ("attn", "moe")] + [("conv", "moe")] * 3
    assert pc.head_dim == 64 and pc.loss_chunk == cfg["recipe"]["loss_chunk"] == 2048
    assert (pc.num_experts, pc.top_k, pc.router_score, pc.gate_eps) == (32, 4, "sigmoid", 1e-6)
    assert (cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]) == (1, 8192)
    assert cfg["recipe"]["attention"] == "splash" and cfg["recipe"]["remat"] == "full"
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 8
    assert "1,665,448,192" in cfg["cut"]


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("sliding_window", 4096), ("num_shared_experts", 1),
    ("tie_word_embeddings", False), ("layer_types", ["conv", "linear", "conv", "conv", "conv"]),
    ("num_dense_layers", 7), ("routed_scaling_factor", 2.5), ("use_expert_bias", False)])
def test_the_adapter_refuses_what_the_kind_cannot_express(key, value):
    cfg = read(CONFIG)
    with pytest.raises(ValueError, match=key):
        manifest.adapter_for(CONFIG, cfg).config({**cfg, key: value})


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    assert adapter.num_params(cfg) == 1_665_448_192 == adapter.config(cfg).num_params()
    whole = {**cfg, **cfg["published"]}
    assert adapter.num_params(whole) == 8_339_930_560 == adapter.config(whole).num_params()
    assert adapter.layers_with(cfg, "attention") == 1
    assert adapter.layers_with(cfg, "grouped_matmul") == 4
    assert adapter.layers_with(whole, "attention") == 6
    # ISSUE 35's count, MFLOP a token forward at 8k: dense layer 122, the
    # attention expert layer 143, a convolution expert layer 122, head 268
    fwd = adapter.forward_flops_per_token(cfg, 8192)
    assert fwd == pytest.approx(900e6, rel=0.01)
    head = 2 * 2048 * 65536
    assert head / fwd == pytest.approx(0.30, abs=0.01)  # 8% at 24 layers
    assert head / adapter.forward_flops_per_token(whole, 8192) == pytest.approx(0.08, abs=0.01)
    assert adapter.train_flops_per_token(cfg, 8192) == 3 * fwd
    cost = adapter.KERNEL_COSTS["grouped_matmul"](cfg, 1, 8192, "fwd")
    assert cost["flops"] == 2 * 32768 * 2048 * 1792  # 1,024 rows an expert
    assert cost == adapter.KERNEL_COSTS["grouped_matmul"](cfg, 1, 8192, "drhs")
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9  # compute-bound on v5e
    att = adapter.KERNEL_COSTS["attention"](cfg, 1, 8192, "fwd")
    assert att["flops"] == 2 * 2 * (32 * 8192 * 8193 / 2) * 64
    with pytest.raises(KeyError):
        adapter.KERNEL_COSTS["grouped_matmul"](cfg, 1, 8192, "bwd")


def test_the_flops_are_what_the_compiled_forward_pass_counts(tiny_root):
    """XLA's own count of the tiny configuration's forward pass (attention
    as the XLA path computes it: every key, not the causal half; the grouped
    products as the interpreted kernel computes them, tile by tile) is the
    adapter's count with full attention, and a few percent of element-wise
    operations (norms, gates, SiLU, sigmoid, softmax) more."""
    import jax
    import jax.numpy as jnp

    root, _, _ = tiny_root
    cfg = read(f"{root}/chipbench/configs/tiny-lfm2.json")
    adapter = manifest.adapter_for(f"{root}/chipbench/configs/tiny-lfm2.json", cfg)
    init_, _, forward_ = adapter.program()
    pc = adapter.config({**cfg, "recipe": {**cfg["recipe"], "param_dtype": "float32"}})
    params = jax.eval_shape(lambda: init_(jax.random.PRNGKey(0), pc))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    counted = jax.jit(lambda p, t: forward_(p, t, pc, remat="none")).lower(
        params, tokens).compile().cost_analysis()["flops"] / 128
    ours = adapter.forward_flops_per_token(cfg, 128)
    full_attention = ours + 2 * 2 * 128 * (128 - 129 / 2)
    assert full_attention < counted < 1.05 * full_attention, (counted, ours)


def test_the_new_metrics_read_the_scopes_and_nothing_from_a_parent(tiny_root):
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-lfm2.bare-routed-8k")
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "fusion.3": 0.25, "fusion.4": 2.0,
           "splash_mha_fwd_residuals": 0.5, "gmm.3": 4.0, "fusion.9": 8.0}
    scopes = {"fusion.1": "jit(step)/conv/in_proj/dot_general",
              "fusion.2": "jit(step)/transpose(jvp(conv/conv))/mul",
              "fusion.3": "jit(step)/checkpoint/conv/out_proj/dot_general",
              "fusion.4": "jit(step)/attn/mixer/dot_general",
              "fusion.9": "jit(step)/moe/experts/mul"}
    fake = {"trace": {"ops": ops, "chips_traced": 1}, "steps_in_window": 2,
            "scopes": scopes, "device": {"kind": "TPU v5 lite"}}
    spec = cell.layer_metric("conv.mixer_s")
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == 1.75 / 2
    spec = cell.layer_metric("attn.mixer_s")  # the scope and the kernels by name
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == 2.5 / 2
    # a program without the scopes (the parent), or a job that hands out no
    # map: nothing to read, no raise
    old = {**fake, "scopes": {"fusion.1": "jit(step)/dot_general"},
           "trace": {"ops": {"fusion.1": 1.0}, "chips_traced": 1}}
    for name in ("conv.mixer_s", "attn.mixer_s"):
        spec = cell.layer_metric(name)
        assert cell.reducer(spec["reducer"]).reduce(old, cell, **spec["args"]) is None
        assert cell.reducer(spec["reducer"]).reduce({}, cell, **spec["args"]) is None
        assert cell.reducer(spec["reducer"]).reduce(
            {**fake, "scopes": None}, cell, **spec["args"]) is None
    # the standing rooflines at this configuration's shapes: four expert
    # layers, one attention layer
    spec = cell.layer_metric("kernel.gmm_roofline")
    cfg, r = cell.config, cell.config["recipe"]
    cost = cell.adapter().KERNEL_COSTS["grouped_matmul"](cfg, 1, r["seq_len"], "fwd")
    one = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == \
        pytest.approx(100 * 12 * 4 * one / 2.0, rel=1e-9)
    spec = cell.layer_metric("kernel.splash_roofline")
    att = cell.adapter().KERNEL_COSTS["attention"]
    floor = sum(calls * max(att(cfg, 1, 128, p)["flops"] / 197e12,
                            att(cfg, 1, 128, p)["bytes"] / 819e9)
                for p, calls in spec["args"]["roofline"]["calls_per_layer"].items())
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == \
        pytest.approx(100 * floor / 0.25, rel=1e-9)


def test_the_cell_stops_where_it_finds_no_tpu(tiny_root, tmp_path):
    """Its reference is a child that gives no CPU answers: the job ends
    there, with the child's exit, before this process would touch JAX."""
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-lfm2.bare-routed-8k")
    assert cell.traffic["job"] == "bare_routed"
    assert cell.job().check_sample_of(cell, cell.adapter())["grad_leaves"] == [
        "embed", "layers.01_attn_moe.router", "layers.01_attn_moe.q_norm",
        "layers.02_conv_moe.conv_w", "layers.04_conv_moe.w_down",
        "layers.04_conv_moe.w_down@expert_norms"]
    with pytest.raises(subprocess.CalledProcessError):
        cell.job().run(cell, seed=2147485001, seconds=1.0, trace=False,
                       out_dir=str(tmp_path), cache_dir=str(tmp_path / "cache"),
                       t_start=time.monotonic())
    assert not [f for f in os.listdir(tmp_path / "cache") if f.startswith("reference_")]


def test_the_configuration_runs_through_the_one_trainer_under_the_manager(managed):
    cell, obs = managed
    assert obs["e2e"]["tok_s_chip"] > 0 and obs["failed"] == 0
    summary = obs["summaries"][0][-1]
    assert summary["config"] == "tiny-lfm2"
    steps = cell.traffic["warmup_steps"] + cell.traffic["min_steps"]
    assert summary["committed"] == steps and summary["discarded"] == 0
    stats = summary["model_stats"]
    assert sorted(stats) == ["moe_bias_moved_share", "moe_load_max_over_mean"]
    assert all(len(v) == steps for v in stats.values())
    assert all(0.0 < x < 1.0 for x in stats["moe_bias_moved_share"])
    assert all(1.0 <= x <= 16.0 for x in stats["moe_load_max_over_mean"])
    assert not any("first loss" in b for b in obs["notes"]["bad"])
    # float32 leaves (the routers) beside the bf16 ones: a bucket of their own
    assert summary["timings"]["allreduce_buckets"] >= 2
    assert summary["frozen_checksum"] is not None
    with pytest.raises(RuntimeError, match="no result"):
        result.build(cell, obs, obs["e2e"], trace=False)
