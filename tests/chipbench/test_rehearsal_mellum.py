"""CPU rehearsal of what PR 43 put into the benchmark, for tests only: the
cell as appended entries, the configuration file against the catalog's row,
the adapter's counts (exact under both masks) against the program's and
against FLOPs counted from a compiled forward pass at tiny widths (a
temporary root, nothing that is there edited), the new layer metrics on a
made-up trace, and the cell's path through ``jobs/bare_routed.py`` up to
where it finds no TPU. The Manager path of the kind is tests/test_mellum.py's.
Refused as a measurement like every CPU run."""

import json
import subprocess
import sys

import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               check_contract, copy_root, files_of, only_appended, read,
                               write)

from chipbench import manifest  # noqa: I001

CELL = "mellum2-12b-a2.5b.bare-window-32k"
CONFIG = f"{ROOT}/chipbench/configs/mellum2-12b-a2.5b.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["attn.window_mixer_s", "attn.full_mixer_s"]
STANDING = ["model.step_device_s", "model.mfu", "kernel.splash_s", "kernel.splash_roofline",
            "kernel.gmm_s", "kernel.gmm_roofline", "moe.block_s", "moe.route_s"]
TINY = dict(hidden_size=48, moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=512, num_experts=8,
            num_experts_per_tok=4, sliding_window=8, num_hidden_layers=4,
            layer_types=["sliding_attention", "full_attention"] * 2,
            mlp_layer_types=["sparse"] * 4)
S, W = 32768, 1024


def test_the_repos_own_manifest_holds_the_cell_as_appended_entries():
    bench = check_contract(ROOT)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 10 and len(names) >= 11
    assert [c["name"] for c in bench["configs"]].index("mellum2-12b-a2.5b") == 6
    # eleven cells, two on four chips: a quarter of eleven is two
    assert [w["name"] for w in bench["workloads"][:11] if w["chips"] == 4] == [
        "internlm2-1.8b.kill-rejoin-4g", "internlm2-1.8b.managed-4g"]
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "mellum"
    assert c.traffic["job"] == "bare_routed"
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in c.per_layer} == set(STANDING) | set(NEW)
    at = [m["name"] for m in bench["per_layer"]].index(NEW[0])
    assert at == 79 and [m["name"] for m in bench["per_layer"][at:at + 2]] == NEW
    for m in bench["per_layer"][at:at + 2]:
        assert m["workloads"][0] == CELL and m["moves"] == "bare_tok_s_chip"
        assert m["source"] == "device_trace"
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert bench["run_seconds"] == 48 and manifest.problems(ROOT) == []


def test_the_configuration_is_the_catalogs_row_but_for_its_cut():
    cfg = read(CONFIG)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts", "vocab_size"])
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the kept layers are the published ones in their order: two whole periods
    assert cfg["layer_types"] == row["config"]["layer_types"][:8] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 2
    dep = cfg["deployment"]
    assert dep["experts_held"] == [0, cfg["num_experts"]] and dep["router_outputs"] == 64
    assert dep["chips_per_layer"] * cfg["num_experts"] == 64 and dep["chips_per_layer"] == 4
    assert cfg["vocab_size"] * dep["vocabulary_slices"] == 98304
    assert dep["published_layers"] == [0, 7]
    pc = manifest.adapter_for(CONFIG, cfg).config(cfg)
    assert pc.layer_types == ("window", "window", "window", "full") * 2 and pc.window == W
    assert (pc.num_experts, pc.n_held, pc.top_k, pc.n_group) == (64, 16, 8, 1)
    assert (pc.router_score, pc.norm_topk_prob, pc.routed_scaling) == ("softmax", True, 1.0)
    assert (pc.dim, pc.n_heads, pc.n_kv_heads, pc.head_dim, pc.ffn_hidden) == (
        2304, 32, 4, 128, 896)
    assert (pc.yarn_factor, pc.yarn_original_max, pc.yarn_beta_fast, pc.yarn_beta_slow) == (
        16.0, 8192, 32.0, 1.0) and pc.yarn_attention_factor == 1.2772588722239782
    assert (cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]) == (1, S)
    assert cfg["recipe"]["attention"] == "splash" and cfg["recipe"]["remat"] == "full"
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 6
    assert "4 chips" in dep["what"] and "read" in dep["what"]
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k for k in cfg["reduced"]
                   if k != "vocab_size")
    # a cut that changes a width is refused
    adapter = manifest.adapter_for(CONFIG, cfg)
    for key, value in (("hidden_size", 2048), ("head_dim", 64), ("moe_intermediate_size", 768),
                       ("num_attention_heads", 16), ("sliding_window", 512)):
        changed = {**cfg, key: value}
        assert sorted(k for k, v in row["config"].items() if changed.get(k) != v) != differ
        assert adapter.config(changed) != pc  # it shows in the program's config object


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    assert adapter.num_params(cfg) == 1_077_059_840
    assert adapter.layers_with(cfg, "attention") == 8
    assert adapter.layers_with(cfg, "attention_window") == 6
    assert adapter.layers_with(cfg, "attention_full") == 2
    assert adapter.layers_with(cfg, "grouped_matmul") == 8
    # the exact mask counts: 33.03M pairs a head under the window, 536.9M without
    window, full = (adapter.pairs(cfg, t, S) for t in ("sliding_attention", "full_attention"))
    assert window == W * (W + 1) / 2 + (S - W) * W == 33_030_656
    assert full == S * (S + 1) / 2 == 536_887_296
    assert adapter.pairs(cfg, "sliding_attention", 512) == 512 * 513 / 2  # a short sequence
    ij = [(i, j) for i in range(40) for j in range(40) if j <= i and i - j < 8]
    assert adapter.pairs({**cfg, "sliding_window": 8}, "sliding_attention", 40) == len(ij)
    fwd = adapter.forward_flops_per_token(cfg, S)
    proj = 8 * (2 * 2304 * 4096 * 2 + 2 * 2 * 2304 * 512 + 2 * 2304 * 64
                + 2 * 3 * 2 * 2304 * 896)
    attn = 2 * 2 * 4096 * (6 * window + 2 * full) / S
    assert fwd == pytest.approx(proj + attn + 2 * 2304 * 24576, rel=1e-12)
    assert adapter.train_flops_per_token(cfg, S) == 3 * fwd
    assert 0.4 < attn / fwd < 0.5  # the two full layers are most of it
    att = {k: adapter.KERNEL_COSTS[k](cfg, 1, S, "fwd")["flops"]
           for k in ("attention", "attention_window", "attention_full")}
    assert att["attention_window"] == 2 * 2 * 32 * window * 128
    assert att["attention_full"] == 2 * 2 * 32 * full * 128
    assert att["attention"] == pytest.approx(
        (6 * att["attention_window"] + 2 * att["attention_full"]) / 8, rel=1e-12)
    # a causal count for a window layer would read its share 16 x too high
    assert att["attention_full"] / att["attention_window"] == pytest.approx(16.25, rel=0.01)
    assert adapter.KERNEL_COSTS["attention"](cfg, 1, S, "bwd")["flops"] == 2.5 * att["attention"]
    cost = adapter.KERNEL_COSTS["grouped_matmul"](cfg, 1, S, "fwd")
    assert cost["flops"] == 2 * 65536 * 2304 * 896  # 4,096 rows an expert
    with pytest.raises(KeyError):
        adapter.KERNEL_COSTS["attention"](cfg, 1, S, "dlhs")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mellum")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = read(CONFIG)
    cfg.update(TINY, name="tiny-mellum")
    cfg["deployment"] = {**cfg["deployment"], "experts_held": [8, 8], "router_outputs": 32,
                         "share_room": 4.0, "published_layers": [0, 3]}
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 128}
    write(f"{root}/chipbench/configs/tiny-mellum.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-mellum", "source": "x", "reduced": cfg["reduced"],
                             "file": "chipbench/configs/tiny-mellum.json", "why": "x"})
    add_cell(root, bench, "tiny-mellum.bare-window-32k", "tiny-mellum", "bare-window-32k", CELL)
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


def test_the_flops_are_what_the_compiled_forward_pass_counts(tiny_root):
    """XLA's own count of the tiny configuration's forward pass on the CPU
    (the XLA attention path multiplies every (i, j), masked or not, and the
    interpreted grouped product the whole buffer) lies between the adapter's
    exact count and a small multiple of it: the count is of the right size
    and leaves no layer out."""
    import jax
    import jax.numpy as jnp

    root, _, _ = tiny_root
    path = f"{root}/chipbench/configs/tiny-mellum.json"
    cfg = read(path)
    adapter = manifest.adapter_for(path, cfg)
    init_, _, forward_ = adapter.program()
    pc = adapter.config({**cfg, "recipe": {**cfg["recipe"], "param_dtype": "float32"}})
    params = jax.eval_shape(lambda: init_(jax.random.PRNGKey(0), pc))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    counted = jax.jit(lambda p, t: jnp.asarray(forward_(p, t, pc, remat="none"))).lower(
        params, tokens).compile().cost_analysis()["flops"] / 128
    ours = adapter.forward_flops_per_token(cfg, 128)
    assert 0.6 * ours < counted < 4 * ours, (counted, ours)


def test_the_new_metrics_read_the_scopes_and_nothing_from_a_parent(tiny_root):
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-mellum.bare-window-32k")
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "fusion.3": 2.0, "fusion.4": 0.25,
           "splash_mha_fwd_residuals.7": 4.0, "splash_mha_dq_no_residuals.1": 8.0,
           "fusion.6": 16.0, "gmm.3": 32.0}
    scopes = {"fusion.1": "jit(step)/while/body/attn_window/mixer/dot_general",
              "fusion.2": "jit(step)/transpose(jvp(attn_window/mixer))/mul",
              "fusion.3": "jit(step)/checkpoint/attn_full/mixer/dot_general",
              "fusion.4": "jit(step)/attn/mixer/dot_general",  # another kind's scope
              "fusion.6": "jit(step)/moe/experts/mul"}
    obs = {"trace": {"ops": ops, "chips_traced": 1}, "steps_in_window": 2, "scopes": scopes,
           "device": {"kind": "TPU v5 lite"}}

    def value(name, obs=obs):
        spec = cell.layer_metric(name)
        return cell.reducer(spec["reducer"]).reduce(obs, cell, **spec.get("args", {}))

    assert value("attn.window_mixer_s") == (1.0 + 0.5) / 2
    assert value("attn.full_mixer_s") == 2.0 / 2
    # the kernels, both kinds' alike, are the standing metrics'
    assert value("kernel.splash_s") == (4.0 + 8.0) / 2
    assert 0 < value("kernel.splash_roofline") and 0 < value("kernel.gmm_roofline")
    assert value("moe.block_s") == (16.0 + 32.0) / 2
    # LFM2's pattern does not catch the two scopes, nor they its
    lfm2 = cell.reducer("device_scope").reduce(obs, cell, scope="attn/mixer")
    assert lfm2 == 0.25 / 2
    # a parent's program has no such scope: nothing to read, the metric is
    # left out, nothing raises
    bare = {**obs, "trace": {"ops": {"fusion.9": 1.0}, "chips_traced": 1}, "scopes": {}}
    assert all(value(n, bare) is None for n in NEW)
    assert all(value(n, {**obs, "scopes": None}) is None for n in NEW)


def test_the_command_line_ends_without_a_result_off_the_chip(tmp_path):
    """``chipbench/run.py`` on the new cell here: the reference's child finds
    no TPU and says so, the command prints no result line and exits 2."""
    out = subprocess.run(
        [sys.executable, f"{ROOT}/chipbench/run.py", "--workload", CELL, "--seed",
         "2147485035", "--seconds", "1"], capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path), env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 2, out.stdout[-2000:] + out.stderr[-2000:]
    assert "no TPU" in out.stderr and '"correct"' not in out.stdout
