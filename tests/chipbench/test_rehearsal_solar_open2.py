"""CPU rehearsal of what PR 64 put into the benchmark, for tests only: the
cell as appended entries (in the repo's own manifest and in a temporary copy
with a tiny configuration), the configuration file against the catalog's
row, the adapter's counts against the program's and against the issue's
arithmetic and FLOPs counted from a compiled forward pass at tiny widths,
the new layer metric on a made-up trace (and on a parent's, which has
nothing for it to read), and the cell's path through ``chipbench/run.py``
up to where it finds no TPU. Refused as a measurement like every CPU run."""

import json
import subprocess
import sys

import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               check_contract, copy_root, files_of, only_appended, read,
                               write)

from chipbench import manifest  # noqa: I001

CELL = "solar-open2-250b.bare-kda-gqa-16k"
CONFIG = f"{ROOT}/chipbench/configs/solar-open2-250b.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["gqa.gated_mixer_s"]
STANDING = ["model.step_device_s", "model.mfu", "kda.mixer_s", "kernel.kda_s",
            "kernel.kda_roofline", "kernel.splash_s", "kernel.splash_roofline", "kernel.gmm_s",
            "kernel.gmm_roofline", "kernel.short_conv_s", "moe.block_s", "moe.route_s",
            "moe.shared_s"]
REDUCED = ["n_routed_experts", "num_hidden_layers", "vocab_size"]
TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512,
            n_routed_experts=4, num_experts_per_tok=4,
            linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                                "num_kv_heads": None})
S = 16384


def tiny_config(name="tiny-solar"):
    cfg = read(CONFIG)
    cfg.update(TINY, name=name)
    cfg["deployment"] = {**cfg["deployment"], "experts_held": [4, 4], "router_outputs": 16,
                         "share_room": 4.0}
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 128, "kda_out_block": 16}
    return cfg


def test_the_repos_own_manifest_holds_the_cell_as_appended_entries():
    bench = check_contract(ROOT)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 16 and len(names) >= 17
    assert [c["name"] for c in bench["configs"]].index("solar-open2-250b") == 11
    # seventeen cells of which two on four chips (four would be allowed)
    assert [w["name"] for w in bench["workloads"][:17] if w["chips"] == 4] == [
        "internlm2-1.8b.kill-rejoin-4g", "internlm2-1.8b.managed-4g"]
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "solar_open2"
    assert c.traffic["job"] == "bare_routed" and c.workload["traffic"] == "bare-kda-gqa-16k"
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    # AMONG the cell's metrics, not all of them: a later PR may append one
    assert set(STANDING) | set(NEW) <= {m["name"] for m in c.per_layer}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW[0])
    m = bench["per_layer"][at]
    assert m["workloads"][0] == CELL and m["moves"] == "bare_tok_s_chip"
    assert (m["source"], m["unit"], m["better"]) == ("device_trace", "s", "lower")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in STANDING:  # appended behind the cells that stood
        assert by_name[name]["workloads"].index(CELL) >= 1, name
    # every share of a roofline or of the peak that moves the cell's metric
    # and has something to read here
    assert {n for n, m in by_name.items() if CELL in m["workloads"]
            and ("roofline" in n or "mfu" in n)} == {
        "model.mfu", "kernel.kda_roofline", "kernel.splash_roofline", "kernel.gmm_roofline"}
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert bench["run_seconds"] == 48 and manifest.problems(ROOT) == []


def test_the_configuration_is_the_catalogs_row_but_for_its_cut():
    cfg = read(CONFIG)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == REDUCED
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 48, "n_routed_experts": 320, "vocab_size": 196608}
    assert cfg["linear_attn_config"] == row["config"]["linear_attn_config"]  # the group whole
    assert cfg["gqa_layers"] == row["config"]["gqa_layers"]  # as published; read below the depth
    dep = cfg["deployment"]
    assert dep["chips_per_layer"] == 32 and dep["experts_held"] == [0, cfg["n_routed_experts"]]
    assert dep["chips_per_layer"] * 10 == dep["router_outputs"] == 320
    assert dep["published_layers"] == [0, 3] and dep["vocabulary_slices"] == 8
    assert cfg["vocab_size"] * dep["vocabulary_slices"] == 196608
    pc = manifest.adapter_for(CONFIG, cfg).config(cfg)
    assert (pc.dim, pc.n_heads, pc.n_kv_heads, pc.head_dim) == (4096, 64, 8, 128)
    assert (pc.kda_head_dim, pc.kda_conv, pc.kda_rank, pc.gqa_layers) == (128, 4, 128, (0,))
    assert (pc.num_experts, pc.n_held, pc.top_k, pc.n_group, pc.topk_group) == (320, 10, 8, 1, 1)
    assert (pc.router_score, pc.norm_topk_prob, pc.routed_scaling, pc.gate_eps) == (
        "sigmoid", True, 1.0, 1e-20)
    assert pc.moe_intermediate_size == 1280 and pc.shared_intermediate_size is None
    assert pc.kinds() == ["gqa", "kda", "kda", "kda"]  # ONE WHOLE PERIOD
    assert [n for n, _, _ in pc.runs()] == ["00_gqa_moe", "01_kda_moe", "02_kda_moe",
                                            "03_kda_moe"]
    recipe = cfg["recipe"]
    assert (recipe["batch_size"], recipe["seq_len"], recipe["remat"]) == (1, S, "full")
    assert recipe["attention"] == "splash" and recipe["loss_chunk"] == 2048
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 8
    for word in ("softplus", "allow_neg_eigval", "rank", "use_rope", "noaux_tc",
                 "sqrt(fan_in)", "uniform(1, 16)", "intermediate_size"):
        assert any(word in line for line in cfg["assumed"]), word
    assert "GiB" in cfg["cut"] and "1,420,941,120" in cfg["cut"]
    # no width is cut
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k for k in cfg["reduced"]
                   if k != "vocab_size")
    adapter = manifest.adapter_for(CONFIG, cfg)
    for key, value in (("hidden_size", 2048), ("head_dim", 64), ("num_key_value_heads", 4),
                       ("moe_intermediate_size", 768), ("num_experts_per_tok", 4),
                       ("linear_attn_config", {**cfg["linear_attn_config"], "head_dim": 64})):
        changed = {**cfg, key: value}
        assert sorted(k for k, v in row["config"].items() if changed.get(k) != v) != differ
        assert adapter.config(changed) != pc  # it shows in the program's config object


def test_what_the_adapter_refuses():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    for changed, word in (
            ({"sliding_window": 4096}, "cannot express key 'sliding_window'"),
            ({"kda_lower_bound": -5}, "cannot express key 'kda_lower_bound'"),
            ({"use_rope": True}, "one value of 'use_rope'"),
            ({"use_gqa_gate": False}, "one value of 'use_gqa_gate'"),
            ({"kda_use_full_proj": True}, "one value of 'kda_use_full_proj'"),
            ({"kda_allow_neg_eigval": False}, "one value of 'kda_allow_neg_eigval'"),
            ({"first_k_dense_replace": 1}, "one value of 'first_k_dense_replace'"),
            ({"n_routed_experts": 20}, "experts held"),
            ({"gqa_layers": [0, 3]}, "gqa_interval"),
            ({"linear_attn_config": {**cfg["linear_attn_config"], "num_kv_heads": 8}},
             "linear_attn_config"),
            ({"linear_attn_config": {**cfg["linear_attn_config"], "expand_v": 2}},
             "linear_attn_config"),
            ({"num_hidden_layers": 5}, "published_layers")):
        with pytest.raises(ValueError, match=word):
            adapter.config({**cfg, **changed})


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    # ISSUE 64's count, leaf by leaf
    kda = (3 * 4096 * 8192 + 8192 * 4096 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
           + 3 * 4 * 8192 + 8192 + 8192 + 64 + 128)
    gqa = 3 * 4096 * 8192 + 2 * 4096 * 1024
    ffn = 4096 * 320 + 3 * 4096 * 1280 + 10 * 3 * 4096 * 1280
    assert (kda, gqa, ffn) == (137_740_480, 109_051_904, 174_325_760)
    assert adapter.num_params(cfg) == 1_420_941_120 == (
        3 * kda + gqa + 4 * (ffn + 2 * 4096) + 2 * 24576 * 4096 + 4096 + 4 * 320)
    assert adapter.layers_with(cfg, "attention") == 1 and adapter.layers_with(cfg, "kda") == 3
    assert adapter.layers_with(cfg, "grouped_matmul") == 4
    fwd = adapter.forward_flops_per_token(cfg, S)
    # ISSUE 64's required work a token, forward, counted again here: three
    # KDA mixers 848M (the issue's 846M to a rounding; the RECURRENT form's 7
    # operations a (position, head, 128, 128)), the GQA mixer 487M of which
    # 268M are scores at 16k, four expert blocks 168M, the sliced head 201M
    kda_f = (2 * (4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64)
             + 3 * 2 * 4 * 8192 + 7 * 8192 * 128)
    gqa_f = 2 * (3 * 4096 * 8192 + 2 * 4096 * 1024) + 2 * 64 * 2 * 128 * (S + 1) / 2
    ffn_f = 2 * 4096 * 320 + (1 + 8 * 10 / 320) * 3 * 2 * 4096 * 1280
    assert fwd == pytest.approx(3 * kda_f + gqa_f + 4 * ffn_f + 2 * 4096 * 24576, rel=1e-12)
    assert round(3 * kda_f / 1e6) == 848 and round(gqa_f / 1e6) == 487
    assert round(2 * 64 * 2 * 128 * (S + 1) / 2 / 1e6) == 268
    assert round(4 * ffn_f / 1e6) == 168 and round(2 * 4096 * 24576 / 1e6) == 201
    assert 0.77 < (3 * kda_f + gqa_f) / fwd < 0.79  # the mixers: 78%
    assert adapter.train_flops_per_token(cfg, S) == 3 * fwd
    att = adapter.KERNEL_COSTS["attention"](cfg, 1, S, "fwd")
    pairs = 64 * S * (S + 1) / 2
    assert att["flops"] == 2 * pairs * 2 * 128
    assert att["bytes"] == 2.0 * S * 128 * (2 * 64 + 2 * 8)  # keys and values a kv head
    assert adapter.KERNEL_COSTS["attention"](cfg, 1, S, "bwd")["flops"] == 2 * pairs * 5 * 128
    scan = adapter.KERNEL_COSTS["kda"](cfg, 1, S, "fwd")
    assert scan["flops"] == 7.0 * S * 64 * 128 * 128  # at 64 heads: the count is this file's
    assert scan["bytes"] == S * 8192 * 12.0 + 4.0 * S * 64
    assert adapter.KERNEL_COSTS["kda"](cfg, 1, S, "bwd")["flops"] == 3 * scan["flops"]
    with pytest.raises(KeyError):
        adapter.KERNEL_COSTS["attention"](cfg, 1, S, "dlhs")


@pytest.mark.parametrize("passes", ["fwd", "dlhs", "drhs"])
def test_the_grouped_products_cost_is_this_cells_by_hand(passes):
    """16,384 tokens x 8 experts a token x 10 of 320 held = 4,096 rows (410
    an expert), one product of 4,096 x 1,280 over them, the rows read and
    written and the ten matrices read (or, for ``drhs``, written) once in
    bf16; three such products a layer and pass."""
    cfg = read(CONFIG)
    cost = manifest.adapter_for(CONFIG, cfg).KERNEL_COSTS["grouped_matmul"](cfg, 1, S, passes)
    assert cost == {"flops": 2.0 * 4096 * 4096 * 1280,
                    "bytes": 2.0 * (4096 * 4096 + 4096 * 1280 + 10 * 4096 * 1280)}


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The cell added to a temporary copy as a PR adds it: a configuration
    file, an entry of ``configs``, an entry of ``workloads``, its name
    appended to what the repo's cell reports; nothing that is there edited."""
    tmp = tmp_path_factory.mktemp("solar")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = tiny_config()
    write(f"{root}/chipbench/configs/tiny-solar.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-solar", "source": "x", "reduced": cfg["reduced"],
                             "file": "chipbench/configs/tiny-solar.json", "why": "x"})
    add_cell(root, bench, "tiny-solar.bare-kda-gqa-16k", "tiny-solar", "bare-kda-gqa-16k", CELL)
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


def test_the_repos_manifest_differs_from_the_parents_by_appended_entries_only():
    """``git show HEAD:BENCHMARK.json`` (the parent's while this PR is the
    working tree; this PR's own once it is committed) against the file."""
    out = subprocess.run(["git", "show", "HEAD:BENCHMARK.json"], capture_output=True,
                         text=True, cwd=ROOT)
    if out.returncode:
        pytest.skip("no git history here")
    assert only_appended(json.loads(out.stdout), read(f"{ROOT}/BENCHMARK.json"))


def test_the_flops_are_what_the_compiled_forward_pass_counts(tiny_root):
    """XLA's own count of the tiny configuration's forward pass on the CPU
    (the XLA attention path multiplies every (i, j), masked or not; the
    interpreted kernels their chunked products and the grouped product the
    whole buffer) lies between the adapter's exact count and a small
    multiple of it: the count is of the right size and leaves no layer out."""
    import jax
    import jax.numpy as jnp

    root, _, _ = tiny_root
    path = f"{root}/chipbench/configs/tiny-solar.json"
    cfg = read(path)
    adapter = manifest.adapter_for(path, cfg)
    init_, _, forward_ = adapter.program()
    pc = adapter.config({**cfg, "recipe": {**cfg["recipe"], "param_dtype": "float32"}})
    params = jax.eval_shape(lambda: init_(jax.random.PRNGKey(0), pc))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    counted = jax.jit(lambda p, t: jnp.asarray(forward_(p, t, pc, remat="none"))).lower(
        params, tokens).compile().cost_analysis()["flops"] / 128
    ours = adapter.forward_flops_per_token(cfg, 128)
    assert 0.6 * ours < counted < 8 * ours, (counted, ours)


def test_the_new_metric_reads_the_scopes_and_nothing_from_a_parent(tiny_root):
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-solar.bare-kda-gqa-16k")
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "fusion.3": 2.0, "fusion.4": 0.25,
           "splash_mha_fwd_residuals.7": 4.0, "splash_mha_dq_no_residuals.1": 8.0,
           "fusion.5": 0.125, "fusion.6": 16.0, "gmm.3": 32.0, "fusion.7": 64.0,
           "kda_fwd.2": 128.0, "kda_bwd.1": 256.0, "fusion.8": 512.0, "short_conv_fwd.3": 1024.0}
    scopes = {"fusion.1": "jit(step)/while/body/gqa/in_proj/dot_general",
              "fusion.2": "jit(step)/transpose(jvp(gqa/gate))/mul",
              "fusion.3": "jit(step)/checkpoint/gqa/out/dot_general",
              "fusion.4": "jit(step)/gqa/attend/reshape",
              "fusion.5": "jit(step)/while/body/kda/gate/softplus",
              "fusion.6": "jit(step)/moe/experts/mul",
              "fusion.7": "jit(step)/moe/shared/dot_general",
              "kda_fwd.2": "jit(step)/kda/scan/kda_fwd/pallas_call",
              "fusion.8": "jit(step)/kda/out/while/body/checkpoint/mul"}
    obs = {"trace": {"ops": ops, "chips_traced": 1}, "steps_in_window": 2, "scopes": scopes,
           "device": {"kind": "TPU v5 lite"}}

    def value(name, obs=obs):
        spec = cell.layer_metric(name)
        return cell.reducer(spec["reducer"]).reduce(obs, cell, **spec.get("args", {}))

    assert value("gqa.gated_mixer_s") == (1.0 + 0.5 + 2.0 + 0.25 + 4.0 + 8.0) / 2
    # the standing metrics the cell reports read this program's scopes too:
    # the low-rank pairs lie under kda/gate and kda/out, the blocks of the
    # norm and gate under kda/out
    assert value("kda.mixer_s") == (0.125 + 128.0 + 256.0 + 512.0) / 2
    assert value("kernel.kda_s") == (128.0 + 256.0) / 2
    assert value("kernel.splash_s") == (4.0 + 8.0) / 2
    assert value("kernel.short_conv_s") == 1024.0 / 2
    assert value("moe.block_s") == (16.0 + 32.0) / 2 and value("moe.shared_s") == 64.0 / 2
    assert 0 < value("kernel.kda_roofline") < 100
    assert 0 < value("kernel.splash_roofline") and 0 < value("kernel.gmm_roofline")
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
