"""CPU rehearsal of what PR 59 put into the benchmark, for tests only: the
cell as appended entries (in the repo's own manifest and in a temporary copy
with a tiny configuration), the configuration file against the catalog's
row, the adapter's counts against the program's and against the issue's
arithmetic and FLOPs counted from a compiled forward pass at tiny widths,
the new layer metrics on a made-up trace (and on a parent's, which has
nothing for them to read), and the cell's path through ``chipbench/run.py``
up to where it finds no TPU. Refused as a measurement like every CPU run."""

import json
import subprocess
import sys

import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               check_contract, copy_root, files_of, only_appended, read,
                               write)

from chipbench import manifest  # noqa: I001

CELL = "deepseek-v2.bare-mla-yarn"
CONFIG = f"{ROOT}/chipbench/configs/deepseek-v2.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["mla.latent_s", "moe.aux_s"]
STANDING = ["model.step_device_s", "model.mfu", "kernel.splash_s", "kernel.splash_roofline",
            "kernel.gmm_s", "kernel.gmm_roofline", "moe.block_s", "moe.route_s",
            "moe.shared_s", "mla.mixer_s", "ffn.block_s"]
REDUCED = ["n_routed_experts", "num_attention_heads", "num_hidden_layers",
           "num_key_value_heads", "vocab_size"]
TINY = dict(hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
            num_attention_heads=2, num_key_value_heads=2, q_lora_rank=24, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, vocab_size=512,
            n_routed_experts=4, num_experts_per_tok=4, n_group=4, topk_group=2)
S = 16384


def tiny_config(name="tiny-deepseek"):
    cfg = read(CONFIG)
    cfg.update(TINY, name=name)
    cfg["rope_scaling"] = {**cfg["rope_scaling"], "original_max_position_embeddings": 32}
    cfg["published"] = {**cfg["published"], "num_attention_heads": 8, "num_key_value_heads": 8}
    cfg["deployment"] = {**cfg["deployment"], "heads_held": [2, 2], "experts_held": [4, 4],
                         "router_outputs": 16, "share_room": 4.0}
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 128}
    return cfg


def test_the_repos_own_manifest_holds_the_cell_as_appended_entries():
    bench = check_contract(ROOT)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 15 and len(names) >= 16
    assert [c["name"] for c in bench["configs"]].index("deepseek-v2") == 10
    # sixteen cells of which two on four chips (four would be allowed)
    assert [w["name"] for w in bench["workloads"][:16] if w["chips"] == 4] == [
        "internlm2-1.8b.kill-rejoin-4g", "internlm2-1.8b.managed-4g"]
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "deepseek"
    assert c.traffic["job"] == "bare_routed" and c.workload["traffic"] == "bare-mla-yarn"
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    # AMONG the cell's metrics, not all of them: a later PR may append one
    assert set(STANDING) | set(NEW) <= {m["name"] for m in c.per_layer}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW[0])
    assert listed[at:at + 2] == NEW
    for m in bench["per_layer"][at:at + 2]:
        assert m["workloads"][0] == CELL and m["moves"] == "bare_tok_s_chip"
        assert (m["source"], m["unit"], m["better"]) == ("device_trace", "s", "lower")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # the layers are the benchmark's own names, letter for letter
    assert by_name["mla.latent_s"]["layer"] == by_name["mla.mixer_s"]["layer"]
    assert by_name["moe.aux_s"]["layer"] == by_name["moe.block_s"]["layer"]
    for name in STANDING:  # appended behind the cells that stood
        assert by_name[name]["workloads"].index(CELL) >= 1, name
    # every share of a roofline or of the peak that moves the cell's metric
    # and has something to read here
    assert {n for n, m in by_name.items() if CELL in m["workloads"]
            and ("roofline" in n or "mfu" in n)} == {
        "model.mfu", "kernel.splash_roofline", "kernel.gmm_roofline"}
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert bench["run_seconds"] == 48 and manifest.problems(ROOT) == []


def test_the_configuration_is_the_catalogs_row_but_for_its_cut():
    cfg = read(CONFIG)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V2")
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == REDUCED
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 60, "n_routed_experts": 160, "num_attention_heads": 128,
        "num_key_value_heads": 128, "vocab_size": 102400}
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"]  # the nested group whole
    assert cfg["aux_loss_alpha"] == 0.001 and "aux_loss_alpha" not in row["config"]
    dep = cfg["deployment"]
    assert dep["chips_per_layer"] == 16 and "sixteen" in dep["what"]
    assert dep["heads_held"] == [0, cfg["num_attention_heads"]] == [0, 8]
    assert dep["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 10]
    assert dep["chips_per_layer"] * 8 == 128 and dep["chips_per_layer"] * 10 == 160
    assert dep["router_outputs"] == 160 and dep["published_layers"] == [0, 4]
    assert cfg["vocab_size"] * dep["vocabulary_slices"] == 102400
    pc = manifest.adapter_for(CONFIG, cfg).config(cfg)
    assert (pc.dim, pc.n_heads, pc.n_held_heads, pc.ffn_hidden) == (5120, 128, 8, 12288)
    assert (pc.q_lora_rank, pc.kv_lora_rank, pc.qk_nope_head_dim, pc.qk_rope_head_dim,
            pc.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (pc.num_experts, pc.n_held, pc.top_k, pc.n_group, pc.topk_group) == (
        160, 10, 6, 8, 3)
    assert (pc.router_score, pc.topk_method, pc.norm_topk_prob, pc.routed_scaling) == (
        "softmax", "group_limited_greedy", False, 16.0)
    assert (pc.moe_intermediate_size, pc.shared_intermediate_size) == (1536, 3072)
    assert (pc.yarn_factor, pc.yarn_original_max, pc.yarn_beta_fast, pc.yarn_beta_slow,
            pc.yarn_mscale, pc.yarn_mscale_all_dim) == (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert [n for n, _, _ in pc.runs()] == ["00_dense"] + [f"{i:02d}_moe" for i in (1, 2, 3, 4)]
    recipe = cfg["recipe"]
    assert (recipe["batch_size"], recipe["seq_len"], recipe["remat"]) == (1, S, "full")
    assert recipe["attention"] == "splash" and recipe["loss_chunk"] == 2048
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 6
    for word in ("aux_loss_alpha", "device-level", "sqrt(fan_in)", "-inf", "learning rate"):
        assert any(word in line for line in cfg["assumed"]), word
    # no width is cut: a count of heads is not a width, and the file says so
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k for k in cfg["reduced"]
                   if k != "vocab_size")
    adapter = manifest.adapter_for(CONFIG, cfg)
    for key, value in (("hidden_size", 4096), ("q_lora_rank", 768), ("kv_lora_rank", 256),
                       ("qk_rope_head_dim", 32), ("moe_intermediate_size", 768),
                       ("intermediate_size", 6144), ("num_experts_per_tok", 4)):
        changed = {**cfg, key: value}
        assert sorted(k for k, v in row["config"].items() if changed.get(k) != v) != differ
        assert adapter.config(changed) != pc  # it shows in the program's config object


def test_what_the_adapter_refuses():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    for changed, word in (
            ({"sliding_window": 4096}, "cannot express key 'sliding_window'"),
            ({"scoring_func": "sigmoid"}, "one value of 'scoring_func'"),
            ({"n_routed_experts": 20}, "experts held"),
            ({"num_attention_heads": 16}, "heads held"),
            ({"rope_scaling": {**cfg["rope_scaling"], "type": "linear"}}, "YaRN"),
            ({"seq_aux": False}, "sequence-wise"),
            ({"topk_method": "greedy"}, "topk_method")):
        with pytest.raises(ValueError, match=word):
            adapter.config({**cfg, **changed})
    # renormalised gates are not scaled, as in the source
    assert adapter.config({**cfg, "norm_topk_prob": True}).routed_scaling == 1.0


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    assert adapter.num_params(cfg) == 1_552_942_080
    assert adapter.layers_with(cfg, "attention") == 5
    assert adapter.layers_with(cfg, "grouped_matmul") == 4
    fwd = adapter.forward_flops_per_token(cfg, S)
    # ISSUE 59's products a token (multiply-adds; x 2 here)
    proj = 5120 * 1536 + 1536 * 8 * 192 + 5120 * 576 + 512 * 8 * 256 + 8 * 128 * 5120
    assert proj == 19_466_240 - 1536 - 512  # the mixer's leaves less its two norms
    scores = 8 * (192 + 128) * (S + 1) / 2
    shared, held, dense = 3 * 5120 * 3072, 6 * 10 / 160 * 3 * 5120 * 1536, 3 * 5120 * 12288
    router, head = 5120 * 160, 5120 * 12800
    assert fwd == pytest.approx(2 * (5 * (proj + scores) + 4 * (shared + held + router)
                                     + dense + head), rel=1e-12)
    assert round(5 * proj / 1e6) == 97 and round(5 * scores / 1e6) == 105
    assert round(4 * shared / 1e6) == 189 and round(4 * held / 1e6) == 35
    assert round(dense / 1e6) == 189 and round(head / 1e6) == 66
    assert round(fwd / 2e6) == 684
    assert adapter.train_flops_per_token(cfg, S) == 3 * fwd
    att = adapter.KERNEL_COSTS["attention"](cfg, 1, S, "fwd")
    pairs = 8 * S * (S + 1) / 2
    assert att["flops"] == 2 * pairs * (192 + 128)  # the model's widths, not the padded 256
    assert att["bytes"] == 2.0 * S * 8 * (2 * 192 + 2 * 128)
    assert adapter.KERNEL_COSTS["attention"](cfg, 1, S, "bwd")["flops"] == 2 * pairs * (
        3 * 192 + 2 * 128)
    with pytest.raises(KeyError):
        adapter.KERNEL_COSTS["attention"](cfg, 1, S, "dlhs")


@pytest.mark.parametrize("passes", ["fwd", "dlhs", "drhs"])
def test_the_grouped_products_cost_is_this_cells_by_hand(passes):
    """The function is Mellum's adapter's (one grouped product over the even
    share's rows); the numbers are this cell's, counted here by hand, so a
    change there that moves ``kernel.gmm_roofline`` here shows here: 16,384
    tokens x 6 experts a token x 10 of 160 held = 6,144 rows (614 an
    expert), one product of 5,120 x 1,536 over them, the rows read and
    written and the ten matrices read (or, for ``drhs``, written) once in
    bf16; three such products an expert layer and pass."""
    cfg = read(CONFIG)
    cost = manifest.adapter_for(CONFIG, cfg).KERNEL_COSTS["grouped_matmul"](cfg, 1, S, passes)
    assert cost == {"flops": 2.0 * 6144 * 5120 * 1536,
                    "bytes": 2.0 * (6144 * 5120 + 6144 * 1536 + 10 * 5120 * 1536)}
    assert 3 * cost["flops"] == 2 * 6144 * 3 * 5120 * 1536 == 289_910_292_480


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """The cell added to a temporary copy as a PR adds it: a configuration
    file, an entry of ``configs``, an entry of ``workloads``, its name
    appended to what the repo's cell reports; nothing that is there edited."""
    tmp = tmp_path_factory.mktemp("deepseek")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = tiny_config()
    write(f"{root}/chipbench/configs/tiny-deepseek.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-deepseek", "source": "x", "reduced": cfg["reduced"],
                             "file": "chipbench/configs/tiny-deepseek.json", "why": "x"})
    add_cell(root, bench, "tiny-deepseek.bare-mla-yarn", "tiny-deepseek", "bare-mla-yarn", CELL)
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


def test_the_flops_are_what_the_compiled_forward_pass_counts(tiny_root):
    """XLA's own count of the tiny configuration's forward pass on the CPU
    (the XLA attention path multiplies every (i, j), masked or not, and the
    padded widths; the interpreted grouped product the whole buffer) lies
    between the adapter's exact count and a small multiple of it: the count
    is of the right size and leaves no layer out."""
    import jax
    import jax.numpy as jnp

    root, _, _ = tiny_root
    path = f"{root}/chipbench/configs/tiny-deepseek.json"
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
    cell = manifest.Cell(root, bench, "tiny-deepseek.bare-mla-yarn")
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "fusion.3": 2.0, "fusion.4": 0.25,
           "splash_mha_fwd_residuals.7": 4.0, "splash_mha_dq_no_residuals.1": 8.0,
           "fusion.5": 0.125, "fusion.6": 16.0, "gmm.3": 32.0, "fusion.7": 64.0,
           "fusion.8": 128.0, "fusion.9": 256.0}
    scopes = {"fusion.1": "jit(step)/while/body/mla/q/dot_general",
              "fusion.2": "jit(step)/transpose(jvp(mla/kv))/mul",
              "fusion.3": "jit(step)/checkpoint/mla/out/dot_general",
              "fusion.4": "jit(step)/mla/attn/concatenate",
              "fusion.5": "jit(step)/while/body/moe/aux/reduce_sum",
              "fusion.6": "jit(step)/moe/experts/mul",
              "fusion.7": "jit(step)/moe/shared/dot_general",
              "fusion.8": "jit(step)/ffn/block/dot_general",
              "fusion.9": "jit(step)/moe/route/softmax"}
    obs = {"trace": {"ops": ops, "chips_traced": 1}, "steps_in_window": 2, "scopes": scopes,
           "device": {"kind": "TPU v5 lite"}}

    def value(name, obs=obs):
        spec = cell.layer_metric(name)
        return cell.reducer(spec["reducer"]).reduce(obs, cell, **spec.get("args", {}))

    assert value("mla.latent_s") == (1.0 + 0.5) / 2
    assert value("moe.aux_s") == 0.125 / 2
    # the standing metrics the cell reports: the whole mixer with its kernels,
    # the block without the balance term, the shared experts, the dense SwiGLU
    assert value("mla.mixer_s") == (1.0 + 0.5 + 2.0 + 0.25 + 4.0 + 8.0) / 2
    assert value("kernel.splash_s") == (4.0 + 8.0) / 2
    assert value("moe.block_s") == (16.0 + 32.0 + 256.0) / 2
    assert value("moe.route_s") == (16.0 + 256.0) / 2
    assert value("moe.shared_s") == 64.0 / 2 and value("ffn.block_s") == 128.0 / 2
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
