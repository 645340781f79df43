"""CPU rehearsal of what PR 67 put into the benchmark, for tests only: the
cell as appended entries (in the repo's own manifest and in a temporary copy
with a tiny configuration), the configuration file against the catalog's
row, what the adapter refuses, its counts against the program's and against
the issue's arithmetic, the new job kind's timed loop against ``bare``'s, the
cell's CHECK at tiny widths in float32 (the program through
``jobs/bare_frozen.py`` against ``reference_deepseek_v32.py``) and with a
fault in, the new layer metrics on a made-up trace (and on a parent's, which
has nothing for them to read), and the cell's path through
``chipbench/run.py`` up to where it finds no TPU. Refused as a measurement
like every CPU run."""

import importlib.util
import inspect
import json
import re
import subprocess
import sys

import jax
import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               check_contract, copy_root, files_of, only_appended, read,
                               write)

from chipbench import manifest  # noqa: I001

CELL = "deepseek-v3.2-exp.bare-dsa-warmup-16k"
CONFIG = f"{ROOT}/chipbench/configs/deepseek-v3.2-exp.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["dsa.indexer_s", "kernel.dsa_kl_s", "kernel.dsa_kl_roofline",
       "kernel.splash_fwd_roofline"]
STANDING = ["model.step_device_s", "model.mfu", "kernel.splash_s", "kernel.gmm_s",
            "moe.block_s", "moe.route_s", "moe.shared_s", "mla.mixer_s", "mla.latent_s",
            "ffn.block_s"]
REDUCED = ["first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
           "num_nextn_predict_layers", "vocab_size"]
S = 16384

bare = manifest.load_module(ROOT, "jobs", "bare")
frozen = manifest.load_module(ROOT, "jobs", "bare_frozen")
# the tiny configuration and the float32 limits are the fault script's own
# (``benchmarks/dsa_check_faults.py cpu`` runs this file's check as a script)
_spec = importlib.util.spec_from_file_location(
    "dsa_check_faults", f"{ROOT}/benchmarks/dsa_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
F32, SEQ = faults.F32, 80


def tiny_config():
    return faults.tiny_config(read(CONFIG))


def test_the_repos_own_manifest_holds_the_cell_as_appended_entries():
    bench = check_contract(ROOT)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 17 and len(names) >= 18
    assert [c["name"] for c in bench["configs"]].index("deepseek-v3.2-exp") == 12
    assert [w["name"] for w in bench["workloads"][:18] if w["chips"] == 4] == [
        "internlm2-1.8b.kill-rejoin-4g", "internlm2-1.8b.managed-4g"]
    before = json.loads(subprocess.run(
        ["git", "show", "3f86f719b37782cdd9a892e6c98fd8061ebc4abf:BENCHMARK.json"], cwd=ROOT,
        capture_output=True, text=True).stdout or "null")
    if before is not None:  # a checkout with its history: the parent's file
        assert only_appended(before, bench)
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "deepseek_v32"
    assert c.traffic["job"] == "bare_frozen" and c.workload["traffic"] == "bare-dsa-warmup-16k"
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert set(STANDING) | set(NEW) <= {m["name"] for m in c.per_layer}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW[0])
    assert listed[at:at + 4] == NEW
    for m in bench["per_layer"][at:at + 4]:
        assert m["workloads"] == [CELL] and m["moves"] == "bare_tok_s_chip"
        assert m["source"] == "device_trace"
        assert (m["unit"], m["better"]) == (("%", "higher") if m["name"].endswith("roofline")
                                            else ("s", "lower"))
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["kernel.splash_fwd_roofline"]["layer"] == by_name["kernel.splash_s"]["layer"]
    assert by_name["kernel.dsa_kl_s"]["layer"] == by_name["kernel.dsa_kl_roofline"]["layer"]
    for name in STANDING:  # appended behind the cells that stood
        assert by_name[name]["workloads"].index(CELL) >= 1, name
    # the step makes ONE forward attention call a layer and no backward one:
    # the standing shares count two and one, and would read over 100%
    assert {n for n, m in by_name.items() if CELL in m["workloads"]
            and ("roofline" in n or "mfu" in n)} == {
        "model.mfu", "kernel.dsa_kl_roofline", "kernel.splash_fwd_roofline"}
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert bench["run_seconds"] == 48 and manifest.problems(ROOT) == []


def test_the_configuration_is_the_catalogs_row_but_for_its_cut():
    cfg = read(CONFIG)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "DeepSeek-V3.2-Exp")
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == REDUCED
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 61, "first_k_dense_replace": 3, "n_routed_experts": 256,
        "vocab_size": 129280, "num_nextn_predict_layers": 1}
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"]  # the nested group whole
    dep = cfg["deployment"]
    assert dep["chips_per_layer"] == 16 and "sixteen" in dep["what"]
    assert dep["heads_held"] == "all" and cfg["num_attention_heads"] == 128
    assert dep["experts_held"] == [0, cfg["n_routed_experts"]] == [0, 16]
    assert dep["chips_per_layer"] * 16 == dep["router_outputs"] == 256
    assert dep["published_layers"] == [0, 4]
    assert cfg["vocab_size"] * dep["vocabulary_slices"] == 129280
    pc = manifest.adapter_for(CONFIG, cfg).config(cfg)
    assert (pc.dim, pc.n_heads, pc.n_held_heads, pc.ffn_hidden) == (7168, 128, 128, 18432)
    assert (pc.q_lora_rank, pc.kv_lora_rank, pc.qk_nope_head_dim, pc.qk_rope_head_dim,
            pc.v_head_dim) == (1536, 512, 128, 64, 128)
    assert (pc.index_n_heads, pc.index_head_dim, pc.index_topk, pc.dsa_stage) == (
        64, 128, 2048, "warmup")
    assert (pc.num_experts, pc.n_held, pc.top_k, pc.n_group, pc.topk_group) == (
        256, 16, 8, 8, 4)
    assert (pc.router_score, pc.topk_method, pc.norm_topk_prob, pc.routed_scaling,
            pc.gate_eps) == ("sigmoid", "noaux_tc", True, 2.5, 1e-20)
    assert (pc.moe_intermediate_size, pc.shared_intermediate_size) == (2048, 2048)
    assert (pc.yarn_factor, pc.yarn_original_max, pc.yarn_mscale, pc.yarn_mscale_all_dim) == (
        40.0, 4096, 1.0, 1.0)
    from torchft_tpu.models import CONFIGS

    assert pc == CONFIGS["deepseek_v32_share"]  # the preset IS the file
    recipe = cfg["recipe"]
    assert (recipe["batch_size"], recipe["seq_len"], recipe["remat"], recipe["lr"]) == (
        1, S, "none", 1e-3)
    assert recipe["attention"] == "splash" and recipe["stage"] == "warmup"
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 8
    for word in ("halves", "LayerNorm", "Hadamard", "FP8", "MEAN over positions",
                 "expert_bias", "sqrt(fan_in)", "second stage"):
        assert any(word in line for line in cfg["assumed"]), word
    for word in ("131,072", "16,384", "4,519,675,136", "69,797,120", "GiB"):
        assert word in cfg["cut"], word
    # no width is cut
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k for k in cfg["reduced"]
                   if k != "vocab_size")
    adapter = manifest.adapter_for(CONFIG, cfg)
    for key, value in (("hidden_size", 4096), ("q_lora_rank", 768), ("kv_lora_rank", 256),
                       ("index_head_dim", 64), ("index_n_heads", 32),
                       ("moe_intermediate_size", 1024), ("intermediate_size", 9216),
                       ("num_experts_per_tok", 4)):
        changed = {**cfg, key: value}
        assert sorted(k for k, v in row["config"].items() if changed.get(k) != v) != differ
        assert adapter.config(changed) != pc  # it shows in the program's config object


def test_what_the_adapter_refuses():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    for changed, word in (
            ({"sliding_window": 4096}, "cannot express key 'sliding_window'"),
            ({"num_nextn_predict_layers": 1}, "one value of 'num_nextn_predict_layers'"),
            ({"ep_size": 8}, "one value of 'ep_size'"),
            ({"scoring_func": "softmax"}, "scoring_func"),
            ({"n_routed_experts": 32}, "experts held"),
            ({"deployment": {**cfg["deployment"], "heads_held": [0, 8]}}, "ALL the layer's"),
            ({"rope_scaling": {**cfg["rope_scaling"], "type": "linear"}}, "YaRN"),
            ({"recipe": {**cfg["recipe"], "stage": "sparse"}}, "recipe.stage"),
            ({"recipe": {**cfg["recipe"], "expert_bias": {"seed": 1, "scale": 0.01}}},
             "recipe.expert_bias"),
            ({"topk_method": "greedy"}, "topk_method")):
        with pytest.raises(ValueError, match=word):
            adapter.config({**cfg, **changed})


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    assert adapter.num_params(cfg) == 4_519_675_136
    assert adapter.num_trainable(cfg) == 69_797_120
    assert [adapter.layers_with(cfg, k) for k in ("attention", "dsa_kl", "grouped_matmul")] == [
        5, 5, 4]
    # ISSUE 67's products a token and layer (x 2 for multiply-adds)
    proj = 2 * (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256 + 128 * 128 * 7168)
    scores = 2 * 128 * (192 + 128) * (S + 1) / 2
    experts = 2 * (7168 * 256 + (1 + 8 * 16 / 256) * 3 * 7168 * 2048)
    dense = 2 * 3 * 7168 * 18432
    assert round(proj / 1e6) == 374 and round(scores / 1e6) == 671
    assert round(experts / 1e6) == 136 and round(dense / 1e6) == 793
    assert adapter.frozen_flops_per_token(cfg, S) == pytest.approx(
        5 * (proj + scores) + 4 * experts + dense, rel=1e-12)
    ix_proj = 2 * (1536 * 8192 + 7168 * 128 + 7168 * 64)
    ix_scores = 2 * 64 * 128 * (S + 1) / 2
    assert round(3 * ix_scores / 1e6) == 403 and round(ix_proj / 1e6) == 28
    # the projections forward and into their weights (x 2: their inputs are
    # frozen; ISSUE 67's 487M counted x 3), the scores x 3, element-wise work
    assert adapter.indexer_flops_per_token(cfg, S) == pytest.approx(
        5 * (2 * ix_proj + 3 * ix_scores + (128 + 6) * (S + 1) / 2), rel=1e-12)
    total = adapter.train_flops_per_token(cfg, S)
    assert total == adapter.frozen_flops_per_token(cfg, S) + adapter.indexer_flops_per_token(
        cfg, S)
    assert 1.40e14 < total * S < 1.50e14  # "about 1.5e14 a step"
    att = adapter.KERNEL_COSTS["attention"](cfg, 1, S, "fwd")
    pairs = S * (S + 1) / 2
    assert att["flops"] == 2 * 128 * pairs * (192 + 128)
    with pytest.raises(KeyError):
        adapter.KERNEL_COSTS["attention"](cfg, 1, S, "bwd")  # the frozen stage has none
    fwd, bwd = (adapter.KERNEL_COSTS["dsa_kl"](cfg, 1, S, p) for p in ("fwd", "bwd"))
    assert fwd["flops"] == pairs * (2 * 64 * 128 + 128 + 6)
    assert bwd["flops"] == pairs * 4 * 64 * 128
    main = 2.0 * S * 128 * 2 * 192
    assert fwd["bytes"] == main + 2.0 * S * (64 * 128 + 128) + 4.0 * S * 64
    assert bwd["bytes"] > fwd["bytes"]
    gm = adapter.KERNEL_COSTS["grouped_matmul"](cfg, 1, S, "fwd")
    assert gm["flops"] == 2.0 * (S * 8 * 16 / 256) * 7168 * 2048  # 8,192 rows, 512 an expert


def test_the_timed_loop_is_bares_but_for_the_held_tree():
    """``bare_frozen.run`` is ``bare.run`` (through ``bare_routed.run``, a
    test of its own) but for the statement that makes the check, the one
    that hands out the compiled step's scopes, and the statements that carry
    the held tree: the line that makes it, the step's third argument at its
    definition and its two call sites, and the loss's first argument, which
    is both trees while the gradient is over the donated one alone."""
    rx = re.compile(r"    verdict = .*?\n(?=    marks\[\"check_s\"\])", re.S)
    scopes = re.compile(r"        obs\[\"scopes\"\] = .*?\n(?=    peak = )", re.S)
    a, b = inspect.getsource(bare.run), inspect.getsource(frozen.run)
    assert len(rx.findall(b)) == 1 and "frozen_check(" in rx.findall(b)[0]
    assert len(scopes.findall(b)) == 1
    b = scopes.sub("", rx.sub("", b))
    assert b.count("    held = adapter.held(seed % SEEDS, pc)\n") == 1
    b = b.replace("    held = adapter.held(seed % SEEDS, pc)\n", "")
    assert b.count("opt_state, held, tokens)") == 3  # the definition and two call sites
    b = b.replace("opt_state, held, tokens)", "opt_state, tokens)")
    # the gradient is over the donated tree alone: the loss closes over the held one
    ours = ("        loss, grads = jax.value_and_grad(lambda p: loss_(\n"
            "            {**p, **held}, tokens, tokens, pc, remat=recipe[\"remat\"]))(params)\n")
    theirs = ("        loss, grads = jax.value_and_grad(loss_)(\n"
              "            params, tokens, tokens, pc, remat=recipe[\"remat\"])\n")
    assert b.count(ours) == 1 and a.count(theirs) == 1
    b = b.replace(ours, theirs)
    assert "held" not in b
    assert rx.sub("", a) == b


def test_the_job_kinds_own_step_runs_at_tiny_widths():
    """The fused step as ``bare_frozen.run`` writes it (its ``def step`` taken
    from the source and given a tiny configuration's adapter): three donated
    steps, the loss falls, the optimizer state holds the trainable leaves'
    moments alone and the held tree is bitwise what it was."""
    import textwrap

    import numpy as np
    import optax

    source = inspect.getsource(frozen.run)
    body = re.search(r"    def step\(params, opt_state, held, tokens\):\n.*?\n(?=\n    jstep = )",
                     source, re.S)[0]
    cfg = tiny_config()
    adapter = manifest.adapter_for(CONFIG, cfg)
    init_, loss_, _ = adapter.program()
    pc, recipe = adapter.config(cfg), cfg["recipe"]
    tx = optax.adamw(recipe["lr"], weight_decay=recipe["weight_decay"])
    scope = {"jax": jax, "optax": optax, "loss_": loss_, "pc": pc, "recipe": recipe, "tx": tx}
    exec(textwrap.dedent(body), scope)
    jstep = jax.jit(scope["step"], donate_argnums=(0, 1))
    params = init_(jax.random.PRNGKey(3), pc)
    held = adapter.held(3, pc)
    assert sorted(params) == ["indexer"] and sorted(held) == ["embed", "expert_bias", "layers"]
    opt_state = tx.init(params)
    assert sum(x.size for x in jax.tree_util.tree_leaves(opt_state) if x.ndim) == 2 * sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    before = jax.device_get(held)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 96), 0, cfg["vocab_size"])
    losses = []
    for _ in range(3):
        params, opt_state, loss = jstep(params, opt_state, held, tokens)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    for a, b in zip(jax.tree_util.tree_leaves(before), jax.tree_util.tree_leaves(held)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_job_kind_shares_bare_routeds_check_and_names_no_model():
    routed = manifest.load_module(ROOT, "jobs", "bare_routed")
    for name in ("decisions", "router_precision", "compare", "scopes_of"):
        assert inspect.getsource(getattr(frozen, name)) == inspect.getsource(
            getattr(routed, name)), name
    assert inspect.getsource(frozen.compare) == inspect.getsource(bare.compare)
    text = open(f"{ROOT}/chipbench/jobs/bare_frozen.py").read().split('"""', 2)[2]
    for word in ("deepseek", "dsa", "indexer", "torchft_tpu.models"):
        assert word not in text.lower(), word


# ---- the cell's check at tiny widths on the CPU

CHECK = read(f"{ROOT}/chipbench/traffic/bare-dsa-warmup-16k.json")["check"]

@pytest.fixture(scope="module")
def rehearsal():
    cfg = tiny_config()
    adapter = manifest.adapter_for(CONFIG, cfg)
    adapter.reference.QUERY_BLOCK = 32  # 80 positions: two whole blocks and a part
    sample = {**CHECK["sample"], "sequences": 2, "positions": 8,
              "grad_leaves": adapter.GRAD_LEAVES}
    pc = adapter.config(cfg)
    tokens, positions = adapter.reference.check_sample(cfg, sample, SEQ)
    params = jax.device_get({
        **adapter.program()[0](jax.random.PRNGKey(sample["seed"]), pc),
        **adapter.held(sample["seed"], pc)})
    ref = adapter.reference.answers(params, tokens, cfg, positions, sample)
    return cfg, adapter, sample, ref


def test_the_check_passes_in_float32_and_compares_what_issue_67_lists(rehearsal):
    cfg, adapter, sample, ref = rehearsal
    assert sample["grad_leaves"] == CHECK["sample"]["grad_leaves"] == [
        f"indexer.{run}.{leaf}" for run in ("00_dense", "04_moe")
        for leaf in ("w_iq", "w_ik", "w_iw", "k_bias")]
    assert ref["logits"].shape == (2, 8, 64) and ref["layer_losses"].shape == (5,)
    assert ref["routing"].shape == (4, 2 * SEQ, 4) and ref["router_in"].shape == (4, 2 * SEQ, 64)
    assert abs(float(ref["loss"]) - float(ref["layer_losses"].sum())) < 1e-5
    verdict = frozen.frozen_check(adapter, cfg, sample, SEQ, ref, F32)
    assert verdict["ok"], verdict
    got = verdict["arithmetic"]
    assert sorted(got) == sorted(
        ["logits_rel", "loss_abs", "grad_norm_rel", "layer_loss_rel", "ok"]
        + ["grad_rel." + p for p in sample["grad_leaves"]])
    assert verdict["decisions"]["differ_pairs"] == 0 and verdict["router"]["prob_rel"] < 1e-6
    assert verdict["free"]["ok"]
    # the limits of the traffic file are there for every number compared
    assert set(F32["tolerances"]) == set(CHECK["tolerances"])


# a compile of the whole check again, 45 s; tests/test_deepseek_v32.py has every fault
@pytest.mark.slow
def test_the_check_refuses_a_fault_in_the_kernels(rehearsal, monkeypatch):
    from torchft_tpu.ops import dsa as kernels

    cfg, adapter, sample, ref = rehearsal
    monkeypatch.setattr(kernels, "RELU", False)
    verdict = frozen.frozen_check(adapter, cfg, sample, SEQ, ref, F32)
    assert not verdict["ok"] and not verdict["arithmetic"]["ok"]
    assert verdict["decisions"]["ok"] and verdict["router"]["ok"]  # the trunk is untouched
    assert verdict["arithmetic"]["logits_rel"] < 1e-4  # and so is the last layer's output


def test_two_layers_that_err_against_each_other_fail_the_terms(rehearsal):
    ref = rehearsal[3]
    system = {**ref, "layer_losses": ref["layer_losses"] * [1.01, 0.99, 1, 1, 1]}
    assert abs(system["layer_losses"].sum() - ref["layer_losses"].sum()) < 1e-3
    assert not frozen.arithmetic(system, ref, F32["tolerances"])["ok"]
    assert frozen.arithmetic(dict(ref), ref, F32["tolerances"])["ok"]


# ---- the cell in a temporary copy, and its layer metrics

@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("dsv32")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = tiny_config()
    write(f"{root}/chipbench/configs/tiny-dsv32.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-dsv32", "source": "x", "reduced": cfg["reduced"],
                             "file": "chipbench/configs/tiny-dsv32.json", "why": "x"})
    add_cell(root, bench, "tiny-dsv32.bare-dsa-warmup-16k", "tiny-dsv32", "bare-dsa-warmup-16k",
             CELL)
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


def test_the_new_metrics_read_the_scopes_and_nothing_from_a_parent(tiny_root):
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-dsv32.bare-dsa-warmup-16k")
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "fusion.3": 2.0, "fusion.4": 0.25,
           # the kernels' names as the chip's trace gave them (PR 67, call A): they run a
           # sequence at a time under vmap; a plain name (no vmap) reads the same
           "splash_mha_fwd_residuals.7": 4.0, "vmap_dsa_kl_fwd_lse_.41": 8.0,
           "vmap_dsa_kl_fwd_.41": 16.0, "dsa_kl_bwd.4": 32.0, "fusion.5": 64.0,
           "fusion.6": 128.0, "gmm.3": 256.0, "fusion_dsa_kl_fwd_like.1": 512.0}
    scopes = {"fusion.1": "jit(step)/jvp()/while/body/closed_call/mla/q/dot_general",
              "fusion.2": "jit(step)/jvp()/while/body/closed_call/jvp(dsa/index_q)/dot_general",
              "fusion.3": "jit(step)/transpose(jvp(dsa/index_k))/dot_general",
              "fusion.4": "jit(step)/transpose(jvp(dsa/kl))/reduce_sum",
              "fusion.5": "jit(step)/jvp()/while/body/closed_call/ffn/block/dot_general",
              "fusion.6": "jit(step)/jvp()/while/body/closed_call/moe/shared/dot_general"}
    obs = {"trace": {"ops": ops, "chips_traced": 1}, "steps_in_window": 2, "scopes": scopes,
           "device": {"kind": "TPU v5 lite"}, "e2e": {"bare_tok_s_chip": 6000.0}}

    def value(name, obs=obs):
        spec = cell.layer_metric(name)
        return cell.reducer(spec["reducer"]).reduce(obs, cell, **spec.get("args", {}))

    assert value("dsa.indexer_s") == (0.5 + 2.0 + 0.25 + 8.0 + 16.0 + 32.0) / 2
    assert value("kernel.dsa_kl_s") == (8.0 + 16.0 + 32.0) / 2
    assert 0 < value("kernel.dsa_kl_roofline") < 105
    assert 0 < value("kernel.splash_fwd_roofline") < 105
    assert value("kernel.splash_s") == 4.0 / 2 and value("mla.mixer_s") == (1.0 + 4.0) / 2
    assert value("ffn.block_s") == 64.0 / 2 and value("moe.shared_s") == 128.0 / 2
    assert value("kernel.gmm_s") == 256.0 / 2
    # the whole step's share of the peak: the adapter's required FLOPs a token
    assert value("model.mfu") == pytest.approx(
        100 * 6000.0 * cell.adapter().train_flops_per_token(
            cell.config, cell.config["recipe"]["seq_len"]) / 197e12)
    # a parent's program has no such scope and no such kernel: nothing to
    # read, the metric is left out, nothing raises
    parent = {**obs, "trace": {"ops": {"fusion.9": 1.0}, "chips_traced": 1}, "scopes": {}}
    assert all(value(n, parent) is None for n in NEW)
    assert all(value(n, {**obs, "trace": None}) is None for n in NEW)


def test_the_command_line_ends_without_a_result_off_the_chip(tmp_path):
    """``chipbench/run.py`` on the new cell here: the reference's child finds
    no TPU and says so, the command prints no result line and exits 2."""
    out = subprocess.run(
        [sys.executable, f"{ROOT}/chipbench/run.py", "--workload", CELL, "--seed",
         "2147485035", "--seconds", "1"], capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path), env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 2, out.stdout[-2000:] + out.stderr[-2000:]
    assert "no TPU" in out.stderr and '"correct"' not in out.stdout
