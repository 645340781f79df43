"""CPU rehearsal of what PR 40 put into the benchmark, for tests only: the
two cells as appended entries, the configuration file against the catalog's
row, the adapter's counts against the program's and against FLOPs counted
from a compiled forward pass at tiny widths (a temporary root, nothing that
is there edited), the new layer metrics on a made-up trace, and the cell's
path through ``jobs/bare_routed.py`` up to where it finds no TPU. The Manager
path of the kind is tests/test_ling.py's. Refused as a measurement like every
CPU run."""

import json
import subprocess
import sys

import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               check_contract, copy_root, files_of, only_appended, read,
                               write)

from chipbench import manifest  # noqa: I001

CELL, FOUR = "ling-3.0-flash.bare-kda-32k", "internlm2-1.8b.managed-4g"
CONFIG = f"{ROOT}/chipbench/configs/ling-3.0-flash.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["kda.mixer_s", "kernel.kda_s", "kernel.kda_roofline", "mla.mixer_s", "moe.shared_s"]
STANDING = ["model.step_device_s", "model.mfu", "kernel.splash_s", "kernel.splash_roofline",
            "kernel.gmm_s", "kernel.gmm_roofline", "moe.block_s", "moe.route_s"]
TINY = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=32,
            num_attention_heads=4, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, vocab_size=512, num_experts=8,
            num_experts_per_tok=4, n_group=4, topk_group=2, num_hidden_layers=4)


def test_the_repos_own_manifest_holds_both_cells_as_appended_entries():
    bench = check_contract(ROOT)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 8 and names.index(FOUR) == 9
    assert [c["name"] for c in bench["configs"]].index("ling-3.0-flash") == 5
    assert [w["name"] for w in bench["workloads"][:10] if w["chips"] == 4] == [
        "internlm2-1.8b.kill-rejoin-4g", FOUR]
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "ling" and c.traffic["job"] == "bare_routed"
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in c.per_layer} == set(STANDING) | set(NEW)
    at = [m["name"] for m in bench["per_layer"]].index(NEW[0])
    assert at == 74 and [m["name"] for m in bench["per_layer"][at:at + 5]] == NEW
    for m in bench["per_layer"][at:at + 5]:
        assert m["workloads"] == [CELL] and m["moves"] == "bare_tok_s_chip"
        assert m["source"] == "device_trace"
    four = check_cell(ROOT, FOUR)
    assert four.chips == 4 and four.traffic["job"] == "managed" and four.traffic["groups"] == 4
    assert {m["name"] for m in four.end_to_end} == {"tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert four.config == manifest.Cell(ROOT, bench, "internlm2-1.8b.managed-1g").config
    one = read(f"{ROOT}/chipbench/traffic/managed-1g.json")
    assert {k: v for k, v in four.traffic.items() if k not in ("what", "groups", "chips_per_group")} \
        == {k: v for k, v in one.items() if k not in ("what", "groups", "chips_per_group")}
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert bench["run_seconds"] == 48


def test_the_configuration_is_the_catalogs_row_but_for_its_cut():
    cfg = read(CONFIG)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash")
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "first_k_dense_replace", "num_experts", "vocab_size",
        "num_nextn_predict_layers"])
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    dep = cfg["deployment"]
    assert dep["experts_held"] == [0, cfg["num_experts"]] and dep["router_outputs"] == 512
    assert dep["chips_per_layer"] * cfg["num_experts"] == 512
    assert cfg["vocab_size"] * dep["vocabulary_slices"] == 157184
    pc = manifest.adapter_for(CONFIG, cfg).config(cfg)
    assert pc.kinds() == [("kda", "dense")] + [("kda", "moe")] * 3 + [("mla", "moe")] \
        + [("kda", "moe")] * 2
    assert (pc.num_experts, pc.n_held, pc.top_k, pc.n_group, pc.topk_group) == (512, 16, 8, 8, 4)
    assert (pc.router_score, pc.routed_scaling, pc.qk_head_dim) == ("sigmoid", 2.5, 192)
    assert (cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]) == (1, 32768)
    assert cfg["recipe"]["attention"] == "splash" and cfg["recipe"]["remat"] == "full"
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 10
    assert "mtp_loss_scaling_factor is 0" in cfg["cut"] and "32 chips" in dep["what"]
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k for k in cfg["reduced"]
                   if k != "vocab_size")


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    assert adapter.num_params(cfg) == 1_105_151_936
    assert adapter.layers_with(cfg, "kda") == 6 and adapter.layers_with(cfg, "attention") == 1
    assert adapter.layers_with(cfg, "grouped_matmul") == 6
    fwd = adapter.forward_flops_per_token(cfg, 32768)
    assert fwd == pytest.approx(1.352e9, rel=0.01)
    assert adapter.train_flops_per_token(cfg, 32768) == 3 * fwd
    # the six KDA mixers about half of the required work, the MLA layer a
    # quarter and more: the new mechanisms do most of the cell's work
    kda = 6 * (2 * 2560 * 4096 * 5 + 7 * 4096 * 128)
    mla = 2 * 32 * (192 + 128) * 32769 / 2
    assert 0.45 < kda / fwd < 0.55 and 0.2 < mla / fwd < 0.3
    cost = adapter.KERNEL_COSTS["grouped_matmul"](cfg, 1, 32768, "fwd")
    assert cost["flops"] == 2 * 8192 * 2560 * 768  # 512 rows an expert
    att = adapter.KERNEL_COSTS["attention"](cfg, 1, 32768, "fwd")
    assert att["flops"] == 2 * (32 * 32768 * 32769 / 2) * (192 + 128)
    scan = adapter.KERNEL_COSTS["kda"](cfg, 1, 32768, "fwd")
    assert scan["bytes"] / 819e9 > scan["flops"] / 197e12  # the HBM bound is the larger
    assert adapter.KERNEL_COSTS["kda"](cfg, 1, 32768, "bwd")["flops"] == 3 * scan["flops"]
    with pytest.raises(KeyError):
        adapter.KERNEL_COSTS["kda"](cfg, 1, 32768, "dlhs")


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ling")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = read(CONFIG)
    cfg.update(TINY, name="tiny-ling")
    cfg["deployment"] = {**cfg["deployment"], "experts_held": [8, 8], "router_outputs": 32,
                         "share_room": 4.0, "published_layers": [3, 6]}
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 128}
    write(f"{root}/chipbench/configs/tiny-ling.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-ling", "source": "x", "reduced": cfg["reduced"],
                             "file": "chipbench/configs/tiny-ling.json", "why": "x"})
    add_cell(root, bench, "tiny-ling.bare-kda-32k", "tiny-ling", "bare-kda-32k", CELL)
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


def test_the_flops_are_what_the_compiled_forward_pass_counts(tiny_root):
    """XLA's own count of the tiny configuration's forward pass on the CPU
    lies between the adapter's count with attention over every key and the
    delta rule's seven operations (the chunked form's products are more, the
    interpreted kernels' loops are counted once) and a small multiple of
    it: the count is of the right size and leaves no layer out."""
    import jax
    import jax.numpy as jnp

    root, _, _ = tiny_root
    path = f"{root}/chipbench/configs/tiny-ling.json"
    cfg = read(path)
    adapter = manifest.adapter_for(path, cfg)
    init_, _, forward_ = adapter.program()
    pc = adapter.config({**cfg, "recipe": {**cfg["recipe"], "param_dtype": "float32"}})
    params = jax.eval_shape(lambda: init_(jax.random.PRNGKey(0), pc))
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    counted = jax.jit(lambda p, t: jnp.asarray(forward_(p, t, pc, remat="none"))).lower(
        params, tokens).compile().cost_analysis()["flops"] / 128
    ours = adapter.forward_flops_per_token(cfg, 128)
    assert 0.6 * ours < counted < 3 * ours, (counted, ours)


def test_the_new_metrics_read_the_scopes_and_nothing_from_a_parent(tiny_root):
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-ling.bare-kda-32k")
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "kda_fwd.3": 2.0, "kda_bwd.1": 4.0,
           "fusion.4": 0.25, "splash_mha_fwd_residuals": 0.5, "fusion.5": 8.0,
           "fusion.6": 16.0, "gmm.3": 32.0}
    scopes = {"fusion.1": "jit(step)/kda/in_proj/dot_general",
              "fusion.2": "jit(step)/transpose(jvp(kda/gate))/mul",
              "kda_fwd.3": "jit(step)/kda/scan/kda_fwd/pallas_call",
              "fusion.4": "jit(step)/checkpoint/mla/kv/dot_general",
              "fusion.5": "jit(step)/moe/shared/dot_general",
              "fusion.6": "jit(step)/moe/experts/mul"}
    obs = {"trace": {"ops": ops, "chips_traced": 1}, "steps_in_window": 2, "scopes": scopes,
           "device": {"kind": "TPU v5 lite"}}

    def value(name, obs=obs):
        spec = cell.layer_metric(name)
        return cell.reducer(spec["reducer"]).reduce(obs, cell, **spec.get("args", {}))

    assert value("kda.mixer_s") == (1.0 + 0.5 + 2.0 + 4.0) / 2
    assert value("kernel.kda_s") == 3.0 and value("mla.mixer_s") == (0.25 + 0.5) / 2
    assert value("moe.shared_s") == 4.0
    assert value("moe.block_s") == (16.0 + 32.0) / 2  # the shared expert is not the block's
    assert 0 < value("kernel.kda_roofline") < 100
    # a parent's program has no such scope and no such kernel: nothing to
    # read, the metric is left out, nothing raises
    bare = {**obs, "trace": {"ops": {"fusion.9": 1.0}, "chips_traced": 1}, "scopes": {}}
    assert all(value(n, bare) is None for n in NEW)
    assert all(value(n, {**obs, "scopes": None}) is None
               for n in ("kda.mixer_s", "mla.mixer_s", "moe.shared_s"))


def test_the_command_line_ends_without_a_result_off_the_chip(tmp_path):
    """``chipbench/run.py`` on the new cell here: the reference's child finds
    no TPU and says so, the command prints no result line and exits 2."""
    out = subprocess.run(
        [sys.executable, f"{ROOT}/chipbench/run.py", "--workload", CELL, "--seed",
         "2147485035", "--seconds", "1"], capture_output=True, text=True, timeout=600,
        cwd=str(tmp_path), env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 2, out.stdout[-2000:] + out.stderr[-2000:]
    assert "no TPU" in out.stderr and '"correct"' not in out.stdout
