"""CPU rehearsal of what PR 52 put into the benchmark, for tests only: the
cell as appended entries, the configuration file against the catalog's row,
the adapter's counts against the program's and against the issue's
arithmetic, the new layer metrics on a made-up trace (and on a parent's,
which has nothing for them to read), and the cell's path through
``chipbench/run.py`` up to where it finds no TPU. The Manager path of the
kind is tests/test_nemotron_h.py's. Refused as a measurement like every CPU
run."""

import json
import subprocess
import sys

import pytest

from chipbench_helpers import ROOT, check_cell, check_config_files, check_contract, read

from chipbench import manifest  # noqa: I001

CELL = "nemotron-3-nano-30b-a3b.bare-ssd-8k"
CONFIG = f"{ROOT}/chipbench/configs/nemotron-3-nano-30b-a3b.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["ssd.mixer_s", "kernel.ssd_s", "kernel.ssd_roofline", "kernel.gmm_relu2_roofline"]
STANDING = ["model.step_device_s", "model.mfu", "kernel.splash_s", "kernel.splash_roofline",
            "kernel.gmm_s", "moe.block_s", "moe.route_s"]
S = 8192


def test_the_repos_own_manifest_holds_the_cell_as_appended_entries():
    bench = check_contract(ROOT)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 13 and len(names) >= 14
    assert [c["name"] for c in bench["configs"]].index("nemotron-3-nano-30b-a3b") == 8
    # fourteen cells, two on four chips of three allowed
    assert [w["name"] for w in bench["workloads"][:14] if w["chips"] == 4] == [
        "internlm2-1.8b.kill-rejoin-4g", "internlm2-1.8b.managed-4g"]
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "nemotron_h"
    assert c.traffic["job"] == "bare_routed"
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    assert {m["name"] for m in c.per_layer} == set(STANDING) | set(NEW)
    # SwiGLU's count of twelve products a layer is not this block's eight
    # ... and moe.shared_s is pinned to Ling's cell alone by a test that stands
    # (tests/chipbench/test_rehearsal_ling.py): PERF.md section 7, PR 52
    for left_out in ("kernel.gmm_roofline", "moe.shared_s"):
        assert CELL not in next(m for m in bench["per_layer"]
                                if m["name"] == left_out)["workloads"]
    at = [m["name"] for m in bench["per_layer"]].index(NEW[0])
    assert [m["name"] for m in bench["per_layer"][at:at + 4]] == NEW
    for m in bench["per_layer"][at:at + 4]:
        assert m["workloads"] == [CELL] and m["moves"] == "bare_tok_s_chip"
        assert m["source"] == "device_trace"
        assert (m["unit"], m["better"]) == (("%", "higher") if "roofline" in m["name"]
                                            else ("s", "lower"))
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert bench["run_seconds"] == 48 and manifest.problems(ROOT) == []


def test_the_configuration_is_the_catalogs_row_but_for_its_cut():
    cfg = read(CONFIG)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts", "vocab_size"])
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # the kept layers are the published ones in their order: the opening
    # segment and one whole copy of the unit the pattern repeats four times
    published = row["config"]["hybrid_override_pattern"]
    assert cfg["hybrid_override_pattern"] == published[:13] == "MEMEM*" + "EMEMEM*"
    assert published[6:34] == "EMEMEM*" * 4 and len(published) == 52
    dep = cfg["deployment"]
    assert dep["experts_held"] == [0, cfg["n_routed_experts"]] and dep["router_outputs"] == 128
    assert dep["chips_per_layer"] * cfg["n_routed_experts"] == 128
    assert cfg["vocab_size"] * dep["vocabulary_slices"] == 131072
    assert dep["published_layers"] == [0, 12]
    pc = manifest.adapter_for(CONFIG, cfg).config(cfg)
    assert pc.pattern == "MEMEM*EMEMEM*" and pc.kinds().count("mamba") == 6
    assert (pc.dim, pc.mamba_num_heads, pc.mamba_head_dim, pc.ssm_state_size,
            pc.mamba_n_groups, pc.conv_kernel) == (2688, 64, 64, 128, 8, 4)
    assert (pc.n_heads, pc.n_kv_heads, pc.head_dim) == (32, 2, 128)
    assert (pc.moe_intermediate_size, pc.shared_intermediate_size, pc.expert_act) == (
        1856, 3712, "relu2")
    assert (pc.num_experts, pc.n_held, pc.top_k, pc.n_group, pc.routed_scaling) == (
        128, 8, 6, 1, 2.5)
    assert (pc.router_score, pc.norm_topk_prob, pc.gate_eps) == ("sigmoid", True, 1e-20)
    assert (cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]) == (2, S)
    assert cfg["recipe"]["attention"] == "splash" and cfg["recipe"]["remat"] == "full"
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 8
    assert "16 chips" in dep["what"] and "read" in dep["what"]
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k for k in cfg["reduced"]
                   if k != "vocab_size")
    # a cut that changes a width is refused: it shows in the program's config object
    adapter = manifest.adapter_for(CONFIG, cfg)
    for key, value in (("hidden_size", 2048), ("mamba_head_dim", 32), ("ssm_state_size", 64),
                       ("moe_intermediate_size", 928), ("num_experts_per_tok", 4),
                       ("moe_shared_expert_intermediate_size", 1856), ("head_dim", 64)):
        assert adapter.config({**cfg, key: value}) != pc


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    assert adapter.num_params(cfg) == 867_977_088 + 640
    assert (adapter.layers_with(cfg, "ssd"), adapter.layers_with(cfg, "attention"),
            adapter.layers_with(cfg, "grouped_matmul")) == (6, 2, 5)
    fwd = adapter.forward_flops_per_token(cfg, S)
    ssd = 2 * (8 * 128 * 128 + 64 * (128 * 64 + 2 * 128 * 64))  # C B^T once a GROUP
    mamba = 2 * 2688 * 10304 + 2 * 4 * 6144 + ssd + 2 * 4096 * 2688
    attn = 2 * 2 * 2688 * 4096 + 2 * 2 * 2688 * 256 + 2 * 2 * 4096 * (S + 1) / 2
    moe_ = 2 * 2688 * 128 + 2 * 2 * 2688 * (6 * 8 / 128 * 1856 + 3712)
    assert fwd == pytest.approx(6 * mamba + 2 * attn + 5 * moe_ + 2 * 2688 * 16384, rel=1e-12)
    # the issue's arithmetic: 1.04 GFLOP a token, the mixers 47%, 465 in their projections
    assert fwd == pytest.approx(1.04e9, rel=0.01) and 0.46 < 6 * mamba / fwd < 0.48
    assert 6 * ssd == pytest.approx(20e6, rel=0.03)
    assert adapter.train_flops_per_token(cfg, S) == 3 * fwd
    cost = adapter.KERNEL_COSTS["ssd"](cfg, 2, S, "fwd")
    assert cost["flops"] == 2 * S * ssd
    assert cost["bytes"] == 2 * S * (2 * 2 * 4096 + 2 * 2 * 1024 + 4 * 64)
    back = adapter.KERNEL_COSTS["ssd"](cfg, 2, S, "bwd")
    assert back["flops"] == 3 * cost["flops"] and back["bytes"] > cost["bytes"]
    gmm = adapter.KERNEL_COSTS["grouped_matmul"](cfg, 2, S, "fwd")
    assert gmm["flops"] == 2 * 6144 * 2688 * 1856  # 768 rows an expert
    att = adapter.KERNEL_COSTS["attention"](cfg, 2, S, "fwd")
    assert att["flops"] == 2 * 2 * 2 * 32 * S * (S + 1) / 2 * 128
    for kernel in ("ssd", "attention", "grouped_matmul"):
        with pytest.raises(KeyError):
            adapter.KERNEL_COSTS[kernel](cfg, 2, S, "sideways")


def test_the_new_metrics_read_the_scopes_and_nothing_from_a_parent():
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, CELL)
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "ssd_fwd.3": 2.0, "ssd_bwd.1": 4.0,
           "fusion.4": 0.25, "gmm.3": 8.0, "tgmm.1": 8.0, "fusion.5": 16.0,
           "selective_scan_fwd.1": 32.0, "fusion.7": 64.0}
    scopes = {"fusion.1": "jit(step)/while/body/ssd/in_proj/dot_general",
              "fusion.2": "jit(step)/transpose(jvp(ssd/norm))/mul",
              "ssd_fwd.3": "jit(step)/checkpoint/ssd/scan/pallas_call",
              "fusion.4": "jit(step)/ssm/scan/mul",  # Mamba-1's scope: another kind's
              "fusion.5": "jit(step)/moe/shared/dot_general",
              "fusion.7": "jit(step)/attn/mixer/dot_general"}
    obs = {"trace": {"ops": ops, "chips_traced": 1}, "steps_in_window": 2, "scopes": scopes,
           "device": {"kind": "TPU v5 lite"}}

    def value(name, obs=obs):
        spec = cell.layer_metric(name)
        return cell.reducer(spec["reducer"]).reduce(obs, cell, **spec.get("args", {}))

    assert value("ssd.mixer_s") == (1.0 + 0.5 + 2.0 + 4.0) / 2  # the kernels by name too
    assert value("kernel.ssd_s") == (2.0 + 4.0) / 2
    assert 0 < value("kernel.ssd_roofline") < 5 and 0 < value("kernel.gmm_relu2_roofline")
    # eight products a layer against SwiGLU's twelve, over the same kernel time
    swiglu = cell.reducer("device_op").reduce(
        obs, cell, **read(f"{ROOT}/chipbench/layer_metrics/kernel.gmm_roofline.json")["args"])
    assert swiglu == pytest.approx(1.5 * value("kernel.gmm_relu2_roofline"))
    assert value("kernel.gmm_s") == 16.0 / 2
    # a parent's program has no such scope and no such kernel: nothing to
    # read, the metric is left out, nothing raises
    bare = {**obs, "trace": {"ops": {"fusion.9": 1.0}, "chips_traced": 1}, "scopes": {}}
    assert all(value(n, bare) is None for n in NEW)
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
