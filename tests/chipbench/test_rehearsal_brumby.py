"""CPU rehearsal of what PR 56 put into the benchmark, for tests only: the
cell as appended entries, the configuration file against the catalog's row,
the adapter's counts against the program's and against the issue's
arithmetic, the new layer metrics on a made-up trace (and on a parent's,
which has nothing for them to read), and the cell's path through
``chipbench/run.py`` up to where it finds no TPU. Refused as a measurement
like every CPU run."""

import json
import subprocess
import sys

import pytest

from chipbench_helpers import ROOT, check_cell, check_config_files, check_contract, read

from chipbench import manifest  # noqa: I001

CELL = "brumby-14b-base.bare-retention"
CONFIG = f"{ROOT}/chipbench/configs/brumby-14b-base.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ["retention.mixer_s", "kernel.retention_s", "kernel.retention_roofline", "ffn.block_s"]
STANDING = ["model.step_device_s", "model.mfu"]
S = 16384


def test_the_repos_own_manifest_holds_the_cell_as_appended_entries():
    bench = check_contract(ROOT)
    names = [w["name"] for w in bench["workloads"]]
    assert names.index(CELL) == 14 and len(names) >= 15
    assert [c["name"] for c in bench["configs"]].index("brumby-14b-base") == 9
    # fifteen cells of which two on four chips
    assert [w["name"] for w in bench["workloads"][:15] if w["chips"] == 4] == [
        "internlm2-1.8b.kill-rejoin-4g", "internlm2-1.8b.managed-4g"]
    c = check_cell(ROOT, CELL)
    assert c.chips == 1 and c.config["adapter"] == "brumby" and c.traffic["job"] == "bare"
    check_config_files(ROOT)
    assert {m["name"] for m in c.end_to_end} == {"bare_tok_s_chip", "peak_hbm_gib", "setup_s"}
    # AMONG the cell's metrics, not all of them: a later PR may append one
    assert set(STANDING) | set(NEW) <= {m["name"] for m in c.per_layer}
    listed = [m["name"] for m in bench["per_layer"]]
    at = listed.index(NEW[0])
    assert listed[at:at + 4] == NEW
    for m in bench["per_layer"][at:at + 4]:
        assert m["workloads"][0] == CELL and m["moves"] == "bare_tok_s_chip"
        assert m["source"] == "device_trace"
        assert (m["unit"], m["better"]) == (("%", "higher") if "roofline" in m["name"]
                                            else ("s", "lower"))
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert bench["run_seconds"] == 48 and manifest.problems(ROOT) == []


def test_the_configuration_is_the_catalogs_row_but_for_its_cut():
    cfg = read(CONFIG)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Brumby-14B-Base")
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k, "absent") != v)
    assert differ == sorted(cfg["reduced"]) == ["num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    dep = cfg["deployment"]
    assert cfg["vocab_size"] * dep["vocabulary_slices"] == 151936
    assert dep["published_layers"] == [0, 3] and "8 chips" in dep["what"]
    pc = manifest.adapter_for(CONFIG, cfg).config(cfg)
    assert (pc.dim, pc.n_heads, pc.n_kv_heads, pc.head_dim, pc.ffn_hidden) == (
        5120, 40, 8, 128, 17408)
    assert (pc.n_layers, pc.vocab_size, pc.rope_theta, pc.norm_eps) == (4, 18992, 1e6, 1e-6)
    assert [n for n, _, _ in pc.runs()] == [f"{i:02d}_retention" for i in range(4)]
    recipe = cfg["recipe"]
    assert (recipe["batch_size"], recipe["seq_len"], recipe["remat"]) == (1, S, "full")
    assert recipe["attention"] == "power_retention" and recipe["loss_chunk"] == 2048
    assert {"assumed", "cut", "stands_for"} <= set(cfg) and len(cfg["assumed"]) >= 8
    for word in ("degree 2", "logsigmoid", "INSIDE the square", "eps_n 1e-6", "0.9 to 0.9999"):
        assert any(word in line for line in cfg["assumed"]), word
    assert not any(k.endswith(("_dim", "_rank")) or "size" in k for k in cfg["reduced"]
                   if k != "vocab_size")
    # a cut that changes a width is refused: it shows in the program's config object
    adapter = manifest.adapter_for(CONFIG, cfg)
    for key, value in (("hidden_size", 4096), ("head_dim", 64), ("intermediate_size", 8704),
                       ("num_key_value_heads", 4), ("num_attention_heads", 16)):
        assert adapter.config({**cfg, key: value}) != pc
    for key, value in (("sliding_window", 4096), ("attention_bias", True), ("experts", 8)):
        with pytest.raises(ValueError, match=key):
            adapter.config({**cfg, key: value})


def test_params_flops_and_kernel_costs_come_from_the_shapes():
    cfg = read(CONFIG)
    adapter = manifest.adapter_for(CONFIG, cfg)
    layer = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 8 + 3 * 5120 * 17408
             + 2 * 5120 + 2 * 128)
    assert layer == 330_352_904  # the issue's count of a layer
    assert adapter.num_params(cfg) == 4 * layer + 2 * 18992 * 5120 + 5120 == 1_515_894_816
    assert adapter.layers_with(cfg, "retention") == 4
    fwd = adapter.forward_flops_per_token(cfg, S)
    retention = 48 * 2 * 8256 * 129  # a read a query head, an update a key/value head
    one = (2 * 5120 * (5120 + 2 * 1024 + 5120) + 2 * 5120 * 8 + retention
           + 3 * 2 * 5120 * 17408)
    assert fwd == pytest.approx(4 * one + 2 * 5120 * 18992, rel=1e-12)
    assert adapter.forward_flops_per_token(cfg, 2 * S) == fwd  # constant in the length
    # the issue's arithmetic: 101 MFLOP a token a layer forward without the normaliser
    assert 48 * 2 * 8256 * 128 == pytest.approx(101e6, rel=0.01)
    assert 0.11 < 4 * retention / fwd < 0.14  # the feed-forward is two thirds
    assert 0.64 < 4 * 3 * 2 * 5120 * 17408 / fwd < 0.68
    assert adapter.train_flops_per_token(cfg, S) == 3 * fwd
    cost = adapter.KERNEL_COSTS["retention"](cfg, 1, S, "fwd")
    assert cost["flops"] == S * retention
    assert cost["bytes"] == S * (2 * 2 * 5120 + 2 * 2 * 1024 + 4 * 8)
    back = adapter.KERNEL_COSTS["retention"](cfg, 1, S, "bwd")
    assert back["flops"] == 2 * cost["flops"] and back["bytes"] > cost["bytes"]
    with pytest.raises(KeyError):
        adapter.KERNEL_COSTS["retention"](cfg, 1, S, "sideways")


def test_the_new_metrics_read_the_scopes_and_nothing_from_a_parent():
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, CELL)
    ops = {"fusion.1": 1.0, "fusion.2": 0.5, "power_retention_fwd.3": 2.0,
           "power_retention_bwd.1": 4.0, "fusion.4": 0.25, "fusion.5": 16.0, "fusion.6": 32.0,
           "fusion.7": 64.0}
    scopes = {"fusion.1": "jit(step)/while/body/retention/in_proj/dot_general",
              "fusion.2": "jit(step)/transpose(jvp(retention/gate))/mul",
              "power_retention_fwd.3": "jit(step)/checkpoint/retention/kernel/pallas_call",
              "fusion.4": "jit(step)/kda/scan/mul",  # another kind's scope
              "fusion.5": "jit(step)/checkpoint/ffn/block/while/body/dot_general",
              "fusion.6": "jit(step)/transpose(jvp(ffn/block))/dot_general",
              "fusion.7": "jit(step)/attn/mixer/dot_general"}
    obs = {"trace": {"ops": ops, "chips_traced": 1}, "steps_in_window": 2, "scopes": scopes,
           "device": {"kind": "TPU v5 lite"}}

    def value(name, obs=obs):
        spec = cell.layer_metric(name)
        return cell.reducer(spec["reducer"]).reduce(obs, cell, **spec.get("args", {}))

    assert value("retention.mixer_s") == (1.0 + 0.5 + 2.0 + 4.0) / 2  # the kernels by name too
    assert value("kernel.retention_s") == (2.0 + 4.0) / 2
    assert value("ffn.block_s") == (16.0 + 32.0) / 2
    # 4 layers x (2 forward + 1 backward of twice the work) x 8.5 ms over 3 s
    floor = 4 * 4 * S * 48 * 2 * 8256 * 129 / 197e12
    assert value("kernel.retention_roofline") == pytest.approx(100 * floor / 3.0, rel=1e-6)
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
