"""CPU rehearsal of the two OLMoE cells, for tests only: the configuration at
tiny widths as new files in a temporary root (nothing that is there edited),
``olmoe-1b-7b.managed-1g``'s path through ``launch.Launch``, ``worker.py``,
the ``olmoe`` adapter and the one trainer as they stand, and
``olmoe-1b-7b.bare-routed``'s through ``jobs/bare_routed.py`` up to where it
finds no TPU. Refused as a measurement like every CPU run."""

import os
import subprocess
import time

import pytest

from chipbench_helpers import (ROOT, add_cell, check_cell, check_config_files,
                               check_contract, copy_root, files_of, only_appended,
                               read, write)

from chipbench import manifest, result  # noqa: I001

TINY = dict(hidden_size=128, intermediate_size=64, num_attention_heads=4,
            num_key_value_heads=4, vocab_size=512, num_hidden_layers=2,
            num_experts=16, num_experts_per_tok=4)


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("olmoe")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    cfg = read(f"{root}/chipbench/configs/olmoe-1b-7b.json")
    cfg.update(TINY, name="tiny-olmoe")
    cfg["recipe"] = {**cfg["recipe"], "seq_len": 128}
    write(f"{root}/chipbench/configs/tiny-olmoe.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-olmoe", "source": "x",
                             "reduced": ["num_hidden_layers"],
                             "file": "chipbench/configs/tiny-olmoe.json", "why": "x"})
    add_cell(root, bench, "tiny-olmoe.bare-routed", "tiny-olmoe", "bare-routed",
             "olmoe-1b-7b.bare-routed")
    add_cell(root, bench, "tiny-olmoe.managed-1g", "tiny-olmoe", "managed-1g",
             "olmoe-1b-7b.managed-1g")
    write(f"{root}/BENCHMARK.json", bench)
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == b for p, b in before.items()) and len(now) == len(before) + 1  # the configuration
    assert only_appended(was, bench) and manifest.problems(root) == []
    return root, bench, tmp


@pytest.fixture(scope="module")
def managed(tiny_root):
    root, bench, tmp = tiny_root
    cell = manifest.Cell(root, bench, "tiny-olmoe.managed-1g")
    obs = cell.job().run(cell, seed=2147485003, seconds=1.0, trace=False,
                         out_dir=str(tmp / "out"), cache_dir=str(tmp / "cache"),
                         t_start=time.monotonic())
    return cell, obs


def test_the_repos_own_manifest_holds_the_two_cells():
    bench = check_contract(ROOT)
    assert [w["name"] for w in bench["workloads"]][4:6] == [
        "olmoe-1b-7b.bare-routed", "olmoe-1b-7b.managed-1g"]
    for name in ("olmoe-1b-7b.bare-routed", "olmoe-1b-7b.managed-1g"):
        c = check_cell(ROOT, name)
        assert c.chips == 1 and c.config["adapter"] == "olmoe"
    check_config_files(ROOT)
    bare = manifest.Cell(ROOT, bench, "olmoe-1b-7b.bare-routed")
    assert {m["name"] for m in bare.per_layer} >= {
        "kernel.gmm_s", "kernel.gmm_roofline", "model.mfu", "kernel.flash_roofline"}
    managed_ = manifest.Cell(ROOT, bench, "olmoe-1b-7b.managed-1g")
    assert {m["name"] for m in managed_.per_layer} >= {
        "kernel.gmm_1g_roofline", "moe.load_max_over_mean", "allreduce.d2h_s"}
    mistral = manifest.Cell(ROOT, bench, "mistral-7b.managed-1g")
    assert {m["name"] for m in managed_.per_layer} - {m["name"] for m in mistral.per_layer} \
        == {"kernel.gmm_1g_roofline", "kernel.flash_1g_roofline", "moe.load_max_over_mean"}


def test_the_configuration_is_the_catalogs_row_but_for_its_depth():
    cfg = read(f"{ROOT}/chipbench/configs/olmoe-1b-7b.json")
    catalog = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
               "hidden_size": 2048, "intermediate_size": 1024,
               "max_position_embeddings": 4096, "model_type": "olmoe",
               "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
               "num_experts_per_tok": 8, "num_hidden_layers": 16,
               "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
               "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}
    differ = sorted(k for k, v in catalog.items() if cfg.get(k, "absent") != v)
    assert differ == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 16}
    pc = manifest.adapter_for(f"{ROOT}/chipbench/configs/olmoe-1b-7b.json", cfg).config(cfg)
    assert pc.capacity_factor is None and pc.qk_norm and not pc.norm_topk_prob


def test_the_adapter_refuses_what_the_block_cannot_express():
    cfg = read(f"{ROOT}/chipbench/configs/olmoe-1b-7b.json")
    adapter = manifest.adapter_for(f"{ROOT}/chipbench/configs/olmoe-1b-7b.json", cfg)
    for key, value in (("clip_qkv", 8.0), ("attention_bias", True),
                       ("num_shared_experts", 2), ("sliding_window", 4096)):
        with pytest.raises(ValueError, match=key):
            adapter.config({**cfg, key: value})


def test_flops_and_kernel_costs_count_eight_experts_a_token():
    cfg = read(f"{ROOT}/chipbench/configs/olmoe-1b-7b.json")
    adapter = manifest.adapter_for(f"{ROOT}/chipbench/configs/olmoe-1b-7b.json", cfg)
    assert adapter.num_params(cfg) == 1_045_186_560 == adapter.config(cfg).num_params()
    assert adapter.train_flops_per_token(cfg, 2048) == pytest.approx(1.475e9, rel=1e-3)
    cost = adapter.KERNEL_COSTS["grouped_matmul"](cfg, 4, 2048, "fwd")
    assert cost["flops"] == 2 * 65536 * 2048 * 1024
    assert cost == adapter.KERNEL_COSTS["grouped_matmul"](cfg, 4, 2048, "drhs")
    assert cost["flops"] / 197e12 > cost["bytes"] / 819e9  # compute-bound on v5e
    att = adapter.KERNEL_COSTS["attention"](cfg, 4, 2048, "fwd")
    assert att["flops"] == 2 * 2 * (4 * 16 * 2048 * 2049 / 2) * 128


def test_the_managed_cell_runs_through_the_one_trainer(managed):
    cell, obs = managed
    assert obs["e2e"]["tok_s_chip"] > 0 and obs["failed"] == 0
    summary = obs["summaries"][0][-1]
    assert summary["config"] == "tiny-olmoe"
    steps = cell.traffic["warmup_steps"] + cell.traffic["min_steps"]
    assert summary["committed"] == steps
    stats = summary["model_stats"]
    assert sorted(stats) == ["moe_aux_loss", "moe_load_max_over_mean"]
    assert len(stats["moe_aux_loss"]) == steps
    assert all(4.0 - 1e-3 <= a < 6.0 for a in stats["moe_aux_loss"])  # k at even load
    # ln(vocab) + 0.01 x the auxiliary loss: inside the traffic file's band
    assert not any("first loss" in b for b in obs["notes"]["bad"])
    # a float32 leaf (the router) beside the bf16 ones: its own bucket
    assert summary["timings"]["allreduce_buckets"] >= 2
    with pytest.raises(RuntimeError, match="no result"):
        result.build(cell, obs, obs["e2e"], trace=False)


def test_its_counters_are_read_through_the_new_files(managed):
    cell, obs = managed
    spec = cell.layer_metric("moe.load_max_over_mean")
    got = cell.reducer(spec["reducer"]).reduce(obs, cell, **spec["args"])
    assert 1.0 <= got <= 16.0
    # a program without the counters (the parent): nothing to read, no raise
    old = {"summaries": {0: [{k: v for k, v in obs["summaries"][0][-1].items()
                              if k != "model_stats"}]}}
    assert cell.reducer(spec["reducer"]).reduce(old, cell, **spec["args"]) is None
    assert cell.reducer(spec["reducer"]).reduce({}, cell, **spec["args"]) is None
    spec = cell.layer_metric("kernel.gmm_1g_roofline")
    fake = {"trace": {"ops": {"gmm": 1.0, "gmm.1": 0.5, "tgmm": 0.5, "fusion.gmm": 9.0},
                      "chips_traced": 1},
            "steps_in_window": 2, "device": {"kind": "TPU v5 lite"}}
    cfg, r = cell.config, cell.config["recipe"]
    cost = cell.adapter().KERNEL_COSTS["grouped_matmul"](
        cfg, r["batch_size"], r["seq_len"], "fwd")
    assert cost["flops"] == 2.0 * r["batch_size"] * r["seq_len"] * 4 * 128 * 64
    one = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)  # tiny: memory-bound
    calls = sum(spec["args"]["roofline"]["calls_per_layer"].values())
    assert calls == 12
    assert cell.reducer(spec["reducer"]).reduce(fake, cell, **spec["args"]) == \
        pytest.approx(100 * calls * cfg["num_hidden_layers"] * one / 1.0, rel=1e-9)
    assert cell.reducer(spec["reducer"]).reduce(
        {**fake, "trace": {"ops": {"fusion.1": 1.0}, "chips_traced": 1}}, cell,
        **spec["args"]) is None


def test_the_bare_routed_cell_stops_where_it_finds_no_tpu(tiny_root, tmp_path):
    """Its reference is a child that gives no CPU answers: the job ends
    there, with the child's exit, before this process would touch JAX."""
    root, bench, _ = tiny_root
    cell = manifest.Cell(root, bench, "tiny-olmoe.bare-routed")
    assert cell.traffic["job"] == "bare_routed"
    assert cell.job().check_sample_of(cell, cell.adapter())["grad_leaves"] == [
        "layers.router", "layers.wq", "layers.w_down"]
    with pytest.raises(subprocess.CalledProcessError):
        cell.job().run(cell, seed=2147485001, seconds=1.0, trace=False,
                       out_dir=str(tmp_path), cache_dir=str(tmp_path / "cache"),
                       t_start=time.monotonic())
    assert not [f for f in os.listdir(tmp_path / "cache") if f.startswith("reference_")]
