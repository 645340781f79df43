"""Each reducer on a hand-made observation; one that finds nothing returns
None, and the metric is then left out of the line."""

import pytest

from chipbench_helpers import ROOT

from chipbench import flops, manifest

S = 1_000_000_000


def cell(name="mistral-7b.managed-1g"):
    return manifest.Cell(ROOT, manifest.load(ROOT), name)


def reducer(name):
    return manifest.load_module(ROOT, "reducers", name).reduce


def proc(replica=0):
    spans = [("manager.allreduce.pack", 1 * S, 2 * S, 7), ("manager.allreduce.pack", 2 * S, 4 * S, 7),
             ("manager.allreduce.pack", 11 * S, 12 * S, 8), ("manager.allreduce.pack", 12 * S, 13 * S, 8),
             ("manager.allreduce.pack", 21 * S, 26 * S, 9),
             ("manager.quorum.quorum_rpc", 0, 5 * S, 7), ("manager.quorum.quorum_rpc", 10 * S, 10 * S + 2_000_000, 8),
             ("manager.allreduce.pack", -9 * S, -8 * S, 6)]          # before the window
    return {"replica": replica, "pid": 1, "window": (0, 30 * S), "spans": spans}


def test_span():
    obs = {"procs": [proc()]}
    span = reducer("span")
    # per step: 3 s, 2 s, 5 s of pack, buckets summed -> median 3
    assert span(obs, cell(), "manager.allreduce.pack") == pytest.approx(3.0)
    assert span(obs, cell(), "manager.allreduce.pack", stat="median") == pytest.approx(1.0)
    assert span(obs, cell(), "manager.quorum.quorum_rpc", stat="max") == pytest.approx(5.0)
    assert span(obs, cell(), "manager.heal.heal_recv") is None
    assert span(obs, cell(), "manager.allreduce.pack", replica=3) is None
    assert span({}, cell(), "manager.allreduce.pack") is None


def test_step_lines_and_phase():
    steps = {0: [(0, 1, 11.4, 4, 60.0), (1, 2, 11.4, 4, 15.0), (2, 3, 11.4, 3, 12.0),
                 (3, 4, 11.4, 3, 14.0), (4, 5, 11.4, 4, 16.0)]}
    lines = reducer("step_lines")
    assert lines({"steps": steps}, cell()) == pytest.approx(14.5)      # warm-up skipped
    assert lines({"steps": steps}, cell(), participants=4) == pytest.approx(15.5)
    assert lines({"steps": steps}, cell(), replica=2) is None
    phase = reducer("phase")
    assert phase({"phases": {"heal.recv_s": 9.5}}, cell(), key="heal.recv_s") == 9.5
    assert phase({"phases": {}}, cell(), key="heal.recv_s") is None


def test_the_rejoin_work_is_a_layer_metric_of_the_traced_line():
    """ISSUE 23 rule 3: what was the end-to-end ``rejoin_work_s`` is read
    through a layer-metric file like any other phase and shows in the traced
    line alone; the untraced line of the failure cell holds no recovery time."""
    from chipbench import result, run

    c = cell("internlm2-1.8b.kill-rejoin-4g")
    obs = {"phases": {"rejoin.work_s": 23.25, "rejoin.init_s": 1.5},
           "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
           "memory_peak_bytes": 9 * 2**30, "correct": True, "attempted": 24,
           "failed": 3, "steps": {}, "procs": [], "summaries": {},
           "e2e": {"peak_hbm_gib": 9.0, "setup_s": 65.0},
           "trace": {"busy_s": 0.5, "window_s": 70.0, "device_ops": [],
                     "idle_gaps": [], "ops": {}, "chips_traced": 4},
           "steps_in_window": None}
    values = run.layer_values(c, obs)
    assert values["rejoin.work_s"] == 23.25 and values["heal.recv_s"] is None
    traced = result.build(c, obs, values, trace=True)["metrics"]
    assert traced["rejoin.work_s"] == {"value": 23.25, "unit": "s"}
    untraced = result.build(c, obs, obs["e2e"], trace=False)["metrics"]
    assert sorted(untraced) == ["peak_hbm_gib", "setup_s"]


def test_device_step_and_mfu():
    obs = {"trace": {"busy_s": 1.8, "chips_traced": 1, "ops": {}}, "steps_in_window": 4,
           "e2e": {"tok_s_chip": 18_723.1}, "device": {"kind": "TPU v5 lite"}}
    assert reducer("device_step")(obs, cell()) == pytest.approx(0.45)
    assert reducer("device_step")({"steps_in_window": 4}, cell()) is None
    c = cell("mistral-7b.bare")
    want = 100 * flops.train_flops_per_token(c.config, 2048) * 18_723.1 / 197e12
    assert reducer("mfu")(obs, c) == pytest.approx(want)
    assert 55 < want < 65
    assert reducer("mfu")(obs, c, of="bare_tok_s_chip") is None
    assert reducer("mfu")({"e2e": {}, "device": {"kind": "TPU v5 lite"}}, c) is None
    with pytest.raises(ValueError, match="no peaks known"):
        reducer("mfu")({**obs, "device": {"kind": "TPU v9"}}, c)


def test_device_op_and_roofline():
    c = cell("mistral-7b.bare")
    ops = {"splash_mha_fwd_residuals.15": 0.06, "splash_mha_dq_no_residuals.9": 0.04,
           "splash_mha_dkv_no_residuals.9": 0.05, "fusion.372": 1.0}
    obs = {"trace": {"ops": ops, "chips_traced": 1, "busy_s": 2.0}, "steps_in_window": 5,
           "device": {"kind": "TPU v5 lite"}}
    op = reducer("device_op")
    assert op(obs, c, pattern="^splash_mha_") == pytest.approx(0.03)
    assert op(obs, c, pattern="^flash_") is None
    # per layer: 2 forward calls (2 matmuls) + 1 backward (5): 9 matmuls'
    # worth of exact causal pairs, x 4 layers, compute-bound on v5e
    pairs = 4 * 32 * 2048 * 2049 / 2
    floor = c.config["num_hidden_layers"] * 9 * 2 * pairs * 128 / 197e12
    got = op(obs, c, pattern="^splash_mha_",
             roofline={"calls_per_layer": {"fwd": 2, "bwd": 1}})
    assert got == pytest.approx(100 * floor / 0.03)


def test_layer_values_reads_every_metric_BENCHMARK_json_lists_for_the_cell():
    """run.layer_values: every per-layer metric of the cell, each through
    the reducer its own file names."""
    from chipbench import run

    c = cell("mistral-7b.bare")
    obs = {"trace": {"ops": {"splash_mha_fwd_residuals.1": 0.1}, "chips_traced": 1,
                     "busy_s": 2.0}, "steps_in_window": 4,
           "e2e": {"bare_tok_s_chip": 18_000.0}, "device": {"kind": "TPU v5 lite"},
           "phases": {}, "steps": {}, "procs": []}
    got = run.layer_values(c, obs)
    assert sorted(got) == ["kernel.splash_roofline", "kernel.splash_s",
                           "model.mfu", "model.step_device_s"]
    assert got["model.step_device_s"] == pytest.approx(0.5)
    assert 50 < got["model.mfu"] < 65
    # the managed cells' copies read the same trace and their own tokens/s
    m = cell("internlm2-1.8b.managed-1g")
    got = run.layer_values(m, {**obs, "e2e": {"tok_s_chip": 1_230.0}})
    assert {"kernel.splash_1g_roofline", "kernel.splash_1g_s", "model.mfu_1g",
            "model.step_device_1g_s"} <= set(got)
    assert got["model.step_device_1g_s"] == pytest.approx(0.5)
    assert 0 < got["model.mfu_1g"] < 5
