"""CPU rehearsal, for tests only: the whole kill -> rejoin job end to end at
tiny widths, 2 groups on virtual devices, through the same job kind, launcher
and worker as the chip cell — and refused as a measurement: no result line
can be built from it. Also a later PR's most likely cell, a new configuration
under the traffic mix that is there, added as one file and two entries."""

import os

import time

import pytest

from chipbench_helpers import TINY, add_cell, copy_root, read, write

from chipbench import manifest, result


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("rehearsal")
    root = copy_root(tmp)
    cfg = read(f"{root}/chipbench/configs/internlm2-1.8b.json")
    cfg.update(TINY, name="tiny-internlm2")
    cfg["recipe"].update(seq_len=128)
    write(f"{root}/chipbench/configs/tiny-internlm2.json", cfg)
    tr = read(f"{root}/chipbench/traffic/kill-rejoin-4g.json")
    tr.update(groups=2, min_replicas=1, chips_per_group=0, timeout_s=120,
              trainer_args=["--virtual-chips", "1"],
              # the slowest restart the job is sized for: a loaded test machine
              # takes its time to start a Python that imports JAX
              ready_after_kill_s=25.0,
              # a first run sized as for steps this long is too short for any
              # step this machine can take, loaded or not: the path of
              # test_a_first_run_sized_too_short_starts_again, every time
              uncalibrated_step_s=60.0)
    write(f"{root}/chipbench/traffic/kill-rejoin-2g-cpu.json", tr)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-internlm2", "source": "x", "reduced": [],
                             "file": "chipbench/configs/tiny-internlm2.json", "why": "x"})
    add_cell(root, bench, "tiny.kill", "tiny-internlm2", "kill-rejoin-2g-cpu",
             "internlm2-1.8b.kill-rejoin-4g")
    write(f"{root}/BENCHMARK.json", bench)
    cell = manifest.Cell(root, bench, "tiny.kill")
    obs = cell.job().run(cell, seed=3, seconds=1.0, trace=False,
                         out_dir=str(tmp / "out"), cache_dir=str(tmp / "cache"),
                         t_start=time.monotonic())
    return cell, obs, tmp


def test_the_event_ran_to_its_end(rehearsal):
    _, obs, _ = rehearsal
    ph = obs["phases"]
    assert ph["new_pid"] != 0 and ph["heal_step"] >= 3
    assert ph["rejoin.restart_s"] > 0 and ph["rejoin.init_s"] > 0
    assert ph["rejoin.work_s"] == pytest.approx(
        ph["rejoin.init_s"] + ph["rejoin.heal_step_s"])
    # it decides nothing: a per-layer metric of the traced run (rule 3)
    assert sorted(obs["e2e"]) == ["peak_hbm_gib", "setup_s"]
    assert ph["heal.recv_s"] is not None and ph["heal.mb_s"] > 0
    sums = {g: s[-1] for g, s in obs["summaries"].items()}
    assert sums[1]["healed"] >= 1
    assert sums[0]["param_checksum"] == sums[1]["param_checksum"]
    assert sums[1]["pid"] == ph["new_pid"]


def test_calibration_is_kept_for_the_next_run(rehearsal):
    cell, _, tmp = rehearsal
    cal = read(tmp / "cache" / "calibration_tiny.kill.json")
    assert cal["step_s"] > 0 and cal["stall_s"] > 0


def test_a_first_run_sized_too_short_starts_again(rehearsal):
    """No step had been seen in this checkout, so the job was sized from the
    traffic file's ``uncalibrated_step_s`` (here a step far longer than any
    this machine takes: 5 steps); the job would have ended before the rejoin,
    and the step timed before the kill says so: one more launch, sized from
    it, and no kill in the first."""
    cell, obs, tmp = rehearsal
    job = cell.job()
    assert job.plan_steps({"step_s": cell.traffic["uncalibrated_step_s"]}, 25.0, 1) == 5
    first = open(tmp / "out" / "launch.log.too_short").read()
    assert "step=2 " in first and "died" not in first
    assert obs["notes"]["steps"] > 5
    assert "restart 1/1" in open(tmp / "out" / "launch.log").read()


def test_it_is_refused_as_a_measurement(rehearsal):
    cell, obs, _ = rehearsal
    assert obs["device"]["platform"] == "cpu" and obs["correct"] is False
    assert any("trainer ran on" in b for b in obs["notes"]["bad"])
    with pytest.raises(RuntimeError, match="no result"):
        result.build(cell, obs, obs["e2e"], trace=False)


@pytest.fixture(scope="module")
def new_config(tmp_path_factory):
    """One new file (the configuration) and two new entries in
    BENCHMARK.json (configs, workloads): nothing that is there is edited."""
    tmp = tmp_path_factory.mktemp("new_config")
    root = copy_root(tmp)
    before = {p: open(p, "rb").read() for d, _, fs in os.walk(f"{root}/chipbench")
              for p in (os.path.join(d, f) for f in fs)}
    cfg = read(f"{root}/chipbench/configs/mistral-7b.json")
    cfg.update(TINY, name="tiny-new")
    cfg["recipe"].update(seq_len=128)
    write(f"{root}/chipbench/configs/tiny-new.json", cfg)
    bench = read(f"{root}/BENCHMARK.json")
    bench["configs"].append({"name": "tiny-new", "source": "x", "reduced": [],
                             "file": "chipbench/configs/tiny-new.json", "why": "x"})
    add_cell(root, bench, "tiny-new.managed-1g", "tiny-new", "managed-1g",
             "mistral-7b.managed-1g")
    write(f"{root}/BENCHMARK.json", bench)
    for p, was in before.items():
        assert open(p, "rb").read() == was, p
    assert manifest.problems(root) == []
    cell = manifest.Cell(root, bench, "tiny-new.managed-1g")

    def run(i, seconds):
        return cell.job().run(cell, seed=i, seconds=seconds, trace=False,
                              out_dir=str(tmp / f"out{i}"), cache_dir=str(tmp / "cache"),
                              t_start=time.monotonic())

    first = run(0, 2.0)
    seen = read(tmp / "cache" / "calibration_tiny-new.managed-1g.json")["step_s"]
    return cell, (first, run(1, seen * (cell.traffic["min_steps"] + 3.5)))


def test_a_new_configuration_runs_under_the_traffic_that_is_there(new_config):
    cell, (first, second) = new_config
    assert cell.traffic_name == "managed-1g"
    for obs in (first, second):
        assert obs["e2e"]["tok_s_chip"] > 0 and obs["failed"] == 0
        assert obs["device"]["platform"] == "cpu" and obs["correct"] is False
        with pytest.raises(RuntimeError, match="no result"):
            result.build(cell, obs, obs["e2e"], trace=False)
    # its per-layer metrics are read through the files that are there
    for name in ("trainer.step_s", "launcher.reach_chip_s"):
        spec = cell.layer_metric(name)
        assert cell.reducer(spec["reducer"]).reduce(first, cell, **spec["args"]) > 0


def test_its_first_run_is_sized_without_a_guess(new_config):
    """No step seen yet: warm-up + min_steps; the next run fills --seconds
    from the step time the first one measured."""
    cell, (first, second) = new_config
    tr = cell.traffic
    assert first["notes"]["steps"] == tr["warmup_steps"] + tr["min_steps"]
    assert second["notes"]["steps"] == first["notes"]["steps"] + 3
