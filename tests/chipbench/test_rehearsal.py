"""CPU rehearsal, for tests only: the whole kill -> rejoin job end to end at
tiny widths, 2 groups on virtual devices, through the same job kind, launcher
and worker as the chip cell — and refused as a measurement: no result line
can be built from it. Also the next model_config PR in small: an adapter that
is not ``llama`` with its own reference and FLOPs, a configuration that names
it, and a bare-kind and a managed-1g cell under the traffic mixes that are
there, added as files and entries; the managed cell runs through
``launch.Launch`` and ``worker.py`` as they stand."""

import os

import time

import pytest

from chipbench_helpers import (TINY, TOY_KEYS, add_cell, add_toy, check_cell,
                               check_config_files, check_contract, copy_root,
                               files_of, only_appended, read, write)

from chipbench import flops, manifest, result


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


def test_the_victim_was_held_at_the_boundary_for_its_kill(rehearsal):
    """Its worker stopped itself once its commit line was out (the marker is
    the victim's own doing in the first launch, which was not killed, and was
    made anew in the second): it asked for no quorum between that line and
    its death, so no survivor lost a step to a quorum with a dead member."""
    _, obs, tmp = rehearsal
    assert os.path.exists(tmp / "out" / "frozen")
    assert obs["failed"] == 0
    log = open(tmp / "out" / "launch.log").read().splitlines()
    line = next(i for i, ln in enumerate(log) if "[replica 1] step=2 " in ln)
    died = next(i for i, ln in enumerate(log) if "replica group 1 died" in ln)
    between = [ln for ln in log[line:died] if ln.startswith("[manager llama_hsdp_1:")]
    assert not any("Start quorum" in ln for ln in between), between
    # the first launch: stopped, then let run again so that SIGTERM ends it
    first = open(tmp / "out" / "launch.log.too_short").read()
    assert "[replica 1] step=2 " in first and "died" not in first


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
    """Three new files (an adapter, its reference, a configuration that
    names the adapter) and new entries in BENCHMARK.json (one configuration,
    two cells, their names appended to ``workloads`` lists): nothing that is
    there is edited."""
    tmp = tmp_path_factory.mktemp("new_config")
    root = copy_root(tmp)
    before, was = files_of(f"{root}/chipbench"), read(f"{root}/BENCHMARK.json")
    bench = read(f"{root}/BENCHMARK.json")
    cfg = read(f"{root}/chipbench/configs/mistral-7b.json")
    add_toy(root, bench, tiny=dict(TINY, recipe={**cfg["recipe"], "seq_len": 128}))
    now = files_of(f"{root}/chipbench")
    assert all(now[p] == bytes_ for p, bytes_ in before.items())
    assert len(now) == len(before) + 3
    assert only_appended(was, read(f"{root}/BENCHMARK.json"))
    assert manifest.problems(root) == []
    cell = manifest.Cell(root, bench, "toy-model.managed-1g")

    def run(i, seconds):
        return cell.job().run(cell, seed=i, seconds=seconds, trace=False,
                              out_dir=str(tmp / f"out{i}"), cache_dir=str(tmp / "cache"),
                              t_start=time.monotonic())

    first = run(0, 2.0)
    seen = read(tmp / "cache" / "calibration_toy-model.managed-1g.json")["step_s"]
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


def test_the_worker_went_through_the_new_adapter(new_config):
    """``launch.Launch`` and ``worker.py`` as they stand: the trainer ran the
    configuration under the name the toy adapter registered it by, at the
    widths the toy's own keys give."""
    cell, (first, _) = new_config
    assert cell.config["adapter"] == "toy" and "hidden_size" not in cell.config
    summary = first["summaries"][0][-1]
    assert summary["config"] == "toy.toy-model"
    assert cell.adapter().config(cell.config).dim == cell.config["d_model"] == 256


def test_six_cells_are_held_to_what_four_were(new_config):
    """Part 1's assertions on the root with the two cells added: green; red
    once an accepted cell is edited there."""
    cell, _ = new_config
    bench = check_contract(cell.root)
    assert [w["name"] for w in bench["workloads"]][4:] == [
        "toy-model.bare", "toy-model.managed-1g"]
    for w in bench["workloads"]:
        check_cell(cell.root, w["name"])
    check_config_files(cell.root)
    bench["workloads"][1]["why"] += " (edited)"
    edited = os.path.join(cell.root, "edited")
    os.makedirs(edited)
    write(f"{edited}/BENCHMARK.json", bench)
    os.symlink(f"{cell.root}/chipbench", f"{edited}/chipbench")
    with pytest.raises(AssertionError):
        check_contract(edited)


def test_the_bare_check_goes_through_the_new_reference(new_config, tmp_path):
    """The bare job's check path for the toy cell: the sample carries the
    adapter's gradient leaves, the cached answers come from the toy
    reference run as a child (its file is in the cache key), the program's
    side is the adapter's, and the job itself stops where it finds no TPU."""
    root = new_config[0].root
    cell = manifest.Cell(root, manifest.load(root), "toy-model.bare")
    bare, adapter = cell.job(), cell.adapter()
    sample = bare.check_sample_of(cell, adapter)
    assert sample["grad_leaves"] == ["lm_head", "layers.wo"]
    assert cell.traffic["check"]["sample"]["grad_leaves"] != sample["grad_leaves"]
    with pytest.raises(RuntimeError, match="no TPU"):  # after the reference's child
        bare.run(cell, seed=2147485001, seconds=1.0, trace=False, out_dir=str(tmp_path),
                 cache_dir=str(tmp_path / "cache"), t_start=time.monotonic())
    assert len([f for f in os.listdir(tmp_path / "cache")
                if f.startswith("reference_")]) == 1
    ref = bare._reference_answers(cell, adapter, sample, str(tmp_path / "cache"))
    assert str(ref["platform"]) == "cpu" and "grad.lm_head" in ref and "grad.embed" not in ref
    # another reference file, another key: the toy's answers are its own
    with open(adapter.reference.__file__, "a") as f:
        f.write("\n# edited\n")
    try:
        bare._reference_answers(cell, manifest.adapter_for(cell.config_path, cell.config),
                                sample, str(tmp_path / "cache"))
        assert len([f for f in os.listdir(tmp_path / "cache")
                    if f.startswith("reference_")]) == 2
    finally:
        text = open(adapter.reference.__file__).read()
        with open(adapter.reference.__file__, "w") as f:
            f.write(text.replace("\n# edited\n", ""))
    # the chip cell's tolerances but the loss's: a mean over 128 tokens here,
    # over 2048 there
    got = bare.compare(bare.system_answers(adapter, cell.config, sample, 128), ref,
                       {**cell.traffic["check"]["tolerances"], "loss_abs": 0.01})
    assert got["ok"] and got["grad_rel.lm_head"] > 1e-4 and "grad_rel.embed" not in got, got


def test_mfu_and_roofline_read_the_new_adapters_counts(new_config):
    cell, (first, _) = new_config
    obs = {"e2e": {"tok_s_chip": 1000.0}, "device": {"kind": "TPU v5 lite"},
           "trace": {"ops": {"splash_mha_fwd": 3.0}, "chips_traced": 1},
           "steps_in_window": 2}
    toy = cell.adapter()
    n = toy.num_params(cell.config)
    hf = {**cell.config, **{k: cell.config[v] for k, v in TOY_KEYS.items()}}
    assert n == flops.num_params(hf)  # the same widths under other names
    spec = cell.layer_metric("model.mfu_1g")
    assert cell.reducer(spec["reducer"]).reduce(obs, cell, **spec["args"]) == \
        pytest.approx(100 * 6.0 * n * 1000.0 / 197e12, rel=1e-12)
    assert 6.0 * n != flops.train_flops_per_token(hf, 128)
    spec = cell.layer_metric("kernel.splash_1g_roofline")
    calls = sum(spec["args"]["roofline"]["calls_per_layer"].values())
    want = 100 * calls * cell.config["n_layer"] * (7.0 * 4 * 128 / 197e12) / 1.5
    assert cell.reducer(spec["reducer"]).reduce(obs, cell, **spec["args"]) == \
        pytest.approx(want, rel=1e-12)
