"""The result line: exactly the contract's keys, and none without a TPU."""

import json

import pytest

from chipbench_helpers import ROOT

from chipbench import manifest, result, run


def _obs(platform="tpu", count=1):
    return {"device": {"platform": platform, "kind": "TPU v5 lite", "count": count},
            "memory_peak_bytes": 7 * 2**30, "correct": True, "attempted": 9,
            "failed": 0,
            "trace": {"busy_s": 1.5, "window_s": 40.0,
                      "device_ops": [[f"op{i}", 1.0 / (i + 1)] for i in range(14)],
                      "idle_gaps": [["manager.allreduce.wire", 3.0]]}}


def _cell(name):
    return manifest.Cell(ROOT, manifest.load(ROOT), name)


def test_untraced_line_has_the_end_to_end_metrics_and_nothing_else():
    cell = _cell("mistral-7b.managed-1g")
    line = json.loads(result.dumps(result.build(cell, _obs(), {
        "tok_s_chip": 712.25, "peak_hbm_gib": 13.9, "setup_s": 41.0,
        "rejoin.work_s": 1.0}, trace=False)))
    assert sorted(line) == ["attempted", "correct", "device", "failed", "metrics"]
    assert sorted(line["metrics"]) == ["peak_hbm_gib", "setup_s", "tok_s_chip"]
    assert line["metrics"]["tok_s_chip"] == {"value": 712.25, "unit": "tokens/s/chip"}
    assert sorted(line["device"]) == ["count", "kind", "memory_peak_bytes", "platform"]


def test_traced_line_has_layer_metrics_busy_window_and_breakdown():
    cell = _cell("internlm2-1.8b.kill-rejoin-4g")
    line = result.build(cell, _obs(count=4), {
        "heal.recv_s": 9.5, "rejoin.restart_s": 21.0, "allreduce.wire_4g_s": None,
        "tok_s_chip": 1.0}, trace=True)
    assert sorted(line) == ["attempted", "breakdown", "correct", "device",
                            "failed", "metrics"]
    assert sorted(line["metrics"]) == ["heal.recv_s", "rejoin.restart_s"]
    assert line["device"]["busy_s"] == 1.5 and line["device"]["window_s"] == 40.0
    assert line["device"]["count"] == 4
    assert len(line["breakdown"]["device_ops"]) == 10
    assert line["breakdown"]["idle_gaps"] == [["manager.allreduce.wire", 3.0]]


@pytest.mark.parametrize("platform,count,chips_cell", [
    ("cpu", 1, "mistral-7b.bare"), ("tpu", 1, "internlm2-1.8b.kill-rejoin-4g")])
def test_no_result_without_the_chips(platform, count, chips_cell):
    with pytest.raises(RuntimeError, match="no result"):
        result.build(_cell(chips_cell), _obs(platform, count),
                     {"setup_s": 1.0}, trace=False)


def test_a_metric_that_is_not_a_number_is_refused():
    with pytest.raises(ValueError):
        result.build(_cell("mistral-7b.bare"), _obs(),
                     {"setup_s": float("nan")}, trace=False)


def test_the_command_fails_here_and_prints_no_result(capsys):
    """This sandbox has no TPU: exit code 2, nothing on stdout."""
    assert run.main(["--workload", "mistral-7b.bare", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: no system to
    measure, exit code 2, nothing on stdout."""
    import shutil
    import subprocess
    import sys

    shutil.copy(f"{ROOT}/BENCHMARK.json", tmp_path)
    shutil.copytree(f"{ROOT}/chipbench", tmp_path / "chipbench")
    shutil.copytree(f"{ROOT}/tests/chipbench", tmp_path / "tests" / "chipbench")
    got = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "mistral-7b.managed-1g",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=60,
        env={k: v for k, v in __import__("os").environ.items() if k != "PYTHONPATH"})
    assert got.returncode == 2 and got.stdout == ""
    assert "not a torchft_tpu checkout" in got.stderr
