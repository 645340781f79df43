"""The headline-bench measurement harness (bench.py:timed_train_step).
The contract is pinned here: stable (tok/s, mfu) return for sweep children
(benchmarks/mfu_sweep.py parses exactly two floats), best-of-2 timing
windows exposed via the LAST_WINDOWS module global, value == max window,
and no utilization against a peak nobody stated."""

import os
import sys

import pytest

pytestmark = pytest.mark.slow  # compiles a (tiny) train step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_mfu_sweep_model_typo_fails_before_probe():
    """A --model typo must cost an argparse error in milliseconds, never
    a backend start-up in a probe child (the same pre-probe rule the
    sweep's --cell validation follows)."""
    import subprocess
    import time

    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "mfu_sweep.py"),
         "--model", "bogus", "--cell", "full,8,0"],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert out.returncode == 2, out.stderr[-500:]  # argparse error exit
    assert "not in CONFIGS" in out.stderr
    # generous bound: interpreter + jax import, but no 90 s probe
    assert time.monotonic() - t0 < 45


def test_timed_train_step_windows_contract(monkeypatch):
    sys.path.insert(0, REPO)
    import bench
    from torchft_tpu import utils
    from torchft_tpu.models.llama import CONFIGS

    # conftest pins the virtual-CPU platform, whose device_kind has no
    # peak: without one the harness refuses before it compiles anything
    with pytest.raises(ValueError, match="device_kind 'cpu'"):
        bench.timed_train_step(CONFIGS["tiny"], 2, 128, 2)

    # the contract under test is the windows, so state a peak for the test
    # platform here, in the open, instead of inheriting a silent default
    monkeypatch.setitem(utils.PEAK_BF16_FLOPS, "cpu", 1e12)
    tps, mfu = bench.timed_train_step(CONFIGS["tiny"], 2, 128, 2)

    assert tps > 0 and mfu > 0
    # two windows, value is the max of them — the artifact's
    # windows_tok_s field is exactly this list
    assert len(bench.LAST_WINDOWS) == 2
    assert all(w > 0 for w in bench.LAST_WINDOWS)
    assert tps == max(bench.LAST_WINDOWS)
