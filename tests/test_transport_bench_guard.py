"""Run the transport bench's --check regression guard in CI (slow tier).

The streaming/in-place RSS properties are design claims verified at 12 GB
in docs/performance.md; this exercises the same guard at a CI-friendly
payload so a streaming path regressing to full materialization (or an
in-place path regressing to wire buffers) fails the suite, not just a
manual bench run. 256 MB = 4 x 64 MB leaves: small enough for CI, large
enough that the leaf-granular in-place bound (3 leaves = 0.75x, one leaf
of noise headroom over the ~2-leaf legitimate transient) stays tighter
than the materialization it guards against (1x+).
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # two processes moving 256 MB per case

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize(
    "args",
    [
        ["--transport", "http"],
        ["--transport", "http", "--inplace"],
        ["--transport", "pg"],
        ["--transport", "pg", "--inplace"],
    ],
    ids=["http", "http-inplace", "pg", "pg-inplace"],
)
def test_two_process_rss_guard(args):
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "transport_bench.py"),
         # bench-internal timeout WELL below this test's subprocess kill:
         # a wedged transport must be reaped by the bench's own handling
         # (which kills the recv child and reports diagnostics), not by a
         # SIGKILL here that would orphan the grandchild
         "--size-mb", "256", "--two-process", "--check",
         "--timeout", "120", *args],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO,
    )
    assert out.returncode == 0, (out.stderr or out.stdout)[-2000:]
    import json

    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["size_mb"] == 256
    assert rec["seconds"] > 0


def test_the_ring_split_is_the_rings_own_count_at_four_lanes():
    """``--transport allreduce --elements``: four ranks, segments over the
    lane floor, so every rank rides four lanes of three threads. The split
    it prints is what the ring counted of itself (process_group
    ._ring_allreduce's ``info``): no ``_Comm`` method, ``_fold`` or
    ``_accum`` is wrapped in a timer, and the numbers hold the arithmetic
    the ring's own clock promises."""
    import json

    bench = os.path.join(REPO, "benchmarks", "transport_bench.py")
    with open(bench) as f:
        text = f.read()
    assert "timed(" not in text and "_Comm." not in text
    assert "pg_mod._fold =" not in text and "pg_mod._accum =" not in text
    out = subprocess.run(
        [sys.executable, bench, "--transport", "allreduce", "--world", "4",
         "--elements", "4200000,4200000", "--donate", "--iters", "2",
         "--chunk-mb", "0.25", "--timeout", "120"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert out.returncode == 0, (out.stderr or out.stdout)[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert (row["lanes"], row["inplace"], row["native_frames"]) == (4, 1, 1)
    for key in ("recv_wait_s", "recv_s", "fold_s", "send_s", "handoff_s",
                "slot_wait_s", "recv_span_s"):
        mean, most = row[key]
        assert 0 <= mean <= most, (key, row)
    assert min(row["recv_s"][0], row["fold_s"][0], row["send_s"][0]) > 0
    # every number is the slowest rank's median, so sums hold to a margin
    assert row["entry_wait_s"] <= row["ring_s"] <= row["step_s"] * 2 + 0.01
    assert row["recv_span_s"][1] <= row["ring_s"] * 1.5 + 0.01
