"""The launcher ends a job whose last groups can no longer form a quorum
(a replacement that came up after the others' last step), instead of
letting them wait out their quorum timeout."""

import os
import signal
import sys
import textwrap
import threading
import time

import pytest

import torchft_tpu.launcher as launcher
from torchft_tpu.launcher import REPLICA_GROUP_ID_ENV, launch_replica_groups

# groups named in argv[1] never finish; those in argv[2] die once first
WORKER = textwrap.dedent(
    f"""
    import os, pathlib, sys, time
    rid = os.environ["{REPLICA_GROUP_ID_ENV}"]
    died = pathlib.Path(__file__).with_name("died_" + rid)
    if rid in sys.argv[2].split(",") and not died.exists():
        died.write_text("x")
        sys.exit(3)
    time.sleep(600 if rid in sys.argv[1].split(",") else 0.2)
    """
)


def _launch(tmp_path, late, restarted="", **kwargs):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    t0 = time.monotonic()
    code = launch_replica_groups(
        [sys.executable, str(script), late, restarted], num_groups=3,
        poll_interval=0.1, max_restarts=1, **kwargs,
    )
    return code, time.monotonic() - t0


def test_groups_too_few_for_a_quorum_are_stopped_once_the_rest_finished(
    tmp_path, monkeypatch
):
    monkeypatch.setattr(launcher, "ORPHAN_GRACE_S", 0.5)
    code, took = _launch(tmp_path, "2", restarted="2", min_replicas=2)
    assert code == 0 and took < 20


@pytest.mark.parametrize("late,kwargs", [
    ("1,2", {"restarted": "1,2", "min_replicas": 2}),  # two can still train
    ("2", {"min_replicas": 2}),  # never restarted: its own epilogue, maybe
    ("2", {"restarted": "2", "min_replicas": 2,
           "lighthouse_addr": "127.0.0.1:1"}),  # groups elsewhere may join
], ids=["a_quorum_is_left", "no_replacement", "another_lighthouse"])
def test_groups_that_may_still_train_run_on(tmp_path, monkeypatch, late, kwargs):
    """Stopped here by the test's own signal, after several grace periods."""
    monkeypatch.setattr(launcher, "ORPHAN_GRACE_S", 0.2)
    launched = threading.Event()
    launched.set()

    def interrupt():
        if launched.is_set():  # the launcher's handler is still in place
            os.kill(os.getpid(), signal.SIGTERM)

    timer = threading.Timer(2.5, interrupt)
    timer.start()
    try:
        code, took = _launch(tmp_path, late, **kwargs)
    finally:
        launched.clear()
        timer.cancel()
    assert code == 1 and took >= 2.5
