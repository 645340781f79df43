"""The pipe the launcher's children share: a line is whole when it leaves a
worker (worker.py's ``_Lines``), and where a writer that does not go through
it left a gap, the reader takes the glued lines apart (launch.py's
``split_glued``). The glued lines here are the ones nine runs of the failure
cell on the chip left in their logs (PERF.md section 6, PR 27). And the
failure cell's victim stops itself at the step boundary, once, so that the
kill cannot come after its request for the next quorum."""

import io
import json
import os
import signal
import subprocess
import sys
import threading
import types

import pytest

from chipbench_helpers import ROOT

from chipbench import launch, worker

STEP_3 = ("[replica 3] step=8 inner=8 loss=11.7810 participants=4 "
          "iter_s=9.80 tok/s=1904")
STEP_1 = ("[replica 1] step=8 inner=4 loss=11.8008 participants=4 "
          "iter_s=9.80 tok/s=2563")
LIGHTHOUSE = ("[lighthouse] New quorum not ready, only have 1 participants, "
              "need min_replicas 3 [1/1 participants healthy]")
MANAGER = ("[manager llama_hsdp_1:32bafb64-9424-43d1-aa08-fc83eb8c2f8f] "
           "should_commit request from 0 should_commit=true")
DIED = ("WARNING:__main__:replica group 1 died (codes=[-9]); restart 1/1 "
        "spawned at epoch 1790509498.333")
SUMMARY_2 = '[replica 2] SUMMARY {"replica": 2, "pid": 3135, "step": 7}'


@pytest.mark.parametrize("raw,want", [
    (STEP_3 + STEP_1, [STEP_3, STEP_1]),                 # change4g/0, line 290
    (STEP_3 + LIGHTHOUSE, [STEP_3 + LIGHTHOUSE]),        # change4g/1, line 162
    (STEP_3 + MANAGER, [STEP_3 + MANAGER]),              # change4g/3, line 277
    (MANAGER + STEP_1, [MANAGER, STEP_1]),
    (STEP_3 + DIED, [STEP_3, DIED]),
    (SUMMARY_2 + STEP_1 + SUMMARY_2, [SUMMARY_2, STEP_1, SUMMARY_2]),
    (STEP_3, [STEP_3]), (LIGHTHOUSE, [LIGHTHOUSE]), (DIED, [DIED]), ("", [""]),
])
def test_glued_lines_are_taken_apart(raw, want):
    assert launch.split_glued(raw) == want


def read_as_the_launch_does(text: str) -> launch.Launch:
    run = launch.Launch.__new__(launch.Launch)  # no launcher is started
    run.proc = types.SimpleNamespace(stdout=io.StringIO(text))
    run.log, run.lines, run.t0 = io.StringIO(), [], 0.0
    run._cond = threading.Condition()
    run._read()
    return run


def test_no_line_the_job_needs_is_lost_to_a_glued_one():
    """A step line behind another replica's, the launcher's restart line
    behind a step line, a SUMMARY with chatter behind it and one behind a
    step line: each cost a run its result (``survivor g has no lines``, ``no
    restart of the victim``, ``Extra data``, ``SUMMARY from [...]``)."""
    run = read_as_the_launch_does("\n".join([
        STEP_3 + STEP_1, "", STEP_3.replace("step=8", "step=9") + DIED, "",
        SUMMARY_2 + MANAGER, "", STEP_1.replace("step=8", "step=9")
        + SUMMARY_2.replace("2", "0"), "", LIGHTHOUSE, ""]))
    assert [s[1] for s in launch.steps(run.lines, 1)] == [8, 9]
    assert [s[1] for s in launch.steps(run.lines, 3)] == [8, 9]
    assert [ln.text for ln in run.lines if ln.replica is None] == [DIED]
    assert launch.DIED_LINE.search(DIED)[1] == "1"
    sums = launch.summaries(run.lines)
    assert sums[2] == [{"replica": 2, "pid": 3135, "step": 7}]
    assert sums[0] == [{"replica": 0, "pid": 3135, "step": 7}]
    # the log keeps every writer's line on a line of its own
    logged = run.log.getvalue().splitlines()
    assert LIGHTHOUSE in logged and sum("step=" in ln for ln in logged) == 4


class Writes:
    """A stream that keeps each write it gets."""

    def __init__(self):
        self.got = []

    def write(self, s):
        self.got.append(s)
        return len(s)

    def flush(self):
        self.got.append(None)


def test_a_print_leaves_the_worker_in_one_write():
    real = Writes()
    out = worker._Lines(real)
    print("[replica 0] step=1", "x", file=out, flush=True)  # five writes
    print("half", end="", file=out)
    assert real.got == ["[replica 0] step=1 x\n", None]
    print(" a line\nand", end="", file=out)
    assert real.got[2:] == ["half a line\n"]
    out.flush()  # a prompt no newline follows is not kept back
    assert real.got[3:] == ["and", None]
    assert out.got is real.got  # everything else is the real stream's


WRITER = """
import sys
sys.path.insert(0, {root!r})
from chipbench import worker
if {lines}:
    sys.stdout = worker._Lines(sys.stdout)
for i in range({n}):
    print("[replica {g}] SUMMARY " + '{{"i": %d, "pad": "%s"}}' % (i, "x" * 1500), flush=True)
"""


def shared_pipe(lines: bool, n: int = 1500, writers: int = 4) -> "list[str]":
    """What a reader gets from ``writers`` unbuffered Pythons printing to
    one pipe at once, as the launcher's workers do."""
    r, w = os.pipe()
    procs = [subprocess.Popen(
        [sys.executable, "-u", "-c",
         WRITER.format(root=ROOT, lines=lines, n=n, g=g)], stdout=w)
        for g in range(writers)]
    os.close(w)
    with os.fdopen(r) as f:
        got = f.read().split("\n")
    assert [p.wait() for p in procs] == [0] * writers
    return got


def test_four_workers_on_one_pipe_write_whole_lines():
    got = [ln for ln in shared_pipe(lines=True) if ln]
    assert len(got) == 4 * 1500
    seen = {g: [] for g in range(4)}
    for ln in got:
        m = launch.REPLICA_LINE.match(ln)
        assert m and m[2].startswith("SUMMARY "), ln[:200]
        seen[int(m[1])].append(json.loads(m[2][8:])["i"])  # nothing behind it
    assert all(v == list(range(1500)) for v in seen.values())


def test_without_it_the_same_writers_glue_their_lines():
    """The control: the same four writers with ``print`` as the launcher's
    ``-u`` leaves it, the text and the newline in two writes. 902 of 6,000
    lines came out glued in this sandbox (PR 27); a machine that happens to
    interleave nothing shows nothing, and that is no failure of the code."""
    got = [ln for ln in shared_pipe(lines=False) if ln]
    glued = [ln for ln in got if len(launch.split_glued(ln)) > 1]
    if not glued:
        pytest.skip("this machine did not interleave the writers")
    assert sum(len(launch.split_glued(ln)) for ln in got) == 4 * 1500


COMMIT = "[replica 1] step=%d inner=%d loss=11.9 participants=4 iter_s=9.85 tok/s=2758"


def test_the_victim_stops_itself_once_its_commit_line_is_out(tmp_path, monkeypatch):
    marker, sent = str(tmp_path / "frozen"), []
    real = Writes()
    real.write = lambda s: real.got.append((s, os.path.exists(marker)))
    monkeypatch.setattr(worker.os, "kill", lambda pid, sig: sent.append((pid, sig)))
    out = worker._Lines(real, freeze=(2, marker))
    print("[replica 1] mesh fsdp=1 starting at step 0", file=out, flush=True)
    print(COMMIT % (1, 1), file=out, flush=True)
    print("[replica 1] step=2 DISCARDED", file=out, flush=True)  # no commit
    assert not sent and not os.path.exists(marker)
    print(COMMIT % (2, 2), file=out, flush=True)
    # the marker is there before the line is (the kill follows the line), the
    # line is out before the process stops, and it stops itself alone
    assert [w for w in real.got if w][-1] == ((COMMIT % (2, 2)) + "\n", True)
    assert sent == [(os.getpid(), signal.SIGSTOP)]
    print(COMMIT % (3, 3), file=out, flush=True)
    assert len(sent) == 1


def test_a_victim_that_joined_late_stops_at_its_first_commit_line(tmp_path, monkeypatch):
    sent = []
    monkeypatch.setattr(worker.os, "kill", lambda pid, sig: sent.append(sig))
    out = worker._Lines(Writes(), freeze=(2, str(tmp_path / "frozen")))
    print(COMMIT % (3, 1), file=out, flush=True)  # launch.py kills at >= 2 too
    assert sent == [signal.SIGSTOP]


@pytest.mark.parametrize("group,there,want", [
    ("1", False, True),    # the victim
    ("1", True, False),    # its replacement: same arguments, the file is there
    ("0", False, False),   # a survivor
])
def test_only_the_victim_stops_and_its_replacement_does_not(
        tmp_path, monkeypatch, group, there, want):
    marker = tmp_path / "frozen"
    if there:
        marker.write_text("")
    monkeypatch.setenv("REPLICA_GROUP_ID", group)
    got = worker.freeze_of(f"1:2:{marker}")
    assert got == ((2, str(marker)) if want else None)
    assert worker.freeze_of(None) is None
