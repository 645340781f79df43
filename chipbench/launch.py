"""The launcher as a child, its output followed line by line and stamped as
it is read (host monotonic clock): a copy of ``chip_smoke.py``'s ``Launch``,
kept here because the program may change and the yardstick may not. The
parent never touches JAX, so it holds no chip."""

import json
import os
import re
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPLICA_LINE = re.compile(r"^\[replica (\d+)\] (.*)$")
STEP_LINE = re.compile(
    r"step=(\d+) inner=\d+ loss=(\S+) participants=(\d+) iter_s=(\S+)")
# launcher.py logs this just before it spawns the replacement (stderr,
# basicConfig(INFO)): the only sign of a restart the program gives
DIED_LINE = re.compile(r"replica group (\d+) died .*restart (\d+)/(\d+)")


# where a line this module reads starts (a replica's, or the launcher's on
# a restart), anywhere in what the pipe gave as one line
LINE_MARK = re.compile(r"\[replica \d+\] |(?:WARNING|ERROR):[\w.]+:replica group \d+ died ")


class Failed(Exception):
    pass


class Line:
    __slots__ = ("t", "replica", "text")

    def __init__(self, t: float, replica, text: str) -> None:
        self.t, self.replica, self.text = t, replica, text


def split_glued(text: str) -> "list[str]":
    """The writers' lines in what was read as one. The pipe is shared by the
    workers, their Managers' native servers and the lighthouse; a writer
    whose text and newline are two writes (an unbuffered Python ``print``)
    leaves a gap, and what another writer sends into it is glued to the text:
    ``[replica 3] step=8 ... tok/s=1904[replica 1] step=8 ...`` (the chip,
    PERF.md section 6, PR 27). worker.py's ``_Lines`` closes the gap at its
    source; this is for a line of a writer that does not go through it."""
    cuts = [m.start() for m in LINE_MARK.finditer(text)]
    if not cuts or cuts == [0]:
        return [text]
    return ([text[:cuts[0]]] if cuts[0] else []) + [
        text[a:b] for a, b in zip(cuts, cuts[1:] + [len(text)])]


def parse_step(text: str):
    """(step, loss, participants, iter_s) of a committed-step line."""
    m = STEP_LINE.match(text)
    return (int(m[1]), float(m[2]), int(m[3]), float(m[4])) if m else None


class Launch:
    def __init__(self, launcher_args, worker_args, log_path: str) -> None:
        os.makedirs(os.path.dirname(log_path), exist_ok=True)
        self.log = open(log_path, "w")
        self.lines: "list[Line]" = []
        self._cond = threading.Condition()
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-u", "-m", "torchft_tpu.launcher",
             os.path.join("chipbench", "worker.py"), *launcher_args, "--",
             *worker_args],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            errors="replace", cwd=REPO, start_new_session=True,
            env={**os.environ, "PYTHONUNBUFFERED": "1"},
        )
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()

    def _read(self) -> None:
        for raw in self.proc.stdout:
            t = time.monotonic()
            for text in split_glued(raw.rstrip("\n")):
                m = REPLICA_LINE.match(text)
                if not m and not DIED_LINE.search(text):
                    self.log.write(text + "\n")  # native servers' RPC chatter: log only
                    continue
                self.log.write(f"{t - self.t0:10.4f} {text}\n")
                with self._cond:
                    self.lines.append(Line(t, int(m[1]), m[2]) if m
                                      else Line(t, None, text))
                    self._cond.notify_all()
        with self._cond:
            self._cond.notify_all()

    def __enter__(self) -> "Launch":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    def wait_for(self, pred, timeout_s: float, what: str):
        """First value of ``pred(lines)`` that is not None."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while True:
                got = pred(self.lines)
                if got is not None:
                    return got
                left = deadline - time.monotonic()
                if left <= 0 or not self._reader.is_alive():
                    raise Failed(f"{what} not seen ("
                                 f"{'launcher exited' if left > 0 else 'timed out'}"
                                 f"; log: {self.log.name})")
                self._cond.wait(min(left, 1.0))

    def finish(self, timeout_s: float) -> "dict[int, list[dict]]":
        """Wait for the launcher to end by itself; SUMMARY objects per
        replica, in order."""
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            rc = "timeout"
        self.stop()
        self._reader.join(10)
        if rc != 0:
            raise Failed(f"launcher rc={rc} (log: {self.log.name})")
        return summaries(self.lines)

    def stop(self) -> None:
        """Leave no process behind, and wait until each has ended: the
        launcher runs in its own session, its workers in its process group
        (a killed TPU process takes seconds to let go of its chip). SIGTERM
        goes to the launcher alone, which then stops its workers itself; sent
        to the group it makes the launcher restart workers it sees die."""
        if self.proc.poll() is None:
            try:
                # a worker that stopped itself (worker.py, the failure cell's
                # victim before its kill) takes no SIGTERM until it runs again
                os.killpg(self.proc.pid, signal.SIGCONT)
                self.proc.terminate()
                self.proc.wait(timeout=45)
            except subprocess.TimeoutExpired:
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        deadline = time.monotonic() + 30
        while _running_in_group(self.proc.pid) and time.monotonic() < deadline:
            time.sleep(0.05)
        self.log.flush()


def _running_in_group(pgid: int) -> int:
    """Processes of the group that have not ended yet (zombies have: they
    wait for whoever inherited them), from /proc."""
    n = 0
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                state, _, pgrp = f.read().rsplit(")", 1)[1].split()[:3]
        except OSError:  # ended while we looked
            continue
        n += int(pgrp) == pgid and state != "Z"
    return n


def summaries(lines) -> "dict[int, list[dict]]":
    """SUMMARY objects per replica, in order. ``raw_decode``: what another
    writer glued behind the object (``split_glued``) is not the object's."""
    out: "dict[int, list[dict]]" = {}
    for ln in lines:
        if ln.replica is not None and ln.text.startswith("SUMMARY "):
            out.setdefault(ln.replica, []).append(
                json.JSONDecoder().raw_decode(ln.text[8:])[0])
    return out


def pids(lines, replica: int) -> "list[tuple[float, int]]":
    return [(ln.t, int(ln.text.split()[0][4:])) for ln in lines
            if ln.replica == replica and ln.text.startswith("pid=")]


def steps(lines, replica: int, after_t: float = float("-inf")):
    """[(t, step, loss, participants, iter_s)] of ``replica``'s commit lines."""
    out = []
    for ln in lines:
        if ln.replica == replica and ln.t > after_t:
            s = parse_step(ln.text)
            if s:
                out.append((ln.t, *s))
    return out
