"""What a traced worker leaves behind and how it is read back: the layout
worker.py writes is the layout hostspans.py collects."""

import io
import json
import os
import shutil

import pytest

from chipbench_helpers import DATA, read

from chipbench import hostspans, worker


def test_tee_shows_whole_lines_to_the_tracer():
    seen = []

    class T:
        def on_line(self, line):
            seen.append(line)

    out = io.StringIO()
    tee = worker._Tee(out, T())
    print("[replica 0] step=1 inner=1 loss=1.0 participants=1 iter_s=2.00", file=tee)
    tee.write("[replica 0] SUM")
    assert len(seen) == 1
    tee.write("MARY {}\nrest")
    assert seen[1] == "[replica 0] SUMMARY {}" and tee.buf == "rest"
    assert out.getvalue().count("\n") == 2   # everything still reaches stdout
    tee.flush()                              # other attributes pass through


def test_tracer_cuts_the_loop_into_phases(tmp_path):
    tr = worker._Tracer(str(tmp_path))
    for name in ("trainer.start_quorum", "trainer.grad_dispatch",
                 "trainer.allreduce_call", "trainer.allreduce_wait"):
        tr.phase(name)
    names = [s[0] for s in tr.spans]
    assert names == ["trainer.start_quorum", "trainer.grad_dispatch",
                     "trainer.allreduce_call"]           # the last one is still open
    assert all(a <= b for _, a, b in tr.spans)
    assert [a[2] for a in tr.spans][:-1] == [b[1] for b in tr.spans][1:]  # no holes


def test_collect_reads_what_the_worker_writes(tmp_path):
    """The recorded chip trace in the worker's layout, with a span ring."""
    meta = read(os.path.join(DATA, "small.host.json"))
    tag = "g2_p4242"
    prof = tmp_path / tag / "plugins" / "profile" / "2026_09_26"
    prof.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "small.xplane.pb"), prof / "vm.xplane.pb")
    a0 = meta["anchor_epoch_ns"]
    with open(tmp_path / f"{tag}.host.json", "w") as f:
        json.dump({"pid": 4242, "replica": 2, "anchor_epoch_ns": a0,
                   "stop_epoch_ns": meta["spans"][-1][2],
                   "spans": meta["spans"]}, f)
    nap = meta["spans"][1]
    with open(tmp_path / f"{tag}.spans.json", "w") as f:
        json.dump({"replica_id": "x", "clock": "epoch_us", "spans": [
            {"name": "wire", "cat": "allreduce", "ts_us": nap[1] // 1000 + 1000,
             "dur_us": 5000, "step": 3, "quorum_id": 1}]}, f)
    # a worker that was killed mid-trace leaves no host.json: skipped
    (tmp_path / "g1_p1" / "plugins").mkdir(parents=True)
    procs = hostspans.collect(str(tmp_path))
    assert [(p["replica"], p["pid"]) for p in procs] == [(2, 4242)]
    names = {s[0] for s in procs[0]["spans"]}
    assert names == {"work", "nap", "manager.allreduce.wire"}
    wire = next(s for s in procs[0]["spans"] if s[0] == "manager.allreduce.wire")
    assert wire[3] == 3 and wire[2] - wire[1] == 5_000_000
    got = hostspans.reduce(procs)
    gaps = dict(got["idle_gaps"])
    # the ring's span lies inside the first nap: it is the innermost there
    assert gaps["manager.allreduce.wire"] == pytest.approx(0.005, rel=1e-3)
    assert gaps["nap"] == pytest.approx(0.060524593 - 0.005, rel=1e-3)
    assert got["chips_traced"] == 1 and got["busy_s"] > 0
