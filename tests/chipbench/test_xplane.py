"""The trace reduction: on hand-made events, and on a small trace recorded
on the chip (data/small.xplane.pb, see record_small_trace.py)."""

import os

import pytest

from chipbench_helpers import DATA, read

from chipbench import xplane

MS = 1_000_000


def test_busy_is_the_union_of_intervals():
    ev = [("a", 0, 10), ("b", 5, 12), ("c", 20, 30), ("d", 22, 25)]
    assert xplane.busy_intervals(ev) == [(0, 12), (20, 30)]
    assert xplane.gaps([(0, 12), (20, 30)], -5, 40) == [(-5, 0), (12, 20), (30, 40)]
    assert xplane.gaps([(0, 12), (20, 30)], 5, 25) == [(12, 20)]


def test_self_time_takes_children_out_of_their_parent():
    # a while loop of 100 over two fusions of 30 and 20, then a lone op
    ev = [("%while.1 = (...) while(...)", 0, 100), ("%fusion.2 = bf16[4] fusion()", 10, 40),
          ("%fusion.3 = bf16[4] fusion()", 50, 70), ("%copy.4 = bf16[4] copy()", 120, 130)]
    got = xplane.self_times(ev)
    assert got == {"while.1": pytest.approx(50e-9), "fusion.2": pytest.approx(30e-9),
                   "fusion.3": pytest.approx(20e-9), "copy.4": pytest.approx(10e-9)}
    assert sum(got.values()) == pytest.approx(110e-9)  # = busy: nothing counted twice


def test_gaps_go_to_the_innermost_host_span():
    idle = [(0, 100 * MS), (200 * MS, 200 * MS + 10)]
    spans = [("trainer.allreduce_wait", 0, 90 * MS),
             ("manager.allreduce.pack", 10 * MS, 40 * MS),
             ("manager.allreduce.wire", 30 * MS, 60 * MS)]
    got = xplane.attribute(idle, spans)
    assert got["manager.allreduce.pack"] == pytest.approx(0.020)   # 10..30
    assert got["manager.allreduce.wire"] == pytest.approx(0.030)   # 30..60: started last
    assert got["trainer.allreduce_wait"] == pytest.approx(0.040)   # 0..10, 60..90
    assert got["(no host span)"] == pytest.approx(0.010)           # 90..100
    assert got["(between ops)"] == pytest.approx(10e-9)
    assert sum(got.values()) == pytest.approx(0.100 + 10e-9)


def test_reduce_and_merge():
    trace = {"devices": {0: [("%a = f32[] add()", 0, 40 * MS), ("%b = f32[] mul()", 60 * MS, 100 * MS)],
                         1: [("%a = f32[] add()", 0, 20 * MS)]}}
    r = xplane.reduce(trace, [("step", 0, 100 * MS)], window=(0, 100 * MS))
    m = xplane.merge([r])
    assert m["chips_traced"] == 2 and m["window_s"] == pytest.approx(0.1)
    assert m["busy_s"] == pytest.approx((0.080 + 0.020) / 2)
    assert dict(m["device_ops"]) == {"a": pytest.approx(0.060), "b": pytest.approx(0.040)}
    assert dict(m["idle_gaps"])["step"] == pytest.approx(0.020 + 0.080)
    with pytest.raises(ValueError, match="no device plane"):
        xplane.merge([xplane.reduce({"devices": {}}, [])])


SMALL = os.path.join(DATA, "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return xplane.read(SMALL), read(os.path.join(DATA, "small.host.json"))


def test_recorded_trace_planes_and_anchor(small):
    trace, meta = small
    assert list(trace["devices"]) == [0] and trace["anchor_ns"] is not None
    names = {xplane.op_name(e[0]) for e in trace["devices"][0]}
    assert any(n.startswith(("fusion", "convolution", "dot")) for n in names), names
    kinds = {a[0] for a in trace["annotations"]}
    assert {"work", "nap"} <= kinds


def test_recorded_trace_busy_idle_and_attribution(small):
    trace, meta = small
    off = trace["anchor_ns"] - meta["anchor_epoch_ns"]
    spans = [(n, a + off, b + off) for n, a, b in meta["spans"]]
    window = (spans[0][1], spans[-1][2])
    m = xplane.merge([xplane.reduce(trace, spans, window)])
    assert m["chips_traced"] == 1
    # three naps of 20 ms: the device is idle through each of them
    assert dict(m["idle_gaps"])["nap"] >= 0.058
    assert m["window_s"] >= 0.060 and 0 < m["busy_s"] < m["window_s"] - 0.058
    assert sum(dict(m["device_ops"]).values()) == pytest.approx(m["busy_s"], rel=1e-6)
    # busy + every attributed gap = the window: nothing lost, nothing twice
    assert m["busy_s"] + sum(dict(m["idle_gaps"]).values()) == pytest.approx(
        m["window_s"], rel=1e-6)
    # the numbers of this one file, pinned. The device's events sit ~1 ms
    # ahead of the host's on the trace's clock (the first of the three
    # 22.7 us calls falls before the window): the skew any gap attribution
    # carries, negligible against gaps of seconds
    assert m["busy_s"] == pytest.approx(4.5512e-05, rel=1e-6)
    assert dict(m["device_ops"])["convolution_reduce_fusion"] == pytest.approx(4.548e-05, rel=1e-6)
    assert dict(m["idle_gaps"])["nap"] == pytest.approx(0.060524593, rel=1e-6)
    assert m["window_s"] == pytest.approx(0.063808512, rel=1e-9)
    # the work ran under the "work" annotation, on the trace's own clock too
    work = [a for a in trace["annotations"] if a[0] == "work"]
    assert len(work) == 3
    for (_, a, b), (_, sa, sb) in zip(work, [s for s in spans if s[0] == "work"]):
        assert abs(a - sa) < 2 * MS and abs(b - sb) < 2 * MS  # anchor puts them together
