"""Several managed allreduces in ONE step (a trainer that hands the gradients
over segment by segment, models/staged.py): they resolve in the order they
were issued, a failing one discards the step, ``timings()`` describes the
step's ops together, every ``allreduce/*`` span says which op it belongs to,
an op holds no device leaf once it has captured it, and
``d2h_under_backward_share`` says how much of the fetching ran before the
staging thread's last wait for gradients returned (CPU: the pieces are made
ready by hand)."""

import gc
import threading
import time
import weakref

import jax
import numpy as np
import pytest

from test_allreduce_stream import CopyingPG, _CAP3, _bits, _device_tree
from test_manager import make_manager, make_quorum
from torchft_tpu import bucketing
from torchft_tpu.process_group import FakeProcessGroupWrapper, ProcessGroupDummy


def _manager(pg=None, **kw):
    return make_manager(pg=pg or CopyingPG(), quorum=make_quorum(),
                        bucket_cap_bytes=_CAP3, **kw)


def _zeros(tree):
    return all(not np.asarray(v).any() for v in tree.values())


class TestSeveralOpsAStep:
    def test_they_resolve_in_the_order_they_were_issued(self):
        m = _manager()
        trees = [_device_tree(seed=s) for s in range(4)]
        for step in range(2):
            m.start_quorum()
            done, works = [], []
            for k, tree in enumerate(trees):
                works.append(m.allreduce(tree))
                works[-1].get_future().add_done_callback(
                    lambda _f, k=k: done.append(k))
            outs = [w.get_future().wait(timeout=30) for w in works]
            assert done == [0, 1, 2, 3]
            for tree, out in zip(trees, outs):
                for name in tree:
                    assert np.array_equal(
                        _bits(out[name]), _bits(np.asarray(tree[name]) / 2))
            assert m.should_commit() is True
            t = m.timings()
            # the step's ops together: 4 ops of 3 buckets, every one of them
            # fetched from the device; the first op of all found an empty
            # pool (a later one may draw what an earlier one gave back)
            assert t["allreduce_ops"] == 4.0
            assert _settled(m, "allreduce_buckets", 12.0)
            assert 0.0 <= t["stage_pool_hit_share"] <= (0.75, 1.0)[step]
            assert t["wire_passthrough_share"] == 0.0  # CopyingPG copies
            assert t["allreduce_pack_s"] > 0 and t["allreduce_unpack_s"] > 0
        m.shutdown(wait=False)

    def test_a_step_of_one_op_counts_one(self):
        m = _manager()
        for _ in range(2):
            m.start_quorum()
            m.allreduce(_device_tree()).get_future().wait(timeout=30)
            assert m.timings()["allreduce_ops"] == 1.0
            assert _settled(m, "allreduce_buckets", 3.0)
            m.should_commit()
        m.shutdown(wait=False)

    @pytest.mark.parametrize("failing", [0, 1])
    def test_a_failing_op_discards_the_step_and_returns_zeros(self, failing):
        """The group's wire breaks at op ``failing`` and stays broken: that
        op's tree and every later one's are zeros (an earlier op's is what
        it reduced to, in a step that does not commit), the vote is False,
        and the next step is whole again."""
        pg = FakeProcessGroupWrapper(ProcessGroupDummy())
        m = _manager(pg=pg)
        trees = [_device_tree(seed=s) for s in range(3)]
        m.start_quorum()
        pg.report_future_error(RuntimeError("injected wire failure"),
                               skip_ops=3 * failing, times=100)
        outs = [w.get_future().wait(timeout=30)
                for w in [m.allreduce(t) for t in trees]]
        assert [_zeros(o) for o in outs] == [k >= failing for k in range(3)]
        for out in outs:  # zeros land where the leaves lived
            assert all(isinstance(v, jax.Array) for v in out.values())
        assert m.errored() is not None
        assert m.should_commit() is False
        pg.report_future_error(RuntimeError("unused"), skip_ops=10 ** 6)
        m.start_quorum()
        outs = [w.get_future().wait(timeout=30)
                for w in [m.allreduce(t) for t in trees]]
        assert not any(_zeros(o) for o in outs)
        assert m.should_commit() is True
        m.shutdown(wait=False)

    def test_every_allreduce_span_says_its_segment(self):
        m = _manager()
        m.start_quorum()
        for w in [m.allreduce(_device_tree(seed=s)) for s in range(3)]:
            w.get_future().wait(timeout=30)
        m.should_commit()
        _settled(m, "allreduce_buckets", 9.0)
        time.sleep(0.1)  # the allreduce/allreduce spans, recorded at resolve
        spans = [s for s in m.tracer.export()["spans"]
                 if s["cat"] == "allreduce"]
        m.shutdown(wait=False)
        assert {s["name"] for s in spans} >= {
            "allreduce", "capture", "grad_wait", "d2h", "dispatch", "h2d",
            "divide", "pack", "wire", "unpack"}
        assert all("segment" in s["args"] for s in spans), [
            s["name"] for s in spans if "segment" not in s["args"]]
        for name, per_op in (("allreduce", 1), ("capture", 1), ("d2h", 3),
                             ("h2d", 3), ("pack", 3)):
            got = sorted(s["args"]["segment"] for s in spans if s["name"] == name)
            assert got == sorted([0, 1, 2] * per_op), (name, got)

    def test_an_op_keeps_no_device_leaf_it_has_captured(self):
        """The pieces hold the data: once ``allreduce`` returns, the
        caller's tree is the only owner of the leaves, and dropping it frees
        them while the op is still in flight (a step's later programs need
        that memory)."""
        gate = _gates()
        m = _manager()
        try:
            gate.install()
            m.start_quorum()
            tree = _device_tree()
            refs = [weakref.ref(v) for v in tree.values()]
            work = m.allreduce(tree)
            want = {k: np.asarray(v) / 2 for k, v in tree.items()}
            del tree
            gc.collect()
            assert [r() for r in refs] == [None] * len(refs)
            gate.open_all()
            out = work.get_future().wait(timeout=30)
        finally:
            gate.uninstall()
        for k in want:
            assert np.array_equal(_bits(out[k]), _bits(want[k]))
        m.shutdown(wait=False)


class _gates:
    """``bucketing.capture`` whose device buckets say they are ready, and
    let ``block_until_ready`` return, only once the test opens their op's
    gate: the backward pass, by hand."""

    def __init__(self):
        self.events = []  # one an op, in issue order
        self._real = bucketing.capture

    def install(self):
        real, events = self._real, self.events

        class Gated(bucketing.Pieces):
            __slots__ = ("gate",)

            def is_ready(self):
                return self.gate.is_set()

            def block_until_ready(self):
                assert self.gate.wait(30)
                return super().block_until_ready()

        def capture(leaves, plan, pool):
            event = threading.Event()
            events.append(event)
            out = []
            for cap in real(leaves, plan, pool):
                if isinstance(cap, bucketing.Pieces):
                    gated = Gated(cap.arrays, cap.bounds, cap.size, cap.dtype)
                    gated.gate = event
                    cap = gated
                out.append(cap)
            return out

        bucketing.capture = capture

    def uninstall(self):
        bucketing.capture = self._real

    def open_all(self):
        for e in self.events:
            e.set()


def _settled(m, key, value, timeout=5.0):
    """record_timings runs in a callback of the resolve, beside the waiter."""
    deadline = time.monotonic() + timeout
    while m.timings().get(key) != value and time.monotonic() < deadline:
        time.sleep(0.01)
    assert m.timings().get(key) == value, (key, m.timings().get(key))
    return True


@pytest.fixture
def gates():
    g = _gates()
    g.install()
    yield g
    g.uninstall()


class TestD2hUnderBackwardShare:
    def test_one_op_after_a_blocked_wait_reads_zero(self, gates):
        """Today's step: the staging thread waits for the whole backward
        pass, then fetches: nothing of the fetch ran under it."""
        m = _manager()
        m.start_quorum()
        work = m.allreduce(_device_tree())
        time.sleep(0.05)  # the staging thread sits in grad_wait
        assert not work.get_future().done()
        gates.open_all()
        work.get_future().wait(timeout=30)
        t = m.timings()
        assert t["d2h_under_backward_share"] == 0.0
        assert t["allreduce_ops"] == 1.0
        m.shutdown(wait=False)

    def test_an_op_that_never_waited_reads_zero(self, gates):
        m = _manager()
        m.start_quorum()
        tree = _device_tree()
        jax.block_until_ready(tree)
        work = m.allreduce(tree)
        gates.open_all()
        work.get_future().wait(timeout=30)
        # whether the gate opened before the staging thread asked (no wait
        # at all) or after (one wait, which ended before the fetch began)
        assert m.timings()["d2h_under_backward_share"] == 0.0
        m.shutdown(wait=False)

    def test_a_later_ops_wait_outlasting_an_earlier_ops_fetch(self, gates):
        """Two ops: the first one's gradients are there, its three buckets
        are fetched while the second one's are still being computed; the
        staging thread's last wait returns after those fetches: they ran
        under the backward pass, the second op's did not."""
        m = _manager()
        m.start_quorum()
        first = m.allreduce(_device_tree(seed=1))
        second = m.allreduce(_device_tree(seed=2))
        gates.events[0].set()
        first.get_future().wait(timeout=30)  # fetched, reduced, landed
        time.sleep(0.05)  # the staging thread sits in the second's grad_wait
        share_so_far = m.timings()["d2h_under_backward_share"]
        gates.events[1].set()
        second.get_future().wait(timeout=30)
        share = m.timings()["d2h_under_backward_share"]
        spans = [s for s in m.tracer.export()["spans"] if s["name"] == "d2h"]
        m.shutdown(wait=False)
        # after each piece of the first op the second's capture was found
        # not ready: the first op's whole fetch ran under the backward pass
        assert share_so_far > 0.5
        assert 0.0 < share < share_so_far
        # the share is the first op's fetch seconds over both ops'
        by_op = {k: sum(s["dur_us"] for s in spans if s["args"]["segment"] == k)
                 for k in (0, 1)}
        assert share == pytest.approx(
            by_op[0] / (by_op[0] + by_op[1]), rel=0.35)
        assert m.timings()["allreduce_ops"] == 2.0

    def test_gradients_that_were_all_there_hide_nothing(self, gates):
        """Two ops whose gradients are ready before the first fetch: no look
        finds the device computing, no wait waits."""
        m = _manager()
        m.start_quorum()
        works = [m.allreduce(_device_tree(seed=s)) for s in (1, 2)]
        # (the staging thread may sit in the first op's wait by now: that
        # wait ends before any fetch begins)
        gates.events[1].set()
        gates.events[0].set()
        for w in works:
            w.get_future().wait(timeout=30)
        assert m.timings()["d2h_under_backward_share"] == 0.0
        assert m.timings()["allreduce_ops"] == 2.0
        m.shutdown(wait=False)

    def test_a_new_step_starts_from_zero(self, gates):
        m = _manager()
        for step in range(2):
            m.start_quorum()
            works = [m.allreduce(_device_tree(seed=s)) for s in (1, 2)]
            gates.events[-2].set()
            works[0].get_future().wait(timeout=30)
            time.sleep(0.05)
            gates.events[-1].set()
            works[1].get_future().wait(timeout=30)
            assert 0.0 < m.timings()["d2h_under_backward_share"] < 1.0
            m.should_commit()
        m.start_quorum()
        work = m.allreduce(_device_tree())
        time.sleep(0.05)
        gates.open_all()
        work.get_future().wait(timeout=30)
        assert m.timings()["d2h_under_backward_share"] == 0.0
        assert m.timings()["allreduce_ops"] == 1.0
        m.shutdown(wait=False)


@pytest.fixture
def slow_transfers(monkeypatch):
    """Every device bucket in pieces of four elements whose transfers take
    2 ms each to wait for (``np.asarray`` sleeps): a bucket of 16 to 20
    pieces, so the fetchers have something to share."""
    from test_bucketing import _HostPiece

    real = bucketing.capture

    def capture(leaves, plan, pool):
        captured = real(leaves, plan, pool)
        for cap in captured:
            cap.arrays[:] = [_HostPiece(np.array(a), 0.002)
                             for a in cap.arrays]
        return captured

    monkeypatch.setattr(bucketing, "FETCH_PIECE_BYTES", 4 * 4)
    monkeypatch.setattr(bucketing, "capture", capture)


def _cores(monkeypatch, n):
    monkeypatch.setattr(
        bucketing.os, "sched_getaffinity", lambda _pid: set(range(n)))


class TestTheFetchersUnderAStepOfSeveralOps:
    @pytest.mark.parametrize("cores", [1, 13], ids=["one_core", "13_cores"])
    def test_the_pg_sees_the_steps_buckets_in_the_same_order(
        self, monkeypatch, slow_transfers, cores
    ):
        """One core is the loop as it was (no helper gets a piece); with
        thirteen the copies of a bucket run on several threads: the PG is
        handed the same flats in the same order either way, each bitwise
        ``np.asarray`` of its packed bucket."""
        _cores(monkeypatch, cores)
        seen = []

        class RecordingPG(CopyingPG):
            def allreduce(self, arrays, *a, **kw):
                # now: a pool buffer is another bucket's a moment later
                seen.extend(np.asarray(x).tobytes() for x in arrays)
                return super().allreduce(arrays, *a, **kw)

        m = _manager(pg=RecordingPG())
        trees = [_device_tree(seed=s) for s in range(3)]
        want = []
        for tree in trees:
            leaves = jax.tree_util.tree_leaves(tree)
            plan = bucketing.build_plan(leaves, _CAP3)
            want += [np.asarray(f).tobytes()
                     for f in bucketing.pack(leaves, plan)[0]]
        m.start_quorum()
        for w in [m.allreduce(t) for t in trees]:
            w.get_future().wait(timeout=30)
        assert m.should_commit() is True
        spans = [s for s in m.tracer.export()["spans"] if s["name"] == "d2h"]
        m.shutdown(wait=False)
        assert seen == want
        assert len(spans) == 9
        width = min(bucketing.FETCH_WIDTH, cores)
        assert {s["args"]["fetchers"] for s in spans} == {width}
        assert [s["args"]["pieces"] for s in spans] == [20, 18, 16] * 3

    def test_timings_say_how_many_copied_at_once_and_how_fast(
        self, monkeypatch, slow_transfers
    ):
        _cores(monkeypatch, 13)
        m = _manager()
        for _step in range(2):
            m.start_quorum()
            for w in [m.allreduce(_device_tree(seed=s)) for s in range(3)]:
                w.get_future().wait(timeout=30)
            assert m.should_commit() is True
        _settled(m, "allreduce_buckets", 9.0)
        t = m.timings()
        spans = [s for s in m.tracer.export()["spans"] if s["name"] == "d2h"]
        m.shutdown(wait=False)
        # 54 waits of 2 ms a op, shared by FETCH_WIDTH threads: well over one
        # at a time, and never more than the threads there are
        assert 1.5 < t["d2h_concurrency"] <= bucketing.FETCH_WIDTH
        assert t["d2h_gb_s"] > 0
        assert t["stage_pool_hit_share"] == 1.0  # the second step's
        last = spans[-9:]  # the second step's nine buckets
        for s in last:
            a = s["args"]
            assert a["fetchers"] == bucketing.FETCH_WIDTH
            assert a["busy_us"] >= a["pieces"] * 2000 > a["copy_us"] >= 0
        # the counter is the spans' own arithmetic
        assert t["d2h_concurrency"] == pytest.approx(
            sum(s["args"]["busy_us"] for s in last)
            / sum(s["dur_us"] for s in last), rel=0.2)
        assert t["d2h_gb_s"] == pytest.approx(
            sum(s["args"]["bytes"] for s in last)
            / sum(s["dur_us"] for s in last) / 1e3, rel=0.2)

    def test_one_core_reads_one_at_a_time(self, monkeypatch, slow_transfers):
        """``d2h_concurrency`` near 1.0 with many pieces: the pool did not
        engage (here: one core in the affinity mask)."""
        _cores(monkeypatch, 1)
        m = _manager()
        m.start_quorum()
        for w in [m.allreduce(_device_tree(seed=s)) for s in range(2)]:
            w.get_future().wait(timeout=30)
        t = m.timings()
        m.shutdown(wait=False)
        assert 0.5 < t["d2h_concurrency"] <= 1.0
        assert t["d2h_gb_s"] > 0
