"""ProcessGroup tests (reference pattern: process_group_test.py).

Replica groups are threads sharing one KV store, like the reference's
MultiPgBaseTest (process_group_test.py:792-891), including the resiliency
harness: crash a rank, expect errors on survivors, reconfigure, verify the
collective works again (:894-950).
"""

import sys
import threading
import time
import types
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np
import pytest

import torchft_tpu.process_group as pg_mod

from torchft_tpu.coordination import KvStoreServer
from torchft_tpu.process_group import (
    ErrorSwallowingProcessGroupWrapper,
    FakeProcessGroupWrapper,
    ManagedProcessGroup,
    ProcessGroupDummy,
    ProcessGroupHost,
    ReduceOp,
)


@pytest.fixture()
def store():
    s = KvStoreServer("127.0.0.1:0")
    yield s
    s.shutdown()


def run_parallel(world, fn):
    """Run fn(rank) in `world` threads, return results by rank, re-raising."""
    with ThreadPoolExecutor(max_workers=world) as ex:
        futs = [ex.submit(fn, r) for r in range(world)]
        return [f.result(timeout=60) for f in futs]


def make_pgs(store, world, quorum_id=1, timeout=10.0, prefix="test"):
    pgs = [ProcessGroupHost(timeout=timeout) for _ in range(world)]
    store_addr = f"127.0.0.1:{store.port}/{prefix}"

    def cfg(rank):
        pgs[rank].configure(store_addr, rank, world, quorum_id=quorum_id)

    run_parallel(world, cfg)
    return pgs


class TestProcessGroupDummy:
    def test_collectives_identity(self):
        pg = ProcessGroupDummy()
        x = np.arange(4.0)
        assert pg.size() == 1
        np.testing.assert_array_equal(pg.allreduce([x]).get_future().wait()[0], x)
        np.testing.assert_array_equal(pg.broadcast([x]).get_future().wait()[0], x)
        assert pg.allgather([x]).get_future().wait()[0][0] is x


class TestProcessGroupHost:
    WORLD = 3

    def test_allreduce_sum_and_avg(self, store):
        pgs = make_pgs(store, self.WORLD)

        def step(rank):
            x = np.full((4,), float(rank + 1), dtype=np.float32)
            s = pgs[rank].allreduce([x], ReduceOp.SUM).get_future().wait()[0]
            a = pgs[rank].allreduce([x], ReduceOp.AVG).get_future().wait()[0]
            return s, a

        for s, a in run_parallel(self.WORLD, step):
            np.testing.assert_allclose(s, np.full((4,), 6.0))
            np.testing.assert_allclose(a, np.full((4,), 2.0))
        for pg in pgs:
            pg.shutdown()

    def test_allreduce_max_multiple_tensors(self, store):
        pgs = make_pgs(store, self.WORLD)

        def step(rank):
            xs = [np.array([float(rank)]), np.array([float(-rank)])]
            return pgs[rank].allreduce(xs, ReduceOp.MAX).get_future().wait()

        for out in run_parallel(self.WORLD, step):
            np.testing.assert_allclose(out[0], [2.0])
            np.testing.assert_allclose(out[1], [0.0])
        for pg in pgs:
            pg.shutdown()

    def test_broadcast(self, store):
        pgs = make_pgs(store, self.WORLD)

        def step(rank):
            x = np.full((2,), float(rank), dtype=np.float32)
            return pgs[rank].broadcast([x], root=1).get_future().wait()[0]

        for out in run_parallel(self.WORLD, step):
            np.testing.assert_allclose(out, np.full((2,), 1.0))
        for pg in pgs:
            pg.shutdown()

    def test_allgather(self, store):
        pgs = make_pgs(store, self.WORLD)

        def step(rank):
            x = np.array([float(rank)])
            return pgs[rank].allgather([x]).get_future().wait()

        for out in run_parallel(self.WORLD, step):
            assert len(out) == self.WORLD
            for r in range(self.WORLD):
                np.testing.assert_allclose(out[r][0], [float(r)])
        for pg in pgs:
            pg.shutdown()

    def test_reduce_scatter(self, store):
        pgs = make_pgs(store, self.WORLD)

        def step(rank):
            chunks = [[np.array([float(rank + r)])] for r in range(self.WORLD)]
            return pgs[rank].reduce_scatter(chunks).get_future().wait()

        outs = run_parallel(self.WORLD, step)
        for r, out in enumerate(outs):
            # sum over ranks of (rank + r)
            expected = sum(float(rank + r) for rank in range(self.WORLD))
            np.testing.assert_allclose(out[0], [expected])
        for pg in pgs:
            pg.shutdown()

    def test_alltoall(self, store):
        pgs = make_pgs(store, self.WORLD)

        def step(rank):
            chunks = [np.array([rank * 10.0 + r]) for r in range(self.WORLD)]
            return pgs[rank].alltoall(chunks).get_future().wait()

        outs = run_parallel(self.WORLD, step)
        for r, out in enumerate(outs):
            for src in range(self.WORLD):
                np.testing.assert_allclose(out[src], [src * 10.0 + r])
        for pg in pgs:
            pg.shutdown()

    def test_send_recv(self, store):
        pgs = make_pgs(store, 2)

        def step(rank):
            if rank == 0:
                pgs[0].send([np.array([42.0])], dst=1, tag=7).wait()
                return None
            return pgs[1].recv(src=0, tag=7).get_future().wait()

        outs = run_parallel(2, step)
        np.testing.assert_allclose(outs[1][0], [42.0])
        for pg in pgs:
            pg.shutdown()

    def test_barrier(self, store):
        pgs = make_pgs(store, self.WORLD)
        run_parallel(self.WORLD, lambda r: pgs[r].barrier().wait())
        for pg in pgs:
            pg.shutdown()

    def test_world_size_one_noop(self, store):
        (pg,) = make_pgs(store, 1)
        x = np.arange(3.0)
        np.testing.assert_allclose(
            pg.allreduce([x], ReduceOp.AVG).get_future().wait()[0], x
        )
        pg.shutdown()

    @pytest.mark.parametrize("donate", [True, False])
    def test_world_of_one_allreduce_hands_back_what_was_donated(
        self, store, donate
    ):
        """Nothing to reduce: a donated ndarray IS the result (no copy, no
        fresh pages); without the caller's word the result is memory of its
        own, as at any larger world."""
        (pg,) = make_pgs(store, 1)
        x, y = np.arange(6.0), np.arange(4, dtype=np.int32)
        fut = pg.allreduce([x, y], ReduceOp.SUM, donate=donate).get_future()
        out = fut.wait(timeout=10)
        pg.shutdown()
        assert (out[0] is x, out[1] is y) == (donate, donate)
        assert np.shares_memory(out[0], x) == donate
        np.testing.assert_array_equal(out[0], np.arange(6.0))
        np.testing.assert_array_equal(out[1], np.arange(4))
        # the dispatch thread still stamps the op: allreduce/wire_run
        enqueued, run0, run1 = fut.stamps
        assert enqueued <= run0 <= run1

    @pytest.mark.parametrize("payload", ["tuple", "compressed"])
    def test_world_of_one_donated_wires_are_still_copied(self, store, payload):
        """The quantized wire formats keep their independent copies, donated
        or not: their callers decode in place."""
        from torchft_tpu.ops.quantization import compress_bucket

        (pg,) = make_pgs(store, 1)
        flat = np.linspace(-3, 3, 1024, dtype=np.float32)
        if payload == "compressed":
            wire = compress_bucket(flat, "int8")
            parts = lambda w: (w.payload, w.scales)
        else:
            wire = (np.arange(8, dtype=np.uint8), np.ones(2, np.float32), 8)
            parts = lambda w: (w[0], w[1])
        (out,) = pg.allreduce([wire], donate=True).get_future().wait(timeout=10)
        pg.shutdown()
        assert type(out) is type(wire)
        for got, given in zip(parts(out), parts(wire)):
            np.testing.assert_array_equal(got, given)
            assert not np.shares_memory(got, given)

    _ONE = {
        "allgather": lambda pg, x: pg.allgather([x]),
        "broadcast": lambda pg, x: pg.broadcast([x]),
        "reduce_scatter": lambda pg, x: pg.reduce_scatter([[x]]),
        "alltoall": lambda pg, x: pg.alltoall([x]),
    }

    @pytest.mark.parametrize("collective", sorted(_ONE))
    def test_world_of_one_other_collectives_still_copy(self, store, collective):
        (pg,) = make_pgs(store, 1)
        x = np.arange(5.0)
        out = self._ONE[collective](pg, x).get_future().wait(timeout=10)
        pg.shutdown()
        got = out[0][0] if collective == "allgather" else out[0]
        np.testing.assert_array_equal(got, x)
        assert not np.shares_memory(got, x)

    # per-collective issue fns for the resiliency matrix (reference
    # process_group_test.py:963-1027 parametrizes its resiliency harness
    # over every collective; an abort must fail and a reconfigure must
    # revive each of them, not just allreduce)
    _COLLECTIVES = {
        "allreduce": lambda pg, rank, world: pg.allreduce(
            [np.array([1.0])]
        ),
        "allgather": lambda pg, rank, world: pg.allgather(
            [np.array([float(rank)])]
        ),
        "broadcast": lambda pg, rank, world: pg.broadcast(
            [np.array([float(rank)])], root=0
        ),
        "reduce_scatter": lambda pg, rank, world: pg.reduce_scatter(
            [[np.array([float(rank)])] for _ in range(world)]
        ),
        "alltoall": lambda pg, rank, world: pg.alltoall(
            [np.array([float(rank * 10 + d)]) for d in range(world)]
        ),
        "barrier": lambda pg, rank, world: pg.barrier(),
    }

    @pytest.mark.parametrize("collective", sorted(_COLLECTIVES))
    def test_resiliency_crash_and_reconfigure(self, store, collective):
        """Crash the last rank mid-life; survivors must observe an error on
        the given collective and then run it successfully after
        reconfiguring to a smaller world."""
        world = 3
        issue = self._COLLECTIVES[collective]
        pgs = make_pgs(
            store, world, quorum_id=1, timeout=3.0, prefix=collective
        )

        # Everyone agrees the mesh works.
        run_parallel(world, lambda r: pgs[r].barrier().wait())

        pgs[2].abort()  # crash

        def survivor_step(rank):
            if rank == 2:
                return "crashed"
            # broadcast is root-push + ack: the dead rank is detected by the
            # ROOT (missing ack); a live non-root receiver got its payload
            # from the live root and legitimately completes. Every other
            # collective rendezvouses all ranks, so every survivor errors.
            if collective == "broadcast" and rank != 0:
                try:
                    issue(pgs[rank], rank, world).get_future().wait(timeout=10)
                except Exception:  # noqa: BLE001 - either outcome is valid
                    pass
                return "errored"
            with pytest.raises(Exception):
                issue(pgs[rank], rank, world).get_future().wait(timeout=10)
            return "errored"

        assert run_parallel(world, survivor_step) == ["errored", "errored", "crashed"]
        assert pgs[0].errored() is not None

        # Reconfigure survivors under a new quorum id with world=2; the
        # same collective must complete WITH world-2 values (a generation
        # that leaked state from the aborted world-3 mesh, or reduced with
        # the wrong world size, must fail here, not just hang).
        def recfg(rank):
            pgs[rank].configure(
                f"127.0.0.1:{store.port}/test_{collective}", rank, 2,
                quorum_id=2,
            )
            return issue(pgs[rank], rank, 2).get_future().wait(timeout=10)

        outs = run_parallel(2, recfg)
        if collective == "allreduce":  # both contribute [1.0]
            for out in outs:
                np.testing.assert_allclose(out[0], [2.0])
        elif collective == "allgather":  # rows = [rank0 leaves, rank1 leaves]
            for out in outs:
                np.testing.assert_allclose(out[0][0], [0.0])
                np.testing.assert_allclose(out[1][0], [1.0])
        elif collective == "broadcast":  # root 0's payload everywhere
            for out in outs:
                np.testing.assert_allclose(out[0], [0.0])
        elif collective == "reduce_scatter":  # chunk r reduced over 2 ranks
            for rank, out in enumerate(outs):
                np.testing.assert_allclose(out[0], [0.0 + 1.0])
        elif collective == "alltoall":  # out[src] = src's chunk for me
            for rank, out in enumerate(outs):
                np.testing.assert_allclose(out[0], [0.0 * 10 + rank])
                np.testing.assert_allclose(out[1], [1.0 * 10 + rank])
        assert pgs[0].errored() is None
        for pg in pgs[:2]:
            pg.shutdown()

    def test_timeout_aborts(self, store):
        """A collective that can't complete (partner never joins it) aborts
        after the timeout instead of hanging forever."""
        pgs = make_pgs(store, 2, timeout=1.0)

        # Only rank 0 issues the collective; rank 1 stays silent.
        with pytest.raises(Exception):
            pgs[0].allreduce([np.array([1.0])]).get_future().wait(timeout=15)
        assert pgs[0].errored() is not None
        for pg in pgs:
            pg.shutdown()


    # What the child-process groups' tests held of isolation, on the
    # mechanism that stays: a host communicator that is wedged is ended by
    # its timeouts, abort() and shutdown(), from any thread.
    _P2P = {
        "send": lambda pg, rank, world: pg.send(
            [np.ones(4, np.float32)], (rank + 1) % world
        ),
        "recv": lambda pg, rank, world: pg.recv((rank + 1) % world),
    }

    @pytest.mark.parametrize("op", sorted(_COLLECTIVES) + sorted(_P2P))
    def test_an_op_after_a_failure_raises_the_failure_at_once(self, store, op):
        """The peer dies and an allreduce fails on it. Whatever is issued
        on that generation afterwards is refused with that very error where
        it is issued: nothing is queued behind a dead mesh to wait out a
        timeout."""
        issue = {**self._COLLECTIVES, **self._P2P}[op]
        pgs = make_pgs(store, 2, timeout=5.0, prefix=f"after_{op}")
        pgs[1].abort()
        with pytest.raises(Exception):
            pgs[0].allreduce([np.ones(4, np.float32)]).get_future().wait(10)
        first = pgs[0].errored()
        assert first is not None
        t0 = time.monotonic()
        with pytest.raises(type(first)) as again:
            issue(pgs[0], 0, 2)
        assert again.value is first
        assert time.monotonic() - t0 < 1.0
        for pg in pgs:
            pg.shutdown()

    # ops that can never complete: the peer is configured and stays silent
    _NEVER_FED = {
        "allreduce": lambda pg: [pg.allreduce([np.ones(4, np.float32)])],
        # a second op queued behind the first is failed too
        "allreduce_and_one_queued": lambda pg: [
            pg.allreduce([np.ones(4, np.float32)]), pg.barrier()
        ],
        "recv": lambda pg: [pg.recv(1)],
        # more than the loopback's socket buffers take: the writer thread
        # blocks in its send
        "send": lambda pg: [pg.send([np.zeros(64 << 20, np.uint8)], 1)],
    }

    @pytest.mark.parametrize("how", ["abort", "shutdown"])
    @pytest.mark.parametrize("op", sorted(_NEVER_FED))
    def test_abort_and_shutdown_fail_what_is_outstanding(self, store, op, how):
        """Called from another thread under an op that would wait out a 60 s
        timeout, both close the sockets: every outstanding future fails
        within a second. After abort() the PG says so; after shutdown() it
        is unconfigured."""
        pgs = make_pgs(store, 2, timeout=60.0, prefix=f"{how}_{op}")
        works = self._NEVER_FED[op](pgs[0])
        time.sleep(0.1)  # let the op reach its socket
        assert not any(w.get_future().done() for w in works)
        t0 = time.monotonic()
        getattr(pgs[0], how)()
        for w in works:
            with pytest.raises(Exception):
                w.get_future().wait(timeout=10)
        assert time.monotonic() - t0 < 2.0
        if how == "abort":
            assert pgs[0].errored() is not None
            with pytest.raises(type(pgs[0].errored())):
                pgs[0].barrier()
        else:
            assert pgs[0].errored() is None
            with pytest.raises(RuntimeError, match="not configured"):
                pgs[0].barrier()
        for pg in pgs:
            pg.shutdown()


class TestRingAllreduce:
    """The bandwidth-optimal path: payloads >= _RING_MIN_BYTES ride a ring
    reduce-scatter + allgather with raw frames; results must match the
    full-mesh exchange exactly and per-rank traffic must be ~2x payload,
    independent of world size."""

    _next_quorum = [1]

    def _run(self, store, world, leaves_fn, op):
        # fresh quorum id per generation: the rendezvous keys are
        # quorum-scoped, so reusing one within a test would read the
        # previous (torn-down) generation's addresses
        self._next_quorum[0] += 1
        pgs = make_pgs(store, world, quorum_id=self._next_quorum[0])

        def step(rank):
            return pgs[rank].allreduce(leaves_fn(rank), op).get_future().wait(60)

        outs = run_parallel(world, step)
        comms = [pg._gen.comm for pg in pgs]
        for pg in pgs:
            pg.shutdown()
        return outs, comms

    def test_matches_reference_reduction(self, store):
        world = 4
        n = 64 * 1024  # 256 KiB of f32 -> ring path
        rng = np.random.default_rng(0)
        vals = [rng.standard_normal(n).astype(np.float32) for _ in range(world)]

        for op, ref in [
            (ReduceOp.SUM, np.sum(vals, axis=0)),
            (ReduceOp.AVG, np.mean(vals, axis=0)),
            (ReduceOp.MAX, np.max(vals, axis=0)),
            (ReduceOp.MIN, np.min(vals, axis=0)),
        ]:
            outs, _ = self._run(store, world, lambda r: [vals[r].copy()], op)
            for out in outs:
                np.testing.assert_allclose(out[0], ref, rtol=1e-5, atol=1e-5)

    def test_multi_leaf_mixed_dtypes_and_shapes(self, store):
        world = 3

        def leaves(rank):
            return [
                np.full((257, 129), float(rank + 1), np.float32),
                np.full((100_001,), rank + 1, np.int64),
                np.full((33, 3, 7), float(rank), np.float64),
            ]

        outs, _ = self._run(store, world, leaves, ReduceOp.SUM)
        for out in outs:
            np.testing.assert_allclose(out[0], np.full((257, 129), 6.0))
            np.testing.assert_array_equal(out[1], np.full((100_001,), 6))
            np.testing.assert_allclose(out[2], np.full((33, 3, 7), 3.0))

    def test_per_rank_traffic_is_world_size_independent(self, store):
        payload = 4 * 1024 * 1024  # 4 MiB of f32 = 16 MiB bytes
        byte_counts = {}
        for world in (2, 4):
            outs, comms = self._run(
                store, world,
                lambda r: [np.ones(payload, np.float32)],
                ReduceOp.SUM,
            )
            nbytes = payload * 4
            sent = [c.bytes_sent for c in comms]
            byte_counts[world] = max(sent)
            # ring bound: 2*(world-1)/world * payload (+ small framing slop)
            bound = 2 * (world - 1) / world * nbytes * 1.05 + 4096
            assert max(sent) <= bound, (world, sent, bound)
        # naive exchange would triple traffic from world 2 -> 4; the ring
        # must stay flat (2/2 -> 6/4 segments: at most 1.5x)
        assert byte_counts[4] <= byte_counts[2] * 1.6, byte_counts

    def test_bfloat16_ring(self, store):
        """bf16 is the dominant TPU gradient dtype; raw frames must carry it
        (memoryview can't export ml_dtypes — regression for the uint8-view
        framing)."""
        import ml_dtypes

        world = 2
        n = 64 * 1024  # 128 KiB of bf16 -> ring path
        vals = [
            (np.arange(n) % 7 + r).astype(ml_dtypes.bfloat16)
            for r in range(world)
        ]
        outs, _ = self._run(store, world, lambda r: [vals[r].copy()], ReduceOp.SUM)
        ref = vals[0].astype(np.float32) + vals[1].astype(np.float32)
        for out in outs:
            assert out[0].dtype == ml_dtypes.bfloat16
            np.testing.assert_allclose(
                out[0].astype(np.float32), ref, rtol=1e-2
            )

    def test_small_payload_uses_exchange(self, store, monkeypatch):
        import torchft_tpu.process_group as pg_mod

        def boom(*a, **k):
            raise AssertionError("ring must not run for small payloads")

        monkeypatch.setattr(pg_mod, "_ring_allreduce", boom)
        world = 2
        outs, comms = self._run(
            store, world, lambda r: [np.ones(8, np.float32)], ReduceOp.SUM
        )
        np.testing.assert_allclose(outs[0][0], np.full(8, 2.0))


def _bits(a):
    return np.ascontiguousarray(a).reshape(-1).view(np.uint8)


def _ring_reference(vals, op):
    """What the ring computes, written out: segment ``j`` (cut at multiples
    of ``ceil(n / world)``) starts as rank ``j``'s and is accumulated, in
    the input dtype, into rank ``j + 1``'s, that into rank ``j + 2``'s and
    so on round the ring; AVG divides at the end."""
    world, n = len(vals), vals[0].size
    seg = -(-n // world)
    out = np.empty_like(vals[0])
    for j in range(world):
        cut = slice(min(j * seg, n), min((j + 1) * seg, n))
        acc = vals[j][cut].copy()
        for i in range(1, world):
            own = vals[(j + i) % world][cut].copy()
            if op == ReduceOp.MAX:
                np.maximum(own, acc, out=own)
            else:
                own += acc
            acc = own
        out[cut] = acc
    if op == ReduceOp.AVG:
        if np.issubdtype(out.dtype, np.integer):
            return out / world
        out /= world
    return out


def _ring_values(dtype, n, world, seed=0):
    rng = np.random.default_rng(seed)
    if np.issubdtype(dtype, np.integer):
        return [rng.integers(-1000, 1000, n).astype(dtype)
                for _ in range(world)]
    return [rng.standard_normal(n).astype(dtype) for _ in range(world)]


@pytest.fixture(scope="module")
def meshes():
    """One ProcessGroupHost mesh a world size, shared by the ring's cases
    (a case is one collective; building a mesh is the slow part)."""
    store = KvStoreServer("127.0.0.1:0")
    made = {}

    def get(world):
        if world not in made:
            made[world] = make_pgs(store, world, quorum_id=100 + world,
                                   timeout=20.0, prefix="ring")
        return made[world]

    yield get
    for pgs in made.values():
        for pg in pgs:
            pg.shutdown()
    store.shutdown()


_CHUNK = 24 * 1024  # a test's frame: several a segment at these lengths


def _ring_said(info):
    """What a ring left on its op's future, less its account of its own
    time, which is checked here: ``t_first`` a clock reading, every term
    the lanes' mean and the largest lane's in whole microseconds, none
    negative, the mean not above the largest."""
    info = dict(info)
    assert isinstance(info.pop("t_first"), float)
    for term in pg_mod._RING_TERMS:
        mean, most = info.pop(term + "_us"), info.pop(term + "_us_max")
        assert isinstance(mean, int) and 0 <= mean <= most, (term, mean, most)
    return info


def _ring_len(dtype, length):
    if length == "min":  # exactly the ring's threshold
        return pg_mod._RING_MIN_BYTES // np.dtype(dtype).itemsize
    return length


class TestRingInPlaceAndStreamed:
    """The ring reduces in what was donated and leaves alone what was not,
    in frames of ``_RING_CHUNK_BYTES``: bit for bit the accumulation in
    ring order, on every rank alike."""

    @pytest.mark.parametrize("donate", [True, False], ids=["donated", "kept"])
    # neither the world nor the frame divides them; "min" is a segment
    # shorter than a frame at a world of three or four
    @pytest.mark.parametrize("length", ["min", 100_003, 250_007])
    @pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVG, ReduceOp.MAX],
                             ids=lambda o: o.value)
    @pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float32, np.int32],
                             ids=["bf16", "f32", "i32"])
    @pytest.mark.parametrize("world", [2, 3, 4])
    def test_bitwise_the_ring_order_reduction(
        self, meshes, monkeypatch, world, dtype, op, length, donate
    ):
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        pgs = meshes(world)
        vals = _ring_values(dtype, _ring_len(dtype, length), world)
        want = _ring_reference(vals, op)
        ins = [v.copy() for v in vals]

        def step(rank):
            fut = pgs[rank].allreduce([ins[rank]], op, donate=donate).get_future()
            return fut.wait(30)[0], fut.ring

        outs = run_parallel(world, step)
        keeps_dtype = not (op == ReduceOp.AVG and dtype is np.int32)
        frames = -(-(-(-vals[0].size // world)) * vals[0].itemsize // _CHUNK)
        for rank, (out, info) in enumerate(outs):
            assert out.dtype == want.dtype and out.shape == want.shape
            assert np.array_equal(_bits(out), _bits(want)), rank
            assert _ring_said(info) == {"inplace": int(donate and keeps_dtype),
                                        "chunks": frames, "lanes": 1}
            if donate and keeps_dtype:
                assert out is ins[rank]
            else:  # left as it was, and the result is memory of its own
                assert np.array_equal(_bits(ins[rank]), _bits(vals[rank]))
                assert not np.shares_memory(out, ins[rank])
        assert not any(
            np.shares_memory(a[0], b[0])
            for i, a in enumerate(outs) for b in outs[i + 1:]
        )

    @pytest.mark.parametrize("donate", [True, False], ids=["donated", "kept"])
    @pytest.mark.parametrize("world", [2, 3, 4])
    def test_leaves_of_several_shapes_and_dtypes_ride_as_one_buffer_a_dtype(
        self, meshes, monkeypatch, world, donate
    ):
        """A dtype's leaves are reduced as if laid end to end (segments and
        frames cross their boundaries), each in its own memory; a leaf of
        no elements and one of no dimensions ride along."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        pgs = meshes(world)
        shapes = [((257, 129), np.float32), ((50_001,), np.int64),
                  ((0, 3), np.float32), ((33, 3, 7), np.float32),
                  ((), np.float32), ((5,), np.int64)]
        vals = [
            [_ring_values(dt, int(np.prod(shape)), 1, seed=7 * r + i)[0]
             .reshape(shape) for i, (shape, dt) in enumerate(shapes)]
            for r in range(world)
        ]
        ins = [[v.copy() for v in leaves] for leaves in vals]
        outs = run_parallel(world, lambda r: pgs[r].allreduce(
            ins[r], ReduceOp.SUM, donate=donate).get_future().wait(30))
        for dt in (np.float32, np.int64):
            idxs = [i for i, (_s, d) in enumerate(shapes) if d is dt]
            want = _ring_reference(
                [np.concatenate([vals[r][i].reshape(-1) for i in idxs])
                 for r in range(world)], ReduceOp.SUM)
            for r in range(world):
                got = np.concatenate([outs[r][i].reshape(-1) for i in idxs])
                assert np.array_equal(_bits(got), _bits(want)), (r, dt)
        for r in range(world):
            for i, (shape, dt) in enumerate(shapes):
                assert outs[r][i].shape == shape and outs[r][i].dtype == dt
                assert (outs[r][i] is ins[r][i]) == donate
                if not donate:
                    assert np.array_equal(_bits(ins[r][i]), _bits(vals[r][i]))
                    assert not np.shares_memory(outs[r][i], ins[r][i])

    @pytest.mark.parametrize("how", ["strided", "read_only"])
    def test_a_donated_leaf_the_ring_cannot_work_in_is_copied(
        self, meshes, how
    ):
        """Donation is honoured where the memory allows it: a leaf that is
        not C-contiguous, or not writable, is left as it was."""
        world = 2
        pgs = meshes(world)
        vals = _ring_values(np.float32, 40_000, world)
        want = _ring_reference(vals, ReduceOp.SUM)

        def leaf(rank):
            if how == "strided":
                wide = np.zeros((40_000, 2), np.float32)
                wide[:, 0] = vals[rank]
                return wide[:, 0]
            a = vals[rank].copy()
            a.flags.writeable = False
            return a

        ins = [leaf(r) for r in range(world)]

        def step(rank):
            fut = pgs[rank].allreduce(
                [ins[rank]], ReduceOp.SUM, donate=True).get_future()
            return fut.wait(30)[0], fut.ring

        for rank, (out, info) in enumerate(run_parallel(world, step)):
            assert np.array_equal(_bits(out), _bits(want))
            assert info["inplace"] == 0
            assert not np.shares_memory(out, ins[rank])
            assert np.array_equal(ins[rank], vals[rank])

    @pytest.mark.parametrize("donate", [True, False], ids=["donated", "kept"])
    def test_a_peer_that_closes_mid_ring_fails_every_survivor_in_time(
        self, store, monkeypatch, donate
    ):
        """Rank 2 dies after its third frame. Rank 0 reads the closed
        socket; rank 1, whose frames come from rank 0, is failed by its
        socket's timeout. What was donated may be left half-reduced (the
        caller gave it up); what was not is left as it was. The survivors
        then form a world of two, and its ring is right."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        world, timeout = 3, 2.0
        pgs = make_pgs(store, world, quorum_id=1, timeout=timeout,
                       prefix="midring")
        vals = _ring_values(np.float32, 250_007, world)
        ins = [v.copy() for v in vals]
        comm = pgs[2]._gen.comm
        recv, seen = comm.recv_raw_into, []

        def dying_recv(peer, out):
            seen.append(peer)
            if len(seen) == 3:
                pgs[2].abort()
            return recv(peer, out)

        comm.recv_raw_into = dying_recv

        def step(rank):
            t0 = time.monotonic()
            with pytest.raises(Exception):
                pgs[rank].allreduce(
                    [ins[rank]], ReduceOp.SUM, donate=donate
                ).get_future().wait(timeout=10)
            return time.monotonic() - t0

        took = run_parallel(world, step)
        assert max(took) < timeout + 2.0, took
        assert pgs[0].errored() is not None and pgs[1].errored() is not None
        if not donate:
            for rank in range(world):
                assert np.array_equal(ins[rank], vals[rank])

        def again(rank):
            pgs[rank].configure(
                f"127.0.0.1:{store.port}/midring", rank, 2, quorum_id=2)
            return pgs[rank].allreduce(
                [vals[rank].copy()], ReduceOp.SUM, donate=donate
            ).get_future().wait(timeout=10)[0]

        want = _ring_reference(vals[:2], ReduceOp.SUM)
        for out in run_parallel(2, again):
            assert np.array_equal(_bits(out), _bits(want))
        for pg in pgs:
            pg.shutdown()


@pytest.fixture(scope="module")
def lane_meshes():
    """One mesh a (world, lanes): ``_RING_LANES`` is read when a generation
    connects, so it is set around ``configure`` alone."""
    store = KvStoreServer("127.0.0.1:0")
    made = {}

    def get(world, lanes):
        if (world, lanes) not in made:
            before, pg_mod._RING_LANES = pg_mod._RING_LANES, lanes
            try:
                made[world, lanes] = make_pgs(
                    store, world, quorum_id=10 * world + lanes, timeout=20.0,
                    prefix="lanes")
            finally:
                pg_mod._RING_LANES = before
        return made[world, lanes]

    yield get
    for pgs in made.values():
        for pg in pgs:
            pg.shutdown()
    store.shutdown()


def _ring_threads():
    """The process group's live threads (the module's shared meshes keep
    theirs: a test compares with what was there before it)."""
    return {t for t in threading.enumerate() if t.name.startswith("pg_host_")}


def _wait_no_ring_threads(but, timeout=5.0):
    deadline = time.monotonic() + timeout
    while _ring_threads() - but and time.monotonic() < deadline:
        time.sleep(0.02)
    left = sorted(t.name for t in _ring_threads() - but)
    assert not left, f"threads left alive: {left}"


class TestRingLanes:
    """A segment's frames ride several connections to each ring neighbour,
    frame ``k`` on lane ``k % lanes``, each lane with its own receiver, fold
    and writer: the bits are the one-lane ring's, which are the
    reference's."""

    # neither the world, the frame nor the lane count divides them; the
    # short one is a segment of fewer frames than four lanes
    @pytest.mark.parametrize("donate", [True, False], ids=["donated", "kept"])
    @pytest.mark.parametrize("length", [40_009, 250_007])
    @pytest.mark.parametrize("op", [ReduceOp.SUM, ReduceOp.AVG, ReduceOp.MAX],
                             ids=lambda o: o.value)
    @pytest.mark.parametrize("dtype", [ml_dtypes.bfloat16, np.float32, np.int32],
                             ids=["bf16", "f32", "i32"])
    @pytest.mark.parametrize("lanes", [1, 2, 4])
    @pytest.mark.parametrize("world", [2, 3, 4])
    def test_bitwise_the_ring_order_reduction_on_every_lane_count(
        self, lane_meshes, monkeypatch, world, lanes, dtype, op, length, donate
    ):
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES", 0)
        pgs = lane_meshes(world, lanes)
        assert all(pg._gen.comm.lanes == lanes for pg in pgs)
        vals = _ring_values(dtype, length, world, seed=lanes)
        want = _ring_reference(vals, op)
        ins = [v.copy() for v in vals]

        def step(rank):
            fut = pgs[rank].allreduce([ins[rank]], op, donate=donate).get_future()
            return fut.wait(30)[0], fut.ring

        keeps_dtype = not (op == ReduceOp.AVG and dtype is np.int32)
        frames = -(-(-(-length // world)) * vals[0].itemsize // _CHUNK)
        if length == 40_009 and world == 4:
            assert frames < 4
        for rank, (out, info) in enumerate(run_parallel(world, step)):
            assert out.dtype == want.dtype and out.shape == want.shape
            assert np.array_equal(_bits(out), _bits(want)), rank
            assert _ring_said(info) == {"inplace": int(donate and keeps_dtype),
                                        "chunks": frames, "lanes": lanes}
            if donate and keeps_dtype:
                assert out is ins[rank]
            else:
                assert np.array_equal(_bits(ins[rank]), _bits(vals[rank]))
                assert not np.shares_memory(out, ins[rank])

    def test_a_segment_under_the_floor_rides_lane_0_alone(
        self, lane_meshes, monkeypatch
    ):
        """The floor is bytes a segment: twice as many frames as lanes at
        the module's sizes. Under it the pass is the one-lane pass, on a
        mesh that has the lanes."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        assert pg_mod._RING_LANE_FLOOR_BYTES == (
            2 * pg_mod._RING_LANES * 4 * 2**20)
        world, lanes = 3, 4
        pgs = lane_meshes(world, lanes)
        vals = _ring_values(np.float32, 250_007, world)
        seg_bytes = -(-250_007 // world) * 4

        def ride(floor):
            monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES", floor)

            def step(rank):
                fut = pgs[rank].allreduce(
                    [vals[rank].copy()], ReduceOp.SUM).get_future()
                return fut.wait(30)[0], fut.ring["lanes"]
            return run_parallel(world, step)

        want = _ring_reference(vals, ReduceOp.SUM)
        for floor, rode in ((seg_bytes + 1, 1), (seg_bytes, lanes)):
            for out, got in ride(floor):
                assert got == rode
                assert np.array_equal(_bits(out), _bits(want))

    def test_leaves_cross_lanes_and_the_counters_cover_every_lane(
        self, lane_meshes, monkeypatch
    ):
        """Frames cut across leaf boundaries on every lane, and a rank's
        traffic is what one lane's would be: ``2 (world - 1) / world`` of
        the payload and a header a frame."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES", 0)
        world, lanes = 4, 4
        pgs = lane_meshes(world, lanes)
        shapes = [(257, 129), (50_001,), (0, 3), (33, 3, 7), (), (5,)]
        vals = [[_ring_values(np.float32, int(np.prod(shape)), 1,
                              seed=7 * r + i)[0].reshape(shape)
                 for i, shape in enumerate(shapes)] for r in range(world)]
        comms = [pg._gen.comm for pg in pgs]
        before = [(c.bytes_sent, c.bytes_recv) for c in comms]
        # 48 threads count into four comms: switch between them as often as
        # the interpreter can, so an unlocked read-modify-write would lose
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            outs = run_parallel(world, lambda r: pgs[r].allreduce(
                [v.copy() for v in vals[r]], ReduceOp.SUM
            ).get_future().wait(30))
        finally:
            sys.setswitchinterval(interval)
        want = _ring_reference(
            [np.concatenate([v.reshape(-1) for v in vals[r]])
             for r in range(world)], ReduceOp.SUM)
        total = want.size
        seg = -(-total // world)
        for r in range(world):
            got = np.concatenate([o.reshape(-1) for o in outs[r]])
            assert np.array_equal(_bits(got), _bits(want)), r
            sent = comms[r].bytes_sent - before[r][0]
            recv = comms[r].bytes_recv - before[r][1]
            segs = [max(0, min(total, (s + 1) * seg) - s * seg)
                    for s in range(world)]
            frames = [-(-4 * n // _CHUNK) for n in segs]
            out_segs = [(r - h) % world for h in range(2 * (world - 1))]
            in_segs = [(r - h - 1) % world for h in range(2 * (world - 1))]
            assert sent == sum(4 * segs[s] + 8 * frames[s] for s in out_segs)
            assert recv == sum(4 * segs[s] + 8 * frames[s] for s in in_segs)

    def test_a_world_of_one_opens_no_lane(self, store):
        (pg,) = make_pgs(store, 1, prefix="one")
        comm = pg._gen.comm
        assert comm.lanes == 1 and comm.lane_socks == [{}] and not comm.peers
        x = np.ones(100_000, np.float32)
        fut = pg.allreduce([x], ReduceOp.SUM, donate=True).get_future()
        assert fut.wait(10)[0] is x and fut.ring == {"lanes": 1}
        pg.shutdown()

    def test_a_world_of_two_shares_a_lanes_socket_both_ways(self, lane_meshes):
        a, b = (pg._gen.comm for pg in lane_meshes(2, 4))
        assert a.lane_socks[0] is a.peers
        assert [list(s) for s in a.lane_socks] == [[1]] * 4
        assert [list(s) for s in b.lane_socks] == [[0]] * 4
        for lane in range(4):
            assert (a.lane_socks[lane][1].getsockname()
                    == b.lane_socks[lane][0].getpeername())

    @pytest.mark.parametrize("how", ["lane_socket_closed", "abort"])
    @pytest.mark.parametrize("donate", [True, False], ids=["donated", "kept"])
    def test_a_lane_that_dies_mid_pass_fails_every_rank_and_leaves_no_thread(
        self, store, monkeypatch, how, donate
    ):
        """Rank 2's lane 1 loses its socket after its third frame (or rank
        2 is aborted there). Its receiver fails every count of every lane
        of the pass; the peers read closed sockets or starve into their
        sockets' timeout. Each op raises once, with every thread of its
        pass ended, and a shut-down group leaves no thread alive."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES", 0)
        monkeypatch.setattr(pg_mod, "_RING_LANES", 4)
        before = _ring_threads()
        world, timeout = 3, 2.0
        pgs = make_pgs(store, world, quorum_id=1, timeout=timeout,
                       prefix="midlane")
        vals = _ring_values(ml_dtypes.bfloat16, 500_009, world)
        ins = [v.copy() for v in vals]
        comm = pgs[2]._gen.comm
        assert comm.lanes == 4
        recv, seen, names = comm.recv_raw_into, [], set()

        def dying_recv(peer, out, lane=0):
            if lane == 1:
                seen.append(peer)
                if len(seen) == 3:
                    # the dispatch thread may still be starting lane 3
                    for _ in range(100):
                        names.update(
                            t.name for t in _ring_threads() - before)
                        if "pg_host_recv3_r2" in names:
                            break
                        time.sleep(0.01)
                    if how == "abort":
                        pgs[2].abort()
                    else:
                        sock = comm.lane_socks[1][peer]
                        sock.shutdown(2)
                        sock.close()
            return recv(peer, out, lane)

        comm.recv_raw_into = dying_recv

        def step(rank):
            t0 = time.monotonic()
            with pytest.raises(Exception) as err:
                pgs[rank].allreduce(
                    [ins[rank]], ReduceOp.SUM, donate=donate
                ).get_future().wait(timeout=10)
            assert not isinstance(err.value, pg_mod._RingFailed)
            return time.monotonic() - t0

        took = run_parallel(world, step)
        assert max(took) < timeout + 2.0, took
        assert all(pg.errored() is not None for pg in pgs)
        if not donate:
            for rank in range(world):
                assert np.array_equal(_bits(ins[rank]), _bits(vals[rank]))
        # mid-pass every lane had its three threads
        for lane in (1, 2, 3):
            for kind in ("recv", "fold", "collwr"):
                assert f"pg_host_{kind}{lane}_r2" in names, names
        for pg in pgs:
            pg.shutdown()
        _wait_no_ring_threads(before)


def _timed_ring(pgs, vals, before=lambda rank: None):
    """One donated SUM through the ring on every rank at once (a barrier,
    then ``before(rank)``): by rank, the ring's account in seconds, with
    ``entry`` (the op's start on the dispatch thread to ``t_first``) and
    ``stream`` (from there to the op's end)."""
    gate = threading.Barrier(len(pgs))

    def step(rank):
        gate.wait(30)
        before(rank)
        fut = pgs[rank].allreduce(
            [vals[rank].copy()], ReduceOp.SUM, donate=True).get_future()
        fut.wait(30)
        _, t_run0, t_run1 = fut.stamps
        _ring_said(fut.ring)
        said = {k[:-3]: v / 1e6 for k, v in fut.ring.items()
                if k.endswith("_us")}
        return {**said, "entry": fut.ring["t_first"] - t_run0,
                "stream": t_run1 - fut.ring["t_first"],
                "lanes": fut.ring["lanes"], "chunks": fut.ring["chunks"]}

    return run_parallel(len(pgs), step)


class TestTheRingTellsItsOwnTime:
    """What ``_ring_allreduce`` leaves in ``info`` beside ``inplace``,
    ``chunks`` and ``lanes``: ``t_first`` and the seconds of
    ``_RING_TERMS`` (every parametrised ring above checks that each is
    there, whole microseconds, none negative, the lanes' mean not above the
    largest lane's: ``_ring_said``)."""

    @pytest.mark.parametrize("floor", ["over", "under"])
    @pytest.mark.parametrize("world", [2, 4])
    def test_the_receivers_three_terms_are_the_stream(
        self, lane_meshes, monkeypatch, world, floor
    ):
        """Buckets whose segments are over ``_RING_LANE_FLOOR_BYTES`` (four
        lanes) and under it (lane 0 alone): header waits, payloads and slot
        waits are what a receiver does from its first header to its last
        payload, so their sum lies inside the stream and fills most of it
        (what is missing is Python between frames and the op's end)."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", 256 * 1024)
        n = 3_000_001
        seg_bytes = -(-n // world) * 4
        monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES",
                            seg_bytes if floor == "over" else seg_bytes + 1)
        pgs = lane_meshes(world, 4)
        vals = _ring_values(np.float32, n, world)
        for said in _timed_ring(pgs, vals):
            assert said["lanes"] == (4 if floor == "over" else 1)
            got = said["recv_wait"] + said["recv"] + said["slot_wait"]
            assert 0.6 * said["recv_span"] <= got <= said["recv_span"] + 1e-5
            assert 0.5 * said["stream"] <= said["recv_span"] <= (
                said["stream"] + 1e-5), said
            # the fold and the writer wait and work inside the op too
            assert said["arrive_wait"] + said["fold"] <= (
                said["entry"] + said["stream"] + 1e-4)
            assert said["handoff"] <= (said["slot_wait"] + said["arrive_wait"]
                                       + said["ready_wait"] + 1e-4)

    @pytest.mark.parametrize("world", [2, 4])
    def test_a_late_rank_is_an_entry_wait_at_its_right_neighbour(
        self, lane_meshes, monkeypatch, world
    ):
        """Rank 1 enters 0.2 s late. Its own first header is there at once;
        its right neighbour has nothing until it enters: an entry wait, and
        a stream that is the ring with everyone present. At a world of four
        the other two get a first frame from a neighbour that was on time
        and then starve at the next hop: a header wait inside the stream."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        pgs = lane_meshes(world, 1)
        vals = _ring_values(np.float32, 250_007, world)
        _timed_ring(pgs, vals)  # warm: scratch, threads
        on_time = _timed_ring(pgs, vals)
        late = _timed_ring(
            pgs, vals, lambda rank: time.sleep(0.2) if rank == 1 else None)
        assert max(s["entry"] + s["stream"] for s in on_time) < 0.15
        assert late[1]["entry"] < 0.1
        right = late[2 % world]
        assert 0.15 < right["entry"] < 0.4
        assert right["stream"] < 0.15 and right["recv_wait"] < 0.15
        for rank in set(range(world)) - {1, 2 % world}:
            assert late[rank]["entry"] < 0.1
            assert late[rank]["recv_wait"] > 0.1
            assert late[rank]["stream"] > 0.15

    def test_a_slow_fold_is_fold_here_and_a_header_wait_to_the_right(
        self, lane_meshes, monkeypatch
    ):
        """Rank 1's folds take 5 ms more each: its ``fold`` holds them, and
        rank 2, whose next hop's frames are the ones rank 1 has to fold
        first, waits for headers that long."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        world = 3
        pgs = lane_meshes(world, 1)
        vals = _ring_values(np.float32, 250_007, world)
        _timed_ring(pgs, vals)
        plain = _timed_ring(pgs, vals)
        fold = pg_mod._fold

        def slow_fold(op, dst, src):
            if threading.current_thread().name.endswith("_r1"):
                time.sleep(0.005)
            fold(op, dst, src)

        monkeypatch.setattr(pg_mod, "_fold", slow_fold)
        slow = _timed_ring(pgs, vals)
        added = (world - 1) * slow[1]["chunks"] * 0.005
        assert slow[1]["fold"] >= plain[1]["fold"] + 0.9 * added
        assert slow[0]["fold"] < 0.5 * added and slow[2]["fold"] < 0.5 * added
        assert slow[2]["recv_wait"] >= plain[2]["recv_wait"] + 0.4 * added
        assert slow[1]["recv_wait"] < slow[2]["recv_wait"]

    def test_several_passes_an_op_add_up_behind_the_first_t_first(
        self, lane_meshes, monkeypatch
    ):
        """Leaves of two dtypes are two passes: the terms add, the first
        pass's first header is the op's ``t_first``, and the second pass's
        first wait is a header wait like any other."""
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        world = 2
        pgs = lane_meshes(world, 1)
        seen = []
        ring_pass = pg_mod._ring_pass

        def watched(comm, parts, op):
            out = ring_pass(comm, parts, op)
            seen.append((comm.rank, parts[0].dtype.name, out[2]))
            return out

        monkeypatch.setattr(pg_mod, "_ring_pass", watched)

        def step(rank):
            fut = pgs[rank].allreduce(
                [np.ones(60_001, np.float32), np.ones(50_001, np.int32)],
                ReduceOp.SUM).get_future()
            fut.wait(30)
            return fut.ring

        for rank, info in enumerate(run_parallel(world, step)):
            assert _ring_said(info)["chunks"] == sum(
                -(-(-(-n // world)) * 4 // _CHUNK) for n in (60_001, 50_001))
            mine = [c for r, _, c in seen if r == rank]
            assert [d for r, d, _ in seen if r == rank] == ["float32", "int32"]
            (first,), (second,) = mine  # one lane a pass
            assert info["t_first"] == first["first_at"] < second["first_at"]
            assert info["recv_us"] == int(
                (first["recv"] + second["recv"]) * 1e6)
            assert info["recv_wait_us"] == int(
                (first["hdr_wait"] - first["first_wait"]
                 + second["hdr_wait"]) * 1e6)

    @pytest.mark.parametrize("path", ["mesh_exchange", "compressed_ring",
                                      "failed_ring"])
    def test_what_is_no_plain_ring_that_ended_well_leaves_no_account(
        self, store, monkeypatch, path
    ):
        from torchft_tpu.ops.quantization import compress_bucket

        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        world = 2
        pgs = make_pgs(store, world, timeout=2.0, prefix="noacct_" + path)
        x = np.ones(100_003, np.float32)
        if path == "mesh_exchange":
            monkeypatch.setattr(pg_mod, "_RING_MIN_BYTES", 1 << 30)
        elif path == "failed_ring":
            comm = pgs[1]._gen.comm
            recv, seen = comm.recv_raw_into, []

            def dying_recv(peer, out, lane=0):
                seen.append(peer)
                if len(seen) == 3:
                    pgs[1].abort()
                return recv(peer, out, lane)

            comm.recv_raw_into = dying_recv

        def step(rank):
            arrays = ([compress_bucket(x, "fp8")]
                      if path == "compressed_ring" else [x.copy()])
            fut = pgs[rank].allreduce(arrays, ReduceOp.SUM).get_future()
            if path == "failed_ring":
                with pytest.raises(Exception):
                    fut.wait(10)
                assert getattr(fut, "stamps", None) is None
            else:
                fut.wait(10)
            return fut.ring

        for info in run_parallel(world, step):
            # a failed ring said how it began, never how its time went
            assert set(info) <= ({"inplace", "chunks", "lanes"}
                                 if path == "failed_ring" else set())
        for pg in pgs:
            pg.shutdown()


@pytest.mark.parametrize("lanes", [1, 2])
def test_the_transport_bench_sweeps_lanes_and_splits_a_step_by_lane(lanes):
    """``transport_bench.py --transport allreduce --elements ... --lanes N``:
    ranks as processes, the bench's own argument sets the module's constant
    in them; the seconds of each of the ring's own terms as the lanes' mean
    and the largest lane's (nothing of the library is patched: the numbers
    are those the ring left on its ops' futures), and the ``result_crc``
    of the ring-order reference whatever the lanes."""
    import json
    import os
    import subprocess
    import sys
    import zlib

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    world, n = 2, 300_001
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "benchmarks", "transport_bench.py"),
         "--transport", "allreduce", "--world", str(world), "--elements",
         str(n), "--donate", "--iters", "1", "--chunk-mb", "0.03125",
         "--lanes", str(lanes), "--timeout", "60"],
        capture_output=True, text=True, timeout=240, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode == 0, (out.stderr or out.stdout)[-2000:]
    row = json.loads(out.stdout.strip().splitlines()[-1])
    assert (row["lanes"], row["inplace"], row["native_fold"],
            row["native_frames"]) == (lanes, 1, 1, 1)
    for key in ("recv_s", "fold_s", "send_s"):
        mean, most = row[key]
        assert 0 < mean <= most, row
    for key in ("recv_wait_s", "handoff_s", "slot_wait_s"):
        mean, most = row[key]
        assert 0 <= mean <= most, row
    assert 0 <= row["entry_wait_s"] <= row["ring_s"] <= row["step_s"], row
    with open(os.path.join(repo, "benchmarks", "transport_bench.py")) as f:
        bench = f.read()
    # one clock on the ring, the program's
    assert "timed(" not in bench and "_Comm." not in bench
    vals = [
        (np.random.default_rng(r).standard_normal(n, np.float32) * 0.01)
        .astype(ml_dtypes.bfloat16) for r in range(world)
    ]
    want = _ring_reference(vals, ReduceOp.SUM)
    assert row["result_crc"] == "%08x" % zlib.crc32(
        want.view(np.uint16).tobytes()[:1 << 24])


class TestNativeFrames:
    """native/net.cc's ``fd_send_all`` / ``fd_recv_all`` move a frame's run
    over a Python socket's fd in one call, and say what Python's own calls
    would have raised."""

    @pytest.mark.parametrize("timeout", [None, 5.0], ids=["blocking", "timed"])
    def test_a_whole_buffer_each_way(self, timeout):
        import socket

        native = pg_mod._native_ring()
        a, b = socket.socketpair()
        a.settimeout(timeout)
        b.settimeout(timeout)
        src = np.random.default_rng(0).integers(0, 256, 9_000_001).astype(np.uint8)
        dst = np.zeros_like(src)
        rcs = []
        t = threading.Thread(target=lambda: rcs.append(native.fd_send_all(
            a.fileno(), src.ctypes.data, src.size, pg_mod._idle_ms(a), 0)))
        t.start()
        assert native.fd_recv_all(
            b.fileno(), dst.ctypes.data, dst.size, pg_mod._idle_ms(b)) == 0
        t.join(10)
        assert rcs == [0] and np.array_equal(src, dst)
        a.close()
        b.close()

    def test_silence_a_closed_peer_and_a_closed_socket(self):
        import errno
        import socket

        native = pg_mod._native_ring()
        a, b = socket.socketpair()
        buf = np.zeros(1024, np.uint8)
        t0 = time.monotonic()
        rc = native.fd_recv_all(b.fileno(), buf.ctypes.data, buf.size, 200)
        assert rc == 1 and 0.15 < time.monotonic() - t0 < 2.0
        with pytest.raises(socket.timeout):
            pg_mod._raise_fd(rc, "recv")
        a.sendall(b"x" * 100)  # part of a run, then the peer goes
        a.close()
        rc = native.fd_recv_all(b.fileno(), buf.ctypes.data, buf.size, 5000)
        assert rc == 2
        with pytest.raises(ConnectionError):
            pg_mod._raise_fd(rc, "recv")
        big = np.zeros(8 << 20, np.uint8)  # more than the buffers hold
        rc = native.fd_send_all(b.fileno(), big.ctypes.data, big.size, 5000, 0)
        assert rc in (2, -errno.ECONNRESET)
        fd = b.fileno()
        b.close()
        rc = native.fd_recv_all(fd, buf.ctypes.data, buf.size, 100)
        assert rc == -errno.EBADF
        with pytest.raises(OSError) as err:
            pg_mod._raise_fd(rc, "recv")
        assert err.value.errno == errno.EBADF
        pg_mod._raise_fd(0, "recv")  # done: nothing raised

    def test_a_read_only_target_is_refused_before_any_byte(self, store):
        pgs = make_pgs(store, 2, prefix="readonly")
        target = np.frombuffer(b"\0" * 64, np.uint8)
        sender = threading.Thread(
            target=pgs[0]._gen.comm.send_raw, args=(1, np.ones(64, np.uint8)))
        sender.start()
        with pytest.raises(ValueError, match="read-only"):
            pgs[1]._gen.comm.recv_raw_into(0, target)
        sender.join(10)
        assert bytes(target) == b"\0" * 64
        for pg in pgs:
            pg.shutdown()

    def test_a_shutdown_under_a_waiting_call_ends_it(self):
        """What ``abort`` does to a receiver that waits in the native
        call: the socket is shut down from another thread."""
        import socket

        native = pg_mod._native_ring()
        a, b = socket.socketpair()
        buf = np.zeros(1024, np.uint8)
        rcs = []
        t = threading.Thread(target=lambda: rcs.append(native.fd_recv_all(
            b.fileno(), buf.ctypes.data, buf.size, -1)))
        t.start()
        time.sleep(0.1)
        b.shutdown(socket.SHUT_RDWR)
        t.join(5)
        assert rcs == [2]
        a.close()
        b.close()

    @pytest.mark.parametrize("python_side", ["sender", "receiver"])
    def test_frames_are_the_same_bytes_either_way(
        self, store, monkeypatch, python_side
    ):
        """A rank that moves its frames with Python's calls and one that
        moves them natively are on one wire: a list of runs of several
        dtypes with an empty one among them, into a bytearray and arrays."""
        real = pg_mod._native_ring
        monkeypatch.setattr(
            pg_mod, "_native_ring",
            lambda: None if threading.current_thread().name == python_side
            else real())
        pgs = make_pgs(store, 2, prefix="mixedframes")
        comms = [pg._gen.comm for pg in pgs]
        runs = [np.arange(70_001, dtype=np.float32),
                np.ones((3, 5), ml_dtypes.bfloat16), np.zeros((0,), np.int64),
                np.frombuffer(b"0123456789abcdef", np.uint8)]
        outs = [np.zeros_like(runs[0]), np.zeros_like(runs[1]),
                np.zeros_like(runs[2]), bytearray(16)]
        sender = threading.Thread(
            target=comms[0].send_raw, args=(1, runs), name="sender")
        receiver = threading.Thread(
            target=comms[1].recv_raw_into, args=(0, outs), name="receiver")
        sender.start()
        receiver.start()
        sender.join(10)
        receiver.join(10)
        for got, want in zip(outs[:3], runs):
            assert np.array_equal(_bits(got), _bits(want))
        assert bytes(outs[3]) == b"0123456789abcdef"
        nbytes = sum(r.nbytes for r in runs) + 8
        assert comms[0].bytes_sent == comms[1].bytes_recv == nbytes
        for pg in pgs:
            pg.shutdown()


def _bf16(bits):
    return np.asarray(bits, np.uint16).view(ml_dtypes.bfloat16)


# NaNs of both signs, quiet and signalling, infinities, zeros, the smallest
# and largest subnormals and normals, ties of the rounding, and a spread
_BF16_OTHERS = np.unique(np.concatenate([
    np.array([0x0000, 0x8000, 0x0001, 0x8001, 0x007f, 0x807f, 0x0080, 0x8080,
              0x7f7f, 0xff7f, 0x7f80, 0xff80, 0x7f81, 0xff81, 0x7fc0, 0xffc0,
              0x7fff, 0xffff, 0x3f80, 0xbf80, 0x3f81, 0x3c00, 0x4000, 0x7f00],
             np.uint16),
    np.random.default_rng(45).integers(0, 1 << 16, 48).astype(np.uint16),
]))


class TestNativeBf16Add:
    """native/reduce.cc's add is ml_dtypes' ``dst += src`` bit for bit."""

    @pytest.mark.parametrize("view", ["whole", "odd_start_odd_length",
                                      "unaligned_bytes"])
    @pytest.mark.parametrize("others_are", ["src", "dst"])
    def test_every_bit_pattern_against_a_spread_of_others(
        self, others_are, view
    ):
        assert pg_mod._native_ring() is not None, "the library exports it"
        assert len(_BF16_OTHERS) >= 64
        every = np.tile(np.arange(1 << 16, dtype=np.uint16), len(_BF16_OTHERS))
        other = np.repeat(_BF16_OTHERS, 1 << 16)
        dst_bits, src_bits = (
            (every, other) if others_are == "src" else (other, every))
        if view == "odd_start_odd_length":
            dst_bits, src_bits = dst_bits[1:-2], src_bits[3:]
        n = dst_bits.size
        with np.errstate(all="ignore"):
            want = _bf16(dst_bits.copy())
            want += _bf16(src_bits)
        if view == "unaligned_bytes":  # elements at odd addresses
            raw = [bytearray(2 * n + 1), bytearray(2 * n + 1)]
            dst, src = (np.frombuffer(r, np.uint16, n, offset=1) for r in raw)
            assert dst.ctypes.data % 2 == 1 and not dst.flags.aligned
            dst[:], src[:] = dst_bits, src_bits
        else:
            dst, src = dst_bits.copy(), src_bits.copy()
        pg_mod._fold(ReduceOp.SUM, dst.view(ml_dtypes.bfloat16),
                     src.view(ml_dtypes.bfloat16))
        wrong = np.flatnonzero(dst != want.view(np.uint16))
        assert wrong.size == 0, [
            (hex(dst_bits[i]), hex(src_bits[i]), hex(dst[i]),
             hex(want.view(np.uint16)[i])) for i in wrong[:5]]
        assert np.array_equal(src, src_bits)  # src is read only

    def test_the_fold_takes_the_native_add_only_where_it_is_the_same_sum(
        self, monkeypatch
    ):
        calls = []
        real = pg_mod._native_ring()
        monkeypatch.setattr(pg_mod, "_NATIVE", types.SimpleNamespace(
            bf16_add=lambda *a: (calls.append(a), real.bf16_add(*a))[1]))
        b = np.ones(64, ml_dtypes.bfloat16)
        for op, dst, native in [
            (ReduceOp.SUM, b.copy(), True), (ReduceOp.AVG, b.copy(), True),
            (ReduceOp.MAX, b.copy(), False), (ReduceOp.PRODUCT, b.copy(), False),
            (ReduceOp.SUM, np.ones(64, np.float32), False),
            (ReduceOp.SUM, np.ones(128, ml_dtypes.bfloat16)[::2], False),
        ]:
            calls.clear()
            src = np.full(64, 2, dst.dtype)
            want = dst.copy()
            pg_mod._accum(op, want, src)
            pg_mod._fold(op, dst, src)
            assert bool(calls) == native, (op, dst.dtype)
            assert np.array_equal(_bits(dst), _bits(want))

    def test_without_the_symbol_the_ring_is_numpys_on_one_lane(
        self, store, monkeypatch, caplog
    ):
        import torchft_tpu.coordination as coordination

        monkeypatch.setattr(coordination, "native_ring", lambda: None)
        monkeypatch.setattr(pg_mod, "_NATIVE", ())
        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", _CHUNK)
        monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES", 0)
        world = 2
        with caplog.at_level("WARNING", logger=pg_mod.__name__):
            pgs = make_pgs(store, world, prefix="nosymbol")
            again = make_pgs(store, world, quorum_id=2, prefix="nosymbol")
        said = [r for r in caplog.records if "tft_bf16_add" in r.getMessage()
                and "tft_fd_recv_all" in r.getMessage()]
        assert len(said) == 1  # once, however many generations
        assert all(pg._gen.comm.lanes == 1 for pg in pgs + again)
        vals = _ring_values(ml_dtypes.bfloat16, 250_007, world)
        want = _ring_reference(vals, ReduceOp.SUM)

        def step(rank):
            fut = pgs[rank].allreduce([vals[rank].copy()]).get_future()
            return fut.wait(30)[0], fut.ring["lanes"]

        for out, lanes in run_parallel(world, step):
            assert lanes == 1
            assert np.array_equal(_bits(out), _bits(want))
        for pg in pgs + again:
            pg.shutdown()

    def test_a_rank_without_it_holds_every_rank_to_one_lane(
        self, store, monkeypatch
    ):
        """The lanes are agreed through the store: a rank that can run
        fewer (its library lacks the fold) sets the count for all, or the
        connects would not pair up."""
        real = pg_mod._native_ring

        def per_thread():
            if threading.current_thread().name == "poor":
                return None
            return real()

        monkeypatch.setattr(pg_mod, "_native_ring", per_thread)
        world = 3
        pgs = [ProcessGroupHost(timeout=10.0) for _ in range(world)]
        addr = f"127.0.0.1:{store.port}/mixed"
        threads = [
            threading.Thread(
                target=pgs[r].configure, args=(addr, r, world, 1),
                name="poor" if r == 1 else f"rich{r}")
            for r in range(world)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
        assert [pg._gen.comm.lanes for pg in pgs] == [1, 1, 1]
        for pg in pgs:
            pg.shutdown()


class TestWrappers:
    def test_error_swallowing(self, store):
        inner = ProcessGroupDummy()
        pg = ErrorSwallowingProcessGroupWrapper(inner)
        x = np.array([5.0])
        out = pg.allreduce([x]).get_future().wait()
        np.testing.assert_allclose(out[0], [5.0])
        assert pg.error() is None

        pg.report_error(RuntimeError("injected"))
        # After an error every op resolves to its input (identity).
        out = pg.allreduce([np.array([7.0])]).get_future().wait()
        np.testing.assert_allclose(out[0], [7.0])

        # Reconfigure clears the error.
        pg.configure("ignored:0/x", 0, 1)
        assert pg.error() is None

    @pytest.mark.parametrize("wrap", [
        ErrorSwallowingProcessGroupWrapper,
        FakeProcessGroupWrapper,
        lambda pg: ErrorSwallowingProcessGroupWrapper(
            FakeProcessGroupWrapper(pg)
        ),
    ], ids=["swallowing", "fake", "swallowing_over_fake"])
    @pytest.mark.parametrize("donate", [True, False])
    def test_wrappers_forward_donate(self, store, wrap, donate):
        (inner,) = make_pgs(store, 1)
        pg = wrap(inner)
        x = np.arange(6.0)
        (out,) = pg.allreduce([x], ReduceOp.SUM, donate=donate).get_future().wait(
            timeout=10
        )
        inner.shutdown()
        assert (out is x) == donate
        assert np.shares_memory(out, x) == donate
        assert pg.errored() is None

    def test_fake_wrapper_injects_future_error(self):
        pg = FakeProcessGroupWrapper(ProcessGroupDummy())
        pg.report_future_error(RuntimeError("boom"))
        with pytest.raises(RuntimeError, match="boom"):
            pg.allreduce([np.array([1.0])]).get_future().wait()
        # next op is clean
        pg.allreduce([np.array([1.0])]).get_future().wait()

    def test_fake_wrapper_injects_configure_error(self):
        pg = FakeProcessGroupWrapper(ProcessGroupDummy())
        pg.report_configure_error(RuntimeError("cfg boom"))
        with pytest.raises(RuntimeError, match="cfg boom"):
            pg.configure("ignored:0/x", 0, 1)
        pg.configure("ignored:0/x", 0, 1)  # clean afterwards

    def test_error_swallowing_over_fake(self):
        """Composition used by integration tests: injected future error is
        swallowed into the default value."""
        fake = FakeProcessGroupWrapper(ProcessGroupDummy())
        pg = ErrorSwallowingProcessGroupWrapper(fake)
        fake.report_future_error(RuntimeError("boom"))
        out = pg.allreduce([np.array([3.0])]).get_future().wait()
        np.testing.assert_allclose(out[0], [3.0])
        assert pg.error() is not None


class TestManagedProcessGroupRank:
    def test_rank_is_int_before_first_quorum(self):
        """replica_rank() is None until a quorum assigns one; the PG contract
        is int (advisor regression: ManagedProcessGroup.rank() returned
        None)."""

        class _MgrStub:
            def replica_rank(self):
                return None

            def num_participants(self):
                return 0

        pg = ManagedProcessGroup(_MgrStub())
        r = pg.rank()
        assert isinstance(r, int) and r == 0

    def test_rank_tracks_manager(self):
        class _MgrStub:
            def replica_rank(self):
                return 3

            def num_participants(self):
                return 4

        pg = ManagedProcessGroup(_MgrStub())
        assert pg.rank() == 3
        assert pg.size() == 4


class TestP2PDeadlockAndModes:
    def test_symmetric_large_sends_do_not_deadlock(self, store):
        """Both ranks send a large payload to each other, then recv — with
        sends on the dispatch thread this deadlocked on full TCP buffers
        until the watchdog aborted (regression: p2p rides per-peer writer
        threads now)."""
        pgs = make_pgs(store, 2, quorum_id=71)
        big = np.arange(2_000_000, dtype=np.float32)  # 8 MB >> TCP buffers

        def run(rank):
            other = 1 - rank
            send_work = pgs[rank].send([big * (rank + 1)], other, tag=5)
            out = pgs[rank].recv(other, tag=5).get_future().wait(30)
            send_work.wait(30)
            return out[0]

        with ThreadPoolExecutor(max_workers=2) as ex:
            outs = list(ex.map(run, range(2)))
        np.testing.assert_allclose(outs[0], big * 2)
        np.testing.assert_allclose(outs[1], big * 1)
        for pg in pgs:
            pg.shutdown()

    def test_p2p_and_collectives_cannot_mix(self, store):
        """Frame ordering: p2p writes ride per-peer writer threads while
        collectives write from the dispatch thread, so one generation
        must reject the mix."""
        pgs = make_pgs(store, 2, quorum_id=72)

        def run(rank):
            other = 1 - rank
            if rank == 0:
                pgs[0].send([np.ones(4, np.float32)], other, tag=1)
            else:
                pgs[1].recv(other, tag=1).get_future().wait(20)
            with pytest.raises(RuntimeError, match="cannot mix"):
                pgs[rank].allreduce([np.ones(2, np.float32)])

        with ThreadPoolExecutor(max_workers=2) as ex:
            list(ex.map(run, range(2)))
        for pg in pgs:
            pg.shutdown()
