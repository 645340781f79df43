"""The bucket pipeline (bucketing.BucketPipeline behind Manager.allreduce /
allreduce_streamed / GradStream).

Pins the PR-3 contracts: the pipeline's numerics are BIT-identical to the
no-plan path (one collective for the whole tree) on both planes, a plan of k
buckets issues exactly k single-array collectives, the staging worker never blocks on a bucket's wire completion,
and a mid-stream bucket failure degrades to the swallowed-zeros +
should_commit()==False story — never a partially-applied reduction.
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from test_manager import make_manager, make_quorum
from torchft_tpu import bucketing
from torchft_tpu.bucketing import _covered_seconds, _pipeline_overlap_stats
from torchft_tpu.coordination import KvStoreServer
from torchft_tpu.process_group import (
    FakeProcessGroupWrapper,
    ProcessGroupDummy,
    ProcessGroupHost,
    ReduceOp,
)
from torchft_tpu.work import Future, FutureWork, GradStream, join_futures


def _tree(n=6, size=9, dtype=np.float32):
    rng = np.random.RandomState(7)
    return {
        f"p{i}": rng.randn(size).astype(dtype) for i in range(n)
    }


class CountingPG(ProcessGroupDummy):
    """World-1 passthrough recording how many arrays each collective took."""

    def __init__(self):
        super().__init__()
        self.allreduce_calls = []

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        arrays = list(arrays)
        self.allreduce_calls.append(len(arrays))
        return super().allreduce(arrays, op)


class GatedPG(ProcessGroupDummy):
    """Passthrough whose allreduce futures resolve only when the test says —
    the observable for 'staging dispatches bucket i+1 while bucket i is
    still on the wire'."""

    def __init__(self):
        super().__init__()
        self.pending = []  # (arrays, fut) in dispatch order
        self.dispatched = threading.Condition()

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        fut = Future()
        with self.dispatched:
            self.pending.append(([np.asarray(a).copy() for a in arrays], fut))
            self.dispatched.notify_all()
        return FutureWork(fut)

    def release_all(self):
        with self.dispatched:
            pending = list(self.pending)
        for arrays, fut in pending:
            fut.set_result(arrays)


def _reduce(m, tree, streamed, **kw):
    m.start_quorum()
    if streamed:
        return m.allreduce_streamed(tree, **kw).wait(timeout=30)
    return m.allreduce(tree, **kw).get_future().wait(timeout=30)


class TestStreamedSerialEquality:
    def test_host_plane_bitwise_identical(self):
        """Same tree through the pipeline (three buckets) and through the
        no-plan path (a cap of 0: one collective carrying every leaf), and
        numpy's own average: every leaf bitwise equal, same dtype — the
        pipeline may not change numerics at all."""
        tree = _tree()
        cap = 2 * 9 * 4  # 2 leaves per bucket -> 3 buckets
        pg = CountingPG()
        no_plan = _reduce(
            make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=0),
            tree, streamed=False,
        )
        assert pg.allreduce_calls == [len(tree)]
        streamed = _reduce(
            make_manager(quorum=make_quorum(), bucket_cap_bytes=cap),
            tree, streamed=True,
        )
        for k in tree:
            s, t = np.asarray(no_plan[k]), np.asarray(streamed[k])
            assert s.dtype == t.dtype
            assert np.array_equal(s, t), f"leaf {k} diverged"
            assert np.array_equal(t, (tree[k] / 2).astype(tree[k].dtype))

    def test_device_plane_bitwise_identical(self):
        """Device-native PGs take per-bucket jax arrays straight through;
        the landed tree must still match the no-plan path, and numpy, bit
        for bit."""
        import jax.numpy as jnp

        class DeviceDummy(CountingPG):
            device_native = True

        tree = {k: jnp.asarray(v) for k, v in _tree(n=5, size=8).items()}
        cap = 2 * 8 * 4
        pg = DeviceDummy()
        no_plan = _reduce(
            make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=0),
            tree, streamed=False,
        )
        assert pg.allreduce_calls == [len(tree)]
        pg = DeviceDummy()
        streamed = _reduce(
            make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=cap),
            tree, streamed=True,
        )
        assert pg.allreduce_calls == [1, 1, 1]
        for k in tree:
            s, t = np.asarray(no_plan[k]), np.asarray(streamed[k])
            assert s.dtype == t.dtype
            assert np.array_equal(s, t), f"leaf {k} diverged"
            assert np.array_equal(t, np.asarray(tree[k]) / np.float32(2))

    def test_mixed_dtypes_survive_streaming(self):
        import jax.numpy as jnp

        rng = np.random.RandomState(3)
        tree = {
            "a": rng.randn(8).astype(np.float32),
            "b": rng.randn(8).astype(np.float16),
            "c": np.asarray(rng.randn(8), jnp.bfloat16),
        }
        out = _reduce(
            make_manager(quorum=make_quorum(), bucket_cap_bytes=16),
            tree, streamed=True,
        )
        for k in tree:
            assert np.asarray(out[k]).dtype == np.asarray(tree[k]).dtype
            np.testing.assert_allclose(
                np.asarray(out[k], np.float32),
                np.asarray(tree[k], np.float32) / 2.0,  # AVG of 2
                rtol=1e-2,
            )


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _landing_tree(dtype, kind):
    """Six (8, 8) leaves of one dtype: numpy, jax, or both in turn. A jax
    leaf is sharded over the 8 virtual devices (two ways) or alone on device
    3, by the bucket it will fall into (a device bucket is one concatenate,
    so its leaves share a device set)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("a", "b"))
    places = [
        NamedSharding(mesh, P("b", None)),
        NamedSharding(mesh, P("a", "b")),
        jax.devices()[3],
    ]
    rng = np.random.RandomState(11)
    tree = {}
    for i in range(6):
        host = (rng.randn(8, 8) * 3).astype(dtype)
        on_device = kind == "jax" or (kind == "mixed" and i % 2 == 0)
        tree[f"p{i}"] = (
            jax.device_put(host, places[i // 2]) if on_device else host
        )
    return tree


_DTYPES = {"bf16": "bfloat16", "f16": "float16", "f32": "float32"}


class TestAverageWhereItLands:
    """The AVG normalisation runs after the reduced slices have landed: a
    device leaf is divided on its device by one jitted, input-donating
    computation, a numpy leaf in numpy. The result is numpy's
    ``(sum / n).astype(dtype)`` bit for bit either way (CPU backend)."""

    @pytest.mark.parametrize("kind", ["jax", "numpy", "mixed"])
    @pytest.mark.parametrize("n", [1, 2, 3, 4])
    @pytest.mark.parametrize("dtype", list(_DTYPES))
    def test_streamed_equals_numpy_and_serial(self, dtype, n, kind):
        import jax
        import ml_dtypes  # noqa: F401 — registers bfloat16 with numpy

        np_dtype = np.dtype(_DTYPES[dtype])
        tree = _landing_tree(np_dtype, kind)
        cap = 2 * 64 * np_dtype.itemsize  # 2 leaves per bucket -> 3 buckets
        quorum = dict(replica_world_size=n, max_world_size=n)
        outs = {}
        for name, kw in {
            "streamed": dict(bucket_cap_bytes=cap),
            "per_leaf": dict(bucket_cap_bytes=0),
        }.items():
            m = make_manager(quorum=make_quorum(**quorum), min_replica_size=1,
                             **kw)
            outs[name] = _reduce(m, tree, streamed=name == "streamed")
            m.shutdown(wait=False)  # 108 idle managers would starve the
            # timing-sensitive tests that share this worker
        for k, orig in tree.items():
            # the dummy PG's SUM is the input itself
            want = (np.asarray(orig) / n).astype(np_dtype)
            for name, out in outs.items():
                got = out[k]
                assert type(got) is type(orig), (name, k)
                assert got.dtype == np_dtype and got.shape == orig.shape
                if isinstance(orig, jax.Array):
                    assert got.sharding == orig.sharding, (name, k)
                assert np.array_equal(_bits(got), _bits(want)), (name, k, n)

    def test_pure_numpy_tree_never_initialises_the_backend(self):
        """A process that only moves host arrays must not take the chip:
        the pipeline's and the no-plan path's AVG over numpy leaves, in a
        fresh interpreter."""
        import os
        import subprocess
        import sys

        code = (
            "import numpy as np, ml_dtypes\n"
            "from jax._src import xla_bridge\n"
            "from test_manager import make_manager, make_quorum\n"
            "for cap in (64, 0):\n"
            "    m = make_manager(quorum=make_quorum(), bucket_cap_bytes=cap)\n"
            "    m.start_quorum()\n"
            "    tree = {f'p{i}': np.arange(16.).astype(ml_dtypes.bfloat16)\n"
            "            for i in range(4)}\n"
            "    out = m.allreduce_streamed(tree).wait(timeout=30)\n"
            "    assert all(type(v) is np.ndarray for v in out.values())\n"
            "    assert np.array_equal(out['p1'], tree['p1'] / 2)\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('HOST-ONLY-OK')\n"
        )
        here = os.path.dirname(os.path.abspath(__file__))
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=120, cwd=here,
            env=dict(os.environ, JAX_PLATFORMS="cpu",
                     PYTHONPATH=os.pathsep.join([os.path.dirname(here), here])),
        )
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        assert "HOST-ONLY-OK" in proc.stdout

    def test_quorum_4_3_4_compiles_once_and_donates(self, monkeypatch):
        """The divisor is a runtime scalar: after the first step a change of
        the participant count compiles nothing. Every landed buffer is
        donated to its quotient, and XLA could use every donation."""
        import warnings

        import jax

        from torchft_tpu.bucketing import _average_on_device

        tree = _landing_tree(np.dtype("bfloat16"), "jax")
        landed = []
        device_put = jax.device_put

        def recording_put(x, *a, **kw):
            out = device_put(x, *a, **kw)
            if isinstance(x, np.ndarray):  # place_leaf's: a reduced slice
                landed.append(out)
            return out

        m = make_manager(quorum=make_quorum(max_world_size=4),
                         bucket_cap_bytes=2 * 64 * 2)
        monkeypatch.setattr(jax, "device_put", recording_put)
        sizes, compiles = [], []
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for qid, n in enumerate([4, 3, 4], start=1):
                m._test_client._quorum.return_value = make_quorum(
                    quorum_id=qid, replica_world_size=n, max_world_size=n
                )
                out = _reduce(m, tree, streamed=True)
                jax.block_until_ready(out)
                assert m.num_participants() == n
                for k, orig in tree.items():
                    want = (np.asarray(orig) / n).astype(orig.dtype)
                    assert np.array_equal(_bits(out[k]), _bits(want)), (k, n)
                sizes.append(_average_on_device()._cache_size())
                compiles.append(sum(
                    s["cat"] == "compile" for s in m.tracer.export()["spans"]
                ))
                m.should_commit()
        # one executable per leaf geometry (three placements of one shape),
        # all of them built in the first step
        m.shutdown(wait=False)
        assert sizes[0] >= 1 and sizes == [sizes[0]] * 3, sizes
        assert compiles == [compiles[0]] * 3, compiles
        assert len(landed) == 3 * len(tree)
        assert all(x.is_deleted() for x in landed)
        assert not [w for w in caught if "onat" in str(w.message)], [
            str(w.message) for w in caught
        ]

    @pytest.mark.parametrize("kind,where", [
        ("jax", "device"), ("numpy", "host"), ("mixed", "mixed"),
    ])
    def test_divide_span_says_where(self, kind, where):
        """allreduce/divide: one per bucket, after h2d, with where / leaves /
        bytes; the unpack stage keeps two children a bucket."""
        m = make_manager(quorum=make_quorum(), bucket_cap_bytes=2 * 64 * 4)
        _reduce(m, _landing_tree(np.dtype("float32"), kind), streamed=True)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:  # stage spans land at resolve
            spans = m.tracer.export()["spans"]
            if sum(s["name"] == "unpack" for s in spans) == 3:
                break
            time.sleep(0.02)
        m.shutdown(wait=False)
        for up in (s for s in spans if s["name"] == "unpack"):
            kids = sorted((s for s in spans if s["parent"] == up["id"]
                           and s["cat"] == "allreduce"),
                          key=lambda s: s["ts_us"])
            # (the default PG hands the staging buffer back: recycle; the
            # watcher's device/landed instant hangs here too)
            assert [s["name"] for s in kids] == ["h2d", "divide", "recycle"]
            h2d, divide, _recycle = (s["args"] for s in kids)
            assert divide["where"] == where
            assert divide["leaves"] == h2d["leaves"] == 2
            assert divide["bytes"] == h2d["bytes"] == 2 * 64 * 4
            assert "queued_us" in h2d and "queued_us" not in divide


class TestPerBucketCollectives:
    @pytest.mark.parametrize("streamed", [True, False])
    def test_one_collective_per_bucket_through_either_call(self, streamed):
        """allreduce() and allreduce_streamed() are one path: a tree with a
        three-bucket plan issues three single-array collectives."""
        tree = _tree()
        cap = 2 * 9 * 4
        plan = bucketing.build_plan(list(tree.values()), cap)
        pg = CountingPG()
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=cap)
        _reduce(m, tree, streamed=streamed)
        assert len(plan) == 3 and pg.allreduce_calls == [1, 1, 1]

    def test_no_plan_degenerates_to_a_one_bucket_stream(self):
        """A cap of 0 is the no-plan path: one collective carrying every
        leaf, and allreduce_streamed's handle covers the whole op."""
        pg = CountingPG()
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=0)
        m.start_quorum()
        stream = m.allreduce_streamed(_tree())
        stream.wait(timeout=30)
        assert pg.allreduce_calls == [6] and stream.num_buckets == 1


class TestStagingNeverBlocksOnWire:
    def test_all_buckets_dispatch_before_any_wire_completes(self):
        """Regression: the staging worker must dispatch bucket i+1 without
        waiting for bucket i's collective to resolve. With every wire gated
        shut, all k per-bucket dispatches must still arrive."""
        tree = _tree()
        cap = 2 * 9 * 4
        plan = bucketing.build_plan(list(tree.values()), cap)
        pg = GatedPG()
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=cap,
                         timeout=30.0)
        m.start_quorum()
        stream = m.allreduce_streamed(tree)
        with pg.dispatched:
            ok = pg.dispatched.wait_for(
                lambda: len(pg.pending) == len(plan), timeout=10
            )
        assert ok, (
            f"staging dispatched {len(pg.pending)}/{len(plan)} buckets "
            "while wires were held open — it is blocking on wire completion"
        )
        assert not any(stream.ready(i) for i in range(stream.num_buckets))
        pg.release_all()
        out = stream.wait(timeout=30)
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(out[k]), tree[k] / 2.0, rtol=1e-6
            )
        assert all(stream.ready(i) for i in range(stream.num_buckets))


class TestMidStreamFailure:
    def test_bucket_failure_yields_zeros_and_blocks_commit(self):
        """A failure on bucket k (not the first!) mid-plan: the aggregate
        degrades to the full zeros tree (never a partially-applied mix) and
        the step's should_commit() vote is False."""
        tree = _tree()
        cap = 2 * 9 * 4
        pg = FakeProcessGroupWrapper(ProcessGroupDummy())
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=cap)
        m.start_quorum()
        pg.report_future_error(RuntimeError("injected wire failure"),
                               skip_ops=1)
        stream = m.allreduce_streamed(tree)
        out = stream.wait(timeout=30)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(out[k]),
                                          np.zeros_like(tree[k]))
        assert not stream.ready(1)
        assert m.errored() is not None  # the wire fault was reported
        assert m.should_commit() is False

    def test_non_participant_contributes_zeros_streamed(self):
        """allow_heal=False + behind the cohort: not participating, the
        streamed path must still run (zero contribution) and commit."""
        m = make_manager(
            quorum=make_quorum(
                heal=True, max_step=1, max_replica_rank=None,
                recover_src_replica_rank=1,
            ),
        )
        m.start_quorum(allow_heal=False)
        tree = {f"x{i}": np.ones(9, np.float32) for i in range(6)}
        out = m.allreduce_streamed(tree, bucket_cap_bytes=2 * 9 * 4).wait(
            timeout=30
        )
        for k in tree:
            np.testing.assert_allclose(np.asarray(out[k]), 0.0)
        assert not m.is_participating()
        assert m.should_commit()


class TestGradStream:
    def test_ready_and_wait_semantics(self):
        tree = _tree()
        cap = 2 * 9 * 4
        plan = bucketing.build_plan(list(tree.values()), cap)
        m = make_manager(quorum=make_quorum(), bucket_cap_bytes=cap)
        m.start_quorum()
        stream = m.allreduce_streamed(tree)
        assert isinstance(stream, GradStream)
        assert len(stream) == stream.num_buckets == len(plan)
        out = stream.wait(timeout=30)
        assert set(out) == set(tree)
        assert all(stream.ready(i) for i in range(len(stream)))
        # the aggregate future and wait() expose the same resolved tree
        again = stream.get_future().wait(timeout=5)
        assert again is out

    def test_timings_carry_pipeline_splits(self):
        m = make_manager(quorum=make_quorum(), bucket_cap_bytes=2 * 9 * 4)
        m.start_quorum()
        m.allreduce_streamed(_tree()).wait(timeout=30)
        deadline = time.monotonic() + 5
        t = {}
        while time.monotonic() < deadline:
            t = m.timings()
            if "allreduce_buckets" in t:
                break
            time.sleep(0.02)
        assert t.get("allreduce_buckets", 0) > 1
        for key in ("allreduce_pack_s", "allreduce_wire_s",
                    "allreduce_unpack_s", "overlap_efficiency"):
            assert key in t, f"missing pipeline split {key}"


class TestJoinFutures:
    def test_resolves_in_order(self):
        futs = [Future() for _ in range(3)]
        joined = join_futures(futs)
        for i, f in enumerate(reversed(futs)):
            f.set_result(2 - i)
        assert joined.wait(timeout=5) == [0, 1, 2]

    def test_fails_fast_on_first_error(self):
        futs = [Future() for _ in range(3)]
        joined = join_futures(futs)
        futs[1].set_exception(RuntimeError("bucket 1 died"))
        with pytest.raises(RuntimeError, match="bucket 1 died"):
            joined.wait(timeout=5)

    def test_empty_list_resolves_immediately(self):
        assert join_futures([]).wait(timeout=1) == []


class TestOverlapStatsMath:
    def test_covered_seconds_merges_overlapping_intervals(self):
        assert _covered_seconds(0, 10, [(1, 4), (3, 6), (8, 9)]) == 6.0
        assert _covered_seconds(0, 10, []) == 0.0
        assert _covered_seconds(5, 5, [(0, 10)]) == 0.0
        # clipping to the probe window
        assert _covered_seconds(2, 4, [(0, 10)]) == 2.0

    def test_overlap_efficiency_from_synthetic_marks(self):
        marks = [
            {"wire": (1.0, 3.0)},
            {"pack": (0.0, 2.0), "wire": (2.0, 4.0)},
        ]
        stats = _pipeline_overlap_stats(marks)
        # bucket0's wire [1,3] fully hidden behind bucket1's pack+wire;
        # bucket1's wire [2,4] only covered on [2,3] by bucket0's wire
        assert stats["allreduce_wire_s"] == pytest.approx(4.0)
        assert stats["overlap_efficiency"] == pytest.approx(3.0 / 4.0)
        assert stats["allreduce_buckets"] == 2.0

    def test_single_bucket_reports_zero_overlap(self):
        stats = _pipeline_overlap_stats([{"wire": (0.0, 1.0)}])
        assert stats["overlap_efficiency"] == 0.0

    def test_unreached_stages_are_tolerated(self):
        # bucket 1 failed before its wire mark landed
        stats = _pipeline_overlap_stats(
            [{"pack": (0.0, 1.0), "wire": (1.0, 2.0)}, {"pack": (0.5, 1.5)}]
        )
        assert stats["allreduce_buckets"] == 2.0
        assert stats["allreduce_wire_s"] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# a device bucket is fetched, in pieces, into a buffer of the Manager's pool


class CopyingPG(ProcessGroupDummy):
    """World of one whose result is a copy of its input, as
    ProcessGroupHost's is: the staging buffer is the input alone."""

    def __init__(self):
        super().__init__()
        self.inputs = []  # every array a collective was handed, in order

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        arrays = list(arrays)
        self.inputs.extend(arrays)
        return super().allreduce([np.array(a, copy=True) for a in arrays], op)


class GatedCopyingPG(CopyingPG):
    """CopyingPG whose futures resolve only when the test says; with
    ``copy=False`` to the very arrays it was handed (a world of one that
    was given a donated buffer)."""

    def __init__(self, copy=True):
        super().__init__()
        self.copy = copy
        self.pending = []
        self.dispatched = threading.Condition()

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        arrays = list(arrays)
        self.inputs.extend(arrays)
        fut = Future()
        with self.dispatched:
            self.pending.append(
                ([np.array(a, copy=self.copy) for a in arrays], fut)
            )
            self.dispatched.notify_all()
        return FutureWork(fut)

    def wait_dispatched(self, n):
        with self.dispatched:
            assert self.dispatched.wait_for(
                lambda: len(self.pending) >= n, timeout=30
            )

    def release_all(self):
        with self.dispatched:
            pending, self.pending = list(self.pending), []
        for arrays, fut in pending:
            fut.set_result(arrays)


_SIZES = (40, 40, 36, 36, 32, 32)
_CAP3 = 2 * 40 * 4  # two leaves a bucket: three buckets, three pool keys
_BUCKET_BYTES = [320, 288, 256]


def _device_tree(dtype=np.float32, seed=5):
    import jax

    rng = np.random.RandomState(seed)
    return {
        f"p{i}": jax.device_put((rng.randn(size) * 3).astype(dtype))
        for i, size in enumerate(_SIZES)
    }


def _pooled(m):
    return sum(len(v) for v in m._buffer_pool._free.values())


def _parked(m):
    with m._pipeline._parked_lock:
        return list(m._pipeline._parked)


def _let_landed_leaves_finish(m):
    """The pipeline asks a parked buffer's tokens and never waits for them;
    a test that counts hits waits here, as a real step's backward pass
    does."""
    import jax

    jax.block_until_ready([t for _buf, tokens in _parked(m) for t in tokens])


@pytest.fixture()
def host_of_one():
    """A real ProcessGroupHost that forms a world of one whatever the
    (mocked) quorum says: the last replica group standing."""
    store = KvStoreServer("127.0.0.1:0")

    class HostOfOne(ProcessGroupHost):
        def configure(self, store_addr, replica_rank, replica_world_size,
                      quorum_id=0):
            super().configure(
                f"127.0.0.1:{store.port}/one", 0, 1, quorum_id=quorum_id
            )

    pg = HostOfOne(timeout=10.0)
    yield pg
    pg.shutdown()
    store.shutdown()


def _d2h_spans(m, n):
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:  # stage spans land at resolve
        spans = m.tracer.export()["spans"]
        if sum(s["name"] == "pack" for s in spans) >= n:
            break
        time.sleep(0.02)
    return spans, [s for s in spans if s["name"] == "d2h"]


class TestStagingThroughThePool:
    def test_hit_share_is_one_from_the_second_step(self):
        """Step 1 allocates one buffer a bucket; steps 2-6 take them back
        from the pool: stage_pool_hit_share 1.0, BufferPool.misses still."""
        tree = _device_tree()
        m = make_manager(pg=CopyingPG(), quorum=make_quorum(),
                         bucket_cap_bytes=_CAP3)
        pool = m._buffer_pool
        want = {k: np.asarray(v) / 2 for k, v in tree.items()}
        shares = []
        for step in range(6):
            out = _reduce(m, tree, streamed=True)
            for k in tree:
                assert np.array_equal(_bits(out[k]), _bits(want[k])), (step, k)
            shares.append(m.timings()["stage_pool_hit_share"])
            if step == 0:
                assert (pool.hits, pool.misses) == (0, 3)
            m.should_commit()
        m.shutdown(wait=False)
        assert shares == [0.0] + [1.0] * 5
        assert (pool.hits, pool.misses) == (15, 3)
        assert _pooled(m) == 3
        # every result was a copy: nothing came back as its staging buffer
        assert m.timings()["wire_passthrough_share"] == 0.0

    def test_one_d2h_span_a_bucket_with_bytes_pieces_pooled(self):
        """Pieces are arguments, never spans: the tree of a step keeps its
        names, and a bucket of 256-320 bytes in pieces of 16 MiB is one."""
        tree = _device_tree()
        m = make_manager(pg=CopyingPG(), quorum=make_quorum(),
                         bucket_cap_bytes=_CAP3)
        for _ in range(2):
            _reduce(m, tree, streamed=True)
            m.should_commit()
        spans, d2h = _d2h_spans(m, 6)
        m.shutdown(wait=False)
        assert [s["args"]["bucket"] for s in d2h] == [0, 1, 2] * 2
        assert [s["args"]["pooled"] for s in d2h] == [0] * 3 + [1] * 3
        assert [s["args"]["bytes"] for s in d2h] == _BUCKET_BYTES * 2
        assert all(s["args"]["pieces"] == 1 for s in d2h)
        assert "queued_us" in d2h[0]["args"] and "queued_us" not in d2h[1]["args"]
        packs = {s["id"] for s in spans if s["name"] == "pack"}
        assert all(s["parent"] in packs for s in d2h)
        assert {s["name"] for s in spans if s["cat"] == "allreduce"} <= {
            "allreduce", "wait_quorum", "configure_commit_wait", "capture",
            "pack", "grad_wait", "d2h", "dispatch", "wire", "wire_run",
            "unpack", "h2d", "divide", "recycle",
        }

    def test_many_pieces_are_still_one_span(self, monkeypatch):
        monkeypatch.setattr(bucketing, "FETCH_PIECE_BYTES", 16 * 4)
        tree = _device_tree()
        m = make_manager(pg=CopyingPG(), quorum=make_quorum(),
                         bucket_cap_bytes=_CAP3)
        out = _reduce(m, tree, streamed=True)
        for k, v in tree.items():
            assert np.array_equal(_bits(out[k]), _bits(np.asarray(v) / 2))
        _spans, d2h = _d2h_spans(m, 3)
        m.shutdown(wait=False)
        assert [s["args"]["pieces"] for s in d2h] == [6, 6, 4]

    def test_not_back_in_the_pool_while_wire_or_landing_runs(self, monkeypatch):
        import jax

        tree = _device_tree()
        pg = GatedCopyingPG()
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=_CAP3)
        landing, may_land = threading.Event(), threading.Event()
        device_put = jax.device_put

        def slow_put(x, *a, **kw):
            if isinstance(x, np.ndarray) and threading.current_thread().name.startswith(
                "torchft_unpack"
            ):
                landing.set()
                assert may_land.wait(30)
            return device_put(x, *a, **kw)

        m.start_quorum()
        stream = m.allreduce_streamed(tree)
        pg.wait_dispatched(3)  # every wire holds its staging buffer
        assert _pooled(m) == 0
        monkeypatch.setattr(jax, "device_put", slow_put)
        pg.release_all()
        assert landing.wait(30)  # bucket 0 is landing
        assert _pooled(m) == 0
        may_land.set()
        stream.wait(timeout=30)
        monkeypatch.undo()
        m.shutdown(wait=False)
        assert _pooled(m) == 3

    @pytest.mark.parametrize("inner", [CopyingPG, ProcessGroupDummy])
    def test_never_after_a_failed_bucket(self, inner):
        """Bucket 1's wire fails: its buffer is dropped, and so is bucket
        2's, whose wire resolves after the op has failed. Bucket 0 landed
        whole and may be back (it races the failure), or parked where the
        PG handed it back."""
        tree = _device_tree()
        pg = FakeProcessGroupWrapper(inner())
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=_CAP3)
        m.start_quorum()
        pg.report_future_error(RuntimeError("injected wire failure"),
                               skip_ops=1)
        out = m.allreduce_streamed(tree).wait(timeout=30)
        assert all(not np.asarray(v).any() for v in out.values())
        time.sleep(0.2)  # whatever lands after the failure
        m.shutdown(wait=False)
        back = {k for k, v in m._buffer_pool._free.items() if v}
        back |= {(b.dtype.str, b.size) for b, _tokens in _parked(m)}
        assert back <= {("<f4", _BUCKET_BYTES[0] // 4)}, back

    @pytest.mark.parametrize("copy", [True, False])
    def test_never_after_a_timed_out_bucket(self, copy):
        """The wire resolves after the op has timed out: its buffer may
        still be read by whatever was slow, and is dropped: whether the
        result is a copy or the buffer itself."""
        tree = _device_tree()
        pg = GatedCopyingPG(copy=copy)
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=_CAP3,
                         timeout=0.3)
        m.start_quorum()
        stream = m.allreduce_streamed(tree)
        pg.wait_dispatched(3)
        out = stream.wait(timeout=30)  # the stage deadline fires: zeros
        assert all(not np.asarray(v).any() for v in out.values())
        pg.release_all()
        time.sleep(0.3)
        m.shutdown(wait=False)
        assert _pooled(m) == 0 and _parked(m) == []

    @pytest.mark.parametrize("kind", ["host_of_one", "dummy"])
    def test_a_passed_through_buffer_recycles_and_every_step_stays_intact(
        self, kind, host_of_one
    ):
        """The result is the staging buffer (a real ProcessGroupHost at a
        world of one, given its donated input; ProcessGroupDummy always):
        nothing is copied, the buffer still comes back once the leaves
        landed from it are done with it, and no later step's fetch writes
        over an earlier step's output."""
        pg = host_of_one if kind == "host_of_one" else ProcessGroupDummy()
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=_CAP3)
        ref = make_manager(pg=CopyingPG(), quorum=make_quorum(),
                           bucket_cap_bytes=_CAP3)
        pool = m._buffer_pool
        outs, wants, hit, passed = [], [], [], []
        for step in range(5):
            tree = _device_tree(seed=step)
            outs.append(_reduce(m, tree, streamed=True))
            wants.append(_reduce(ref, tree, streamed=True))
            hit.append(m.timings()["stage_pool_hit_share"])
            passed.append(m.timings()["wire_passthrough_share"])
            if kind == "host_of_one":
                assert m.timings()["ring_lanes"] == 1.0
            # (a sweep before a later bucket's fetch may already have
            # returned an earlier bucket's)
            assert _pooled(m) + len(_parked(m)) == 3
            _let_landed_leaves_finish(m)
            m.should_commit()
            ref.should_commit()
        spans = m.tracer.export()["spans"]
        m.shutdown(wait=False)
        ref.shutdown(wait=False)
        assert hit == [0.0] + [1.0] * 4
        assert passed == [1.0] * 5
        assert (pool.hits, pool.misses) == (12, 3)
        for step, (out, want) in enumerate(zip(outs, wants)):
            for k in want:  # still what it was when it resolved
                assert np.array_equal(_bits(out[k]), _bits(want[k])), (step, k)
        h2d = [s for s in spans if s["name"] == "h2d"]
        assert len(h2d) == 15
        assert all(s["args"]["passed_through"] == 1 for s in h2d)
        unpacks = {s["id"] for s in spans if s["name"] == "unpack"}
        recycle = [s for s in spans if s["name"] == "recycle"]
        assert len(recycle) == 15
        assert all(s["parent"] in unpacks and s["args"]["leaves"] == 2
                   for s in recycle)
        if kind == "host_of_one":  # the PG's thread still stamps the op
            runs = [s for s in spans if s["name"] == "wire_run"]
            assert len(runs) == 15 and all(s["args"]["world"] == 1 for s in runs)
            # no neighbour, no ring: one lane, and the step's timings say so
            assert all(s["args"]["lanes"] == 1 for s in runs)
            assert "inplace" not in runs[0]["args"]

    def test_parked_until_the_landed_leaves_are_ready(
        self, host_of_one, monkeypatch
    ):
        """While a transfer may still read the buffer it is in neither the
        pool nor anybody's hands: a second step draws buffers of its own
        and the first step's output stays what it was. Once ready, back."""

        class Gate:
            ready = False

            def is_ready(self):
                return self.ready

        gates = []

        real_average = bucketing._average_on_device()

        def gated_average():
            # the division's token is what the buffer is parked on: one a
            # bucket
            def average(xs, n):
                gates.append(Gate())
                return real_average(xs, n)[0], gates[-1]
            return average

        monkeypatch.setattr(bucketing, "_average_on_device", gated_average)
        a, b = _device_tree(seed=1), _device_tree(seed=2)
        m = make_manager(pg=host_of_one, quorum=make_quorum(),
                         bucket_cap_bytes=_CAP3)
        pool = m._buffer_pool
        out_a = _reduce(m, a, streamed=True)
        m.should_commit()
        held_a = [buf for buf, _tokens in _parked(m)]
        assert len(gates) == 3 and len(held_a) == 3 and _pooled(m) == 0
        out_b = _reduce(m, b, streamed=True)  # a's transfers "in flight"
        m.should_commit()
        assert (pool.hits, pool.misses) == (0, 6) and _pooled(m) == 0
        held_b = [buf for buf, _tokens in _parked(m) if not any(
            buf is x for x in held_a)]
        assert len(held_b) == 3
        for x in held_a:
            for y in held_b:
                assert not np.shares_memory(x, y)
        for k in a:
            assert np.array_equal(_bits(out_a[k]), _bits(np.asarray(a[k]) / 2))
        for g in gates[:3]:  # a's leaves are ready, b's not yet
            g.ready = True
        out_c = _reduce(m, a, streamed=True)
        m.should_commit()
        m.shutdown(wait=False)
        assert (pool.hits, pool.misses) == (3, 6)
        assert m.timings()["stage_pool_hit_share"] == 1.0
        parked = [buf for buf, _tokens in _parked(m)]
        assert len(parked) == 6 and all(
            any(buf is x for x in held_a + held_b) for buf in parked
        )
        for k in a:
            assert np.array_equal(_bits(out_b[k]), _bits(np.asarray(b[k]) / 2))
            assert np.array_equal(_bits(out_c[k]), _bits(np.asarray(a[k]) / 2))

    @pytest.mark.parametrize("reduce_op,back", [
        (ReduceOp.SUM, False), (ReduceOp.AVG, True),
    ])
    def test_numpy_leaves_that_are_views_keep_their_buffer(
        self, host_of_one, reduce_op, back
    ):
        """Under SUM a landed numpy leaf IS a slice of the passed-through
        buffer: the caller's for good, never refilled. An AVG's quotient
        is memory of its own and nothing reads the buffer any more."""
        m = make_manager(pg=host_of_one, quorum=make_quorum(),
                         bucket_cap_bytes=_CAP3)
        outs, wants = [], []
        for step in range(3):
            tree = {k: np.asarray(v) for k, v in _device_tree(seed=step).items()}
            wants.append({
                k: v / 2 if reduce_op == ReduceOp.AVG else v.copy()
                for k, v in tree.items()
            })
            outs.append(_reduce(m, tree, streamed=True, reduce_op=reduce_op))
            m.should_commit()
        m.shutdown(wait=False)
        assert _parked(m) == []
        assert (_pooled(m), m._buffer_pool.hits) == ((3, 6) if back else (0, 0))
        assert "wire_passthrough_share" not in m.timings()  # no device bucket
        for out, want in zip(outs, wants):
            for k in want:
                assert np.array_equal(_bits(out[k]), _bits(want[k]))

    def test_a_device_leaf_that_is_a_slice_of_the_buffer_keeps_it(self):
        """A CPU backend's device_put of aligned memory copies nothing: with
        no divide after it the landed leaf IS the buffer. After the
        (donating) divide it is not, and a token says when it was read."""
        import jax

        raw = np.zeros(4096 + 16, np.float32)
        start = (-raw.ctypes.data % 64) // 4
        buf = raw[start:start + 4096]
        buf[:] = np.arange(4096)
        placed = jax.device_put(buf[:1024].reshape(32, 32))
        if placed.unsafe_buffer_pointer() != buf.ctypes.data:
            pytest.skip("this backend copied")
        assert bucketing.readers_of(buf, [placed]) is None
        assert bucketing.readers_of(buf, [np.ones(3), placed]) is None
        halved = bucketing._average(placed, 2, landed=True)
        (token,) = bucketing.readers_of(buf, [np.ones(3), halved])
        jax.block_until_ready(token)
        assert token.is_ready()
        halved.delete()  # the caller's next step donated it
        assert token.is_ready()
        assert bucketing.readers_of(buf, [np.ones(3)]) == []
        assert bucketing.readers_of(buf, [buf[5:9]]) is None

    def test_deleting_the_leaves_after_the_call_changes_nothing(self, monkeypatch):
        """Donation safety with the capture in pieces: the next jitted step
        may donate (delete) the gradients as soon as allreduce() returns."""
        monkeypatch.setattr(bucketing, "FETCH_PIECE_BYTES", 24 * 4)
        tree = _device_tree()
        want = {k: np.asarray(v) / 2 for k, v in tree.items()}
        pg = GatedCopyingPG()
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=_CAP3)
        m.start_quorum()
        stream = m.allreduce_streamed(tree)
        for v in tree.values():
            v.delete()
        pg.wait_dispatched(3)
        pg.release_all()
        out = stream.wait(timeout=30)
        m.shutdown(wait=False)
        assert m.errored() is None
        for k in want:
            assert np.array_equal(_bits(out[k]), _bits(want[k]))

    @pytest.mark.parametrize("copy", [True, False])
    def test_two_steps_in_flight_share_no_buffer(self, copy):
        a, b = _device_tree(seed=1), _device_tree(seed=2)
        pg = GatedCopyingPG(copy=copy)
        m = make_manager(pg=pg, quorum=make_quorum(), bucket_cap_bytes=_CAP3)
        # a first step fills the pool, so the two below draw from it
        m.start_quorum()
        warm = m.allreduce_streamed(a)
        pg.wait_dispatched(3)
        pg.release_all()
        warm.wait(timeout=30)
        m.should_commit()
        pg.inputs.clear()
        m.start_quorum()
        s1 = m.allreduce_streamed(a)
        s2 = m.allreduce_streamed(b)
        pg.wait_dispatched(6)
        held = list(pg.inputs)
        assert len(held) == 6
        for i, x in enumerate(held):
            for y in held[i + 1:]:
                assert not np.shares_memory(x, y)
        # each still holds its own step's bytes
        for x, leafs in zip(held, [("p0", "p1"), ("p2", "p3"), ("p4", "p5")] * 2):
            src = a if x is held[0] or x is held[1] or x is held[2] else b
            want = np.concatenate([np.asarray(src[k]) for k in leafs])
            assert np.array_equal(x, want)
        pg.release_all()
        o1, o2 = s1.wait(timeout=30), s2.wait(timeout=30)
        m.shutdown(wait=False)
        for k in a:
            assert np.array_equal(_bits(o1[k]), _bits(np.asarray(a[k]) / 2))
            assert np.array_equal(_bits(o2[k]), _bits(np.asarray(b[k]) / 2))


def _device_spans(m, n):
    """The watcher's ``device/*`` spans, once it has recorded ``n``."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        spans = m.tracer.export()["spans"]
        got = [s for s in spans if s["cat"] == "device"]
        if len(got) >= n:
            break
        time.sleep(0.01)
    return spans, got


class TestDeviceMilestonesOfAStep:
    """What the device finished, and when, for the recorder's watcher
    (``SpanRecorder.when_ready``): registered by the pipeline, on threads
    that are there anyway, none of which waits."""

    def _step_of_two_ops(self, m):
        head, layers = _device_tree(seed=1), _device_tree(seed=2)
        m.start_quorum()
        works = [m.allreduce(head), m.allreduce(layers)]
        out = [w.get_future().wait(timeout=30) for w in works]
        m.should_commit()
        return (head, layers), out

    @pytest.mark.parametrize("pg", [ProcessGroupDummy, CopyingPG])
    def test_forward_backward_and_a_landing_a_bucket(self, pg, monkeypatch):
        """Segment 0's last piece is ``device/forward``, a later segment's
        ``device/backward``, and every landed bucket an instant: on the
        token of the bucket's one division, which recycling a passed-through
        staging buffer parks it on too."""
        tokens = []
        real = bucketing._landed_token()

        def counting():
            def token(leaf):
                tokens.append(leaf)
                return real(leaf)
            return token

        monkeypatch.setattr(bucketing, "_landed_token", counting)
        m = make_manager(pg=pg(), quorum=make_quorum(), bucket_cap_bytes=_CAP3)
        trees, outs = self._step_of_two_ops(m)
        spans, dev = _device_spans(m, 8)
        m.shutdown(wait=False)
        for tree, out in zip(trees, outs):  # the gradients are what they were
            for k, v in tree.items():
                assert np.array_equal(_bits(out[k]), _bits(np.asarray(v) / 2))
        # (the first call compiles: its buckets may land, on the unpack
        # thread, before this thread registers the second op's)
        ops = [s for s in dev if s["name"] != "landed"]
        assert [(s["name"], s["args"]["segment"]) for s in ops] == [
            ("forward", 0), ("backward", 1)]
        landed = [s for s in dev if s["name"] == "landed"]
        assert len(landed) == 6
        assert [(s["args"]["segment"], s["args"]["bucket"]) for s in landed] \
            == [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1), (1, 2)]
        assert all(s["dur_us"] == 1 for s in landed)
        assert all({"waited_us", "late"} <= set(s["args"]) for s in dev)
        # under AVG the division's own token serves both the recycling of a
        # passed-through buffer and the milestone: no token program at all
        assert tokens == []
        # each hangs where its cause does: the op's allreduce span, the
        # bucket's unpack stage
        by_id = {s["id"]: s for s in spans}
        assert all(by_id[s["parent"]]["name"] == "allreduce" for s in ops)
        assert [by_id[s["parent"]]["args"]["segment"] for s in ops] == [0, 1]
        assert all(by_id[s["parent"]]["name"] == "unpack"
                   and by_id[s["parent"]]["args"]["bucket"] == s["args"]["bucket"]
                   for s in landed)
        assert len({s["step"] for s in dev}) == 1

    def test_a_host_tree_and_a_disabled_recorder_register_nothing(
        self, monkeypatch
    ):
        tokens = []
        real = bucketing._landed_token()
        monkeypatch.setattr(
            bucketing, "_landed_token",
            lambda: lambda leaf: tokens.append(leaf) or real(leaf))
        m = make_manager(pg=CopyingPG(), quorum=make_quorum(),
                         bucket_cap_bytes=_CAP3)
        _reduce(m, _tree(), streamed=False)
        m.should_commit()
        off = make_manager(pg=CopyingPG(), quorum=make_quorum(),
                           bucket_cap_bytes=_CAP3, tracing=False)
        out = _reduce(off, _device_tree(), streamed=False)
        off.should_commit()
        assert np.array_equal(
            _bits(out["p0"]), _bits(np.asarray(_device_tree()["p0"]) / 2))
        spans, dev = _device_spans(m, 0)
        assert dev == [] and m.tracer._watcher is None
        assert off.tracer._watcher is None and off.tracer._watched.empty()
        assert tokens == []  # a copied result and no recorder: none dispatched
        m.shutdown(wait=False)
        off.shutdown(wait=False)

    def test_manager_shutdown_ends_the_watcher(self):
        m = make_manager(pg=ProcessGroupDummy(), quorum=make_quorum(),
                         bucket_cap_bytes=_CAP3)
        self._step_of_two_ops(m)
        watcher = m.tracer._watcher
        assert watcher is not None and watcher.is_alive()
        m.shutdown(wait=False)
        watcher.join(5)
        assert not watcher.is_alive()


_RING_SIZES = (20_000, 20_000, 18_000, 18_000, 17_001, 17_001)
_RING_CAP = 2 * 20_000 * 4  # two leaves a bucket, each over the ring's floor


def _ring_tree(rank, step):
    import jax

    rng = np.random.RandomState(100 * step + rank)
    return {
        f"p{i}": jax.device_put((rng.randn(size) * 3).astype(np.float32))
        for i, size in enumerate(_RING_SIZES)
    }


@pytest.fixture()
def world_of_two():
    """``make(rank, **manager_kwargs)``: a Manager over a real
    ProcessGroupHost that joins a world of two on a real store, whatever
    the (mocked) quorum's store address says."""
    store = KvStoreServer("127.0.0.1:0")

    class HostOfTwo(ProcessGroupHost):
        def configure(self, store_addr, replica_rank, replica_world_size,
                      quorum_id=0):
            super().configure(f"127.0.0.1:{store.port}/two", replica_rank, 2,
                              quorum_id=quorum_id)

    made = []

    def make(rank, timeout=10.0, **kwargs):
        pg = HostOfTwo(timeout=timeout)
        made.append(pg)
        if kwargs.pop("bare", False):
            return pg
        return make_manager(pg=pg, quorum=make_quorum(replica_rank=rank),
                            bucket_cap_bytes=_RING_CAP, timeout=timeout,
                            **kwargs)

    yield make
    for pg in made:
        pg.shutdown()
    store.shutdown()


class TestTheRingWorksInTheStagingBuffer:
    @pytest.mark.parametrize("lanes", [1, 4])
    def test_a_world_of_two_copies_nothing_and_every_step_is_right(
        self, world_of_two, monkeypatch, lanes
    ):
        """Behind a real ring the donated pool buffer comes back reduced:
        `wire_passthrough_share` reads 1.0 as it does at a world of one,
        the buffers recycle, and six steps' trees are bit for bit the mean
        of the two groups', on one lane (buckets under the lane floor) and
        on the ring's four; `wire_run` and `timings()` say which."""
        import torchft_tpu.process_group as pg_mod

        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", 32 * 1024)
        assert pg_mod._RING_LANES == 4
        if lanes == 4:
            monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES", 0)
        ms = [world_of_two(rank) for rank in range(2)]
        steps = 6

        def run(rank):
            m, outs, shares = ms[rank], [], []
            for step in range(steps):
                outs.append(_reduce(m, _ring_tree(rank, step), streamed=True))
                shares.append((m.timings()["stage_pool_hit_share"],
                               m.timings()["wire_passthrough_share"]))
                assert m.timings()["ring_lanes"] == float(lanes)
                assert _pooled(m) + len(_parked(m)) == 3
                _let_landed_leaves_finish(m)
                m.should_commit()
            return outs, shares

        with ThreadPoolExecutor(2) as ex:
            results = list(ex.map(run, range(2)))
        spans = [m.tracer.export()["spans"] for m in ms]
        for m in ms:
            m.shutdown(wait=False)
        for rank, (outs, shares) in enumerate(results):
            assert shares == [(0.0, 1.0)] + [(1.0, 1.0)] * (steps - 1)
            for step, out in enumerate(outs):
                a, b = _ring_tree(0, step), _ring_tree(1, step)
                for k in a:  # still what it was when it resolved
                    want = (np.asarray(a[k]) + np.asarray(b[k])) / np.float32(2)
                    assert np.array_equal(_bits(out[k]), _bits(want)), (step, k)
            h2d = [s for s in spans[rank] if s["name"] == "h2d"]
            assert len(h2d) == 3 * steps
            assert all(s["args"]["passed_through"] == 1 for s in h2d)
            runs = [s for s in spans[rank] if s["name"] == "wire_run"]
            assert len(runs) == 3 * steps
            for s in runs:
                frames = -(-(s["args"]["bytes"] // 2) // (32 * 1024))
                assert (s["args"]["world"], s["args"]["inplace"],
                        s["args"]["chunks"], s["args"]["lanes"]) == (
                            2, 1, frames, lanes)

    def test_a_ring_that_fails_half_way_gives_no_buffer_back(
        self, world_of_two, monkeypatch
    ):
        """The peer closes after its second frame: the step is discarded
        (zeros, no commit) and the half-reduced staging buffer is dropped,
        not recycled."""
        import torchft_tpu.process_group as pg_mod

        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", 16 * 1024)
        m = world_of_two(0, timeout=3.0)
        peer = world_of_two(1, timeout=3.0, bare=True)
        joined = threading.Thread(target=peer.configure,
                                  args=("", 1, 2, 1))
        joined.start()
        m.start_quorum()
        m.wait_quorum()
        joined.join(10)
        comm = peer._gen.comm
        recv, seen = comm.recv_raw_into, []

        def dying_recv(frm, out):
            seen.append(frm)
            if len(seen) == 2:
                peer.abort()
            return recv(frm, out)

        comm.recv_raw_into = dying_recv
        theirs = peer.allreduce(
            [np.ones(2 * 20_000, np.float32)], ReduceOp.SUM, donate=True)
        out = m.allreduce_streamed(_ring_tree(0, 0)).wait(timeout=30)
        with pytest.raises(Exception):
            theirs.get_future().wait(timeout=10)
        assert all(not np.asarray(v).any() for v in out.values())
        assert not m.should_commit()
        time.sleep(0.2)  # whatever lands after the failure
        # the ring that failed left no half account: its future carries no
        # stamps, so no wire_run, no child of one, and no key of the step
        assert not {"t_first", "fold_us"} & set(theirs.get_future().ring)
        names = {s["name"] for s in m.tracer.export()["spans"]}
        assert not names & {"wire_run", "ring_entry_wait", "ring_stream"}
        assert not set(bucketing.RING_KEYS) & set(m.timings())
        m.shutdown(wait=False)
        assert _pooled(m) == 0 and _parked(m) == []


_WIRE_RUN_ARGS = {"bucket", "segment", "bytes", "world", "queued_us"}
_RING_TERMS = ("recv_wait", "recv", "slot_wait", "recv_span", "arrive_wait",
               "fold", "ready_wait", "send", "handoff")


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


class TestTheRingTellsItsOwnTime:
    """A plain ring's ``wire_run`` has two children recorded from the
    ring's own stamps, ``ring_entry_wait`` and ``ring_stream``, and the
    step's ``timings()`` the six ``RING_KEYS``; whatever runs no plain ring
    says what it said."""

    @pytest.mark.parametrize("lanes", [1, 4])
    def test_the_two_children_tile_their_wire_run_and_timings_sum_the_step(
        self, world_of_two, monkeypatch, lanes
    ):
        import torchft_tpu.process_group as pg_mod

        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", 32 * 1024)
        if lanes == 4:
            monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES", 0)
        ms = [world_of_two(rank) for rank in range(2)]
        steps = 3

        def run(rank):
            m, seen = ms[rank], []
            for step in range(steps):
                _reduce(m, _ring_tree(rank, step), streamed=True)
                seen.append(m.timings())
                _let_landed_leaves_finish(m)
                m.should_commit()
            m.start_quorum()  # the next step has begun: its keys are its own
            seen.append(m.timings())
            return seen

        with ThreadPoolExecutor(2) as ex:
            results = list(ex.map(run, range(2)))
        spans = [m.tracer.export()["spans"] for m in ms]
        for m in ms:
            m.shutdown(wait=False)
        for rank, seen in enumerate(results):
            runs = _by_name(spans[rank], "wire_run")
            assert len(runs) == 3 * steps
            by_parent = {}
            for s in spans[rank]:
                if s["name"] in ("ring_entry_wait", "ring_stream"):
                    by_parent.setdefault(s["parent"], {})[s["name"]] = s
            assert set(by_parent) == {r["id"] for r in runs}
            for r in runs:
                assert set(r["args"]) == _WIRE_RUN_ARGS | {
                    "inplace", "chunks", "lanes"}
                entry = by_parent[r["id"]]["ring_entry_wait"]
                stream = by_parent[r["id"]]["ring_stream"]
                for child in (entry, stream):
                    assert child["cat"] == "allreduce"
                    assert child["step"] == r["step"]
                    assert (child["args"]["bucket"], child["args"]["segment"]
                            ) == (r["args"]["bucket"], r["args"]["segment"])
                assert set(entry["args"]) == {"bucket", "segment"}
                # one set of stamps: the two tile the run, to a rounding
                assert abs(entry["dur_us"] + stream["dur_us"]
                           - r["dur_us"]) <= 3
                assert abs(entry["ts_us"] - r["ts_us"]) <= 50
                assert abs(stream["ts_us"] + stream["dur_us"]
                           - r["ts_us"] - r["dur_us"]) <= 50
                args = stream["args"]
                assert set(args) == {
                    "bucket", "segment", "bytes", "lanes", "chunks", "gb_s",
                    *(t + "_us" for t in _RING_TERMS),
                    *(t + "_us_max" for t in _RING_TERMS)}
                assert (args["bytes"], args["lanes"], args["chunks"]) == (
                    r["args"]["bytes"], lanes, r["args"]["chunks"])
                assert args["gb_s"] == pytest.approx(
                    args["bytes"] / 1e3 / stream["dur_us"], rel=0.05, abs=2e-3)
                for t in _RING_TERMS:
                    assert 0 <= args[t + "_us"] <= args[t + "_us_max"]
                # the receiver's three terms lie inside the stream
                assert (args["recv_wait_us"] + args["recv_us"]
                        + args["slot_wait_us"]) <= args["recv_span_us"] + 3
                assert args["recv_span_us_max"] <= stream["dur_us"] + 3
            # timings() of a step: its runs' sums, to the SUMMARY's digits
            for step in range(steps):
                mine = [r for r in runs if r["step"] == runs[0]["step"] + step]
                assert len(mine) == 3
                t = seen[step]
                assert t["ring_lanes"] == float(lanes)
                assert t["ring_entry_wait_s"] == pytest.approx(sum(
                    by_parent[r["id"]]["ring_entry_wait"]["dur_us"]
                    for r in mine) / 1e6, abs=2e-5)
                for term in ("recv_wait", "recv", "fold", "send", "handoff"):
                    assert t[f"ring_{term}_s"] == pytest.approx(sum(
                        by_parent[r["id"]]["ring_stream"]["args"][term + "_us"]
                        for r in mine) / 1e6, abs=1e-9)
                assert t["ring_entry_wait_s"] + t["ring_recv_s"] <= (
                    t["allreduce_wire_s"])
            # begin_step took the step's keys away
            assert not {*bucketing.RING_KEYS, "ring_lanes"} & set(seen[-1])
            assert "allreduce_wire_s" in seen[-1]  # the others stay, as before

    def test_ring_lanes_is_the_fewest_any_ring_of_the_step_rode(
        self, world_of_two, monkeypatch
    ):
        """The step's first run is under the lane floor and its second over
        it: ``ring_lanes`` reads 1.0, where the last run's lanes hid it."""
        import jax

        import torchft_tpu.process_group as pg_mod

        monkeypatch.setattr(pg_mod, "_RING_CHUNK_BYTES", 32 * 1024)
        monkeypatch.setattr(pg_mod, "_RING_LANE_FLOOR_BYTES", 70_000)
        ms = [world_of_two(rank) for rank in range(2)]

        def run(rank):
            rng = np.random.RandomState(rank)
            tree = {f"p{i}": jax.device_put(rng.randn(n).astype(np.float32))
                    for i, n in enumerate((17_001, 17_001, 20_000, 20_000))}
            _reduce(ms[rank], tree, streamed=True)
            return ms[rank].timings()["ring_lanes"]

        with ThreadPoolExecutor(2) as ex:
            assert list(ex.map(run, range(2))) == [1.0, 1.0]
        for m in ms:
            rode = [s["args"]["lanes"] for s in _by_name(
                m.tracer.export()["spans"], "wire_run")]
            m.shutdown(wait=False)
            assert rode == [1, 4]

    def test_a_world_of_one_says_what_it_said(self, host_of_one):
        m = make_manager(pg=host_of_one, quorum=make_quorum(),
                         bucket_cap_bytes=_CAP3)
        _reduce(m, _device_tree(seed=0), streamed=True)
        spans = m.tracer.export()["spans"]
        timings = m.timings()
        m.shutdown(wait=False)
        runs = _by_name(spans, "wire_run")
        assert runs and all(
            set(r["args"]) == _WIRE_RUN_ARGS | {"lanes"} for r in runs)
        assert not _by_name(spans, "ring_entry_wait") + _by_name(
            spans, "ring_stream")
        assert timings["ring_lanes"] == 1.0
        assert not set(bucketing.RING_KEYS) & set(timings)

    @pytest.mark.parametrize("how", ["mesh_exchange", "compressed_ring"])
    def test_no_plain_ring_no_account(self, world_of_two, monkeypatch, how):
        """Buckets under ``_RING_MIN_BYTES`` cross in the mesh exchange,
        compressed ones ride the self-healing ring: neither fills ``info``,
        and the spans and ``timings()`` are the parent commit's."""
        import torchft_tpu.process_group as pg_mod

        kwargs = {}
        if how == "mesh_exchange":
            monkeypatch.setattr(pg_mod, "_RING_MIN_BYTES", 1 << 30)
        else:
            kwargs["compress"] = "fp8"
        ms = [world_of_two(rank, **kwargs) for rank in range(2)]

        def run(rank):
            _reduce(ms[rank], _ring_tree(rank, 0), streamed=True)
            return ms[rank].timings()

        with ThreadPoolExecutor(2) as ex:
            timings = list(ex.map(run, range(2)))
        for m, t in zip(ms, timings):
            spans = m.tracer.export()["spans"]
            m.shutdown(wait=False)
            runs = _by_name(spans, "wire_run")
            assert len(runs) == 3
            assert all(set(r["args"]) == _WIRE_RUN_ARGS for r in runs)
            assert not _by_name(spans, "ring_entry_wait") + _by_name(
                spans, "ring_stream")
            assert not {*bucketing.RING_KEYS, "ring_lanes"} & set(t)
