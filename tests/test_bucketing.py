"""Shared bucketing layer (torchft_tpu/bucketing.py) + the collective-count
CI guard: a many-leaf pytree through Manager.allreduce must hit the process
group with at most ceil(total_bytes / cap) flat arrays — the whole point of
bucketing — and bitwise-identical values either way."""

import contextlib
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_allreduce_stream import CopyingPG, world_of_two  # noqa: F401 (fixture)
from test_manager import make_manager, make_quorum
from torchft_tpu import bucketing
from torchft_tpu.process_group import ProcessGroupDummy, ReduceOp


class TestBufferPool:
    def test_acquire_release_reuses_buffer(self):
        pool = bucketing.BufferPool()
        a = pool.acquire(16, np.float32)
        assert pool.misses == 1 and pool.hits == 0
        pool.release(a)
        b = pool.acquire(16, np.float32)
        assert b is a
        assert pool.hits == 1

    def test_key_is_dtype_and_size(self):
        pool = bucketing.BufferPool()
        a = pool.acquire(16, np.float32)
        pool.release(a)
        assert pool.acquire(16, np.float64) is not a
        assert pool.acquire(8, np.float32) is not a

    def test_max_per_key_caps_retention(self):
        pool = bucketing.BufferPool(max_per_key=1)
        a, b = pool.acquire(4, np.float32), pool.acquire(4, np.float32)
        pool.release(a)
        pool.release(b)  # beyond the cap: dropped, not retained
        assert pool.acquire(4, np.float32) is a
        c = pool.acquire(4, np.float32)
        assert c is not a and c is not b


class TestPlanCache:
    def test_plan_for_memoizes_on_treedef_and_spec(self):
        leaves, treedef = jax.tree_util.tree_flatten(
            {"a": np.ones(3, np.float32), "b": np.ones(5, np.float32)}
        )
        p1 = bucketing.plan_for(leaves, 1 << 20, treedef=treedef)
        p2 = bucketing.plan_for(leaves, 1 << 20, treedef=treedef)
        assert p2 is p1  # cache hit: the identical plan object
        assert bucketing.plan_for(leaves, 1 << 10, treedef=treedef) is not p1

    def test_same_structure_different_geometry_gets_new_plan(self):
        _, treedef = jax.tree_util.tree_flatten({"a": 0, "b": 0})
        small = [np.ones(3, np.float32), np.ones(5, np.float32)]
        big = [np.ones(7, np.float32), np.ones(9, np.float32)]
        p_small = bucketing.plan_for(small, 1 << 20, treedef=treedef)
        p_big = bucketing.plan_for(big, 1 << 20, treedef=treedef)
        assert p_big is not p_small
        assert p_big.sizes != p_small.sizes


class TestPackUnpackRoundtrip:
    def test_host_roundtrip_bitwise(self):
        rng = np.random.RandomState(0)
        leaves = [
            rng.randn(4, 3).astype(np.float32),
            rng.randn(7).astype(np.float32),
            rng.randn(2, 2).astype(np.float64),
        ]
        plan = bucketing.build_plan(leaves, 1 << 20)
        assert len(plan) == 2  # one bucket per dtype
        flats, pooled = bucketing.pack(leaves, plan)
        assert not pooled  # no pool passed
        out = bucketing.unpack(flats, plan)
        for orig, got in zip(leaves, out):
            assert got.shape == orig.shape and got.dtype == orig.dtype
            np.testing.assert_array_equal(np.asarray(got), orig)

    def test_device_groups_pack_as_jax_arrays(self):
        leaves = [jnp.arange(4, dtype=jnp.float32), jnp.ones(3, jnp.float32)]
        plan = bucketing.build_plan(leaves, 1 << 20)
        flats, _ = bucketing.pack(leaves, plan)
        assert len(flats) == 1 and isinstance(flats[0], jax.Array)
        out = bucketing.unpack(flats, plan)
        np.testing.assert_array_equal(np.asarray(out[0]), np.arange(4))
        np.testing.assert_array_equal(np.asarray(out[1]), np.ones(3))

    def test_pack_into_pool_buffer(self):
        pool = bucketing.BufferPool()
        leaves = [np.ones(3, np.float32), np.full(5, 2.0, np.float32)]
        plan = bucketing.build_plan(leaves, 1 << 20)
        flats, pooled = bucketing.pack(leaves, plan, pool=pool)
        assert pooled == [flats[0]]
        np.testing.assert_array_equal(
            flats[0], np.array([1, 1, 1, 2, 2, 2, 2, 2], np.float32)
        )

    def test_oversized_leaf_gets_own_bucket(self):
        leaves = [np.ones(100, np.float32), np.ones(2, np.float32)]
        plan = bucketing.build_plan(leaves, cap_bytes=16)
        assert len(plan) == 2  # leaf 0 alone exceeds the cap; never dropped


class CountingPG(ProcessGroupDummy):
    """World-1 passthrough PG that records how many arrays each collective
    carried — the observable the CI guard asserts on."""

    def __init__(self):
        super().__init__()
        self.allreduce_calls = []

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        arrays = list(arrays)
        self.allreduce_calls.append(len(arrays))
        return super().allreduce(arrays, op)

    @property
    def total_arrays(self):
        return sum(self.allreduce_calls)


def _many_leaf_tree(n=100, size=17):
    return {f"p{i}": np.full((size,), float(i), np.float32) for i in range(n)}


class TestCollectiveCountGuard:
    """CI guard (deterministic, tier-1): bucketing must actually reduce the
    number of arrays hitting the wire, and must not change the values."""

    def _reduce(self, tree, **manager_kwargs):
        pg = CountingPG()
        m = make_manager(pg=pg, quorum=make_quorum(), **manager_kwargs)
        m.start_quorum()
        out = m.allreduce(tree).get_future().wait(timeout=30)
        return pg, out

    def test_100_leaf_tree_is_one_collective_at_default_cap(self, monkeypatch):
        monkeypatch.delenv("TORCHFT_BUCKET_CAP_MB", raising=False)
        tree = _many_leaf_tree()
        pg, out = self._reduce(tree)
        # all float32, far under 1 GiB -> a single flat bucket
        assert pg.total_arrays == 1
        for i in range(100):
            np.testing.assert_allclose(out[f"p{i}"], i / 2.0)  # avg of 2

    def test_array_count_bounded_by_ceil_bytes_over_cap(self, monkeypatch):
        monkeypatch.delenv("TORCHFT_BUCKET_CAP_MB", raising=False)
        tree = _many_leaf_tree()
        cap = 1024
        total_bytes = sum(v.nbytes for v in tree.values())
        pg, out = self._reduce(tree, bucket_cap_bytes=cap)
        bound = math.ceil(total_bytes / cap)
        assert 1 < pg.total_arrays <= bound, (
            f"{pg.total_arrays} arrays for {total_bytes}B at cap={cap} "
            f"(bound {bound})"
        )
        np.testing.assert_allclose(out["p7"], 3.5)

    def test_cap_zero_disables_bucketing(self, monkeypatch):
        monkeypatch.delenv("TORCHFT_BUCKET_CAP_MB", raising=False)
        tree = _many_leaf_tree(n=10)
        pg, out = self._reduce(tree, bucket_cap_bytes=0)
        assert pg.total_arrays == 10  # per-leaf, unbucketed
        np.testing.assert_allclose(out["p4"], 2.0)

    def test_env_var_overrides_cap(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_BUCKET_CAP_MB", "0")
        tree = _many_leaf_tree(n=10)
        pg, _ = self._reduce(tree, bucket_cap_bytes=1 << 30)
        assert pg.total_arrays == 10


# ---------------------------------------------------------------------------
# the host plane's capture of a device bucket: pieces, fetched into a buffer


def _device_leaves(shapes, dtype, seed=3):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    return [
        jnp.asarray(np.asarray(rng.randn(*s) * 100, np.float32)).astype(dtype)
        for s in shapes
    ]


# name -> (leaf shapes, piece size in ELEMENTS)
_FETCH_CASES = {
    "not_a_multiple_of_the_piece": ([(7,), (3, 50), (1000,), (5, 5)], 64),
    "smaller_than_one_piece": ([(7,), (3, 5), ()], 1 << 10),
    "exactly_one_piece": ([(16,), (4, 12)], 64),
    "leaf_straddles_a_piece_boundary": ([(10,), (6, 40), (4, 6, 10), (9,)], 100),
    "one_leaf_bucket": ([(40, 24)], 96),
    "one_leaf_one_dimension": ([(1000,)], 96),
    "row_wider_than_a_piece": ([(3, 500), (8,)], 128),
    "an_empty_leaf": ([(0,), (33,), (2, 0), (70, 3)], 32),
}


class TestFetchInto:
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32", "int32"])
    @pytest.mark.parametrize("case", sorted(_FETCH_CASES))
    def test_bitwise_np_asarray_of_the_concatenation(self, case, dtype):
        """pack(piece_bytes=...) + fetch_into against np.asarray of the flat
        that pack builds without it: same bytes, every piece within the
        size, the pieces a partition of the bucket, each dropped once it
        has landed."""
        shapes, piece_elems = _FETCH_CASES[case]
        dt = np.dtype(dtype)
        leaves = _device_leaves(shapes, dt)
        plan = bucketing.build_plan(leaves, 1 << 30)
        assert len(plan) == 1
        want = np.asarray(bucketing.pack(leaves, plan)[0][0])
        piece_bytes = piece_elems * dt.itemsize
        flats, pooled = bucketing.pack(leaves, plan, piece_bytes=piece_bytes)
        pieces = flats[0]
        assert isinstance(pieces, bucketing.Pieces) and pooled == []
        assert (pieces.size, pieces.dtype) == (want.size, want.dtype)
        assert [a for a, _ in pieces.bounds] == [0] + [
            b for _, b in pieces.bounds[:-1]
        ]
        assert pieces.bounds[-1][1] == want.size
        assert all(0 < b - a <= piece_elems for a, b in pieces.bounds)
        assert [int(x.size) for x in pieces.arrays] == [
            b - a for a, b in pieces.bounds
        ]
        out = np.full(want.size, 99, dt)
        n = bucketing.fetch_into(pieces.block_until_ready(), out)
        assert n == len(pieces.bounds)
        assert out.dtype == want.dtype and out.tobytes() == want.tobytes()
        assert pieces.arrays == [None] * n

    def test_pieces_are_private_copies(self):
        """Deleting the leaves right after the capture (what a donating jit
        step does) changes nothing: also for a one-dimensional leaf that is
        a whole piece by itself, which the split hands through unsliced."""
        leaves = _device_leaves([(64,), (4, 16), (5,)], np.float32)
        plan = bucketing.build_plan(leaves, 1 << 30)
        want = np.asarray(bucketing.pack(leaves, plan)[0][0]).copy()
        pieces = bucketing.pack(leaves, plan, piece_bytes=64 * 4)[0][0]
        for leaf in leaves:
            leaf.delete()
        out = np.empty(want.size, want.dtype)
        bucketing.fetch_into(pieces, out)
        assert out.tobytes() == want.tobytes()

    def test_a_large_leaf_is_cut_between_rows_and_whole_tiles(self):
        """The device slices rows of the leaf as it lies: a cut inside a
        row, or (where rows are many) inside a tile of 32 rows, would go
        through a temporary the size of the leaf on the TPU."""
        metas = [(0, 0, 1000 * 48, (1000, 48)), (1, 1000 * 48, 10, (10,))]
        bounds = bucketing._piece_bounds(metas, 4, 100 * 48 * 4)
        big = [(a, b) for a, b in bounds if b <= 1000 * 48]
        assert all(a % (32 * 48) == 0 for a, _ in big)
        assert all(b - a == 96 * 48 for a, b in big[:-1])
        assert bounds[-1] == (1000 * 48, 1000 * 48 + 10)

    def test_host_groups_are_packed_as_before(self):
        tree = [np.arange(6, dtype=np.float32), np.ones(3, np.float32)]
        plan = bucketing.build_plan(tree, 1 << 30)
        pool = bucketing.BufferPool()
        flats, pooled = bucketing.pack(tree, plan, pool=pool, piece_bytes=8)
        assert isinstance(flats[0], np.ndarray) and pooled == [flats[0]]

    def test_acquire_hit_says_whether_the_buffer_is_recycled(self):
        pool = bucketing.BufferPool()
        a, hit = pool.acquire_hit(8, np.float32)
        assert not hit and (pool.hits, pool.misses) == (0, 1)
        pool.release(a)
        b, hit = pool.acquire_hit(8, np.float32)
        assert hit and b is a and (pool.hits, pool.misses) == (1, 1)


class _HostPiece:
    """A piece whose transfer takes ``seconds`` to wait for, or fails on
    the threads whose name starts with ``fails_on``."""

    def __init__(self, data, seconds=0.0, fails_on=None):
        self.data, self.seconds, self.fails_on = data, seconds, fails_on
        self.size = data.size

    def is_ready(self):
        return True

    def __array__(self, dtype=None, copy=None):
        name = threading.current_thread().name
        if self.fails_on is not None and name.startswith(self.fails_on):
            raise RuntimeError(f"transfer failed on {name}")
        time.sleep(self.seconds)
        return self.data


def _slow_pieces(n, elems=8, seconds=0.005, dtype=np.float32):
    import ml_dtypes  # noqa: F401 — np.dtype("bfloat16")

    flat = (np.arange(n * elems) % 251).astype(np.dtype(dtype))
    arrays = [_HostPiece(flat[k * elems:(k + 1) * elems], seconds)
              for k in range(n)]
    bounds = [(k * elems, (k + 1) * elems) for k in range(n)]
    return bucketing.Pieces(arrays, bounds, flat.size, flat.dtype), flat


@pytest.fixture
def fetchers():
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=8, thread_name_prefix="test_fetch")
    yield pool
    pool.shutdown(wait=True)


class TestFetchers:
    """fetch_into with a pool: the caller and its helpers take the pieces in
    flat order; the bytes, the drops and the look after each piece are what
    the one loop's were."""

    @pytest.mark.parametrize("short_last", [False, True],
                             ids=["whole_last", "short_last"])
    @pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
    @pytest.mark.parametrize("n_pieces", [1, 2, 7, 64])
    def test_bitwise_np_asarray_of_the_packed_flat(
        self, fetchers, n_pieces, dtype, short_last
    ):
        dt = np.dtype(dtype)
        piece_elems = 96
        # a leaf that is cut into whole pieces, and one that is the last
        shapes = [((n_pieces - 1) * piece_elems,),
                  (piece_elems - (5 if short_last else 0),)]
        leaves = _device_leaves(shapes[n_pieces == 1:], dt)
        plan = bucketing.build_plan(leaves, 1 << 30)
        want = np.asarray(bucketing.pack(leaves, plan)[0][0])
        pieces = bucketing.pack(
            leaves, plan, piece_bytes=piece_elems * dt.itemsize)[0][0]
        sizes = [b - a for a, b in pieces.bounds]
        assert len(sizes) == n_pieces
        if short_last and n_pieces > 1:
            assert sizes[-1] < min(sizes[:-1])
        seen = []
        out = np.full(want.size, 99, dt)
        n = bucketing.fetch_into(
            pieces, out, lambda: seen.append(1), fetchers)
        assert n == n_pieces == len(seen)
        assert out.dtype == want.dtype and out.tobytes() == want.tobytes()
        assert pieces.arrays == [None] * n

    @pytest.mark.parametrize("width", [1, 2, 3, 8])
    def test_after_piece_is_called_once_a_piece_whatever_the_width(
        self, fetchers, monkeypatch, width
    ):
        monkeypatch.setattr(bucketing, "FETCH_WIDTH", width)
        monkeypatch.setattr(
            bucketing.os, "sched_getaffinity", lambda _pid: set(range(13)))
        pieces, flat = _slow_pieces(24)
        out, threads = np.zeros_like(flat), []
        got, busy_s, copy_s = bucketing._fetch(
            pieces, out,
            lambda: threads.append(threading.current_thread().name), fetchers)
        assert got == width and len(threads) == 24
        assert out.tobytes() == flat.tobytes()
        assert pieces.arrays == [None] * 24
        # this thread is one of the fetchers; the others are the pool's
        mine = threading.current_thread().name
        assert 1 <= len(set(threads)) <= width
        assert set(threads) - {mine} <= {t.name for t in fetchers._threads}
        if width > 1:
            assert len(set(threads)) > 1  # 5 ms a piece: the helpers got some
        # 24 waits of 5 ms, however many threads shared them
        assert busy_s >= 24 * 0.005 > copy_s >= 0.0

    def test_the_width_is_capped_by_the_pieces_and_by_the_cores(self, monkeypatch):
        monkeypatch.setattr(
            bucketing.os, "sched_getaffinity", lambda _pid: set(range(13)))
        width = bucketing.FETCH_WIDTH
        assert 1 < width <= 8
        assert [bucketing._fetch_width(n) for n in (1, 2, 64)] == [
            1, 2, width]
        monkeypatch.setattr(
            bucketing.os, "sched_getaffinity", lambda _pid: {0, 5})
        assert [bucketing._fetch_width(n) for n in (1, 2, 64)] == [1, 2, 2]
        monkeypatch.setattr(
            bucketing.os, "sched_getaffinity", lambda _pid: {3})
        assert bucketing._fetch_width(64) == 1

    def test_a_bucket_of_one_piece_is_copied_here_with_no_hand_off(self):
        class NoHandOff:
            def submit(self, *a, **kw):
                raise AssertionError("a lone piece was handed off")

        pieces, flat = _slow_pieces(1, seconds=0.0)
        out = np.zeros_like(flat)
        assert bucketing._fetch(pieces, out, None, NoHandOff())[0] == 1
        assert out.tobytes() == flat.tobytes()

    @pytest.mark.parametrize("raises_on", ["test_fetch", "MainThread"])
    def test_a_fetcher_that_raises_stops_the_others_and_raises_here(
        self, fetchers, raises_on
    ):
        """Whoever takes a piece from the second on fails if it is a helper
        (or: if it is the caller): the exception comes out of fetch_into
        once every fetcher has stopped, and the pieces not yet taken stay."""
        assert threading.current_thread().name == "MainThread"
        pieces, flat = _slow_pieces(48)
        for piece in pieces.arrays[1:]:
            piece.fails_on = raises_on
        out = np.zeros_like(flat)
        with pytest.raises(RuntimeError, match="transfer failed on " + raises_on):
            bucketing.fetch_into(pieces, out, None, fetchers)
        left = [k for k, a in enumerate(pieces.arrays) if a is not None]
        assert left and len(left) < 48
        before = list(pieces.arrays)
        time.sleep(0.05)  # nobody is still at it
        assert pieces.arrays == before


# ---------------------------------------------------------------------------
# the seams of the data plane: stage, land_reduced, BucketPipeline. None of
# these needs a Manager or a lighthouse.


class TestStage:
    def test_device_bucket_is_the_flat_bitwise_and_a_pool_hit_from_the_second_call(self):
        leaves = _device_leaves([(7,), (3, 50), (33,)], np.float32)
        plan = bucketing.build_plan(leaves, 1 << 30)
        want = np.asarray(bucketing.pack(leaves, plan)[0][0])
        pool = bucketing.BufferPool()
        for step, pooled in enumerate([0, 1, 1]):
            captured = bucketing.capture(leaves, plan, pool)
            assert isinstance(captured[0], bucketing.Pieces)
            host_flat, pooled_buf, info = bucketing.stage(captured, plan, 0, pool)
            assert host_flat.tobytes() == want.tobytes()
            assert pooled_buf is host_flat and captured == [None]
            assert info == {"pieces": 1, "pooled": pooled, "bytes": want.nbytes}
            pool.release(pooled_buf)  # what the pipeline does once it landed
        assert (pool.hits, pool.misses) == (2, 1)

    def test_host_group_returns_the_pool_buffer_itself(self):
        leaves = [np.arange(6, dtype=np.float32), np.ones(3, np.float32)]
        plan = bucketing.build_plan(leaves, 1 << 30)
        pool = bucketing.BufferPool()
        captured = bucketing.capture(leaves, plan, pool)
        packed = captured[0]
        assert (pool.hits, pool.misses) == (0, 1)
        host_flat, pooled_buf, info = bucketing.stage(captured, plan, 0, pool)
        assert host_flat is packed and pooled_buf is packed
        assert info == {"bytes": 36} and (pool.hits, pool.misses) == (0, 1)
        assert np.array_equal(host_flat, np.concatenate(leaves))

    def test_non_participant_is_zeros_and_takes_nothing_from_the_pool(self):
        import ml_dtypes

        leaves = [np.ones((2, 3), ml_dtypes.bfloat16), np.ones(5, np.float32)]
        plan = bucketing.build_plan(leaves, 1 << 30)
        pool = bucketing.BufferPool()
        for i, (size, dtype) in enumerate(zip(plan.sizes, plan.dtypes)):
            host_flat, pooled_buf, info = bucketing.stage(None, plan, i, pool)
            assert host_flat.shape == (size,) and host_flat.dtype == dtype
            assert not host_flat.any() and pooled_buf is None and info == {}
        assert (pool.hits, pool.misses) == (0, 0)


class TestLandReduced:
    """The landing alone, against numpy: one reduced flat back to leaves
    placed where the originals live and divided there."""

    @pytest.mark.parametrize("kind", ["jax", "numpy", "mixed"])
    def test_a_bucket_and_a_lone_leaf_against_numpy(self, kind):
        rng = np.random.RandomState(2)
        host = [(rng.randn(4, 6) * 3).astype(np.float32) for _ in range(3)]
        leaves = [
            jnp.asarray(h) if kind == "jax" or (kind == "mixed" and i == 1)
            else h
            for i, h in enumerate(host)
        ]
        plan = bucketing.build_plan(leaves, 1 << 30)
        place = bucketing.leaf_placer()
        seen = []

        @contextlib.contextmanager
        def span(name, **args):
            seen.append((name, args.get("where")))
            yield

        for bucket, flat in enumerate(bucketing.pack(leaves, plan)[0]):
            pairs = bucketing.land_reduced(
                np.asarray(flat), leaves, plan, bucket, 3, place, span
            )
            for i, got in pairs:
                assert type(got) is type(leaves[i]), (kind, i)
                assert got.shape == (4, 6) and got.dtype == np.float32
                assert np.array_equal(np.asarray(got), host[i] / np.float32(3))
        # the no-plan path: leaf 2 alone, a plain SUM (no divisor, no divide)
        [(i, got)] = bucketing.land_reduced(
            host[2].copy(), leaves, None, 2, None, place
        )
        assert i == 2 and type(got) is type(leaves[2])
        assert np.array_equal(np.asarray(got), host[2])
        assert [n for n, _ in seen] == ["h2d", "divide"] * len(plan)
        assert {w for n, w in seen if n == "divide"} == {
            {"jax": "device", "numpy": "host", "mixed": "mixed"}[kind]
        }


class TestPipelineWithoutAManager:
    def test_three_buckets_with_a_pg_a_tracer_and_a_pool(self):
        from torchft_tpu.tracing import SpanRecorder, TraceConfig

        leaves = _device_leaves([(40,), (40,), (36,), (36,), (32,), (32,)],
                                np.float32)
        plan = bucketing.build_plan(leaves, 2 * 40 * 4)
        tracer = SpanRecorder("no-manager", TraceConfig(enabled=True))
        pool, stats = bucketing.BufferPool(), {}
        pipeline = bucketing.BucketPipeline(
            CopyingPG(), tracer, pool, stats.update
        )
        try:
            for step in range(2):
                pipeline.begin_step()
                op = pipeline.allreduce_buckets(
                    leaves, plan, ReduceOp.SUM, participating=True,
                    divisor=2, place=bucketing.leaf_placer(), timeout=30.0,
                    parent=None,
                )
                out = op.final.wait(30)
                assert all(f.done() for f in op.bucket_futs)
                pipeline.record_timings(op)
        finally:
            pipeline.shutdown(wait=True)
        for leaf, got in zip(leaves, out):
            assert isinstance(got, jax.Array)
            assert np.array_equal(np.asarray(got), np.asarray(leaf) / 2)
        assert stats["stage_pool_hit_share"] == 1.0
        assert stats["allreduce_buckets"] == 3.0
        assert (pool.hits, pool.misses) == (3, 3)
        names = [s["name"] for s in tracer.export()["spans"]]
        for name, count in {"capture": 2, "grad_wait": 6, "d2h": 6,
                            "dispatch": 6, "h2d": 6, "divide": 6,
                            "pack": 6, "wire": 6, "unpack": 6}.items():
            assert names.count(name) == count, (name, names)

    @pytest.mark.parametrize("raises_on", ["torchft_fetch", "torchft_stage"])
    def test_a_fetcher_that_raises_fails_every_bucket_future_of_the_op(
        self, monkeypatch, raises_on
    ):
        """A piece of the op's first bucket fails on a fetcher thread (or on
        the staging thread, which is one): every bucket future of the op
        and its final fail with that exception, nothing was dispatched, the
        buffer drawn for the bucket does not come back to the pool, and
        shutdown leaves none of the pipeline's threads behind."""
        from torchft_tpu.tracing import SpanRecorder, TraceConfig

        leaves = _device_leaves([(40,), (40,), (36,), (36,), (32,), (32,)],
                                np.float32)
        plan = bucketing.build_plan(leaves, 2 * 40 * 4)
        monkeypatch.setattr(bucketing, "FETCH_PIECE_BYTES", 4 * 4)
        real = bucketing.capture

        def capture(leaves, plan, pool):
            captured = real(leaves, plan, pool)
            first = captured[0]
            assert len(first.arrays) == 20
            first.arrays[:] = [
                _HostPiece(np.array(a), 0.005, raises_on if k else None)
                for k, a in enumerate(first.arrays)]
            return captured

        monkeypatch.setattr(bucketing, "capture", capture)
        pg, pool = CopyingPG(), bucketing.BufferPool()
        pipeline = bucketing.BucketPipeline(
            pg, SpanRecorder("x", TraceConfig(enabled=False)), pool)
        try:
            pipeline.begin_step()
            op = pipeline.allreduce_buckets(
                leaves, plan, ReduceOp.SUM, participating=True, divisor=2,
                place=bucketing.leaf_placer(), timeout=30.0)
            with pytest.raises(RuntimeError, match="transfer failed on " + raises_on):
                op.final.wait(30)
            for fut in op.bucket_futs:  # (the first to fail failed final)
                with pytest.raises(RuntimeError, match="transfer failed"):
                    fut.wait(5)
            assert pg.inputs == []
            assert (pool.hits, pool.misses) == (0, 1) and not any(
                pool._free.values())
        finally:
            pipeline.shutdown(wait=True)
        executors = (pipeline._staging_executor, pipeline._fetch_executor,
                     pipeline._unpack_executor)
        assert pipeline._fetch_executor._threads  # the helpers did run
        assert not any(t.is_alive() for e in executors for t in e._threads)

    @pytest.mark.parametrize("path", ["pipeline", "no_plan"])
    def test_one_submit_arms_the_backstop_for_either_path(self, path):
        """An op ahead wedges its stage forever: the stage of the op behind
        it never runs, so its own deadline is never armed, and the backstop
        of the one submit() fails it within (depth + 2) * timeout."""
        import threading
        import time

        from torchft_tpu.tracing import SpanRecorder, TraceConfig
        from torchft_tpu.work import Future

        timeout = 0.3
        pipeline = bucketing.BucketPipeline(
            CopyingPG(), SpanRecorder("x", TraceConfig(enabled=False)),
            bucketing.BufferPool(),
        )
        wedge, ahead = threading.Event(), Future()
        leaves = [np.ones(8, np.float32), np.ones(8, np.float32)]
        kw = dict(participating=True, divisor=None,
                  place=bucketing.leaf_placer(), timeout=timeout)
        try:
            pipeline.submit(lambda: wedge.wait(60), ahead, timeout)
            t0 = time.monotonic()
            if path == "pipeline":
                behind = pipeline.allreduce_buckets(
                    leaves, bucketing.build_plan(leaves, 32), ReduceOp.SUM,
                    **kw,
                ).final
            else:
                behind = pipeline.allreduce_leaves(
                    leaves, ReduceOp.SUM, quantize=False, **kw
                )
            # the op ahead is still pending at this submit: depth 1
            with pytest.raises(TimeoutError, match="staging timed out"):
                behind.wait(30)
            took = time.monotonic() - t0
            assert 3 * timeout * 0.9 <= took <= 3 * timeout + 2.0, took
            with pytest.raises(TimeoutError, match="staging timed out"):
                ahead.wait(5)  # its own stage-start deadline
        finally:
            wedge.set()
            pipeline.shutdown(wait=False)


def test_glibc_is_told_to_keep_the_heap_through_a_trim(monkeypatch):
    """mmap threshold 32 MiB, trim threshold and top pad both the largest a
    C int holds: a step that frees more than 2 GiB of pieces (Mistral-7B at
    four layers) still keeps 2 GiB of them mapped (PERF.md section 6,
    PR 34); no libc.so.6, or one without mallopt: False and nothing set."""
    import ctypes

    calls = []

    class Libc:
        class mallopt:  # noqa: N801 — ctypes' function object, by attribute
            argtypes = restype = None

            def __new__(cls, param, value):
                calls.append((param, value))
                return 1

    monkeypatch.setattr(ctypes, "CDLL", lambda name: Libc)
    assert bucketing._keep_freed_blocks_mapped.__wrapped__() is True
    assert calls == [(-3, 32 << 20), (-1, 2**31 - 1), (-2, 2**31 - 1)]

    def missing(name):
        raise OSError(name)

    monkeypatch.setattr(ctypes, "CDLL", missing)
    assert bucketing._keep_freed_blocks_mapped.__wrapped__() is False


# ---------------------------------------------------------------------------
# a host-plane op over device leaves moves in RUNS of whole leaves: its plan
# is cut at bucketing.RUN_BYTES, and a run lands while the next is fetched

_RUN = 1024  # bytes: RUN_BYTES for these tests


def _run_leaves():
    """bf16 leaves with one above a run and a float32 leaf among them, in
    leaf order: 600 B, 2,400 B (above a run: its own), 4 x 400 B, a float32
    of 200 B, 2 x 400 B."""
    bf16 = _device_leaves(
        [(300,), (1200,), (200,), (200,), (200,), (200,), (200,), (200,)],
        jnp.bfloat16)
    f32 = _device_leaves([(50,)], np.float32, seed=5)
    return bf16[:6] + f32 + bf16[6:]


def _pipeline(pg=None, tracer=None, stats=None):
    from torchft_tpu.tracing import SpanRecorder, TraceConfig

    return bucketing.BucketPipeline(
        pg or CopyingPG(),
        tracer or SpanRecorder("runs", TraceConfig(enabled=False)),
        bucketing.BufferPool(),
        (stats if stats is not None else {}).update,
    )


def _through(pipeline, leaves, plan, pg_op=ReduceOp.SUM, divisor=None,
             place=None):
    pipeline.begin_step()
    op = pipeline.allreduce_buckets(
        leaves, plan, pg_op, participating=True, divisor=divisor,
        place=place or bucketing.leaf_placer(), timeout=30.0)
    try:
        return op, op.final.wait(30)
    finally:
        pipeline.record_timings(op)


class TestRuns:
    def test_the_cap_of_a_device_op_over_a_run_is_the_run(self, monkeypatch):
        monkeypatch.setattr(bucketing, "RUN_BYTES", _RUN)
        leaves = _run_leaves()
        assert bucketing.run_cap(leaves, 1 << 30) == _RUN
        assert bucketing.run_cap(leaves, 512) == 512  # "at most this" stands
        assert bucketing.run_cap(leaves[2:4], 1 << 30) == 1 << 30  # under a run
        host = [np.asarray(l) for l in leaves]
        assert bucketing.run_cap(host, 1 << 30) == 1 << 30
        assert bucketing.run_cap(leaves[:3] + host[3:], 1 << 30) == 1 << 30
        plan = bucketing.build_plan(leaves, bucketing.run_cap(leaves, 1 << 30))
        # whole leaves of one dtype, in leaf order; the large one by itself
        assert plan.groups == [[0], [1], [2, 3], [4, 5], [7, 8], [6]]

    @pytest.mark.parametrize("pg_op,divisor", [
        (ReduceOp.SUM, None), (ReduceOp.SUM, 1), (ReduceOp.SUM, 4)],
        ids=["sum", "avg_of_1", "avg_of_4"])
    def test_runs_land_bit_for_bit_what_one_bucket_lands(
        self, monkeypatch, pg_op, divisor
    ):
        monkeypatch.setattr(bucketing, "RUN_BYTES", _RUN)
        leaves = _run_leaves()
        whole = bucketing.build_plan(leaves, 1 << 30)
        runs = bucketing.build_plan(leaves, bucketing.run_cap(leaves, 1 << 30))
        assert (len(whole), len(runs)) == (2, 6)
        pipeline = _pipeline()
        try:
            _, one = _through(pipeline, leaves, whole, pg_op, divisor)
            _, many = _through(pipeline, leaves, runs, pg_op, divisor)
        finally:
            pipeline.shutdown(wait=True)
        for leaf, a, b in zip(leaves, one, many):
            assert isinstance(b, jax.Array) and b.dtype == leaf.dtype
            assert b.shape == leaf.shape
            assert np.array_equal(np.asarray(a), np.asarray(b))
            want = np.asarray(leaf)
            if divisor:
                want = (want / divisor).astype(want.dtype)
            assert np.array_equal(np.asarray(b), want)

    def test_a_run_lands_while_the_next_is_fetched_and_the_op_resolves_once(
        self, monkeypatch
    ):
        """Run k's landing is handed to the unpack worker before run k+1's
        fetch returns (both on the staging thread behind a PG that resolves
        at dispatch), and the op's future resolves once, after the last
        run has landed, with the leaves in leaf order."""
        monkeypatch.setattr(bucketing, "RUN_BYTES", _RUN)
        leaves = _run_leaves()
        plan = bucketing.build_plan(leaves, bucketing.run_cap(leaves, 1 << 30))
        log, lock = [], threading.Lock()

        def note(*event):
            with lock:
                log.append(event)

        real_stage = bucketing.stage

        def stage(captured, plan, i, *args, **kwargs):
            out = real_stage(captured, plan, i, *args, **kwargs)
            note("fetched", i)
            return out

        monkeypatch.setattr(bucketing, "stage", stage)
        pipeline = _pipeline(pg=ProcessGroupDummy())
        real_submit = pipeline._unpack_executor.submit

        def submit(fn, op, i, *args):
            note("landing_submitted", i)
            return real_submit(fn, op, i, *args)

        pipeline._unpack_executor.submit = submit
        real_place = bucketing.leaf_placer()

        def place(orig, host):
            note("placed", None)
            return real_place(orig, host)

        try:
            pipeline.begin_step()
            op = pipeline.allreduce_buckets(
                leaves, plan, ReduceOp.SUM, participating=True, divisor=None,
                place=place, timeout=30.0)
            op.final.add_done_callback(
                lambda f: note("resolved", len(f.value())))
            out = op.final.wait(30)
        finally:
            pipeline.shutdown(wait=True)
        n = len(plan)
        for k in range(n - 1):
            assert log.index(("landing_submitted", k)) < log.index(
                ("fetched", k + 1)), log
        assert [e for e in log if e[0] == "resolved"] == [("resolved", 9)]
        # every leaf was placed before the one resolve
        assert log.index(("resolved", 9)) > max(
            j for j, e in enumerate(log) if e[0] == "placed")
        assert sum(e[0] == "placed" for e in log) == 9
        for leaf, got in zip(leaves, out):
            assert got.shape == leaf.shape and got.dtype == leaf.dtype
            assert np.array_equal(np.asarray(got), np.asarray(leaf))

    def test_a_middle_run_that_fails_fails_the_op_and_hands_out_zeros(
        self, monkeypatch
    ):
        """The third run's collective fails: the aggregate fails with it,
        the Manager hands out the zeros of its error path and no leaf of
        the runs that did land, and the step will not commit."""
        from torchft_tpu.work import FutureWork, Future as TftFuture

        monkeypatch.setattr(bucketing, "RUN_BYTES", _RUN)
        monkeypatch.delenv("TORCHFT_BUCKET_CAP_MB", raising=False)

        class FailsThird(CopyingPG):
            def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
                if len(self.inputs) == 2:
                    self.inputs.extend(arrays)
                    fut = TftFuture()
                    fut.set_exception(ConnectionError("run 2 lost its peer"))
                    return FutureWork(fut)
                return super().allreduce(arrays, op, donate=donate)

        leaves = _run_leaves()
        tree = {f"l{i}": leaf for i, leaf in enumerate(leaves)}
        pg = FailsThird()
        m = make_manager(pg=pg, quorum=make_quorum())
        try:
            m.start_quorum()
            out = m.allreduce(tree, reduce_op=ReduceOp.SUM).get_future().wait(30)
            assert m.errored() is not None
            assert len(pg.inputs) >= 3  # two runs went through before it
            for i, leaf in enumerate(leaves):
                got = out[f"l{i}"]
                assert got.shape == leaf.shape and got.dtype == leaf.dtype
                assert not np.asarray(got).any(), f"leaf {i} landed"
        finally:
            m.shutdown(wait=False)

    @pytest.mark.parametrize("who", [
        "numpy_tree", "device_native_pg", "compressed_wire", "one_leaf",
        "callers_own_cap"])
    def test_everything_else_gets_the_plan_it_got(self, monkeypatch, who):
        """Only a host-plane op over device leaves is cut at a run: a numpy
        tree, a device-native PG, the compressed wire, a caller's own cap
        for the call (ddp.py) keep the plan of the cap, and a lone leaf has
        none (``allreduce_leaves``)."""
        monkeypatch.setattr(bucketing, "RUN_BYTES", _RUN)
        monkeypatch.delenv("TORCHFT_BUCKET_CAP_MB", raising=False)
        monkeypatch.delenv("TORCHFT_COMPRESS", raising=False)
        caps = []
        real = bucketing.plan_for

        def plan_for(leaves, cap, treedef=None):
            caps.append(cap)
            return real(leaves, cap, treedef=treedef)

        monkeypatch.setattr(bucketing, "plan_for", plan_for)
        bf16 = _run_leaves()[:6]
        tree = {f"l{i}": leaf for i, leaf in enumerate(bf16)}
        kwargs, call = {}, {}
        pg = CountingPG()
        if who == "numpy_tree":
            tree = {k: np.asarray(v, np.float32) for k, v in tree.items()}
        elif who == "device_native_pg":
            pg.device_native = True
        elif who == "compressed_wire":
            kwargs["compress"] = "fp8"
        elif who == "one_leaf":
            tree = {"l1": bf16[1]}
        elif who == "callers_own_cap":
            call["bucket_cap_bytes"] = 1 << 29
        m = make_manager(pg=pg, quorum=make_quorum(), **kwargs)
        try:
            m.start_quorum()
            m.allreduce_streamed(tree, reduce_op=ReduceOp.SUM, **call).wait()
            timings = m.timings()
        finally:
            m.shutdown(wait=False)
        if who == "one_leaf":
            assert caps == [] and timings["allreduce_runs"] == 1.0
            return
        assert caps == [call.get("bucket_cap_bytes", 1 << 30)]
        # 4,400 bytes of bf16 under a cap of a gigabyte: one bucket
        assert pg.allreduce_calls == [1]
        assert timings["allreduce_runs"] == 1.0

    def test_a_device_tree_on_the_host_plane_is_cut_at_the_run(self, monkeypatch):
        monkeypatch.setattr(bucketing, "RUN_BYTES", _RUN)
        monkeypatch.delenv("TORCHFT_BUCKET_CAP_MB", raising=False)
        leaves = _run_leaves()
        tree = {f"l{i}": leaf for i, leaf in enumerate(leaves)}
        pg = CopyingPG()
        m = make_manager(pg=pg, quorum=make_quorum())
        try:
            for _ in range(2):
                m.start_quorum()
                out = m.allreduce(tree, reduce_op=ReduceOp.SUM).get_future().wait(30)
                m.should_commit()
            timings = m.timings()
        finally:
            m.shutdown(wait=False)
        assert [a.nbytes for a in pg.inputs] == [600, 2400, 800, 800, 800, 200] * 2
        assert timings["allreduce_ops"] == 1.0
        assert timings["allreduce_runs"] == 6.0
        assert timings["stage_pool_hit_share"] == 1.0
        for i, leaf in enumerate(leaves):
            assert np.array_equal(np.asarray(out[f"l{i}"]), np.asarray(leaf))

    def test_land_under_fetch_share(self, monkeypatch):
        """0.0 for an op of one run; in (0, 1] for an op of several whose
        fetches and whose placer take their time, so that a run's landing
        is still placing leaves while the next run's pieces arrive."""
        monkeypatch.setattr(bucketing, "RUN_BYTES", _RUN)
        monkeypatch.setattr(bucketing, "FETCH_PIECE_BYTES", 200)
        leaves = _run_leaves()
        real_capture = bucketing.capture

        def capture(leaves, plan, pool):
            captured = real_capture(leaves, plan, pool)
            for cap in captured:
                cap.arrays[:] = [
                    _HostPiece(np.array(a), 0.004) for a in cap.arrays]
            return captured

        monkeypatch.setattr(bucketing, "capture", capture)
        real_place = bucketing.leaf_placer()

        def place(orig, host):
            time.sleep(0.01)
            return real_place(orig, host)

        stats = {}
        pipeline = _pipeline(stats=stats)
        try:
            whole = bucketing.build_plan(leaves[2:4], 1 << 30)
            _through(pipeline, leaves[2:4], whole, place=place)
            assert stats["allreduce_runs"] == 1.0
            assert stats["land_under_fetch_share"] == 0.0
            runs = bucketing.build_plan(
                leaves, bucketing.run_cap(leaves, 1 << 30))
            _through(pipeline, leaves, runs, place=place)
        finally:
            pipeline.shutdown(wait=True)
        assert stats["allreduce_runs"] == 6.0
        assert 0.0 < stats["land_under_fetch_share"] <= 1.0

    def test_a_step_that_draws_many_equal_runs_finds_them_all_again(self):
        """Six equal runs drawn before any comes back: the pool keeps six
        free of that key from then on, where its own bound is four."""
        pool = bucketing.BufferPool()
        for n in range(1, 7):
            pool.keep_at_least(100, np.float32, n)
        bufs = [pool.acquire(100, np.float32) for _ in range(6)]
        others = [pool.acquire(50, np.float32) for _ in range(6)]
        for b in bufs + others:
            pool.release(b)
        assert len(pool._free[(np.dtype(np.float32).str, 100)]) == 6
        assert len(pool._free[(np.dtype(np.float32).str, 50)]) == 4


def test_two_groups_on_the_host_ring_cut_the_same_runs(
    world_of_two, monkeypatch
):
    """A world of two on the real host ring: both groups cut the same runs
    from the same tree and end with the same bits, the sum of the two."""
    from concurrent.futures import ThreadPoolExecutor

    monkeypatch.setattr(bucketing, "RUN_BYTES", _RUN)
    monkeypatch.delenv("TORCHFT_BUCKET_CAP_MB", raising=False)
    ms = [world_of_two(rank) for rank in range(2)]  # a cap of 160,000

    def tree(rank):
        return {f"l{i}": leaf * (rank + 1)
                for i, leaf in enumerate(_run_leaves())}

    def run(rank):
        m = ms[rank]
        m.start_quorum()
        out = m.allreduce(tree(rank), reduce_op=ReduceOp.SUM).get_future().wait(30)
        m.should_commit()
        return out, m.timings()["allreduce_runs"]

    try:
        with ThreadPoolExecutor(2) as ex:
            (a, runs_a), (b, runs_b) = ex.map(run, range(2))
    finally:
        for m in ms:
            m.shutdown(wait=False)
    assert runs_a == runs_b == 6.0
    t0, t1 = tree(0), tree(1)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k
        assert np.array_equal(np.asarray(a[k]), np.asarray(t0[k] + t1[k])), k
