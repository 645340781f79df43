"""Shared pytree bucketing, and the managed data plane built on it.

One bucketing implementation for every consumer — ``Manager.allreduce``,
``ddp.PureDistributedDataParallel``, and DiLoCo's fragment sync
(local_sgd.py) — so a pytree of hundreds of leaves becomes a handful of
flat same-dtype collectives on both the host ring and the XLA plane.
Fewer, larger collectives amortize the per-op framing/pickling overhead of
the host DCN plane — the same motivation as the reference's bucketized
allreduce (local_sgd.py:498-566), minus the NCCL-launch angle which does
not exist on TPU.

``Manager.allreduce`` keeps the state machine (quorum, participants, the
error policy) and hands the data to :class:`BucketPipeline`, which takes a
process group, a span recorder and a pool, and no Manager. Three decisions,
one home each:

- *the staging format*: :func:`capture` (the caller's thread) and
  :func:`stage` (the staging thread): device bucket → :class:`Pieces` → pool
  buffer; host group → pool buffer; non-participant → zeros of the plan's
  size and dtype. Nothing outside this module names a piece.
- *the landing*: :func:`land_reduced` beside :func:`unpack_bucket`: slice,
  place where the leaf lives (:func:`leaf_placer`), divide there. The
  pipeline's buckets and the no-plan path land through it alike.
- *the schedule*: :class:`BucketPipeline`: which thread runs which stage,
  the per-bucket futures and marks, the stage-start deadline and the
  depth-aware backstop (one :meth:`BucketPipeline.submit`), the shutdown
  sweep, when a pool buffer goes back.

Underneath, these pieces keep the steady-state step allocation-free:

- :func:`plan_for` — a cached flatten plan (:class:`BucketPlan`): bucket
  membership and unpack metadata are a pure function of the tree structure
  and the leaves' shapes/dtypes, so they are computed once per (treedef,
  leaf-spec, cap) and memoized. A training loop that allreduces the same
  gradient tree every step pays the grouping cost exactly once.
- :class:`BufferPool` — reusable host staging buffers keyed by
  (dtype, size). Host-plane packs write into a recycled buffer instead of
  allocating a gradient-sized array per step.
- :func:`pack` / :func:`unpack` — bucket materialization. Groups whose
  leaves are all ``jax.Array`` pack on device (one fused concatenate, async
  dispatch, no host round-trip — and the fresh buffer doubles as the
  donation-safe capture the Manager's staging path needs); any other group
  packs into a (pooled) numpy buffer.
- :class:`Pieces` / :func:`fetch_into` — the host plane's capture of a device
  group: the same bytes as the flat, cut on the device into pieces of at most
  ``FETCH_PIECE_BYTES`` whose transfers start at the capture, and copied
  piece by piece into a pooled host buffer whose pages are mapped.

Bucketing is bitwise-transparent: an allreduce is elementwise across
replicas, so packing leaves into flat buffers changes neither the reduction
order per element nor the dtype — the DiLoCo regression fixtures stay
bitwise green with it on or off.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor, wait as wait_all
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.futures import arm_deadline
from torchft_tpu.ops.quantization import (
    compress_bucket,
    decompress_bucket,
    is_compressed_wire,
)
from torchft_tpu.work import Future, join_futures

__all__ = [
    "DEFAULT_BUCKET_CAP_BYTES",
    "SEGMENT_FLOOR_BYTES",
    "RUN_BYTES",
    "run_cap",
    "BucketPlan",
    "BufferPool",
    "build_plan",
    "plan_for",
    "leaf_dtype",
    "is_float_dtype",
    "pack",
    "Pieces",
    "fetch_into",
    "FETCH_PIECE_BYTES",
    "FETCH_WIDTH",
    "capture",
    "stage",
    "unpack",
    "unpack_bucket",
    "leaf_placer",
    "land_reduced",
    "BucketPipeline",
    "make_buckets",
    "pack_group",
    "unpack_buckets",
]

# 1 GiB default bucket cap (reference: local_sgd.py:176)
DEFAULT_BUCKET_CAP_BYTES = 1 << 30

# The least gradient bytes a trainer hands ONE allreduce of a step's several
# (models/staged.py: a segment of whole layers an op). Each op pays a fixed
# toll beside its fetch: the capture's dispatch 3-10 ms, the landing's
# enqueue 9-18 ms a bucket (PERF.md section 5). At the 10-11 GB/s a fetch
# runs at on four fetchers (6-9 when this was set, on one: PR 36), 128 MiB
# is 12-13 ms of fetch: under it the tolls are the op. A constant and no
# option: a segment is whole layers, so most are several times this.
SEGMENT_FLOOR_BYTES = 1 << 27

# The most bytes of a host-plane op over device leaves that move as ONE run
# (run_cap): the op's plan is cut at this, and run k rides the wire and lands
# (H2D and the division, unpack worker) while run k+1 is fetched (staging
# thread and fetchers), where one bucket of 0.63-0.70 GB was fetched whole
# (60-70 ms) and then landed whole (55-75 ms) with nothing to hide the step's
# last op under. Reasoned like the floor above, against a run's tolls: one
# capture dispatch (1-2 ms on the caller's thread), one collective, one
# landing dispatch on the device's queue (_average_on_device), against
# 12-13 ms of fetch at 128 MiB. What cannot hide is the first run's fetch
# and the last run's landing, so smaller is better until the tolls are the
# run. TPU v5e, one traced run each, tokens/s/chip against one bucket an op
# (PERF.md section 6, PR 50): 128 MiB +7.3% / +4.0% / +10.8% (Mistral-7B at
# four layers, InternLM2-1.8B at two, OLMoE-1B-7B at two), 256 MiB +6.0% /
# +1.4% / +10.1%; under the per-leaf landing that stood before, 64 MiB read
# -3.7% on Mistral (34 runs a step, a capture of 0.135 s) where 128 read
# -0.6%. At a world of four a ring segment is a quarter of a run and rides
# process_group._RING_LANES only from _RING_LANE_FLOOR_BYTES = 32 MiB: a
# full run of 128 MiB is the least that keeps them, and the runs a greedy
# cut leaves short of that ride one lane (the four-group cell read the same
# rate at 128, at 256 and uncut: PERF.md section 7, PR 50). A constant and
# no option: TORCHFT_BUCKET_CAP_MB under it still means "at most this".
RUN_BYTES = 1 << 27

# metas entry: (leaf_index, offset_elems, size_elems, shape)
Meta = Tuple[int, int, int, Tuple[int, ...]]


def leaf_dtype(leaf: Any) -> np.dtype:
    """Leaf dtype without forcing a device→host transfer (jax.Array and
    ml_dtypes dtypes pass through np.dtype unchanged)."""
    dt = getattr(leaf, "dtype", None)
    if dt is not None:
        return np.dtype(dt)
    return np.asarray(leaf).dtype


def _leaf_size(leaf: Any) -> int:
    size = getattr(leaf, "size", None)
    if size is not None:
        return int(size)
    return int(np.asarray(leaf).size)


def leaf_stand_in(leaf: Any) -> Any:
    """What an op in flight keeps of a leaf it has CAPTURED: for a device
    leaf its shape, dtype and sharding (all that a landing, or the zeros of
    the error path, reads of the original), so that the leaf's device memory
    is the caller's alone to drop; a numpy leaf as it is."""
    import jax

    if isinstance(leaf, jax.Array):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=leaf.sharding)
    return leaf


def lives_on_device(leaf: Any) -> bool:
    """A device leaf, or the :func:`leaf_stand_in` of one."""
    import jax

    return isinstance(leaf, (jax.Array, jax.ShapeDtypeStruct))


def _leaf_shape(leaf: Any) -> Tuple[int, ...]:
    shape = getattr(leaf, "shape", None)
    if shape is not None:
        return tuple(shape)
    return tuple(np.shape(leaf))


def run_cap(leaves: Sequence[Any], cap_bytes: int) -> int:
    """The cap at which the plan of a HOST-PLANE op over ``leaves`` is cut:
    ``RUN_BYTES`` where every leaf lives on a device and together they are
    more than one run, never above ``cap_bytes`` (whoever set a cap still
    gets "at most this"); ``cap_bytes`` as it is for anything else (a host
    tree is packed at the capture and has no fetch to land under). The
    plan's buckets are then the op's runs, and the pipeline's three threads
    do the rest: :class:`BucketPipeline`."""
    import jax

    if cap_bytes <= RUN_BYTES or not leaves:
        return cap_bytes
    nbytes = 0
    for leaf in leaves:
        if not isinstance(leaf, jax.Array):
            return cap_bytes
        nbytes += leaf.size * leaf.dtype.itemsize
    return RUN_BYTES if nbytes > RUN_BYTES else cap_bytes


class BucketPlan:
    """Bucket membership + unpack metadata for one leaf list.

    A plan is a pure function of the leaves' (shape, dtype) sequence and the
    cap — it holds no array data, so one plan serves every step of a
    training loop over the same tree.
    """

    # __weakref__ lets the pipeline key per-bucket error-feedback residuals
    # by plan identity (WeakKeyDictionary): residuals die with the plan when
    # the plan cache evicts, instead of leaking per-tree forever
    __slots__ = (
        "groups", "metas", "sizes", "dtypes", "num_leaves", "cap_bytes",
        "__weakref__",
    )

    def __init__(
        self,
        groups: List[List[int]],
        metas: List[List[Meta]],
        sizes: List[int],
        dtypes: List[np.dtype],
        num_leaves: int,
        cap_bytes: int,
    ) -> None:
        self.groups = groups
        self.metas = metas
        self.sizes = sizes  # flat element count per bucket
        self.dtypes = dtypes  # dtype per bucket
        self.num_leaves = num_leaves
        self.cap_bytes = cap_bytes

    def __len__(self) -> int:
        return len(self.groups)


def build_plan(leaves: Sequence[Any], cap_bytes: int) -> BucketPlan:
    """Group leaf indices into flat same-dtype buckets of at most
    ``cap_bytes`` (a single leaf above the cap gets its own bucket)."""
    by_dtype: Dict[np.dtype, List[int]] = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(leaf_dtype(leaf), []).append(i)
    groups: List[List[int]] = []
    dtypes: List[np.dtype] = []
    for dtype, idxs in by_dtype.items():
        itemsize = dtype.itemsize
        cur: List[int] = []
        cur_bytes = 0
        for i in idxs:
            nbytes = _leaf_size(leaves[i]) * itemsize
            if cur and cur_bytes + nbytes > cap_bytes:
                groups.append(cur)
                dtypes.append(dtype)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nbytes
        if cur:
            groups.append(cur)
            dtypes.append(dtype)
    metas: List[List[Meta]] = []
    sizes: List[int] = []
    for g in groups:
        offset = 0
        group_metas: List[Meta] = []
        for i in g:
            size = _leaf_size(leaves[i])
            group_metas.append((i, offset, size, _leaf_shape(leaves[i])))
            offset += size
        metas.append(group_metas)
        sizes.append(offset)
    return BucketPlan(groups, metas, sizes, dtypes, len(leaves), cap_bytes)


# plan cache: key -> BucketPlan. Bounded by wholesale clear — a trainer
# touches a handful of distinct trees, and rebuilding a plan is cheap; the
# cache exists to take the O(leaves) grouping off EVERY step, not to be an
# LRU.
_plan_cache: Dict[Any, BucketPlan] = {}
_plan_cache_lock = threading.Lock()
_PLAN_CACHE_MAX = 128


def plan_for(
    leaves: Sequence[Any], cap_bytes: int, treedef: Any = None
) -> BucketPlan:
    """Memoized :func:`build_plan`, keyed by (treedef, leaf specs, cap).

    ``treedef`` (hashable, from ``jax.tree_util.tree_flatten``) keys the
    tree identity; the (shape, dtype) spec guards against a same-structure
    tree with different leaf geometry sharing a plan.
    """
    try:
        spec = tuple((str(leaf_dtype(l)), _leaf_shape(l)) for l in leaves)
        key = (treedef, spec, cap_bytes)
        with _plan_cache_lock:
            plan = _plan_cache.get(key)
        if plan is not None:
            return plan
    except TypeError:  # unhashable treedef — build uncached
        return build_plan(leaves, cap_bytes)
    plan = build_plan(leaves, cap_bytes)
    with _plan_cache_lock:
        if len(_plan_cache) >= _PLAN_CACHE_MAX:
            _plan_cache.clear()
        _plan_cache[key] = plan
    return plan


class BufferPool:
    """Reusable 1-D host staging buffers keyed by (dtype, size).

    ``acquire`` returns a recycled buffer when one is free, else allocates;
    ``release`` returns a buffer for reuse. The pool caps how many buffers
    it retains per key so a one-off giant tree can't pin memory forever.
    Thread-safe: acquire/release may run on the train loop and the
    Manager's staging worker concurrently.
    """

    def __init__(self, max_per_key: int = 4) -> None:
        self._lock = threading.Lock()
        self._free: Dict[Tuple[str, int], List[np.ndarray]] = {}
        self._max_per_key = max_per_key
        # keys of which a caller draws more at a time (keep_at_least)
        self._room: Dict[Tuple[str, int], int] = {}
        self.hits = 0
        self.misses = 0

    def keep_at_least(self, size: int, dtype: Any, n: int) -> None:
        """Retain up to ``n`` free buffers of this (dtype, size) where that
        is more than the pool's own bound: whoever draws ``n`` of one key
        before giving any back (a step's equal runs, parked while their
        landings read them) finds them again at its next round."""
        if n > self._max_per_key:
            key = (np.dtype(dtype).str, int(size))
            with self._lock:
                if n > self._room.get(key, 0):
                    self._room[key] = n

    def acquire(self, size: int, dtype: Any) -> np.ndarray:
        return self.acquire_hit(size, dtype)[0]

    def acquire_hit(self, size: int, dtype: Any) -> Tuple[np.ndarray, bool]:
        """:meth:`acquire`, and whether the buffer is a recycled one (its
        pages written before) rather than a new allocation."""
        dtype = np.dtype(dtype)
        key = (dtype.str, int(size))
        with self._lock:
            bucket = self._free.get(key)
            if bucket:
                self.hits += 1
                return bucket.pop(), True
            self.misses += 1
        return np.empty(int(size), dtype=dtype), False

    def release(self, buf: np.ndarray) -> None:
        if not isinstance(buf, np.ndarray) or buf.ndim != 1:
            return
        key = (buf.dtype.str, buf.shape[0])
        with self._lock:
            bucket = self._free.setdefault(key, [])
            if len(bucket) < self._room.get(key, self._max_per_key):
                bucket.append(buf)


# A device bucket reaches the host as pieces of at most this many bytes,
# every piece's transfer issued at the capture. TPU v5e, a 0.973 GB bf16
# bucket into a buffer written before, GB/s (chip runs, PR 29; np.asarray of
# the whole bucket: 0.63): with freed blocks kept mapped
# (_keep_freed_blocks_mapped) 8 MiB 8.4-8.6, 16 MiB 8.7-9.6, 32 MiB (glibc
# mmaps it again) 6.7; as glibc comes 2.1-3.3 at any size up to 32 MiB, and
# 1.0 at 128 MiB.
FETCH_PIECE_BYTES = 16 << 20
# The pieces of a bucket are copied into its pool buffer by this many threads
# at most, the staging thread among them (_fetch_width caps it by the pieces
# and the process's cores). TPU v5e host of 13 cores, 0.537 GB in 32 pieces,
# from the moment the producing program is ready, GB/s behind an idle chip /
# under a running program (benchmarks/d2h_under_compute_check.py, chip runs,
# PR 36): the transfers alone (np.asarray, no copy) 13.9 / 13.9; one thread
# 6.6 / 6.4; 2 threads 8.8 / 8.6; 3: 10.1 / 9.8; 4: 10.3 / 10.3; 6: 11.1 /
# 10.8; 8: 11.7 / 11.4. The copies alone (transfers long over) run at 11.4
# on one thread and 35 on eight, so the threads mostly WAIT: a transfer makes
# no progress while a copy runs (one thread that only waits and one that only
# copies: 6.3), and the fetch takes the transfers' 39 ms plus the copies'
# seconds over the width. In the managed cells (traced runs at 4 / 6 / 8):
# d2h seconds a step 0.191 / 0.194 / 0.183 (OLMoE), 0.228 / 0.220 / 0.218
# (Mistral), while the landing beside it slows with every thread (unpack
# 0.103 / 0.121 / 0.133 and 0.197 / 0.208 / 0.223) and the chip's idle share
# is lowest at 4: the smallest width within 5% of the best there and under a
# running program.
FETCH_WIDTH = 4
# rows of the second-minor dimension in one TPU tile, at the narrowest dtype
_TILE_ROWS = 32


def _piece_bounds(
    metas: Sequence[Meta], itemsize: int, piece_bytes: int
) -> List[Tuple[int, int]]:
    """Cut a bucket's flat element range into ``[a, b)`` pieces of at most
    ``piece_bytes``. A leaf larger than a piece is cut on its own: between
    rows of its last dimension where a row fits a piece (the device slices
    whole rows of the leaf as it lies, and never builds its flat), between
    elements otherwise. Smaller leaves share a piece with their neighbours."""
    cap = max(1, piece_bytes // itemsize)
    bounds: List[Tuple[int, int]] = []
    start = end = 0  # the open run of whole small leaves
    for _i, off, n, shape in metas:
        if n > cap:
            if end > start:
                bounds.append((start, end))
            row = shape[-1] if shape and shape[-1] <= cap else 1
            rows = cap // row
            if row > 1 and rows > _TILE_ROWS:
                # whole tiles: a slice that starts inside one is relaid out
                # through a temporary on the TPU
                rows -= rows % _TILE_ROWS
            step = rows * row
            bounds.extend(
                (a, min(a + step, off + n)) for a in range(off, off + n, step)
            )
            start = end = off + n
            continue
        if end - start + n > cap:
            bounds.append((start, end))
            start = off
        end = off + n
    if end > start:
        bounds.append((start, end))
    return bounds


@functools.lru_cache(maxsize=64)
def _splitter(
    metas: Tuple[Meta, ...], dtype: str, piece_bytes: int
) -> Tuple[Any, List[Tuple[int, int]]]:
    """``(split, bounds)`` for one bucket of a plan (its ``metas``):
    ``split(*leaves)`` is ONE jitted dispatch whose outputs are the bucket's
    pieces, 1-D, in flat order: private copies (the leaves may be donated
    right after), together the size of the flat that :func:`pack` would
    build, which is never built."""
    import jax
    import jax.numpy as jnp

    bounds = _piece_bounds(metas, np.dtype(dtype).itemsize, piece_bytes)

    def split(*leaves: Any) -> List[Any]:
        pieces = []
        for a, b in bounds:
            parts = []
            for leaf, (_i, off, n, shape) in zip(leaves, metas):
                lo, hi = max(a, off) - off, min(b, off + n) - off
                if lo >= hi:
                    continue
                row = shape[-1] if len(shape) > 1 else 1
                if lo % row or hi % row:
                    row = 1
                parts.append(
                    leaf.reshape(-1, row)[lo // row : hi // row].reshape(-1)
                )
            pieces.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts))
        return pieces

    return jax.jit(split), bounds


@functools.lru_cache(maxsize=None)
def _keep_freed_blocks_mapped() -> bool:
    """Tell glibc, once a process, to serve every block under 32 MiB (the
    most it takes) from its heap and never to trim the heap: the
    destination the runtime allocates for each piece's transfer is then a
    block that the last step's pieces freed, its pages still mapped. Without
    it each one is a fresh ``mmap`` whose every 4 KiB page is faulted in by
    the runtime's copy (same chip, same 0.973 GB bucket: 2.6 GB/s against
    8.7; a piece alone 0.77 against 3.7). This holds for the arena of the
    thread that ISSUES the transfers, which is why :func:`pack` issues them
    on its caller's thread: the main thread's arena is one heap that is
    never given back, while a worker thread's is made of 64 MiB heaps that
    glibc unmaps whenever one is wholly free, whatever it was told (2.7
    GB/s from a worker thread, same settings). Process-wide and one-way:
    freed heap memory stays with the process (its high-water mark, about
    the size of the gradients), blocks of 32 MiB and more are ``mmap``-ed
    and returned as before. The trim threshold is a C int: 2 GiB at most.
    A step that frees more than that (Mistral-7B at four layers: 2.28 GB of
    pieces) leaves a free top over the threshold whenever nothing else
    happens to sit in it, and glibc then gives back all of it but the top
    pad, every step: 6 of 7 processes ran so, at half the speed, until the
    pad was set to the same 2 GiB, which keeps that much through a trim
    (PERF.md section 6, PR 34). The pad is address space asked for ahead,
    not memory. False where libc is not glibc; the fetch then runs at the
    first rate."""
    import ctypes

    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes, mallopt.restype = [ctypes.c_int, ctypes.c_int], ctypes.c_int
    m_trim_threshold, m_top_pad, m_mmap_threshold = -1, -2, -3  # <malloc.h>
    most = 2**31 - 1  # the parameters are C ints
    return bool(
        mallopt(m_mmap_threshold, 32 << 20)
        and mallopt(m_trim_threshold, most)
        and mallopt(m_top_pad, most)
    )


class Pieces:
    """A device group's capture for the host plane: ``arrays[k]`` holds the
    flat elements ``bounds[k]`` of a bucket of ``size`` elements, its
    transfer to the host already issued. An entry is dropped (None) once
    :func:`fetch_into` has it on the host, so the device memory goes back
    piece by piece."""

    __slots__ = ("arrays", "bounds", "size", "dtype")

    def __init__(
        self, arrays: List[Any], bounds: List[Tuple[int, int]],
        size: int, dtype: np.dtype,
    ) -> None:
        self.arrays = arrays
        self.bounds = bounds
        self.size = size
        self.dtype = dtype

    def block_until_ready(self) -> "Pieces":
        import jax

        jax.block_until_ready(self.arrays)
        return self

    def is_ready(self) -> bool:
        """Whether the device has computed the pieces (it asks, never
        waits): false while the program that makes the gradients, or the
        one that cuts them, still runs. They are one program's outputs and
        ready together, so the last one answers for all."""
        last = self.arrays[-1] if self.arrays else None
        return last is None or last.is_ready()


class _InOrder:
    """The indices ``0 .. n-1``, each handed out once and in order to
    whichever fetcher asks next; nothing more after :meth:`stop`."""

    def __init__(self, n: int) -> None:
        self._lock = threading.Lock()
        self._next, self._n = 0, n

    def __iter__(self) -> "_InOrder":
        return self

    def __next__(self) -> int:
        with self._lock:
            if self._next >= self._n:
                raise StopIteration
            self._next += 1
            return self._next - 1

    def stop(self) -> None:
        with self._lock:
            self._n = 0


def _fetch_width(pieces: int) -> int:
    """How many threads copy a bucket of ``pieces`` pieces: ``FETCH_WIDTH``,
    never more than the pieces there are or the cores this process may run
    on (four trainers share the failure cell's host; a container of two
    cores gets two)."""
    return max(1, min(FETCH_WIDTH, pieces, len(os.sched_getaffinity(0))))


def _fetch(
    pieces: Pieces, out: np.ndarray,
    after_piece: Optional[Callable[[], None]],
    fetchers: Optional[ThreadPoolExecutor],
) -> Tuple[int, float, float]:
    """:func:`fetch_into`, and how it went: ``(width, busy_s, copy_s)``, the
    threads that copied (the caller among them) and, summed over them, the
    seconds inside a piece (the wait for its transfer and its copy) and
    inside its copy alone."""
    arrays, bounds = pieces.arrays, pieces.bounds
    todo = _InOrder(len(arrays))

    def share() -> Tuple[float, float]:
        busy_s = copy_s = 0.0
        try:
            for k in todo:
                a, b = bounds[k]
                t0 = time.perf_counter()
                host = np.asarray(arrays[k])
                t1 = time.perf_counter()
                np.copyto(out[a:b], host)
                arrays[k] = host = None
                t2 = time.perf_counter()
                busy_s += t2 - t0
                copy_s += t2 - t1
                if after_piece is not None:
                    after_piece()
        except BaseException:
            todo.stop()  # the others finish the piece they hold, no more
            raise
        return busy_s, copy_s

    width = 1 if fetchers is None else _fetch_width(len(arrays))
    helpers: List[Any] = []
    try:
        for _ in range(width - 1):
            helpers.append(fetchers.submit(share))
        shares = [share()]
    finally:
        # nobody writes ``out`` or holds a piece once this returns or raises
        wait_all(helpers)
    shares.extend(h.result() for h in helpers)
    return width, sum(s[0] for s in shares), sum(s[1] for s in shares)


def fetch_into(
    pieces: Pieces, out: np.ndarray,
    after_piece: Optional[Callable[[], None]] = None,
    fetchers: Optional[ThreadPoolExecutor] = None,
) -> int:
    """Copy a captured device bucket into ``out`` (1-D, ``pieces.size``
    elements: afterwards bitwise ``np.asarray`` of the packed flat) and
    return how many pieces it came in. Every piece's transfer has been in
    flight since the capture, so a piece is waited for, copied to its offset
    and dropped with its device buffer. The pieces are disjoint ranges of
    ``out`` and nothing orders their copies among themselves: with
    ``fetchers`` (a thread pool; the pipeline's) the caller and up to
    ``FETCH_WIDTH - 1`` of its threads take the pieces in flat order, each
    the next one not yet taken, and this returns when all have landed; a
    bucket of one piece, or no pool, is copied here with no hand-off. A
    fetcher that raises stops the others at their next piece and its
    exception is raised here, after all of them have stopped. What the
    runtime allocated per transfer is piece-sized and reused from step to
    step (:func:`_keep_freed_blocks_mapped`); the only bucket-sized host
    memory is ``out``, which the caller takes from a :class:`BufferPool` so
    that its pages are mapped from the second step on. ``after_piece()`` is
    called as each piece has landed, on the thread that copied it (the
    pipeline looks up from its copying there: is the device still computing
    gradients?)."""
    _fetch(pieces, out, after_piece, fetchers)
    return len(pieces.arrays)


def pack(
    leaves: Sequence[Any],
    plan: BucketPlan,
    pool: Optional[BufferPool] = None,
    piece_bytes: Optional[int] = None,
) -> Tuple[List[Any], List[np.ndarray]]:
    """Materialize the plan's buckets from ``leaves``.

    Returns ``(flats, pooled)``: one flat buffer per bucket, plus the
    subset of ``flats`` that came from ``pool`` (the caller releases those
    back once the collective has resolved). Device groups (all leaves
    ``jax.Array``) concatenate on device — a fresh buffer, so it is safe
    against the caller's next donating jit step; host groups copy into a
    pooled (or fresh) numpy buffer, which is likewise a private capture.
    With ``piece_bytes`` (the host plane, whose buckets go through
    :func:`fetch_into`) a device group comes back as :class:`Pieces`
    instead of one flat: the same private capture, already cut, and every
    piece's transfer to the host issued from this thread, to start as soon
    as the device has computed the piece.
    """
    import jax

    flats: List[Any] = []
    pooled: List[np.ndarray] = []
    for g, metas, size, dtype in zip(plan.groups, plan.metas, plan.sizes, plan.dtypes):
        if all(isinstance(leaves[i], jax.Array) for i in g):
            import jax.numpy as jnp

            if piece_bytes is not None:
                split, bounds = _splitter(tuple(metas), dtype.name, piece_bytes)
                arrays = list(split(*(leaves[i] for i in g)))
                _keep_freed_blocks_mapped()
                for piece in arrays:
                    piece.copy_to_host_async()
                flat = Pieces(arrays, bounds, size, dtype)
            elif len(g) == 1:
                # single-leaf bucket: reshape is a view-like device op, but
                # the Manager's staging contract needs a private buffer —
                # copy explicitly
                flat = jnp.copy(leaves[g[0]]).reshape(-1)
            else:
                flat = jnp.concatenate(
                    [leaves[i].reshape(-1) for i in g]
                )
        else:
            if pool is not None:
                flat = pool.acquire(size, dtype)
                pooled.append(flat)
            else:
                flat = np.empty(size, dtype=dtype)
            for (i, off, n, _shape) in metas:
                flat[off : off + n] = np.asarray(leaves[i]).reshape(-1)
        flats.append(flat)
    return flats, pooled


def capture(
    leaves: Sequence[Any], plan: BucketPlan, pool: BufferPool
) -> List[Any]:
    """The host plane's capture of a participant's leaves, one entry a
    bucket, for :func:`stage` to turn into host memory. Runs on the
    CALLER's thread, before ``allreduce()`` returns: the staging thread
    reads the capture afterwards, by which time the caller's next jitted
    step may have donated (deleted) the device buffers or overwritten a
    reused numpy buffer. A device group comes back cut into :class:`Pieces`
    (one jitted dispatch a bucket: private copies, their transfers issued
    from this thread, whose arena keeps freed blocks mapped:
    :func:`_keep_freed_blocks_mapped`); a host group is copied into a pool
    buffer here."""
    flats, _pooled = pack(
        leaves, plan, pool=pool, piece_bytes=FETCH_PIECE_BYTES
    )
    return flats


def stage(
    captured: Optional[List[Any]], plan: BucketPlan, i: int, pool: BufferPool,
    after_piece: Optional[Callable[[], None]] = None,
    fetchers: Optional[ThreadPoolExecutor] = None,
) -> Tuple[np.ndarray, Optional[np.ndarray], Dict[str, int]]:
    """Bucket ``i`` of a :func:`capture` as host memory for the wire, on the
    staging thread: ``(host_flat, pooled_buf, info)``. ``pooled_buf`` is the
    pool buffer to give back once the bucket has LANDED, and what landed
    from it has read it (None: nothing was taken); ``info`` is what the ``d2h`` span says of it (``bytes``, and for
    a device bucket ``pieces`` and ``pooled``: 1 when the buffer is a
    recycled one; with ``fetchers``, the pipeline's pool for
    :func:`fetch_into`, also ``fetchers``, the threads that copied, and
    summed over them ``busy_us``, inside a piece, and ``copy_us``, inside
    its copy). ``captured`` is None for a non-participant: its
    contribution is zeros of the plan's size and dtype, built from shapes
    alone, and nothing comes out of the pool. The entry is dropped from
    ``captured`` so that nothing holds the capture once it is staged."""
    if captured is None:
        return np.zeros((plan.sizes[i],), plan.dtypes[i]), None, {}
    cap, captured[i] = captured[i], None
    if isinstance(cap, Pieces):
        # a device bucket: its pieces, in flight since the capture, into a
        # pool buffer (mapped pages from the second step on), device memory
        # dropped as each lands
        host_flat, hit = pool.acquire_hit(cap.size, cap.dtype)
        width, busy_s, copy_s = _fetch(cap, host_flat, after_piece, fetchers)
        info = {"pieces": len(cap.arrays), "pooled": int(hit)}
        if fetchers is not None:
            info.update(fetchers=width, busy_us=int(busy_s * 1e6),
                        copy_us=int(copy_s * 1e6))
    else:
        # a host group: packed into its pool buffer at the capture
        host_flat, info = cap, {}
    info["bytes"] = host_flat.nbytes
    return host_flat, host_flat, info


def unpack(flats: Sequence[Any], plan: BucketPlan) -> List[Any]:
    """Slice the reduced flat buckets back into per-leaf arrays (views for
    numpy flats, lazy device slices for jax flats), in leaf order."""
    out: List[Optional[Any]] = [None] * plan.num_leaves
    for bucket, flat in enumerate(flats):
        for i, leaf in unpack_bucket(flat, plan, bucket):
            out[i] = leaf
    assert all(o is not None for o in out)
    return out  # type: ignore[return-value]


def unpack_bucket(flat: Any, plan: BucketPlan, bucket: int) -> List[Tuple[int, Any]]:
    """Slice ONE reduced bucket into ``(leaf_index, array)`` pairs.

    The streaming pipeline unpacks each bucket as its wire completes instead
    of waiting for the whole plan; slices are views (numpy) or lazy device
    slices (jax), exactly as :func:`unpack` produces for that bucket.
    """
    import jax

    if not isinstance(flat, jax.Array):
        flat = np.asarray(flat)
    return [
        (i, flat[off : off + size].reshape(shape))
        for (i, off, size, shape) in plan.metas[bucket]
    ]


# ---------------------------------------------------------------------------
# the landing: one reduced array back to where its leaves live, averaged there


def is_float_dtype(dtype: Any) -> bool:
    """True for dtypes the wire codecs can compress (incl. ml_dtypes
    bfloat16, which numpy does not class as np.floating)."""
    return bool(
        np.issubdtype(np.dtype(dtype), np.floating)
        or "bfloat16" in str(dtype)
    )


def _payload_nbytes(payload: Any) -> int:
    """Bytes of one bucket as it is handled: an ndarray, or a compressed
    wire (codes + scales)."""
    if is_compressed_wire(payload):
        return int(payload.payload.nbytes + payload.scales.nbytes)
    return int(getattr(payload, "nbytes", 0))


@functools.lru_cache(maxsize=None)
def _average_on_device() -> Callable[[Any, Any], Any]:
    """The AVG normalisation of the landed device leaves of ONE bucket as a
    jitted computation: ``(leaves, n) -> (quotients, token)``.

    It donates its inputs, so each quotient reuses the buffer the H2D just
    filled and the step's HBM peak does not grow by a leaf; the divisor is a
    float32 runtime scalar, so a quorum that goes 4 -> 3 -> 4 compiles
    nothing new (one executable per bucket geometry). Bit for bit numpy's
    result a leaf, except that XLA flushes a subnormal input or quotient
    (under 2**-126) to zero where numpy keeps it. ONE dispatch a bucket and
    not one a leaf: the runtime holds some thirty programs in flight, a
    landing's wait behind the backward pass that is running, and a dispatch
    past that blocks the unpack thread until the pass ends (TPU v5e, four
    Mistral layers: the third op's ``divide`` stood 84 ms, the next op's
    landing behind it; PERF.md section 6, PR 50). ``token`` is a scalar of
    the program's own, ready when the leaves were read: what
    :func:`_landed_token` would say of them, without a dispatch more. Built
    on first use: a process that only moves host arrays never gets here."""
    import jax

    def average_landed_leaves(xs: Any, n: Any) -> Any:  # its name in a trace
        return [(x / n).astype(x.dtype) for x in xs], np.int32(0)

    return jax.jit(average_landed_leaves, donate_argnums=0)


def _average(x: Any, num_participants: int, landed: bool = False) -> Any:
    """A reduced SUM divided by the participants, rounded once back to its
    own dtype (bf16 / f16 in, float32 quotient): the one expression of the
    AVG normalisation, run where ``x`` is. ``landed`` says ``x`` is a leaf
    ``place`` has just put where its original lives, a private buffer: on a
    device it is then divided there, in place. Anything else (a numpy
    array; a device-native PG's own result) gets a fresh array."""
    import jax

    if landed and isinstance(x, jax.Array):
        return _average_landed([x], num_participants)[0][0]
    return (x / num_participants).astype(x.dtype)


def _average_landed(
    values: Sequence[Any], num_participants: int
) -> Tuple[List[Any], Optional[Any]]:
    """:func:`_average` of a bucket's landed leaves, each where it is: the
    device leaves together in one donating dispatch
    (:func:`_average_on_device`), a numpy leaf in numpy. Returns the
    quotients in order and the dispatch's token (None: no device leaf)."""
    import jax

    out = [
        v if isinstance(v, jax.Array) else _average(v, num_participants)
        for v in values
    ]
    on_device = [k for k, v in enumerate(out) if isinstance(v, jax.Array)]
    if not on_device:
        return out, None
    quotients, token = _average_on_device()(
        [out[k] for k in on_device], np.float32(num_participants))
    for k, q in zip(on_device, quotients):
        out[k] = q
    return out, token


def leaf_placer() -> Callable[[Any, Any], Any]:
    """``place(orig, host)``: one reduced slice put where its original leaf
    lives (a numpy leaf stays numpy). One placer an allreduce, shared by
    every landing and by the zeros of its error path, so all of them land
    leaves through identical expressions.

    Staleness check at RESOLVE time: if the input leaf's sharding
    references a device client that is no longer the live backend
    (ProcessGroupXLA tore down + rejoined its per-quorum jax.distributed
    world between the caller computing the values and this resolve), a
    device_put onto it can SUCCEED and produce an array the next jitted
    computation rejects as "incompatible devices". Such leaves land on the
    live backend instead — _sync_device_world re-lands the user's own state
    the same way at should_commit. LAZY on purpose: jax.devices()
    initializes the backend, and a pure-host tree must never trigger that
    (a process that only moves host arrays should not take the chip)."""
    import jax

    live_client = [False]

    def _is_live(sharding: Any) -> bool:
        if live_client[0] is False:
            try:
                live_client[0] = getattr(jax.devices()[0], "client", None)
            except Exception:  # noqa: BLE001
                live_client[0] = None
        if live_client[0] is None:
            return True
        try:
            dev = next(iter(sharding.device_set))
            return getattr(dev, "client", None) is live_client[0]
        except Exception:  # noqa: BLE001
            return False

    def place(orig: Any, host: Any) -> Any:
        import jax.numpy as jnp

        if lives_on_device(orig):
            if _is_live(orig.sharding):
                return jax.device_put(host, orig.sharding)
            return jnp.asarray(np.asarray(host))
        return np.asarray(host)

    return place


def _no_span(name: str, **args: Any) -> Any:
    return contextlib.nullcontext()


def land_reduced(
    flat: Any,
    leaves: Sequence[Any],
    plan: Optional[BucketPlan],
    bucket: int,
    divisor: Optional[int],
    place: Callable[[Any, Any], Any],
    span: Callable[..., Any] = _no_span,
    tokens: Optional[List[Any]] = None,
) -> List[Tuple[int, Any]]:
    """One reduced array — the plan's bucket ``bucket``, or with no plan the
    lone leaf of that index — to ``(leaf_index, leaf)`` pairs: sliced
    (views), each slice placed where its original lives
    (:func:`leaf_placer`), and with a ``divisor`` (the participants, under
    AVG) divided by it where it then is: a device leaf on its device
    (dispatched from here, nothing waits for it), a numpy leaf in numpy, so
    a pure-host tree still never initialises a backend. The no-plan path
    and every bucket of the pipeline land through this one function, so
    they stay bit-identical on every backend. ``span(name, **args)``: the
    pipeline's allreduce/h2d and allreduce/divide; the no-plan path records
    none. ``tokens``: a list that receives the division's token where device
    leaves were divided (:func:`_average_landed`): ready once they are."""
    import jax

    idxs = [bucket] if plan is None else plan.groups[bucket]
    sized = {"bytes": _payload_nbytes(flat), "leaves": len(idxs)}
    on_device_plane = isinstance(flat, jax.Array)
    if divisor and on_device_plane:
        # a device-native PG's result may be a buffer the PG (or the
        # caller) still holds: divided where it is, into a fresh one
        with span("divide", where="device", **sized):
            flat = _average(flat, divisor)
    pairs = (
        [(bucket, flat)] if plan is None
        else unpack_bucket(flat, plan, bucket)
    )
    # on a device leaf place is jax.device_put: as far as it returns before
    # the bytes have moved, h2d is the enqueue
    with span("h2d", **sized):
        pairs = [(i, place(leaves[i], v)) for i, v in pairs]
    if divisor and not on_device_plane:
        on_device = sum(lives_on_device(leaves[i]) for i in idxs)
        where = (
            "device" if on_device == len(idxs)
            else "mixed" if on_device else "host"
        )
        with span("divide", where=where, **sized):
            quotients, token = _average_landed([v for _, v in pairs], divisor)
            pairs = [(i, q) for (i, _), q in zip(pairs, quotients)]
        if token is not None and tokens is not None:
            tokens.append(token)
    return pairs


@functools.lru_cache(maxsize=None)
def _landed_token() -> Callable[[Any], Any]:
    """``token(leaves)``: a device scalar that is ready once ``leaves`` (a
    leaf, or a bucket's list of them: one dispatch) are,
    dispatched and never waited for. It is the pipeline's own, so it can
    still be asked after the caller has donated the leaves to its next step
    (a deleted array says nothing of whether the transfer that filled it is
    over). ``keep_unused``: the runtime starts a program once every argument
    it was handed is defined, read or not."""
    import jax

    def landed_token(leaves: Any) -> Any:  # its name in a trace
        return np.int32(0)

    return jax.jit(landed_token, keep_unused=True)


def readers_of(
    buf: np.ndarray, leaves: Sequence[Any],
    tokens: Optional[List[Any]] = None,
) -> Optional[List[Any]]:
    """What still reads the staging buffer ``buf`` after ``leaves`` landed
    from it (the collective handed ``buf`` back as its own result): a token
    (``is_ready()``) for the device leaves, whose transfers out of ``buf``
    may be in flight: ``tokens`` where the landing's division left one
    (:func:`land_reduced`), else one dispatch for all of them; nothing for
    numpy leaves in memory of their own (an AVG's quotient). None where a
    leaf IS a slice of ``buf`` (a numpy leaf under SUM; a CPU backend's
    ``device_put`` of aligned memory, which copies nothing, with no divide
    after it): that buffer is the caller's now."""
    import jax

    lo = buf.ctypes.data
    hi = lo + buf.nbytes
    on_device = []
    for leaf in leaves:
        if isinstance(leaf, jax.Array):
            if any(
                s.device.platform == "cpu"
                and lo <= s.data.unsafe_buffer_pointer() < hi
                for s in leaf.addressable_shards
            ):
                return None
            on_device.append(leaf)
        elif not isinstance(leaf, np.ndarray) or np.shares_memory(leaf, buf):
            return None
    if not on_device:
        return []
    return list(tokens) if tokens else [_landed_token()(on_device)]


# ---------------------------------------------------------------------------
# the schedule: which thread runs which stage of which bucket


def _settle(
    fut: Future, result: Any = None, exc: Optional[BaseException] = None
) -> None:
    """Resolve ``fut`` unless somebody already did. The wire, the stage
    deadline, the submission backstop and the shutdown sweep all race to
    the same futures; the loser is a no-op."""
    try:
        if exc is not None:
            fut.set_exception(exc)
        else:
            fut.set_result(result)
    except RuntimeError:
        pass


def _settle_from(fut: Future, src: Future) -> None:
    """:func:`_settle` ``fut`` with the outcome of the completed ``src``."""
    exc = src.exception()
    if exc is not None:
        _settle(fut, exc=exc)
    else:
        _settle(fut, src.value())


def _covered_seconds(
    start: float, end: float, intervals: List[Any]
) -> float:
    """Length of ``[start, end]`` covered by the union of ``intervals``."""
    if end <= start:
        return 0.0
    clipped = sorted(
        (max(start, a), min(end, b))
        for a, b in intervals
        if b > start and a < end
    )
    total = 0.0
    cur_s: Optional[float] = None
    cur_e = 0.0
    for a, b in clipped:
        if cur_s is None:
            cur_s, cur_e = a, b
        elif a <= cur_e:
            cur_e = max(cur_e, b)
        else:
            total += cur_e - cur_s
            cur_s, cur_e = a, b
    if cur_s is not None:
        total += cur_e - cur_s
    return total


def _pipeline_overlap_stats(marks: List[Dict[str, Any]]) -> Dict[str, float]:
    """Summarize one bucketed allreduce's per-bucket stage marks.

    ``marks[i]`` maps stage name (``pack`` / ``wire`` / ``unpack``) to a
    ``(start, end)`` perf_counter interval; stages a bucket never reached
    (mid-stream failure, timeout) are simply absent. ``overlap_efficiency``
    is Σᵢ |wireᵢ ∩ ∪ⱼ≠ᵢ(packⱼ ∪ wireⱼ ∪ unpackⱼ)| / Σᵢ |wireᵢ| — the
    fraction of wire time hidden behind other buckets' pipeline stages
    (a lower bound: overlap with caller compute is not observable here).
    A single-bucket plan has nothing to hide behind and reports 0.0."""
    pack_s = sum(e - s for m in marks if "pack" in m for s, e in [m["pack"]])
    wire_s = sum(e - s for m in marks if "wire" in m for s, e in [m["wire"]])
    unpack_s = sum(
        e - s for m in marks if "unpack" in m for s, e in [m["unpack"]]
    )
    hidden = 0.0
    for i, m in enumerate(marks):
        if "wire" not in m:
            continue
        s, e = m["wire"]
        others = [
            iv
            for j, mj in enumerate(marks)
            if j != i
            for iv in mj.values()
        ]
        hidden += _covered_seconds(s, e, others)
    return {
        "allreduce_pack_s": pack_s,
        "allreduce_wire_s": wire_s,
        "allreduce_unpack_s": unpack_s,
        "allreduce_buckets": float(len(marks)),
        "overlap_efficiency": (hidden / wire_s) if wire_s > 0 else 0.0,
    }


# timings() keys of a step whose ops rode the plain host ring, each summed
# over the step's runs: the wait from a run's start on the PG's dispatch
# thread until the first peer byte, and the lanes' mean seconds blocked for
# a header after it, copying payloads in, adding, inside the send, and in
# the hand-offs between a lane's three threads. Absent (with ``ring_lanes``)
# from a step in which no ring ran: begin_step drops them.
RING_KEYS = ("ring_entry_wait_s", "ring_recv_wait_s", "ring_recv_s",
             "ring_fold_s", "ring_send_s", "ring_handoff_s")


class _StepTally:
    """What the allreduces of ONE step add up to (a step runs from one
    :meth:`BucketPipeline.begin_step` to the next: ``Manager.start_quorum``
    calls it). A trainer that hands the gradients over in several ops a step
    reads its ``timings()`` of the step, not of the step's last op."""

    def __init__(self) -> None:
        self.ops = 0  # allreduces begun (timings()["allreduce_ops"])
        # the runs they were cut into (timings()["allreduce_runs"]): a
        # plan's buckets, one for an op without a plan
        self.runs = 0
        # of the ops resolved so far (record_timings, under the lock): the
        # stage sums, the wire seconds hidden behind an op's other buckets,
        # device buckets and those that came back as their staging buffer
        self.lock = threading.Lock()
        self.stage_sums: Dict[str, float] = {}
        self.hidden_s = 0.0
        self.from_device = self.passed_through = 0
        self.ring_lanes = 0  # the fewest any ring among them rode; 0: none ran
        # what their rings said of their own time (process_group
        # ._ring_allreduce's account), summed over the runs: RING_KEYS'
        # seconds, the lanes' means
        self.ring_s: Dict[str, float] = {}
        # seconds inside the landings' h2d and divide spans, and the part
        # of them before their own op's last fetch had ended
        self.land_s = self.land_under_fetch_s = 0.0
        # the newest device capture of the step (the caller's thread sets
        # it): while ITS pieces are not ready, gradients are being computed
        self.newest: Optional[Pieces] = None
        # the staging thread's alone: device buckets it fetched, those into
        # a recycled buffer, each fetch's (start, end), their bytes, and the
        # seconds its fetchers spent inside a piece
        self.acquired = self.hits = 0
        # how many buffers of one (dtype, size) the step has drawn so far:
        # what the pool keeps free of that key (BufferPool.keep_at_least)
        self.drawn: Dict[Tuple[str, int], int] = {}
        self.d2h: List[Tuple[float, float]] = []
        self.fetched_bytes = 0
        self.fetch_busy_s = 0.0
        # the last moment the device was seen still computing this step's
        # gradients (the staging thread at a grad_wait, a fetcher after a
        # piece: a float, stored whole)
        self.computing_until: Optional[float] = None

    def still_computing(self) -> None:
        """Asked after each fetched piece, by the fetcher that copied it:
        gradients that a later op of the step has captured are not there
        yet."""
        newest = self.newest
        if newest is not None and not newest.is_ready():
            self.computing_until = time.perf_counter()

    def staging_shares(self) -> Dict[str, float]:
        """``stage_pool_hit_share``, and ``d2h_under_backward_share``: of
        the step's d2h seconds so far, the part that ran while the device
        was seen computing the step's gradients: before the last grad_wait
        that had to wait returned, or a later op's capture was found not
        ready after a piece. A lower bound, to a piece (a backward pass that
        ends between two looks is seen at the earlier one); 0.0 where one op
        carries the whole tree: every fetch follows its one wait. Over the
        same d2h seconds, ``d2h_concurrency``: the seconds the fetchers
        spent inside a piece (1.0: one at a time; towards ``FETCH_WIDTH``
        when the pool is busy), and ``d2h_gb_s``: the bytes fetched."""
        total = sum(t1 - t0 for t0, t1 in self.d2h)
        until = self.computing_until
        under = 0.0 if until is None else sum(
            max(0.0, min(t1, until) - t0) for t0, t1 in self.d2h)
        per_second = 1.0 / total if total > 0 else 0.0
        return {
            "stage_pool_hit_share": self.hits / self.acquired,
            "d2h_under_backward_share": under * per_second,
            "d2h_concurrency": self.fetch_busy_s * per_second,
            "d2h_gb_s": self.fetched_bytes * per_second / 1e9,
        }


class _BucketOp:
    """One bucketed allreduce in flight: what its stages, each on its own
    thread, share. ``final`` resolves to the landed leaves in leaf order
    once every bucket has landed; it is fed from the join of
    ``bucket_futs`` but owned here, so that the stage deadline and the
    shutdown sweep can fail it directly."""

    def __init__(
        self,
        leaves: Sequence[Any],
        plan: BucketPlan,
        divisor: Optional[int],
        place: Callable[[Any, Any], Any],
        parent: Optional[int],
        new_id: Callable[[], int],
        tally: _StepTally,
        segment: int,
    ) -> None:
        n = len(plan)
        self.leaves, self.plan = leaves, plan
        self.divisor, self.place, self.parent = divisor, place, parent
        # the step this op belongs to, and which of its ops it is: every
        # allreduce/* span of the op says ``segment=``
        self.tally, self.segment = tally, segment
        self.bucket_bytes = [
            size * np.dtype(dtype).itemsize
            for size, dtype in zip(plan.sizes, plan.dtypes)
        ]
        # per-bucket (start, end) wall-clock marks per stage, for
        # pack_s/wire_s/unpack_s + overlap_efficiency in timings()
        self.marks: List[Dict[str, Any]] = [{} for _ in range(n)]
        # ids of the three stage spans per bucket (recorded from the marks
        # once the op resolves), for their children, and what the PG
        # stamped on each bucket's op: (enqueued, fn started, fn ended) ->
        # allreduce/wire_run, and what its ring left beside them (inplace,
        # chunks): that span's args
        self.stage_ids = [
            {st: new_id() for st in ("pack", "wire", "unpack")}
            for _ in range(n)
        ]
        self.wire_runs: List[Any] = [None] * n
        self.wire_rings: List[Dict[str, int]] = [{} for _ in range(n)]
        # which buckets were fetched from the device into a pool buffer, and
        # which got that buffer back as the collective's result
        # (wire_passthrough_share)
        self.from_device = [False] * n
        self.passed_through = [False] * n
        # when the op's last device bucket was on the host (the staging
        # thread's), and the (start, end) of every h2d and divide span of
        # its landings (the unpack thread's): land_under_fetch_share
        self.fetched_at: Optional[float] = None
        self.landings: List[Tuple[float, float]] = []
        self.bucket_futs: List[Future] = [Future() for _ in range(n)]
        self.final: Future = Future()
        join_futures(self.bucket_futs).then(self._assemble).add_done_callback(
            functools.partial(_settle_from, self.final)
        )

    def _assemble(self, joined: Future) -> List[Any]:
        placed: Dict[int, Any] = {}
        for pairs in joined.value():
            placed.update(pairs)
        return [placed[i] for i in range(len(self.leaves))]


class BucketPipeline:
    """The data plane of a managed allreduce: a process group, a span
    recorder and a :class:`BufferPool`, and which thread runs which stage.

    A tree with a plan takes :meth:`allreduce_buckets`: one PG collective
    PER BUCKET, three stages a bucket: pack (:func:`capture` on the caller's
    thread, :func:`stage` on the one staging thread, which shares the copies
    of a device bucket's pieces with its fetcher threads, waits for the
    bucket, and alone decides the order of buckets and ops: a fetcher
    copies and never dispatches), wire (the PG's
    dispatch thread, or XLA), unpack (:func:`land_reduced` on the one unpack
    thread). Bucket i+1 packs while bucket i rides the wire and bucket i−1
    unpacks; no stage ever waits for the LAST bucket's wire. The plan of a
    host-plane op over device leaves is cut at ``RUN_BYTES``
    (:func:`run_cap`, the Manager's choice), so that such an op is several
    buckets, its RUNS, and a run lands while the next is fetched where one
    bucket an op was fetched whole and then landed whole
    (``land_under_fetch_share``, ``allreduce_runs``). A tree without
    one (a single leaf, a cap of 0, the monolithic quantized exchange) takes
    :meth:`allreduce_leaves`: one collective carrying every leaf, landed by
    the same function. Numerics are bit-identical between the two:
    per-bucket collectives reduce each flat independently just like one call
    carrying the list.

    Only a device-native PG (ProcessGroupXLA) bypasses the staging thread:
    it takes jax.Arrays straight through (the collective runs on device
    over ICI/DCN with no host staging, the quantized one too: the Pallas
    kernels quantize there and the payload ships as packed uint8 device
    arrays, collectives.py _pack_wire_device), and its ops rendezvous by
    (kind, seq) so issue order across threads cannot mismatch. On a host PG
    EVERYTHING — including the quantized exchange, whose alltoall/allgather
    would otherwise be issued from an unordered helper thread — goes through
    the one ordered staging thread: the host exchange matches messages
    purely by arrival order, and cross-replica issue order is the contract.

    ``on_timings(stats)`` receives what ``Manager.timings()`` shows of the
    pipeline, each value over the ops of the step so far
    (:meth:`begin_step`): ``allreduce_ops`` and ``allreduce_runs``; from
    the staging thread
    ``stage_pool_hit_share``, ``d2h_under_backward_share``,
    ``d2h_concurrency`` and ``d2h_gb_s``; the stage
    sums, ``wire_passthrough_share``, ``land_under_fetch_share``,
    ``ring_lanes`` and ``RING_KEYS`` from :meth:`record_timings` (a value of
    None, at :meth:`begin_step`: the key is gone until it is said again)."""

    def __init__(
        self,
        pg: Any,
        tracer: Any,
        pool: BufferPool,
        on_timings: Callable[[Dict[str, Any]], None] = lambda stats: None,
    ) -> None:
        self._pg = pg
        self._tracer = tracer
        self._pool = pool
        self._on_timings = on_timings
        # one ordered worker for host-plane staging: D2H + wire dispatch off
        # the train loop, issue order preserved across replicas
        self._staging_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="torchft_stage"
        )
        # the staging thread's helpers for the copies of a device bucket's
        # pieces (fetch_into); threads start when a bucket first needs them
        self._fetch_executor = ThreadPoolExecutor(
            max_workers=max(1, FETCH_WIDTH - 1),
            thread_name_prefix="torchft_fetch",
        )
        # stage 3: per-bucket unpack + device landing runs here so it
        # neither blocks the PG's dispatch thread (which would serialize
        # the NEXT bucket's wire behind this bucket's unpack) nor waits for
        # the last bucket's wire
        self._unpack_executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="torchft_unpack"
        )
        # (executor future, staged future) pairs still in flight: shutdown
        # must fail the staged futures of cancelled tasks or their waiters
        # stall for the full timeout. Guarded together with the shutdown
        # flag so a submit can't race the shutdown sweep.
        self._staged_pending: List[Any] = []
        self._staged_lock = threading.Lock()
        self._staging_down = False
        # per-(plan, bucket) error-feedback residuals: what quantization
        # rounded away this step is added back before quantizing the next
        # step, so the compression error stays bounded instead of
        # accumulating (LocalSGD/DiLoCo convergence depends on this).
        # Keyed by plan identity via weakref so evicted plans drop their
        # residual buffers with them; buffers come from the BufferPool.
        self._ef_residuals: "weakref.WeakKeyDictionary" = (
            weakref.WeakKeyDictionary()
        )
        self._ef_lock = threading.Lock()
        # staging buffers that came back as their collective's result, each
        # with the tokens of the device leaves landed from it: back to the
        # pool once those are ready (_recycle parks, _sweep_parked releases)
        self._parked: List[Tuple[np.ndarray, List[Any]]] = []
        self._parked_lock = threading.Lock()
        self._tally = _StepTally()

    @property
    def device_native(self) -> bool:
        return bool(getattr(self._pg, "device_native", False))

    def begin_step(self) -> None:
        """A new step: the next op is the step's segment 0, and what
        ``on_timings`` hears from here on adds up over this step's ops. The
        ring's keys are the step's own: None takes a key away until a ring
        of this step says it again."""
        self._tally = _StepTally()
        self._on_timings(dict.fromkeys((*RING_KEYS, "ring_lanes")))

    def next_segment(self) -> int:
        """Count one more allreduce of this step (``allreduce_ops``) and
        return which one it is, from 0."""
        tally = self._tally
        tally.ops += 1
        self._on_timings({"allreduce_ops": float(tally.ops)})
        return tally.ops - 1

    def _count_runs(self, n: int) -> None:
        """``n`` more runs begun this step (``allreduce_runs``): an op's
        buckets, each through fetch, wire and landing by itself."""
        tally = self._tally
        tally.runs += n
        self._on_timings({"allreduce_runs": float(tally.runs)})

    # ------------------------------------------------------------ schedule
    def submit(
        self, stage_fn: Callable[[], None], fut: Future, timeout: float
    ) -> None:
        """Queue ``stage_fn`` (stage and dispatch, never the wire) on the
        staging thread, with ``fut``, the future its op resolves, bounded
        twice. The tight deadline is armed when staging BEGINS (not at
        submission: queue time behind an in-flight quantized sync must not
        count against this op) and spans the WHOLE staged op — D2H,
        dispatch, AND the wire phase the PG worker resolves via callback
        after ``stage_fn`` returns; disarmed at dispatch it would leave a
        never-resolving wire (hung peer whose abort path also fails)
        unbounded. It is cancelled the moment ``fut`` settles.

        The submission-time depth-aware BACKSTOP: if an op ahead of this
        one wedges its stage forever (D2H against a hung device, a dispatch
        that never returns), ``stage_fn`` never runs and the tight deadline
        is never armed. Healthy queue time is bounded by one deadline per op
        ahead (each stage blocks at most ``timeout``), so depth+2 slots
        never fire on a healthy queue; both timers race to the same
        :func:`_settle` and the loser is a no-op."""

        def expire() -> None:
            _settle(fut, exc=TimeoutError("allreduce staging timed out"))

        def run() -> None:
            try:
                cancel = arm_deadline(expire, timeout)
                fut.add_done_callback(lambda _f: cancel())
                stage_fn()
            except Exception as e:  # noqa: BLE001
                _settle(fut, exc=e)

        # submit + register atomically vs the shutdown sweep: a pair
        # appended after the sweep would never have its staged future
        # failed (full-timeout stall), and a submit after executor shutdown
        # raises anyway
        with self._staged_lock:
            if self._staging_down:
                raise RuntimeError("manager is shut down")
            depth = len(self._staged_pending)
            backstop_cancel = arm_deadline(expire, (depth + 2) * timeout)
            fut.add_done_callback(lambda _f: backstop_cancel())
            pair = (self._staging_executor.submit(run), fut)
            self._staged_pending.append(pair)

        def _unpin(_f: Future) -> None:
            # release the (gradient-sized) result reference as soon as the
            # wire resolves, not at the next allreduce
            with self._staged_lock:
                try:
                    self._staged_pending.remove(pair)
                except ValueError:
                    pass

        fut.add_done_callback(_unpin)

    def shutdown(self, wait: bool = True) -> None:
        """Stop the workers. On a non-waiting shutdown queued (not-yet-run)
        staging tasks are cancelled: they would otherwise dispatch against
        the PG after its shutdown, spuriously reporting errors on a
        torn-down manager — and their staged futures are failed so any
        waiter unblocks immediately instead of riding out the full
        timeout."""
        with self._staged_lock:
            self._staging_down = True
        self._staging_executor.shutdown(wait=wait, cancel_futures=not wait)
        # after the staging thread, which is the only one that hands the
        # fetchers work: a stage cut off here fails at its next hand-off
        self._fetch_executor.shutdown(wait=wait, cancel_futures=not wait)
        # cancelled bucket unpacks leave their bucket futures unresolved —
        # the aggregate is bounded by the stage deadline / the sweep below,
        # so no waiter stalls past the timeout
        self._unpack_executor.shutdown(wait=wait, cancel_futures=not wait)
        with self._staged_lock:
            pending, self._staged_pending = self._staged_pending, []
        for exec_fut, staged_fut in pending:
            if exec_fut.cancelled():
                _settle(
                    staged_fut,
                    exc=RuntimeError("manager shut down before dispatch"),
                )

    # ------------------------------------------------------ with a plan
    def allreduce_buckets(
        self,
        leaves: Sequence[Any],
        plan: BucketPlan,
        pg_op: Any,
        *,
        participating: bool,
        divisor: Optional[int],
        place: Callable[[Any, Any], Any],
        timeout: float,
        compress: str = "off",
        parent: Optional[int] = None,
        segment: int = 0,
    ) -> _BucketOp:
        """One collective a bucket of ``plan``. Returns the op in flight:
        ``op.bucket_futs[i]`` resolves as bucket ``i`` lands, ``op.final`` to
        the landed leaves, in leaf order (``divisor``: the AVG's
        participants, None for a plain SUM; ``place``: :func:`leaf_placer`).
        A non-participant contributes zeros. ``compress`` ("off" | "fp8" |
        "int8") is the host plane's wire codec, with error feedback.
        ``parent``: the span the stages hang under; ``segment``: which of
        its step's ops this is (:meth:`next_segment`). Once a participant's
        leaves are captured the op holds stand-ins for them
        (:func:`leaf_stand_in`): a caller that drops its tree frees it."""
        op = _BucketOp(
            leaves, plan, divisor, place, parent, self._tracer.new_id,
            self._tally, segment,
        )
        self._count_runs(len(plan))
        if self.device_native:
            self._issue_on_device(op, pg_op, participating)
            return op
        # host plane: capture on the caller thread, then ONE staging task
        # walks the buckets — D2H bucket i, non-blocking dispatch, straight
        # on to bucket i+1 while the PG's dispatch thread runs the wire. A
        # single task keeps per-plan dispatch atomic across concurrent
        # callers, preserving cross-replica arrival order (the SPMD
        # contract of the host exchange).
        captured = None
        if participating:
            with self._tracer.span(
                "capture", cat="allreduce", parent=parent,
                bytes=sum(op.bucket_bytes), segment=segment,
            ):
                captured = capture(leaves, plan, self._pool)
            op.leaves = [leaf_stand_in(l) for l in leaves]
            newest = next(
                (c for c in reversed(captured) if isinstance(c, Pieces)),
                None)
            if newest is not None:
                op.tally.newest = newest
                if newest.arrays:
                    # the device's milestone of this op: its last piece is
                    # ready once the program that made these gradients and
                    # the split are done (segment 0: the forward pass and
                    # the head; later: that segment's backward pass). The
                    # watcher drops the piece then, before its fetch can end
                    self._tracer.when_ready(
                        "backward" if segment else "forward", "device",
                        newest.arrays[-1], parent=parent, segment=segment,
                    )
        # Non-float buckets ride uncompressed — the decision depends only
        # on the shared plan + mode, so it is SPMD-consistent across
        # replicas. Non-participants compress their zero contribution too
        # (the ring needs uniform wire geometry) but never touch the EF
        # residuals.
        modes = [
            compress if is_float_dtype(dtype) else "off"
            for dtype in plan.dtypes
        ]
        ef_store = (
            self._bucket_residuals(plan)
            if compress != "off" and participating
            else None
        )
        self.submit(
            functools.partial(
                self._stage_buckets, op, captured, pg_op, modes, ef_store,
                time.perf_counter(),
            ),
            op.final,
            timeout,
        )
        return op

    def _issue_on_device(
        self, op: _BucketOp, pg_op: Any, participating: bool
    ) -> None:
        """Device plane: issue per-bucket collectives straight from the
        caller thread — ProcessGroupXLA rendezvouses ops by (kind, seq), and
        per-bucket ops let XLA overlap ICI transfers with adjacent
        compute."""
        import jax
        import jax.numpy as jnp

        plan = op.plan
        t0p = time.perf_counter()
        if participating:
            up = [
                l if isinstance(l, jax.Array) else jnp.asarray(l)
                for l in op.leaves
            ]
            dev_flats, _ = pack(up, plan)
        else:
            # zero contribution, built directly at bucket shape (cheaper
            # than zeroing per leaf then packing)
            dev_flats = [
                jnp.zeros(size, dtype)
                for size, dtype in zip(plan.sizes, plan.dtypes)
            ]
        op.marks[0]["pack"] = (t0p, time.perf_counter())
        for i, flat in enumerate(dev_flats):
            t0w = time.perf_counter()
            w = self._pg.allreduce([flat], pg_op)
            w.get_future().add_done_callback(
                functools.partial(self._wire_done, op, i, t0w, None)
            )

    def _stage_buckets(
        self,
        op: _BucketOp,
        captured: Optional[List[Any]],
        pg_op: Any,
        modes: List[str],
        ef_store: Optional[List[Any]],
        t_submit: float,
    ) -> None:
        """Stages 1 and 2 of every bucket of a host-plane op, on the
        staging thread: host memory (:func:`stage`; the fetchers help with
        a device bucket's copies and are done when it returns), the codec,
        the dispatch. It never waits for a wire."""
        tracer, plan, tally = self._tracer, op.plan, op.tally
        seg = {"segment": op.segment}
        try:
            for i in range(len(plan)):
                t0b = time.perf_counter()
                pk_id = op.stage_ids[i]["pack"]
                if captured is None:
                    host_flat, pooled_buf, _info = stage(
                        None, plan, i, self._pool
                    )
                else:
                    if isinstance(captured[i], Pieces):
                        # the wait the fetch below would make anyway (the
                        # backward pass and the device split still running),
                        # under its own name: d2h is then the copy alone
                        waits = not captured[i].is_ready()
                        with tracer.span(
                            "grad_wait", cat="allreduce", parent=pk_id,
                            bucket=i, **seg,
                        ):
                            captured[i].block_until_ready()
                        if waits:
                            tally.computing_until = time.perf_counter()
                    # what earlier landings have finished with goes back
                    # to the pool before this bucket draws from it
                    self._sweep_parked()
                    if isinstance(captured[i], Pieces):
                        # a step that draws several equal runs finds as
                        # many free buffers at its next round
                        key = (plan.dtypes[i].str, plan.sizes[i])
                        tally.drawn[key] = tally.drawn.get(key, 0) + 1
                        self._pool.keep_at_least(
                            plan.sizes[i], plan.dtypes[i], tally.drawn[key])
                    # bucket 0 carries how long the staging worker took to
                    # get to this op
                    with tracer.span(
                        "d2h", cat="allreduce", parent=pk_id, bucket=i,
                        **seg,
                        **({"queued_us": int((t0b - t_submit) * 1e6)}
                           if i == 0 else {}),
                    ) as sp:
                        t0d = time.perf_counter()
                        host_flat, pooled_buf, info = stage(
                            captured, plan, i, self._pool,
                            tally.still_computing, self._fetch_executor,
                        )
                        sp.args.update(info)
                    if "pooled" in info:
                        op.from_device[i] = True
                        tally.acquired += 1
                        tally.hits += info["pooled"]
                        op.fetched_at = time.perf_counter()
                        tally.d2h.append((t0d, op.fetched_at))
                        tally.fetched_bytes += info["bytes"]
                        tally.fetch_busy_s += info["busy_us"] / 1e6
                payload: Any = host_flat
                if modes[i] != "off":
                    # quantize inside the pack stage so pack_s absorbs the
                    # codec cost and overlap accounting stays honest
                    with tracer.span(
                        "codec", cat="allreduce", parent=pk_id, bucket=i,
                        bytes=host_flat.nbytes, **seg,
                    ) as sp:
                        payload = self._compress_bucket_ef(
                            host_flat, modes[i], plan.dtypes[i], ef_store, i
                        )
                        sp.args["bytes_out"] = _payload_nbytes(payload)
                # a buffer drawn from the pool for this bucket is the
                # pipeline's own and is not touched again before it lands:
                # the group may hand it back as the result (a world of one
                # has nothing to reduce, the ring reduces in it), and _land
                # sees that it did
                donate = pooled_buf is not None and modes[i] == "off"
                with tracer.span(
                    "dispatch", cat="allreduce", parent=pk_id, bucket=i,
                    **seg,
                ):
                    w = self._pg.allreduce([payload], pg_op, donate=donate)
                t1b = time.perf_counter()
                op.marks[i]["pack"] = (t0b, t1b)
                w.get_future().add_done_callback(
                    functools.partial(self._wire_done, op, i, t1b, pooled_buf)
                )
            if tally.acquired:
                # the step's device buckets so far, this op's among them
                self._on_timings(tally.staging_shares())
        except Exception as e:  # noqa: BLE001
            for bf in op.bucket_futs:
                _settle(bf, exc=e)

    def _wire_done(
        self, op: _BucketOp, i: int, t0w: float, pooled_buf: Any, f: Future
    ) -> None:
        """Bucket ``i``'s collective has resolved. On the host plane this
        runs on the PG dispatch thread — keep it tiny: stamp, then hand
        unpack to the unpack worker so the NEXT bucket's wire starts
        immediately. The device plane lands where the callback runs."""
        op.marks[i]["wire"] = (t0w, time.perf_counter())
        host_plane = not self.device_native
        if host_plane:
            # ProcessGroupHost leaves these on its op's future; another
            # PG's has none
            op.wire_runs[i] = getattr(f, "stamps", None)
            op.wire_rings[i] = getattr(f, "ring", None) or {}
        try:
            flat = f.value()[0]
        except Exception as e:  # noqa: BLE001
            _settle(op.bucket_futs[i], exc=e)
            return
        if not host_plane:
            self._land(op, i, flat, None)
            return
        try:
            self._unpack_executor.submit(self._land, op, i, flat, pooled_buf)
        except RuntimeError as e:  # shutdown
            _settle(op.bucket_futs[i], exc=e)

    def _land(
        self, op: _BucketOp, i: int, flat: Any, pooled_buf: Any
    ) -> None:
        """Stage 3, off the PG dispatch thread: slice + landing + AVG divide
        (:func:`land_reduced`) for ONE bucket. A failure here fails the
        aggregate via the join; earlier buckets' landed slices are only
        reachable through the aggregate, so a mid-stream error can never
        leak a partially-applied reduction."""
        try:
            t0u = time.perf_counter()
            tokens: Optional[List[Any]] = None
            # a PG that hands its input back as its result (a world of
            # one, given a donated buffer): the landed leaves may be views
            # of, or transfers still reading, the staging buffer
            passed_through = (
                pooled_buf is not None
                and isinstance(flat, np.ndarray)
                and np.shares_memory(flat, pooled_buf)
            )
            op.passed_through[i] = passed_through
            # the bucket's first unpack child carries how long it sat
            # behind earlier buckets on the one unpack worker (device
            # plane: unpack runs in the wire's callback)
            first = {"queued_us": int(
                (t0u - op.marks[i]["wire"][1]) * 1e6
            )} if "wire" in op.marks[i] else {}

            @contextlib.contextmanager
            def span(name: str, **args: Any) -> Any:
                args.update(first)
                first.clear()
                if name == "h2d":
                    args["passed_through"] = int(passed_through)
                t0 = time.perf_counter()
                with self._tracer.span(
                    name, cat="allreduce",
                    parent=op.stage_ids[i]["unpack"], bucket=i,
                    segment=op.segment, **args,
                ) as sp:
                    yield sp
                if name in ("h2d", "divide"):
                    op.landings.append((t0, time.perf_counter()))

            if is_compressed_wire(flat):
                # the bucket rode the wire compressed; the codes carry the
                # reduced SUM, restored here at the plan's bucket dtype so
                # slice/land/divide below run the exact uncompressed
                # expressions
                with span("decode", bytes=_payload_nbytes(flat)):
                    flat = decompress_bucket(flat)
            divided: List[Any] = []  # the division's token, if it ran
            pairs = land_reduced(
                flat, op.leaves, op.plan, i, op.divisor, op.place, span,
                divided,
            )
            if pooled_buf is not None and not op.final.done():
                # recycle this bucket's staging buffer: on success only (an
                # op that already failed or timed out drops it: its wire
                # thread may still read the buffer). The moment it lands
                # where the result is memory of its own; where the PG passed
                # the buffer through, once what landed from it has read it
                if passed_through:
                    with span("recycle", leaves=len(pairs)):
                        tokens = self._recycle(
                            pooled_buf, [v for _, v in pairs], divided)
                else:
                    self._pool.release(pooled_buf)
            if self._tracer.enabled and not self.device_native:
                # the device's milestone of this bucket (an instant: a link
                # of the watcher's chain): its leaves, divided, are in HBM.
                # The token that recycling parked the buffer on, or the
                # division's; where there is neither (a SUM whose result
                # was a copy), one of this line's own on the landed device
                # leaves (the caller's update donates the leaves themselves)
                on_device = [v for _, v in pairs if lives_on_device(v)]
                token = (tokens or divided or [None])[-1]
                if token is None and on_device:
                    token = _landed_token()(on_device)
                if token is not None:
                    self._tracer.when_ready(
                        "landed", "device", token, span=False,
                        parent=op.stage_ids[i]["unpack"], bucket=i,
                        segment=op.segment,
                    )
            op.marks[i]["unpack"] = (t0u, time.perf_counter())
            _settle(op.bucket_futs[i], pairs)
        except Exception as e:  # noqa: BLE001
            _settle(op.bucket_futs[i], exc=e)

    def _recycle(
        self, buf: np.ndarray, landed: Sequence[Any],
        divided: Optional[List[Any]] = None,
    ) -> Optional[List[Any]]:
        """``buf``, which its collective handed back as the result, has
        landed as ``landed``: into the pool now if nothing reads it any
        more, parked while a transfer may (:func:`readers_of`, whose tokens
        it returns), dropped if a landed leaf is a slice of it. Before the
        bucket's future settles, while the leaves are still the pipeline's
        alone; it dispatches and does not wait."""
        tokens = readers_of(buf, landed, divided)
        if tokens is None:
            return None
        if not tokens:
            self._pool.release(buf)
            return tokens
        with self._parked_lock:
            self._parked.append((buf, tokens))
        return tokens

    def _sweep_parked(self) -> None:
        """Give back to the pool every parked buffer whose landed leaves
        are ready: the transfers that read it are over. It asks and never
        waits, so a buffer still being read stays parked, and the bucket
        that wanted it allocates (a second step in flight)."""
        done: List[Tuple[np.ndarray, List[Any]]] = []
        with self._parked_lock:
            parked, self._parked = self._parked, []
            for entry in parked:
                ready = all(t.is_ready() for t in entry[1])
                (done if ready else self._parked).append(entry)
        for buf, _tokens in done:
            self._pool.release(buf)

    def record_timings(self, op: _BucketOp) -> None:
        """Fold one resolved op's per-bucket stage marks into its step's
        (the ops of a step are described together) and those into
        ``on_timings``: summed ``allreduce_pack_s`` /
        ``allreduce_wire_s`` / ``allreduce_unpack_s``, the bucket count, and
        ``overlap_efficiency`` — the fraction of total wire time that ran
        concurrently with OTHER buckets' pipeline stages of the same op (a lower bound on
        the real win: overlap with the caller's own compute, e.g. the next
        microbatch's grad_fn, is invisible from here); and record the stage
        spans, which are known only now, from the same marks."""
        tally = op.tally
        mine = _pipeline_overlap_stats(op.marks)
        hidden_s = mine.pop("overlap_efficiency") * mine["allreduce_wire_s"]
        with tally.lock:
            for key, value in mine.items():
                tally.stage_sums[key] = tally.stage_sums.get(key, 0.0) + value
            tally.hidden_s += hidden_s
            tally.from_device += sum(op.from_device)
            tally.passed_through += sum(
                p for p, d in zip(op.passed_through, op.from_device) if d)
            # of the landings' seconds, those before the op's own last
            # fetch had ended: run k landing while run k+1 is fetched (an
            # op of one run, or of host leaves: none)
            fetched = op.fetched_at
            tally.land_s += sum(t1 - t0 for t0, t1 in op.landings)
            if fetched is not None:
                tally.land_under_fetch_s += sum(
                    max(0.0, min(t1, fetched) - t0) for t0, t1 in op.landings)
            stats = dict(tally.stage_sums)
            wire_s = stats["allreduce_wire_s"]
            stats["overlap_efficiency"] = (
                tally.hidden_s / wire_s if wire_s > 0 else 0.0)
            stats["land_under_fetch_share"] = (
                tally.land_under_fetch_s / tally.land_s
                if tally.land_s > 0 else 0.0)
            if tally.from_device:
                # of the device buckets, those whose collective resolved to
                # the staging buffer it was given: nothing was copied on
                # the wire
                stats["wire_passthrough_share"] = (
                    tally.passed_through / tally.from_device)
            # the fewest connections to a ring neighbour that any ring of
            # the step rode (process_group._RING_LANES; 1 under its floor,
            # at a world of one, or where the native fold is missing)
            lanes = [r["lanes"] for r in op.wire_rings if "lanes" in r]
            if lanes:
                tally.ring_lanes = min(tally.ring_lanes or lanes[0], *lanes)
            if tally.ring_lanes:
                stats["ring_lanes"] = float(tally.ring_lanes)
            # the rings' own account, where a plain ring ran: the wait from
            # the run's start to its first peer byte, and the ring's terms
            for run, ring in zip(op.wire_runs, op.wire_rings):
                if run is None or "t_first" not in ring:
                    continue
                for key in RING_KEYS:
                    term = key[len("ring_"):-len("_s")]
                    tally.ring_s[key] = tally.ring_s.get(key, 0.0) + (
                        ring["t_first"] - run[1] if term == "entry_wait"
                        else ring[term + "_us"] / 1e6)
            stats.update(tally.ring_s)
        self._on_timings(stats)
        for i, mark in enumerate(op.marks):
            for name in ("pack", "wire", "unpack"):
                if name not in mark:
                    continue
                t0_pc, t1_pc = mark[name]
                self._tracer.record_rel(
                    name, cat="allreduce", t0_pc=t0_pc, t1_pc=t1_pc,
                    id=op.stage_ids[i][name], parent=op.parent, bucket=i,
                    segment=op.segment,
                )
            run = op.wire_runs[i]
            if run is not None:
                # what the PG's dispatch thread did for this bucket, from
                # the stamps it left on the op's future: fn(comm) alone
                # (at a world of one the donated buffer handed back, or a
                # copy of what was not donated; the ring otherwise, which
                # says whether it reduced in the donated buffer and in how
                # many frames a hop); the time the op sat in its queue
                # behind earlier buckets is an arg
                t_enq, t_run0, t_run1 = run
                ring = dict(op.wire_rings[i])
                t_first = ring.pop("t_first", None)
                account = {k: ring.pop(k) for k in list(ring)
                           if k.endswith(("_us", "_us_max"))}
                run_id = self._tracer.new_id() if t_first is not None else None
                self._tracer.record_rel(
                    "wire_run", "allreduce", t_run0, t_run1, id=run_id,
                    parent=op.stage_ids[i]["wire"], bucket=i,
                    segment=op.segment, bytes=op.bucket_bytes[i],
                    world=self._pg.size(),
                    queued_us=int((t_run0 - t_enq) * 1e6),
                    **ring,
                )
                if t_first is None:
                    continue
                # a plain ring's wire_run in two, from the ring's own
                # stamps: until the first peer byte was here (the left
                # neighbour had not entered), and from then to the end,
                # with where the lanes' threads spent it (mean and largest
                # lane, microseconds) and the rate the bytes imply
                self._tracer.record_rel(
                    "ring_entry_wait", "allreduce", t_run0, t_first,
                    parent=run_id, bucket=i, segment=op.segment,
                )
                self._tracer.record_rel(
                    "ring_stream", "allreduce", t_first, t_run1,
                    parent=run_id, bucket=i, segment=op.segment,
                    bytes=op.bucket_bytes[i], lanes=ring["lanes"],
                    chunks=ring["chunks"],
                    gb_s=round(op.bucket_bytes[i] / 1e9
                               / max(t_run1 - t_first, 1e-9), 3),
                    **account,
                )

    # ------------------------------------------------------- compression
    def _bucket_residuals(self, plan: BucketPlan) -> List[Any]:
        """Per-bucket error-feedback residual slots for one plan.

        Keyed by plan identity (plans are cached and reused every step, so
        the same tree keeps the same slots); weakref-keyed so an evicted
        plan drops its residual buffers with it. Slots start None and are
        allocated from the BufferPool on first compression."""
        with self._ef_lock:
            store = self._ef_residuals.get(plan)
            if store is None:
                store = [None] * len(plan)
                self._ef_residuals[plan] = store
            return store

    def _compress_bucket_ef(
        self,
        host_flat: np.ndarray,
        mode: str,
        out_dtype: Any,
        store: Optional[List[Any]],
        i: int,
    ) -> Any:
        """Quantize one packed bucket for the wire, with error feedback.

        The residual — everything rowwise quantization rounded away this
        step — is carried into the NEXT step's bucket before quantizing,
        so the compression error stays bounded (standard EF-SGD) instead
        of accumulating across LocalSGD/DiLoCo syncs. ``store`` is None
        for non-participants (zero contribution, nothing to feed back).
        Runs on the single staging worker, so residual updates for one
        plan never race."""
        resid = store[i] if store is not None else None
        if resid is not None:
            # one fused pass: the add IS the private f32 copy
            work = host_flat + resid
        else:
            work = np.asarray(host_flat, dtype=np.float32)
        wire = compress_bucket(work, mode, dtype=out_dtype)
        if store is not None:
            resid = store[i]
            if resid is None:
                resid = self._pool.acquire(work.size, np.float32)
                store[i] = resid
            np.subtract(
                work, decompress_bucket(wire, np.float32), out=resid
            )
        return wire

    # ---------------------------------------------------- without a plan
    def allreduce_leaves(
        self,
        leaves: Sequence[Any],
        pg_op: Any,
        *,
        quantize: bool,
        participating: bool,
        divisor: Optional[int],
        place: Callable[[Any, Any], Any],
        timeout: float,
        parent: Optional[int] = None,
        segment: int = 0,
    ) -> Future:
        """The no-plan path: ONE collective carrying every leaf (or, with
        ``quantize``, ``collectives.allreduce_quantized``: never
        pre-bucketed — it already concatenates into one flat wire buffer,
        and packing first would shift the fp8 rowwise-scale boundaries).
        Resolves to the landed leaves, in leaf order, each through
        :func:`land_reduced` as a bucket of the pipeline is."""
        self._count_runs(1)
        if self.device_native:
            fut = self._issue_leaves_on_device(
                leaves, pg_op, quantize, participating
            )
        else:
            fut = Future()
            self.submit(
                self._leaf_stager(
                    leaves, pg_op, quantize, participating, timeout, parent,
                    segment, fut,
                ),
                fut,
                timeout,
            )

        def land(f: Future) -> List[Any]:
            out: List[Any] = [None] * len(leaves)
            for b, flat in enumerate(f.value()):
                for i, v in land_reduced(flat, leaves, None, b, divisor, place):
                    out[i] = v
            return out

        return fut.then(land)

    def _issue_leaves_on_device(
        self, leaves: Sequence[Any], pg_op: Any, quantize: bool,
        participating: bool,
    ) -> Future:
        import jax
        import jax.numpy as jnp

        dev_leaves = [
            l if isinstance(l, jax.Array) else jnp.asarray(l) for l in leaves
        ]
        if not participating:
            dev_leaves = [jnp.zeros_like(h) for h in dev_leaves]
        if quantize:
            from torchft_tpu.collectives import allreduce_quantized

            work = allreduce_quantized(dev_leaves, pg_op, self._pg)
        else:
            work = self._pg.allreduce(dev_leaves, pg_op)
        return work.get_future()

    def _leaf_stager(
        self,
        leaves: Sequence[Any],
        pg_op: Any,
        quantize: bool,
        participating: bool,
        timeout: float,
        parent: Optional[int],
        segment: int,
        staged_fut: Future,
    ) -> Callable[[], None]:
        """Capture ``leaves`` now, on the caller's thread (the staging
        thread reads them AFTER allreduce() returns, by which time the
        caller's next jitted step may have donated the device buffers or
        overwritten a reused numpy buffer), and return what the staging
        thread runs. jax.Arrays get a device-side copy (HBM bandwidth, async
        dispatch — far cheaper than blocking the train loop on the D2H
        transfer); numpy leaves get a host memcpy. Non-participants skip
        the capture entirely — they contribute zeros built from shapes
        alone (the reference zeroes the buffer in place; arrays are
        immutable here)."""
        import jax
        import jax.numpy as jnp

        captured = (
            [
                jnp.copy(l) if isinstance(l, jax.Array)
                else np.array(l, copy=True)
                for l in leaves
            ]
            if participating
            else None
        )
        # a non-participant's contribution: zeros, from shapes alone
        zero_specs = (
            None if participating
            else [(np.shape(l), leaf_dtype(l)) for l in leaves]
        )
        tracer = self._tracer

        def stage_leaves() -> None:
            """D2H + dispatch only — the PG's own ordered worker runs the
            wire, and the result chains in via callback. Blocking here
            would serialize overlapped allreduces on this one thread and
            charge queue time against later calls' deadlines. EXCEPTION:
            the quantized exchange runs to completion here — its alltoall
            and allgather must be issued in staged order (they would
            otherwise race other staged ops from its helper thread), and
            quantized syncs are rare boundary events (DiLoCo) where the
            serialization is acceptable."""
            if captured is None:
                host_leaves = [np.zeros(s, d) for s, d in zero_specs]
            elif quantize:
                # jax copies stay as they are: single-device trees take
                # the Pallas engine
                host_leaves = captured
            else:
                with tracer.span(
                    "d2h", cat="allreduce", parent=parent, segment=segment
                ) as sp:
                    host_leaves = [np.asarray(l) for l in captured]
                    sp.args["bytes"] = sum(h.nbytes for h in host_leaves)
            if quantize:
                from torchft_tpu.collectives import allreduce_quantized

                w = allreduce_quantized(host_leaves, pg_op, self._pg)
                _settle(staged_fut, w.get_future().wait(timeout))
                return
            with tracer.span(
                "dispatch", cat="allreduce", parent=parent, segment=segment
            ):
                w = self._pg.allreduce(host_leaves, pg_op)
            w.get_future().add_done_callback(
                functools.partial(_settle_from, staged_fut)
            )

        return stage_leaves


# ---------------------------------------------------------------------------
# list-of-(flat, metas) API — the shape local_sgd.py's fragment sync (and its
# tests) use; kept as thin wrappers over the plan machinery so there is one
# grouping/packing implementation.


def make_buckets(arrays: List[Any], cap_bytes: int) -> List[tuple]:
    """Pack arrays into flat same-dtype buckets of at most ``cap_bytes``.

    Returns ``[(flat_buffer, metas), ...]`` with ``metas = [(arr_index,
    offset, size, shape), ...]``.
    """
    plan = build_plan(arrays, cap_bytes)
    flats, _pooled = pack(arrays, plan)
    return list(zip(flats, plan.metas))


def pack_group(arrays: List[Any], idxs: List[int]) -> tuple:
    """Pack one explicit index group into ``(flat, metas)``."""
    import jax

    metas: List[Meta] = []
    offset = 0
    for i in idxs:
        a = arrays[i]
        metas.append((i, offset, _leaf_size(a), _leaf_shape(a)))
        offset += _leaf_size(a)
    if all(isinstance(arrays[i], jax.Array) for i in idxs):
        import jax.numpy as jnp

        flat = jnp.concatenate([arrays[i].reshape(-1) for i in idxs])
    else:
        flat = np.empty(offset, dtype=leaf_dtype(arrays[idxs[0]]))
        for (i, off, size, _shape) in metas:
            flat[off : off + size] = np.asarray(arrays[i]).reshape(-1)
    return flat, metas


def unpack_buckets(
    buckets_out: List[Any], bucket_metas: List[List[tuple]], n: int
) -> List[Any]:
    """Inverse of :func:`make_buckets` over reduced flats."""
    import jax

    out: List[Optional[Any]] = [None] * n
    for flat, metas in zip(buckets_out, bucket_metas):
        if not isinstance(flat, jax.Array):
            flat = np.asarray(flat)
        for (i, off, size, shape) in metas:
            out[i] = flat[off : off + size].reshape(shape)
    assert all(o is not None for o in out)
    return out  # type: ignore[return-value]
