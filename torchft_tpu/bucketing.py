"""Shared pytree bucketing for the managed data plane.

One bucketing implementation for every consumer — ``Manager.allreduce``,
``ddp.PureDistributedDataParallel``, and DiLoCo's fragment sync
(local_sgd.py) — so a pytree of hundreds of leaves becomes a handful of
flat same-dtype collectives on both the host ring and the XLA plane.
Fewer, larger collectives amortize the per-op framing/pickling overhead of
the host DCN plane — the same motivation as the reference's bucketized
allreduce (local_sgd.py:498-566), minus the NCCL-launch angle which does
not exist on TPU.

Three pieces keep the steady-state step allocation-free:

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

import functools
import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = [
    "DEFAULT_BUCKET_CAP_BYTES",
    "BucketPlan",
    "BufferPool",
    "build_plan",
    "plan_for",
    "pack",
    "Pieces",
    "fetch_into",
    "FETCH_PIECE_BYTES",
    "unpack",
    "unpack_bucket",
    "make_buckets",
    "pack_group",
    "unpack_buckets",
]

# 1 GiB default bucket cap (reference: local_sgd.py:176)
DEFAULT_BUCKET_CAP_BYTES = 1 << 30

# metas entry: (leaf_index, offset_elems, size_elems, shape)
Meta = Tuple[int, int, int, Tuple[int, ...]]


def _leaf_dtype(leaf: Any) -> np.dtype:
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


def _leaf_shape(leaf: Any) -> Tuple[int, ...]:
    shape = getattr(leaf, "shape", None)
    if shape is not None:
        return tuple(shape)
    return tuple(np.shape(leaf))


class BucketPlan:
    """Bucket membership + unpack metadata for one leaf list.

    A plan is a pure function of the leaves' (shape, dtype) sequence and the
    cap — it holds no array data, so one plan serves every step of a
    training loop over the same tree.
    """

    # __weakref__ lets the Manager key per-bucket error-feedback residuals
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
        by_dtype.setdefault(_leaf_dtype(leaf), []).append(i)
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
        spec = tuple((str(_leaf_dtype(l)), _leaf_shape(l)) for l in leaves)
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
        self.hits = 0
        self.misses = 0

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
            if len(bucket) < self._max_per_key:
                bucket.append(buf)


# A device bucket reaches the host as pieces of at most this many bytes,
# every piece's transfer issued at the capture. TPU v5e, a 0.973 GB bf16
# bucket into a buffer written before, GB/s (chip runs, PR 29; np.asarray of
# the whole bucket: 0.63): with freed blocks kept mapped
# (_keep_freed_blocks_mapped) 8 MiB 8.4-8.6, 16 MiB 8.7-9.6, 32 MiB (glibc
# mmaps it again) 6.7; as glibc comes 2.1-3.3 at any size up to 32 MiB, and
# 1.0 at 128 MiB.
FETCH_PIECE_BYTES = 16 << 20
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
    and returned as before. False where libc is not glibc; the fetch then
    runs at the first rate."""
    import ctypes

    try:
        mallopt = ctypes.CDLL("libc.so.6").mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes, mallopt.restype = [ctypes.c_int, ctypes.c_int], ctypes.c_int
    m_trim_threshold, m_mmap_threshold = -1, -3  # <malloc.h>
    return bool(
        mallopt(m_mmap_threshold, 32 << 20)
        and mallopt(m_trim_threshold, 2**31 - 1)
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


def fetch_into(pieces: Pieces, out: np.ndarray) -> int:
    """Copy a captured device bucket into ``out`` (1-D, ``pieces.size``
    elements: afterwards bitwise ``np.asarray`` of the packed flat) and
    return how many pieces it came in. Every piece's transfer has been in
    flight since the capture, so this waits for each in turn, copies it to
    its offset and drops it with its device buffer. What the runtime
    allocated per transfer is piece-sized and reused from step to step
    (:func:`_keep_freed_blocks_mapped`); the only bucket-sized host memory
    is ``out``, which the caller takes from a :class:`BufferPool` so that
    its pages are mapped from the second step on."""
    arrays = pieces.arrays
    for k, (a, b) in enumerate(pieces.bounds):
        np.copyto(out[a:b], np.asarray(arrays[k]))
        arrays[k] = None
    return len(arrays)


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
        flat = np.empty(offset, dtype=_leaf_dtype(arrays[idxs[0]]))
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
