"""Quantized collectives: fp8-compressed allreduce / reduce-scatter.

Algorithm mirror of the reference (torchft/collectives.py:159-415): quantize
to rowwise-scaled fp8, alltoall so each rank owns one chunk, dequantize +
reduce locally in f32, requantize, allgather the reduced chunks, dequantize.
SUM and AVG only. Cuts the replicated-dim wire traffic ~4x vs f32 — on a
TPU fleet this is DCN bandwidth between replica groups, usually the
scarcest link.

Three quantization engines behind one wire format (uint8 fp8 payload + f32
row scales + element count):

- **device (Pallas)**: single-device ``jax.Array`` trees run the
  quantize / dequantize+reduce / requantize stages as the fused Pallas
  kernels (ops/quantization.py) on the accelerator — matching the
  reference's Triton kernels (torchft/quantization.py:531-686 called from
  collectives.py:297-415). Only the ~1 byte/element compressed payload
  crosses to the host for the wire, so D2H traffic drops ~4x too.
- **SPMD (shard_map + Pallas)**: mesh-sharded leaves (fsdp-sharded DiLoCo
  pseudogradients) quantize shard-locally — the Pallas kernel is
  shard_map'ed over each leaf's own mesh, so the full f32 buffer never
  leaves its sharding; the reduced result lands back on the same
  mesh/spec. A layout signature rides the wire so ranks with divergent
  shardings fail loudly instead of reducing misaligned chunks.
- **host (numpy)**: fallback for numpy inputs (and any mixed pytree).

The pipeline runs on a worker thread (reference `_QuantizedOpFuture`,
collectives.py:139-156) and resolves a Work future with the reduced arrays.
"""

from __future__ import annotations

import threading
from typing import Any, List, Sequence

import numpy as np

from torchft_tpu.ops.quantization import (
    compress_bucket,
    decompress_bucket,
    dequantize_fp8_rowwise,
    fused_dequantize_fp8,
    fused_quantize_fp8,
    quantize_fp8_rowwise,
)
from torchft_tpu.process_group import ProcessGroup, ReduceOp
from torchft_tpu.work import Future, FutureWork, Work

__all__ = [
    "allreduce_compressed",
    "allreduce_quantized",
    "is_device_tree",
    "reduce_scatter_quantized",
]

_ROW = 512


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def is_device_tree(arrays: Sequence[Any]) -> bool:
    """True iff every leaf is a jax.Array (any sharding).

    Single-device trees run the fused Pallas engine on the global flat
    buffer. Mesh-sharded leaves (NamedSharding over >1 device — e.g.
    fsdp-sharded DiLoCo pseudogradients) run the SPMD engine: the Pallas
    quantize kernel is shard_map'ed over each leaf's own mesh, so every
    device compresses its local shard in place and only the ~1
    byte/element fp8 payload ever crosses D2H (the reference keeps its
    fp8 pipeline on-accelerator the same way,
    torchft/quantization.py:531-686 via collectives.py:297-415). Leaves
    whose sharded dims don't divide evenly fall back to the host engine
    at call time (shard_map needs even shards).
    """
    import jax

    return bool(arrays) and all(isinstance(a, jax.Array) for a in arrays)


def _flatten(arrays: Sequence[Any]) -> tuple[np.ndarray, List[tuple], List[np.dtype]]:
    hosts = [np.asarray(a) for a in arrays]
    shapes = [h.shape for h in hosts]
    dtypes = [h.dtype for h in hosts]
    flat = (
        np.concatenate([h.astype(np.float32).reshape(-1) for h in hosts])
        if hosts
        else np.zeros(0, np.float32)
    )
    return flat, shapes, dtypes


def _unflatten(flat: np.ndarray, shapes, dtypes) -> List[np.ndarray]:
    out = []
    off = 0
    for shape, dtype in zip(shapes, dtypes):
        size = int(np.prod(shape)) if shape else 1
        out.append(flat[off : off + size].reshape(shape).astype(dtype))
        off += size
    return out


def _run_async(fn) -> Work:
    fut: Future[Any] = Future()

    def runner():
        try:
            fut.set_result(fn())
        except BaseException as e:  # noqa: BLE001
            try:
                fut.set_exception(e)
            except RuntimeError:
                pass

    threading.Thread(target=runner, daemon=True, name="torchft_quant_coll").start()
    return FutureWork(fut)


def _flatten_jax(arrays: Sequence[Any]):
    import jax.numpy as jnp

    shapes = [a.shape for a in arrays]
    dtypes = [a.dtype for a in arrays]
    flat = jnp.concatenate([a.astype(jnp.float32).reshape(-1) for a in arrays])
    return flat, shapes, dtypes


def _unflatten_jax(flat, shapes, dtypes) -> List[Any]:
    out = []
    off = 0
    for shape, dtype in zip(shapes, dtypes):
        size = 1
        for s in shape:
            size *= s
        out.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return out


def _wire_from_device(q, scales, n: int):
    """Device fp8 (rows, row) + scales (rows, 1) -> host wire tuple
    (uint8 payload, f32 scales, n). The only D2H transfer is the ~1
    byte/element compressed payload."""
    return (
        np.asarray(q).view(np.uint8),
        np.asarray(scales).reshape(-1),
        n,
    )


def _device_from_wire(tuples: List[tuple], row: int):
    """Stack same-shaped wire tuples, dequantize in ONE fused kernel call,
    return (world, chunk) f32 on device."""
    import jax.numpy as jnp

    from torchft_tpu.ops.quantization import _FP8

    world = len(tuples)
    qs = np.stack([np.asarray(t[0]).view(_FP8) for t in tuples])  # (w, rows, row)
    ss = np.stack([np.asarray(t[1]) for t in tuples])  # (w, rows)
    rows = qs.shape[1]
    deq = fused_dequantize_fp8(
        jnp.asarray(qs).reshape(world * rows, row),
        jnp.asarray(ss).reshape(world * rows, 1),
        world * rows * row,
        row,
    )
    return deq.reshape(world, rows * row)


def _pack_wire_device(q, scales):
    """(rows, row) fp8 + (rows, 1) f32 scales -> ONE flat uint8 device
    array. For device-native PGs the compressed wire must be a single
    array (a jitted XLA collective cannot move host tuples) — and packing
    keeps the whole exchange on device: on hardware the alltoall of the
    ~1 byte/element payload rides ICI/DCN with zero host staging."""
    import jax
    import jax.numpy as jnp

    qb = jax.lax.bitcast_convert_type(q, jnp.uint8).reshape(-1)
    sb = jax.lax.bitcast_convert_type(
        scales.astype(jnp.float32), jnp.uint8
    ).reshape(-1)
    return jnp.concatenate([qb, sb])


def _unpack_dequant_device(bufs, rows: int, row: int):
    """Inverse of _pack_wire_device over a list of same-shape wires:
    dequantize all in ONE fused kernel call; returns (len(bufs), rows*row)
    f32 on device."""
    import jax
    import jax.numpy as jnp

    world = len(bufs)
    stacked = jnp.stack([jnp.asarray(b) for b in bufs])  # (w, nbytes) u8
    qb = stacked[:, : rows * row].reshape(world * rows, row)
    sb = stacked[:, rows * row:].reshape(world * rows, 1, 4)
    q = jax.lax.bitcast_convert_type(qb, jnp.float8_e4m3fn)
    s = jax.lax.bitcast_convert_type(sb, jnp.float32).reshape(world * rows, 1)
    deq = fused_dequantize_fp8(q, s, world * rows * row, row)
    return deq.reshape(world, rows * row)


def _reduce_scatter_core_device(flat, op: ReduceOp, pg: ProcessGroup, row: int):
    """Device-path pipeline: pad so chunks are whole fp8 rows, quantize the
    whole buffer in one Pallas call, slice per destination for the wire,
    then dequantize+reduce the received chunks on device.

    Wire format by PG plane: device-native PGs exchange packed uint8
    device arrays (the collective stays on device end to end); host PGs
    get the host tuple wire (uint8 payload, f32 scales, n)."""
    import jax.numpy as jnp

    world = pg.size()
    device_pg = bool(getattr(pg, "device_native", False))
    chunk_rows = max(1, _ceil_div(_ceil_div(int(flat.size), world), row))
    chunk = chunk_rows * row
    padded = jnp.zeros((chunk * world,), jnp.float32).at[: flat.size].set(flat)
    q, scales, _ = fused_quantize_fp8(padded, row)  # (world*chunk_rows, row)
    if device_pg:
        sends = [
            _pack_wire_device(
                q[r * chunk_rows:(r + 1) * chunk_rows],
                scales[r * chunk_rows:(r + 1) * chunk_rows],
            )
            for r in range(world)
        ]
        recvd = pg.alltoall(sends).get_future().wait()
        deq = _unpack_dequant_device(list(recvd), chunk_rows, row)
    else:
        sends = [
            _wire_from_device(
                q[r * chunk_rows:(r + 1) * chunk_rows],
                scales[r * chunk_rows:(r + 1) * chunk_rows],
                chunk,
            )
            for r in range(world)
        ]
        recvd = pg.alltoall(sends).get_future().wait()
        deq = _device_from_wire(list(recvd), row)  # (world, chunk) f32
    acc = deq.sum(axis=0)
    if op == ReduceOp.AVG:
        acc = acc / world
    return acc, chunk, chunk_rows


def _allreduce_quantized_device(flat, shapes, dtypes, op, pg, row):
    world = pg.size()
    device_pg = bool(getattr(pg, "device_native", False))
    acc, chunk, chunk_rows = _reduce_scatter_core_device(flat, op, pg, row)

    q, scales, _ = fused_quantize_fp8(acc, row)
    if device_pg:
        gathered = pg.allgather([_pack_wire_device(q, scales)]) \
            .get_future().wait()
        deq = _unpack_dequant_device([g[0] for g in gathered], chunk_rows, row)
    else:
        gathered = pg.allgather([_wire_from_device(q, scales, chunk)]) \
            .get_future().wait()
        deq = _device_from_wire([g[0] for g in gathered], row)  # (w, chunk)
    out = deq.reshape(world * chunk)[: flat.size]
    return _unflatten_jax(out, shapes, dtypes)


# ---------------------------------------------------------------------------
# SPMD engine: mesh-sharded leaves quantize shard-locally via shard_map
# ---------------------------------------------------------------------------
class _UnevenSharding(Exception):
    """Leaf's sharded dims don't divide evenly; caller falls back to host."""


def _sharded_axes(spec) -> tuple:
    """Flatten a PartitionSpec into the ordered tuple of mesh axis names it
    shards over (the rows-layout order of the wire)."""
    axes: List[Any] = []
    for part in spec:
        if part is None:
            continue
        if isinstance(part, tuple):
            axes.extend(part)
        else:
            axes.append(part)
    return tuple(axes)


def _leaf_plan(a, row: int):
    """Per-leaf wire plan: how this leaf's rows lay out on the wire.

    kind "sharded": quantized shard-locally (mesh-order row stacking);
    kind "single": quantized on the leaf's one device (or replicated).
    """
    import jax
    from jax.sharding import NamedSharding

    sh = a.sharding
    if isinstance(sh, NamedSharding):
        axes = _sharded_axes(sh.spec)
        n_shards = 1
        for ax in axes:
            n_shards *= sh.mesh.shape[ax]
        if n_shards > 1:
            try:
                # shard_shape raises when a sharded dim doesn't divide
                # evenly — exactly the shapes shard_map can't handle
                local_shape = sh.shard_shape(a.shape)
            except ValueError as e:
                raise _UnevenSharding(str(e)) from None
            local_n = 1
            for s in local_shape:
                local_n *= s
            local_rows = max(1, _ceil_div(local_n, row))
            return {
                "kind": "sharded",
                "sharding": sh,
                "axes": axes,
                "local_shape": local_shape,
                "local_n": local_n,
                "rows": local_rows * n_shards,
                "shape": a.shape,
                "dtype": a.dtype,
            }
    n = int(a.size)
    return {
        "kind": "single",
        "sharding": sh,
        "n": n,
        "rows": max(1, _ceil_div(n, row)),
        "shape": a.shape,
        "dtype": a.dtype,
    }


def _quantize_leaf(a, plan, row: int):
    """Quantize one leaf per its plan; returns host (uint8 rows, f32 scales).

    Sharded leaves never materialize off their mesh: shard_map runs the
    Pallas quantize kernel on each device's own shard, and the only D2H is
    np.asarray on the fp8 output."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    if plan["kind"] == "sharded":
        sh = plan["sharding"]
        axes = plan["axes"]

        def local(x):
            q, s, _ = fused_quantize_fp8(x.reshape(-1), row)
            return q, s

        q, s = shard_map(
            local,
            mesh=sh.mesh,
            in_specs=(sh.spec,),
            out_specs=(P(axes, None), P(axes, None)),
            check_vma=False,
        )(a)
    else:
        q, s, _ = fused_quantize_fp8(a.reshape(-1), row)
    return np.asarray(q).view(np.uint8), np.asarray(s).reshape(-1)


def _reconstruct_leaf(q_rows: np.ndarray, scales: np.ndarray, plan, row: int):
    """Inverse of _quantize_leaf: land the reduced fp8 rows back on the
    leaf's own mesh (sharded H2D of compressed bytes, then a shard-local
    Pallas dequantize into the original spec)."""
    import jax
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torchft_tpu.ops.quantization import _FP8

    if plan["kind"] == "sharded":
        sh = plan["sharding"]
        axes = plan["axes"]
        rows_sharding = NamedSharding(sh.mesh, P(axes, None))
        dq = jax.device_put(q_rows.view(_FP8), rows_sharding)
        ds = jax.device_put(
            scales.reshape(-1, 1).astype(np.float32), rows_sharding
        )
        local_n, local_shape, dtype = (
            plan["local_n"], plan["local_shape"], plan["dtype"],
        )

        def local(qv, sv):
            flat = fused_dequantize_fp8(qv, sv, local_n, row)
            return flat.reshape(local_shape).astype(dtype)

        return shard_map(
            local,
            mesh=sh.mesh,
            in_specs=(P(axes, None), P(axes, None)),
            out_specs=sh.spec,
            check_vma=False,
        )(dq, ds)

    import jax.numpy as jnp

    flat = fused_dequantize_fp8(
        jnp.asarray(q_rows.view(_FP8)),
        jnp.asarray(scales.reshape(-1, 1).astype(np.float32)),
        plan["n"],
        row,
    )
    out = flat.reshape(plan["shape"]).astype(plan["dtype"])
    return jax.device_put(out, plan["sharding"])


def _allreduce_quantized_sharded(arrays, op: ReduceOp, pg: ProcessGroup,
                                 row: int, plans=None):
    """SPMD fp8 allreduce for trees with mesh-sharded leaves.

    Wire layout: per-leaf row blocks, each leaf's rows stacked in its
    mesh-iteration shard order. Every rank must hold identically-sharded
    leaves (the SPMD contract — same program, same meshes); the layout
    signature rides the wire so a divergent peer fails loudly instead of
    reducing misaligned chunks."""
    import zlib

    world = pg.size()
    if plans is None:
        plans = [_leaf_plan(a, row) for a in arrays]
    parts = [_quantize_leaf(a, p, row) for a, p in zip(arrays, plans)]
    Q = np.concatenate([q for q, _ in parts], axis=0)  # (total_rows, row) u8
    S = np.concatenate([s for _, s in parts])  # (total_rows,)
    total_rows = Q.shape[0]
    # The signature must pin the full element ordering, not just the row
    # counts: two shardings of the same leaf (e.g. P(('fsdp','tp'), None)
    # vs P('fsdp','tp') on a 2x2 mesh) produce identical row counts but
    # different shard-local flattening orders — equal-rows collisions
    # would reduce misaligned elements silently.
    sig = zlib.crc32(
        repr((row, world, [
            (p["kind"], p.get("axes"), tuple(p["shape"]),
             p.get("local_shape"), str(p["dtype"]), p["rows"])
            for p in plans
        ])).encode()
    )

    chunk_rows = _ceil_div(total_rows, world)
    pad_rows = chunk_rows * world - total_rows
    if pad_rows:
        Q = np.concatenate([Q, np.zeros((pad_rows, row), np.uint8)], axis=0)
        S = np.concatenate([S, np.ones(pad_rows, np.float32)])
    chunk = chunk_rows * row
    device_pg = bool(getattr(pg, "device_native", False))

    def _pack_host(q_rows: np.ndarray, s_rows: np.ndarray) -> np.ndarray:
        """Host-side packed wire (same layout as _pack_wire_device, sig
        appended as 4 LE bytes): a device-native PG's jitted collective
        moves single arrays, not host tuples."""
        return np.concatenate([
            q_rows.reshape(-1),
            s_rows.astype(np.float32).view(np.uint8).reshape(-1),
            np.frombuffer(
                int(sig).to_bytes(4, "little"), dtype=np.uint8
            ).copy(),
        ])

    def _unpack_host(buf, n_rows: int):
        """-> (q (rows,row) u8, scales (rows,) f32); verifies the sig."""
        host = np.asarray(buf).view(np.uint8).reshape(-1)
        got_sig = int.from_bytes(bytes(host[-4:]), "little")
        if got_sig != sig:
            raise RuntimeError(
                "quantized-allreduce wire layout mismatch: a peer sent "
                f"signature {got_sig} vs local {sig} — ranks must hold "
                "identically-sharded leaves (same meshes, specs, and leaf "
                "order)"
            )
        q_part = host[: n_rows * row].reshape(n_rows, row)
        s_part = host[n_rows * row:-4].view(np.float32).reshape(n_rows)
        return q_part, s_part

    if device_pg:
        sends = [
            _pack_host(Q[r * chunk_rows:(r + 1) * chunk_rows],
                       S[r * chunk_rows:(r + 1) * chunk_rows])
            for r in range(world)
        ]
        recvd_packed = list(pg.alltoall(sends).get_future().wait())
        recvd = [
            (*_unpack_host(b, chunk_rows), chunk) for b in recvd_packed
        ]
    else:
        sends = [
            (Q[r * chunk_rows:(r + 1) * chunk_rows],
             S[r * chunk_rows:(r + 1) * chunk_rows], chunk, sig)
            for r in range(world)
        ]
        recvd = list(pg.alltoall(sends).get_future().wait())
        for t in recvd:
            if len(t) != 4 or t[3] != sig:
                raise RuntimeError(
                    "quantized-allreduce wire layout mismatch: a peer sent "
                    f"signature {t[3] if len(t) == 4 else '<legacy 3-tuple>'} "
                    f"vs local {sig} — ranks must hold identically-sharded "
                    "leaves (same meshes, specs, and leaf order)"
                )

    # chunk-sized stages run on the default device via the fused kernels
    # (a chunk is 1/world of the compressed buffer — small next to the
    # sharded full buffer the SPMD stages above keep distributed)
    deq = _device_from_wire([t[:3] for t in recvd], row)  # (world, chunk)
    acc = deq.sum(axis=0)
    if op == ReduceOp.AVG:
        acc = acc / world
    q2, s2, _ = fused_quantize_fp8(acc, row)
    q2_host = np.asarray(q2).view(np.uint8)
    s2_host = np.asarray(s2).reshape(-1)
    if device_pg:
        gathered_packed = pg.allgather([_pack_host(q2_host, s2_host)]) \
            .get_future().wait()
        gathered_qs = [
            _unpack_host(g[0], chunk_rows) for g in gathered_packed
        ]
    else:
        gathered = pg.allgather([(q2_host, s2_host, chunk, sig)]) \
            .get_future().wait()
        for g in gathered:
            if len(g[0]) != 4 or g[0][3] != sig:
                raise RuntimeError(
                    "quantized-allreduce wire layout mismatch in allgather"
                )
        gathered_qs = [
            (np.asarray(g[0][0]).view(np.uint8), np.asarray(g[0][1]))
            for g in gathered
        ]

    Qr = np.concatenate([q for q, _ in gathered_qs], axis=0)[:total_rows]
    Sr = np.concatenate(
        [s.reshape(-1) for _, s in gathered_qs]
    )[:total_rows]

    out, off = [], 0
    for plan in plans:
        rows_l = plan["rows"]
        out.append(
            _reconstruct_leaf(Qr[off:off + rows_l], Sr[off:off + rows_l],
                              plan, row)
        )
        off += rows_l
    return out


def _has_multidevice_leaf(arrays: Sequence[Any]) -> bool:
    return any(len(a.sharding.device_set) > 1 for a in arrays)


def _reduce_scatter_core(
    flat: np.ndarray, op: ReduceOp, pg: ProcessGroup, row: int
) -> tuple[np.ndarray, int]:
    """Shared pipeline: pad -> per-dest-chunk quantize -> alltoall -> f32
    accumulate (-> AVG). Returns (this rank's reduced f32 chunk, chunk size).

    Chunks are rounded up to whole fp8 rows — the SAME partitioning as the
    device (Pallas) path, so a quorum where some ranks quantize on device
    and others on host exchanges identically-aligned chunks."""
    world = pg.size()
    chunk = max(1, _ceil_div(_ceil_div(flat.size, world), row)) * row
    padded = np.zeros(chunk * world, np.float32)
    padded[: flat.size] = flat
    sends = []
    for r in range(world):
        q, scales, n = quantize_fp8_rowwise(padded[r * chunk : (r + 1) * chunk], row)
        sends.append((q, scales, n))
    recvd = pg.alltoall(sends).get_future().wait()
    acc = np.zeros(chunk, np.float64)
    for q, scales, n in recvd:
        acc[:n] += dequantize_fp8_rowwise(np.asarray(q), np.asarray(scales), n)
    if op == ReduceOp.AVG:
        acc /= world
    return acc.astype(np.float32), chunk


def allreduce_quantized(
    arrays: Sequence[Any], op: ReduceOp, pg: ProcessGroup, row: int = _ROW
) -> Work:
    """fp8-compressed allreduce over the PG. Returns Work resolving to the
    reduced arrays (same shapes/dtypes as inputs)."""
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(f"allreduce_quantized supports SUM/AVG, got {op}")

    if is_device_tree(arrays):
        if _has_multidevice_leaf(arrays):
            try:
                plans = [_leaf_plan(a, row) for a in arrays]
            except _UnevenSharding:
                plans = None  # host fallback below
            if plans is not None:
                leaves = list(arrays)

                def run_sharded() -> List[Any]:
                    if pg.size() <= 1:
                        return leaves
                    return _allreduce_quantized_sharded(
                        leaves, op, pg, row, plans
                    )

                return _run_async(run_sharded)
            # uneven shards: run the host engine but keep the return-type
            # contract — results land back on each input leaf's sharding
            # so callers never see the engine choice
            shardings = [a.sharding for a in arrays]
            hflat, hshapes, hdtypes = _flatten(arrays)

            def run_host_restore() -> List[Any]:
                import jax

                world = pg.size()
                if world <= 1:
                    outs = _unflatten(hflat, hshapes, hdtypes)
                else:
                    outs = _host_allreduce_pipeline(
                        hflat, hshapes, hdtypes, op, pg, row
                    )
                return [
                    jax.device_put(o, s) for o, s in zip(outs, shardings)
                ]

            return _run_async(run_host_restore)
        else:
            dflat, dshapes, ddtypes = _flatten_jax(arrays)

            def run_device() -> List[Any]:
                if pg.size() <= 1:
                    return _unflatten_jax(dflat, dshapes, ddtypes)
                return _allreduce_quantized_device(
                    dflat, dshapes, ddtypes, op, pg, row
                )

            return _run_async(run_device)

    flat, shapes, dtypes = _flatten(arrays)

    def run() -> List[np.ndarray]:
        if pg.size() <= 1:
            out = flat if op == ReduceOp.SUM else flat.copy()
            return _unflatten(out, shapes, dtypes)
        return _host_allreduce_pipeline(flat, shapes, dtypes, op, pg, row)

    return _run_async(run)


def allreduce_compressed(
    arrays: Sequence[Any],
    op: ReduceOp,
    pg: ProcessGroup,
    mode: str = "fp8",
    row: int = _ROW,
) -> Work:
    """Compressed allreduce through the PG's self-healing ring.

    Unlike :func:`allreduce_quantized` (alltoall + allgather, one codec
    boundary per destination chunk), this ships ONE CompressedWire per
    call straight into ``pg.allreduce`` — on ``ProcessGroupHost`` that is
    the compressed ring whose reduce step dequantizes → accumulates →
    requantizes per hop and which re-forms around a dead link
    mid-collective (``inject_link_fault`` / ``set_reroute_observer``).
    ``mode`` is ``"fp8"`` or ``"int8"``. The Manager's streaming pipeline
    uses the same wire per bucket; this is the direct, non-managed entry
    for tests and custom callers. Host (numpy) inputs only."""
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(f"allreduce_compressed supports SUM/AVG, got {op}")
    flat, shapes, dtypes = _flatten(arrays)
    wire = compress_bucket(flat, mode, row=row)

    def run() -> List[np.ndarray]:
        if pg.size() <= 1:
            return _unflatten(flat.copy(), shapes, dtypes)
        out = pg.allreduce([wire], op).get_future().wait()
        return _unflatten(decompress_bucket(out[0]), shapes, dtypes)

    return _run_async(run)


def _host_allreduce_pipeline(flat, shapes, dtypes, op, pg, row):
    """Host-engine allreduce body: reduce-scatter, requantize, allgather."""
    world = pg.size()
    acc, chunk = _reduce_scatter_core(flat, op, pg, row)

    q, scales, n = quantize_fp8_rowwise(acc, row)
    gathered = pg.allgather([(q, scales, n)]).get_future().wait()

    out = np.zeros(chunk * world, np.float32)
    for r in range(world):
        (qg, sg, ng) = gathered[r][0]
        out[r * chunk : r * chunk + ng] = dequantize_fp8_rowwise(
            np.asarray(qg), np.asarray(sg), ng
        )
    return _unflatten(out[: flat.size], shapes, dtypes)


def reduce_scatter_quantized(
    arrays: Sequence[Any], op: ReduceOp, pg: ProcessGroup, row: int = _ROW
) -> Work:
    """fp8-compressed reduce-scatter: future resolves to this rank's reduced
    flat chunk (f32) of the concatenated input.

    Single-device jax trees run the fused Pallas engine (quantize, wire,
    dequantize+reduce all on-accelerator — the reference keeps its
    reduce-scatter on-GPU the same way, collectives.py:159-296) and the
    chunk comes back as a jax.Array; numpy and mesh-sharded inputs use
    the host engine (mesh-sharded only while fully addressable — the
    host flatten gathers, so multi-host shardings raise on the future;
    allreduce_quantized is the op with an SPMD engine). Both engines
    share the row-aligned chunk partition, so mixed quorums exchange
    identically-aligned chunks."""
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(f"reduce_scatter_quantized supports SUM/AVG, got {op}")

    if is_device_tree(arrays) and not _has_multidevice_leaf(arrays):
        leaves = list(arrays)

        def run_device():
            # flatten inside the worker: cross-leaf device disagreement
            # (leaves committed to different devices) must resolve through
            # the Work future like every other error in this module
            dflat, _, _ = _flatten_jax(leaves)
            if pg.size() <= 1:
                return dflat
            acc, _chunk, _rows = _reduce_scatter_core_device(
                dflat, op, pg, row
            )
            return acc

        return _run_async(run_device)

    flat, _, _ = _flatten(arrays)

    def run() -> np.ndarray:
        if pg.size() <= 1:
            return flat.copy()
        acc, _ = _reduce_scatter_core(flat, op, pg, row)
        return acc

    return _run_async(run)

