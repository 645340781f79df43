"""Rowwise-scaled fp8 quantization for compressed collectives.

Role-equivalent of the reference's Triton kernels
(torchft/quantization.py:53-686 — its only GPU-kernel code): fused rowwise
quantize/dequantize used by the quantized allreduce. The TPU equivalents are
Pallas kernels (fused_quantize_fp8 / fused_dequantize_fp8) plus plain numpy
host helpers used by the host TCP collectives.

Layout: values are viewed as rows of ``row`` elements (padded); each row gets
one f32 scale = amax/448 (float8_e4m3 max normal). The wire format keeps the
fp8 payload and the f32 scales as separate arrays rather than the reference's
interleaved flat buffer — on TPU, separate dense arrays stay tileable by XLA.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import numpy as np

try:  # ml_dtypes ships with jax
    import ml_dtypes

    _FP8 = np.dtype(ml_dtypes.float8_e4m3fn)
except Exception:  # pragma: no cover - ml_dtypes is a jax dependency
    _FP8 = None

FP8_MAX = 448.0  # float8_e4m3fn max normal value
INT8_MAX = 127.0

COMPRESS_ENV = "TORCHFT_COMPRESS"
COMPRESS_MODES = ("off", "fp8", "int8")

__all__ = [
    "quantize_fp8_rowwise",
    "dequantize_fp8_rowwise",
    "quantize_int8_rowwise",
    "dequantize_int8_rowwise",
    "fused_quantize_fp8",
    "fused_dequantize_fp8",
    "CompressedWire",
    "is_compressed_wire",
    "codec",
    "resolve_compress_mode",
    "compress_bucket",
    "decompress_bucket",
    "COMPRESS_ENV",
    "COMPRESS_MODES",
]


# ---------------------------------------------------------------------------
# Host (numpy) path — used by ProcessGroupHost quantized collectives
# ---------------------------------------------------------------------------
def _pad_rows(flat: np.ndarray, row: int) -> Tuple[np.ndarray, int, int]:
    """View ``flat`` as a (rows, row) f32 matrix, zero-padding the tail.

    The hot path (bucket sizes that are exact row multiples, which is every
    bucket the packer cuts except possibly the last) is a zero-copy reshape;
    only ragged tails pay the pad-and-copy.
    """
    flat = np.ascontiguousarray(flat, dtype=np.float32).reshape(-1)
    n = flat.size
    rows = max(1, -(-n // row))
    if n == rows * row:
        return flat.reshape(rows, row), rows, n
    padded = np.zeros(rows * row, dtype=np.float32)
    padded[:n] = flat
    return padded.reshape(rows, row), rows, n


@functools.lru_cache(maxsize=1)
def _fp8_dequant_lut() -> np.ndarray:
    """All 256 float8_e4m3fn values as f32, indexed by bit pattern.

    A table lookup decodes ~2x faster than ml_dtypes' elementwise cast on
    host CPUs and is bit-identical by construction (the table IS the cast).
    """
    assert _FP8 is not None
    return np.arange(256, dtype=np.uint8).view(_FP8).astype(np.float32)


def quantize_fp8_rowwise(
    flat: np.ndarray, row: int = 512
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Quantize a flat f32/bf16 array to (fp8 payload, f32 row scales, n).

    The payload is returned as uint8 (fp8 bit pattern) so it pickles/ships
    compactly; ``n`` is the unpadded element count.
    """
    assert _FP8 is not None, "ml_dtypes with float8_e4m3fn is required"
    mat, rows, n = _pad_rows(flat, row)
    amax = np.max(np.abs(mat), axis=1, keepdims=True)
    scales = np.where(amax > 0, amax / FP8_MAX, 1.0).astype(np.float32)
    # multiply by the reciprocal: one rows-long divide instead of an
    # elements-long one (broadcast multiplies are cheaper than divides)
    q = (mat * (np.float32(1.0) / scales)).astype(_FP8)
    return q.view(np.uint8), scales[:, 0], n


def dequantize_fp8_rowwise(
    payload: np.ndarray, scales: np.ndarray, n: int, dtype=np.float32
) -> np.ndarray:
    """Inverse of quantize_fp8_rowwise; returns a flat array of length n."""
    assert _FP8 is not None
    # accept both engines' scale shapes — (rows,) host vs (rows, 1) fused —
    # a (rows, 1) input would otherwise broadcast to (rows, rows, row) and
    # silently return truncated garbage
    scales = np.asarray(scales).reshape(-1)
    mat = _fp8_dequant_lut()[payload.reshape(scales.size, -1)]
    mat *= scales[:, None]
    out = mat.reshape(-1)[:n]
    return out if dtype == np.float32 else out.astype(dtype)


def quantize_int8_rowwise(
    flat: np.ndarray, row: int = 512
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Symmetric rowwise int8: (int8 payload viewed uint8, f32 scales, n).

    Same layout contract as the fp8 codec (rows of ``row`` elements, one
    f32 scale per row = amax/127) so the two are interchangeable on the
    compressed wire.
    """
    mat, rows, n = _pad_rows(flat, row)
    amax = np.max(np.abs(mat), axis=1, keepdims=True)
    all_finite = bool(np.isfinite(amax).all())
    # non-finite rows (inf/nan) would poison rint(); saturate them at the
    # largest finite magnitude in the row instead of propagating nan codes
    finite_amax = (
        amax if all_finite
        else np.where(np.isfinite(amax), amax, np.float32(0.0))
    )
    scales = np.where(finite_amax > 0, finite_amax / INT8_MAX, 1.0).astype(
        np.float32
    )
    q = mat * (np.float32(1.0) / scales)
    np.rint(q, out=q)
    np.clip(q, -INT8_MAX, INT8_MAX, out=q)
    if not all_finite:
        # amax propagates any inf/nan in its row, so an all-finite amax
        # proves the whole matrix is finite and this pass can be skipped
        q = np.nan_to_num(q, nan=0.0, posinf=INT8_MAX, neginf=-INT8_MAX)
    q = q.astype(np.int8)
    return q.view(np.uint8), scales[:, 0], n


def dequantize_int8_rowwise(
    payload: np.ndarray, scales: np.ndarray, n: int, dtype=np.float32
) -> np.ndarray:
    """Inverse of quantize_int8_rowwise; returns a flat array of length n."""
    scales = np.asarray(scales).reshape(-1)
    mat = payload.view(np.int8).reshape(scales.size, -1).astype(np.float32)
    mat *= scales[:, None]
    out = mat.reshape(-1)[:n]
    return out if dtype == np.float32 else out.astype(dtype)


# ---------------------------------------------------------------------------
# Compressed-wire surface — per-bucket codec used by the streaming pipeline
# and the host compressed ring (process_group._ring_allreduce_compressed)
# ---------------------------------------------------------------------------
class CompressedWire(NamedTuple):
    """One bucket's compressed payload as it rides the host wire.

    A NamedTuple (not a class) on purpose: ``process_group._to_host`` and
    the full-mesh exchange path pass tuples through untouched, so the wire
    survives every PG boundary without special-casing.
    """

    mode: str  # "fp8" | "int8"
    payload: np.ndarray  # (rows, row) uint8 bit patterns of the codes
    scales: np.ndarray  # (rows,) f32 rowwise scales
    n: int  # unpadded element count
    dtype: str  # original dtype str, restored on decompress
    row: int  # row length the scales are keyed to


def is_compressed_wire(x) -> bool:
    return isinstance(x, CompressedWire)


def codec(mode: str):
    """(quantize, dequantize) pair for a compress mode."""
    if mode == "fp8":
        return quantize_fp8_rowwise, dequantize_fp8_rowwise
    if mode == "int8":
        return quantize_int8_rowwise, dequantize_int8_rowwise
    raise ValueError(f"no codec for compress mode {mode!r}")


def resolve_compress_mode(mode: Optional[str] = None) -> str:
    """Resolve the wire-compression mode: env > constructor arg > "off".

    Raises ValueError (with the valid set) on a bad value — doctor.py's
    compress-env check funnels through here so the CLI and the Manager
    reject identically.
    """
    # knobs.env_raw (not os.environ) so a policy-plane override on
    # TORCHFT_COMPRESS retargets the codec live, and still beats a
    # stale ambient env var the operator exported at launch.
    from torchft_tpu import knobs

    raw = knobs.env_raw(COMPRESS_ENV)
    if raw is not None:
        value = raw.strip().lower() or "off"
    elif mode is not None:
        value = str(mode).strip().lower() or "off"
    else:
        value = "off"
    if value not in COMPRESS_MODES:
        raise ValueError(
            f"invalid compress mode {value!r} (from {COMPRESS_ENV} or "
            f"constructor): expected one of {COMPRESS_MODES}"
        )
    return value


def compress_bucket(
    flat: np.ndarray, mode: str, row: int = 512, dtype=None
) -> CompressedWire:
    """Quantize one flat host bucket into a CompressedWire."""
    quantize, _ = codec(mode)
    out_dtype = np.dtype(dtype if dtype is not None else flat.dtype)
    payload, scales, n = quantize(flat, row=row)
    return CompressedWire(
        mode=mode,
        payload=payload,
        scales=scales,
        n=n,
        # .name (not .str) round-trips ml_dtypes extended dtypes (bfloat16)
        dtype=out_dtype.name,
        row=row,
    )


def decompress_bucket(wire: CompressedWire, dtype=None) -> np.ndarray:
    """Inverse of compress_bucket; flat array of length ``wire.n``."""
    _, dequantize = codec(wire.mode)
    out_dtype = np.dtype(dtype if dtype is not None else wire.dtype)
    return dequantize(wire.payload, wire.scales, wire.n, dtype=out_dtype)


# ---------------------------------------------------------------------------
# Device (Pallas) path — fused kernels for on-device quantization
# ---------------------------------------------------------------------------
def _quantize_kernel(x_ref, q_ref, scale_ref):
    x = x_ref[...].astype(jnp_f32())
    amax = jnp().max(jnp().abs(x), axis=-1, keepdims=True)
    scale = jnp().where(amax > 0, amax / FP8_MAX, 1.0)
    q_ref[...] = (x / scale).astype(jnp().float8_e4m3fn)
    scale_ref[...] = scale[:, :1]


def _dequantize_kernel(q_ref, scale_ref, out_ref):
    q = q_ref[...].astype(jnp_f32())
    out_ref[...] = q * scale_ref[...].astype(jnp_f32())


@functools.lru_cache(None)
def jnp():
    import jax.numpy as jnp

    return jnp


def jnp_f32():
    return jnp().float32


def _use_interpret() -> bool:
    """Pallas interpret mode on the CPU test platform only; on a TPU the
    kernels compile through Mosaic, and no other backend runs them."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the fused fp8 kernels run on tpu (compiled) or cpu (interpret "
        f"mode, tests); got backend {backend!r}"
    )


def fused_quantize_fp8(x, row: int = 512):
    """Pallas: quantize a device array to (fp8[rows,row], scales f32[rows,1], n).

    Rows map onto the VPU lane layout; one grid step per row-block keeps the
    whole row in VMEM (see /opt/skills/guides/pallas_guide.md tiling rules).
    Compiled on a TPU; interpret mode on the CPU test platform so the same
    code paths are testable on the CPU mesh.
    """
    import jax
    import jax.numpy as jnumpy
    from jax.experimental import pallas as pl

    flat = x.reshape(-1).astype(jnumpy.float32)
    n = flat.size
    rows = max(1, -(-n // row))
    padded = jnumpy.zeros((rows * row,), jnumpy.float32).at[:n].set(flat)
    mat = padded.reshape(rows, row)

    block_rows = min(rows, 256)
    grid = (-(-rows // block_rows),)
    q, scales = pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, row), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, row), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, row), jnumpy.float8_e4m3fn),
            jax.ShapeDtypeStruct((rows, 1), jnumpy.float32),
        ],
        interpret=_use_interpret(),
    )(mat)
    return q, scales, n


def fused_dequantize_fp8(q, scales, n: int, row: int = 512):
    """Pallas: inverse of fused_quantize_fp8; returns flat f32 of length n."""
    import jax
    import jax.numpy as jnumpy
    from jax.experimental import pallas as pl

    rows = q.shape[0]
    block_rows = min(rows, 256)
    grid = (-(-rows // block_rows),)
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, row), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, row), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, row), jnumpy.float32),
        interpret=_use_interpret(),
    )(q, scales)
    return out.reshape(-1)[:n]
