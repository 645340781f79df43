"""The mixers' short depthwise causal convolution, for the training hot path.

For every channel ``d`` of ``x [B, T, di]``, with ``w [k, di]`` (``w[k-1]``
weighs the current position) and zeros before position 0::

    pre_t[d] = sum_j x_{t-(k-1-j)}[d] * w[j, d]  (+ b[d])     # j = 0 .. k-1, in
    y_t[d]   = activation(pre_t[d])                           # that order, float32

Mamba's, Mamba-2's and KDA's mixers take ``k`` = 4 and SiLU (Ling without a
bias), LFM2's ``k`` = 3 and neither bias nor activation. The work is bound by
memory: a pass that reads the narrow rows once and writes them once moves 4
bytes an element, and a backward pass 6. Written in ``jax.numpy`` and
differentiated by XLA (:func:`short_conv_reference`) the float32 copies of
the padded sequence and of the pre-activation go to HBM and come back: 72 to
121 bytes an element a step at the cells' shapes (PERF.md section 6, PR 53).

So: a Pallas kernel pair under ``jax.custom_vjp`` (``short_conv_fwd`` /
``short_conv_bwd`` in a device trace), as ``ops/ssd.py`` and
``ops/selective_scan.py`` are built. A grid step owns ``TILE`` positions of
``_lanes(di)`` channels in the rows' own dtype and walks them ``_ROWS`` at a
time; a row group is widened to float32 in registers, its ``k - 1``
neighbours come from the group before (the tile before: eight float32 rows
carried in VMEM scratch along the sequential sequence axis), shifted by
sublane rotations, and the sum, the bias, the activation and the narrowing
happen before anything is stored. The backward pass keeps ``x``, ``w`` and
``b`` only: it computes the pre-activation again, ``dpre = dy *
activation'(pre)`` (``jax.vjp`` of the same callable inside the kernel) into
float32 VMEM scratch, then ``dx_t = sum_j w[j] * dpre_{t+(k-1-j)}``, which
reaches ``k - 1`` rows into the NEXT tile: those rows of ``x`` and ``dy``
come as a second, sixteen-row block of the same arrays. ``dw`` and ``db``
are summed in float32 over the batch and the sequence in their output
blocks (the channel axis of the backward grid is the outer one). ``dpre`` is
never rounded on its way to ``dx``, ``dw`` or ``db``.

:func:`short_conv` is the one entry: the kernels where the shape tiles
(``di`` whole lanes of 128, ``T`` whole tiles), the ``jax.numpy`` form
otherwise (the debug configurations' widths and lengths). Off the TPU the
kernels run interpreted, as the other kernels do; nothing else selects a
path.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["short_conv", "short_conv_reference", "TILE"]

# The tile, by measurement on a v5e (PERF.md section 6, PR 53: ms for a
# layer's forward, rematerialised forward and backward at [2, 8192, 6144],
# positions x channels): 256 x 512 3.41, 512 x 512 3.12, 1024 x 256 3.72,
# 1024 x 512 2.96, 1024 x 1024 3.23, 2048 x 512 2.93, 4096 x 512 2.94 (the
# ``jax.numpy`` form: 14.61); the same order at [1, 8192, 5120] and
# [1, 32768, 4096]. 512 channels are the four float32 registers a row group
# the inner loop keeps of every value; past 512 positions a tile's size
# matters little. The kernels are bound by the vector unit, not by HBM: 6.1
# bytes-an-element-equivalents forward and 11.8 backward against 4 and 6.
TILE = 1024  # positions a grid step owns
_LANES = 512  # channels a grid step owns, where they divide ``di``
_ROWS = 16  # positions widened, summed and stored at a time: a bf16 tile's
_G = 8  # a float32 tile's rows: the unit the taps are shifted in
_F32 = jnp.float32


def short_conv_reference(x: jax.Array, w: jax.Array, b: Optional[jax.Array],
                         activation: Optional[Callable[[jax.Array], jax.Array]] = jax.nn.silu
                         ) -> jax.Array:
    """The same function as shifted multiply-adds over the padded sequence,
    widened to float32 once: what a shape that does not tile runs, and the
    kernels' test oracle."""
    k, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))).astype(_F32)
    out = sum(padded[:, j:j + T] * w[j].astype(_F32) for j in range(k))
    if b is not None:
        out = out + b.astype(_F32)
    return (activation(out) if activation else out).astype(x.dtype)


def _lanes(di: int) -> int:
    """Channels a grid step owns: ``_LANES``, or the most whole lanes of 128
    under it that divide ``di``."""
    return next(c for c in range(min(_LANES, di), 0, -128) if di % c == 0)


def _tiles(x: jax.Array, w: jax.Array) -> bool:
    """Whole lanes, whole tiles, and taps that reach no further back than
    the eight rows a tile carries."""
    return x.ndim == 3 and x.shape[2] % 128 == 0 and x.shape[1] % TILE == 0 \
        and w.shape[0] <= _G + 1 and jnp.issubdtype(x.dtype, jnp.floating)


def _behind(cur, before, s, rows):
    """Row ``t`` of the result is row ``t - s`` of ``cur`` [8, lanes], from
    ``before`` (the eight rows ahead of it) where that is above its top.
    ``before`` comes rotated already (:func:`_turned`)."""
    return jnp.where(rows >= s, pltpu.roll(cur, s, 0), before)


def _ahead(cur, after, s, rows):
    """Row ``t`` of the result is row ``t + s`` of ``cur``, from ``after``
    (the eight rows below it) where that is past its end."""
    return jnp.where(rows < _G - s, pltpu.roll(cur, _G - s, 0), pltpu.roll(after, _G - s, 0))


def _turned(group, k):
    """``group`` rotated down by 1 .. k-1 rows: what the next group's
    :func:`_behind` reads its first rows from."""
    return tuple(pltpu.roll(group, s, 0) for s in range(1, k))


def _pre(cur, before, taps, bias, rows):
    """The pre-activation of eight rows, and the views it was summed from
    (view ``j`` is ``x_{t-(k-1-j)}``): the same sum in the same order as
    :func:`short_conv_reference`'s."""
    k = len(taps)
    views = [_behind(cur, before[k - 2 - j], k - 1 - j, rows) for j in range(k - 1)] + [cur]
    pre = views[0] * taps[0]
    for j in range(1, k):
        pre = pre + views[j] * taps[j]
    return (pre if bias is None else pre + bias), views


def _consts(w_ref, b_ref):
    """Made once a grid step: every tap and the bias (or None) as eight
    equal rows, and the row index of a group."""
    lanes = w_ref.shape[1]
    taps = [jnp.broadcast_to(w_ref[j:j + 1, :], (_G, lanes)) for j in range(w_ref.shape[0])]
    bias = None if b_ref is None else jnp.broadcast_to(b_ref[...], (_G, lanes))
    return taps, bias, jax.lax.broadcasted_iota(jnp.int32, (_G, lanes), 0)


def _fwd_kernel(act, has_bias, x_ref, w_ref, *rest):
    b_ref, (y_ref, last) = (rest[0] if has_bias else None), rest[-2:]

    @pl.when(pl.program_id(2) == 0)
    def _():
        last[...] = jnp.zeros_like(last)

    tile, k = x_ref.shape[0], w_ref.shape[0]
    taps, bias, rows = _consts(w_ref, b_ref)

    def over_rows(i, before):
        at = pl.multiple_of(i * _ROWS, _ROWS)
        block, out = x_ref[pl.ds(at, _ROWS), :].astype(_F32), []
        for g in range(_ROWS // _G):
            cur = block[g * _G:(g + 1) * _G]
            pre, _ = _pre(cur, before, taps, bias, rows)
            out.append(act(pre) if act else pre)
            before = _turned(cur, k)
        y_ref[pl.ds(at, _ROWS), :] = jnp.concatenate(out, axis=0).astype(y_ref.dtype)
        return before

    jax.lax.fori_loop(0, tile // _ROWS, over_rows, _turned(last[...], k))
    last[...] = x_ref[pl.ds(tile - _ROWS, _ROWS), :].astype(_F32)[_ROWS - _G:]


def _bwd_kernel(act, has_bias, x_ref, dy_ref, xn_ref, dyn_ref, w_ref, *rest):
    """One tile: ``dpre`` of its rows and of the next tile's first eight
    into ``dpre_scr`` (with ``dw`` and ``db`` of its own rows), then ``dx``."""
    b_ref, rest = (rest[0], rest[1:]) if has_bias else (None, rest)
    dx_ref, dw_ref, (last, dpre_scr) = rest[0], rest[1], rest[-2:]
    db_ref = rest[2] if has_bias else None

    @pl.when(pl.program_id(2) == 0)
    def _():
        last[...] = jnp.zeros_like(last)

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if has_bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    tile, k = x_ref.shape[0], w_ref.shape[0]
    taps, bias, rows = _consts(w_ref, b_ref)

    def dpre_of(cur, before, dy):
        pre, views = _pre(cur, before, taps, bias, rows)
        return (jax.vjp(act, pre)[1](dy)[0] if act else dy), views

    def over_rows(i, carry):
        before, sums = carry[0], list(carry[1])
        at = pl.multiple_of(i * _ROWS, _ROWS)
        block = x_ref[pl.ds(at, _ROWS), :].astype(_F32)
        dys, out = dy_ref[pl.ds(at, _ROWS), :].astype(_F32), []
        for g in range(_ROWS // _G):
            cur = block[g * _G:(g + 1) * _G]
            dpre, views = dpre_of(cur, before, dys[g * _G:(g + 1) * _G])
            for j in range(k):
                sums[j] = sums[j] + dpre * views[j]
            if has_bias:
                sums[k] = sums[k] + dpre
            out.append(dpre)
            before = _turned(cur, k)
        dpre_scr[pl.ds(at, _ROWS), :] = jnp.concatenate(out, axis=0)
        return before, tuple(sums)

    zeros = jnp.zeros_like(taps[0])
    before, sums = jax.lax.fori_loop(
        0, tile // _ROWS, over_rows, (_turned(last[...], k), (zeros,) * (k + has_bias)))
    last[...] = x_ref[pl.ds(tile - _ROWS, _ROWS), :].astype(_F32)[_ROWS - _G:]
    for j in range(k):
        dw_ref[j:j + 1, :] += jnp.sum(sums[j], axis=0, keepdims=True)
    if has_bias:
        db_ref[...] += jnp.sum(sums[k], axis=0, keepdims=True)
    # the next tile's first rows: their dpre reaches back into this tile's dx
    ahead, _ = dpre_of(xn_ref[...].astype(_F32)[:_G], before, dyn_ref[...].astype(_F32)[:_G])
    dpre_scr[pl.ds(tile, _G), :] = jnp.where(
        pl.program_id(2) == pl.num_programs(2) - 1, 0.0, ahead)

    def dx_rows(i, _):
        at = pl.multiple_of(i * _ROWS, _ROWS)
        block, out = dpre_scr[pl.ds(at, _ROWS + _G), :], []
        for g in range(_ROWS // _G):
            cur, after = block[g * _G:(g + 1) * _G], block[(g + 1) * _G:(g + 2) * _G]
            dx = cur * taps[k - 1]
            for j in range(k - 2, -1, -1):
                dx = dx + _ahead(cur, after, k - 1 - j, rows) * taps[j]
            out.append(dx)
        dx_ref[pl.ds(at, _ROWS), :] = jnp.concatenate(out, axis=0).astype(dx_ref.dtype)
        return 0

    jax.lax.fori_loop(0, tile // _ROWS, dx_rows, 0)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params(semantics):
    if _interpret():
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=64 * 2**20)}


# Jitted, so that a program's calls of one shape share one traced and lowered
# kernel: Ling's step holds 54 of them, and each costs a tenth of a second of
# set-up to lower where it is not shared.
@functools.partial(jax.jit, static_argnums=(3, 4))
def _forward(x, w, b, act, blocks):
    """x [B, T, di]; w [k, di] and b [1, di] or None, float32; ``blocks``:
    the (positions, channels) a grid step owns -> y as x."""
    (B, T, di), (tile, lanes) = x.shape, blocks
    rows = pl.BlockSpec((None, tile, lanes), lambda n, c, t: (n, t, c))
    taps = pl.BlockSpec((w.shape[0], lanes), lambda n, c, t: (0, c))
    bias = pl.BlockSpec((1, lanes), lambda n, c, t: (0, c))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, act, b is not None), grid=(B, di // lanes, T // tile),
        in_specs=[rows, taps] + [bias] * (b is not None), out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((_G, lanes), _F32)],
        name="short_conv_fwd", **_params(("parallel", "parallel", "arbitrary")),
    )(x, w, *(() if b is None else (b,)))


@functools.partial(jax.jit, static_argnums=(4, 5))
def _backward(x, w, b, dy, act, blocks):
    """-> (dx as x, dw [k, di] float32, db [1, di] float32 if ``b``)."""
    (B, T, di), (tile, lanes), k = x.shape, blocks, w.shape[0]
    per, last = tile // _ROWS, T // _ROWS - 1
    rows = pl.BlockSpec((None, tile, lanes), lambda c, n, t: (n, t, c))
    # the sixteen rows after the tile (the last tile: any, they are not read)
    nxt = pl.BlockSpec((None, _ROWS, lanes),
                       lambda c, n, t: (n, jnp.minimum((t + 1) * per, last), c))
    taps = pl.BlockSpec((k, lanes), lambda c, n, t: (0, c))
    bias = pl.BlockSpec((1, lanes), lambda c, n, t: (0, c))
    has_bias = b is not None
    return pl.pallas_call(
        functools.partial(_bwd_kernel, act, has_bias), grid=(di // lanes, B, T // tile),
        in_specs=[rows, rows, nxt, nxt, taps] + [bias] * has_bias,
        out_specs=[rows, taps] + [bias] * has_bias,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(w.shape, _F32)]
        + [jax.ShapeDtypeStruct((1, di), _F32)] * has_bias,
        scratch_shapes=[pltpu.VMEM((_G, lanes), _F32), pltpu.VMEM((tile + _G, lanes), _F32)],
        name="short_conv_bwd", **_params(("parallel", "arbitrary", "arbitrary")),
    )(x, dy, x, dy, w, *((b,) if has_bias else ()))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _short_conv(x, w, b, act, blocks):
    return _forward(x, w, b, act, blocks)


def _short_conv_fwd(x, w, b, act, blocks):
    return _forward(x, w, b, act, blocks), (x, w, b)


def _short_conv_bwd(act, blocks, saved, dy):
    x, w, b = saved
    dx, dw, *db = _backward(x, w, b, dy, act, blocks)
    return dx, dw, (db[0] if db else None)


_short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)


def short_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array],
               activation: Optional[Callable[[Any], jax.Array]] = jax.nn.silu) -> jax.Array:
    """``activation`` (None: none) of the depthwise causal convolution of
    x [B, T, di] with w [k, di] and b [di] or None, summed in float32, in
    x's dtype: the kernels where ``di`` is whole lanes and ``T`` whole tiles,
    :func:`short_conv_reference` otherwise."""
    if not _tiles(x, w):
        return short_conv_reference(x, w, b, activation)
    return _short_conv(x, w.astype(_F32), None if b is None else b.astype(_F32)[None], activation,
                       (TILE, _lanes(x.shape[2])))
