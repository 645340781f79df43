"""Selective scan (the Mamba-1 recurrence) for the training hot path.

For every channel ``d`` of ``d_inner`` and state ``n`` of ``d_state``::

    h_t[n, d] = exp(dt_t[d] * A[d, n]) * h_{t-1}[n, d] + dt_t[d] * x_t[d] * B_t[n]
    y_t[d]    = sum_n h_t[n, d] * C_t[n]  +  D[d] * x_t[d]          (then * silu(z_t[d]))

The recurrence is a Pallas kernel pair under ``jax.custom_vjp``
(``selective_scan_fwd`` / ``selective_scan_bwd`` in a device trace): grid
over batch, blocks of ``d_inner`` and chunks of the sequence, the chunk axis
last and sequential, the ``[d_state, block]`` state in VMEM scratch across
chunks, so the ``[T, d_inner, d_state]`` float32 state (2.7 GB a layer at
8,192 tokens of Jamba's width) never reaches HBM. The backward pass keeps
the inputs and the state at the chunk boundaries only, recomputes the
states of one chunk into VMEM and walks the chunks in reverse with the
adjoint state in scratch. The state, the ``exp`` and every sum are float32
whatever the dtype of ``x``. ``D * x`` and the gate are plain XLA around the
kernel (they fuse into its neighbours). Off the TPU the same kernels run
interpreted, as ``models/moe.py``'s grouped matmul does; nothing else
selects a path.

Layout inside the kernels: state on sublanes, channels on lanes, one
timestep after another. ``B`` and ``C`` come transposed (``[d_state, T]``)
so that a timestep's 16 values are one lane of a tile, broadcast across the
channels' lanes; the tile is rotated by eight lanes after every eight steps
so that every lane index in the unrolled body is static.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan", "selective_scan_reference"]

CHUNK = 256  # timesteps between two saved states
BLOCK = 1024  # channels of d_inner a grid step owns (lanes)
_STEPS = 8  # timesteps unrolled: one sublane tile of x, dt and y rows
_F32 = jnp.float32
# What the decay and the state are rounded to after every step. float32 is
# the only value the program runs with; the tests (and the chip reading of
# benchmarks/jamba_check_faults.py) set bfloat16 here to show that the
# checks refuse it.
STATE_DTYPE = jnp.float32


def _step(h, dt_u, x_u, b_u, a):
    """One position: (the decay, the state after it). dt_u, x_u [1, bd];
    b_u [N, 1]; a, h [N, bd]."""
    decay = jnp.exp(dt_u * a).astype(STATE_DTYPE).astype(_F32)
    return decay, (decay * h + (dt_u * x_u) * b_u).astype(STATE_DTYPE).astype(_F32)


def _row_select(rows, u, value, block):
    """``block`` [8, bd] with row ``u`` (static) replaced by ``value`` [1, bd]."""
    return jnp.where(rows == u, value, block)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hs_ref, h_scr,
                *, chunk: int, tile: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hs_ref[...] = h_scr[...]  # the state this chunk starts from
    a = a_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, (_STEPS, a.shape[1]), 0)

    def over_tile(k, h):
        off = pl.multiple_of(k * tile, tile)

        def over_group(g, carry):
            h, bt, ct = carry
            row = pl.multiple_of(off + g * _STEPS, _STEPS)
            xs = x_ref[pl.ds(row, _STEPS), :].astype(_F32)
            dts = dt_ref[pl.ds(row, _STEPS), :]
            ys = jnp.zeros_like(xs)
            for u in range(_STEPS):
                _, h = _step(h, dts[u:u + 1, :], xs[u:u + 1, :], bt[:, u:u + 1], a)
                ys = _row_select(rows, u, jnp.sum(h * ct[:, u:u + 1], axis=0,
                                                  keepdims=True), ys)
            y_ref[pl.ds(row, _STEPS), :] = ys
            return (h, pltpu.roll(bt, tile - _STEPS, 1),
                    pltpu.roll(ct, tile - _STEPS, 1))

        return jax.lax.fori_loop(
            0, tile // _STEPS, over_group,
            (h, b_ref[:, pl.ds(off, tile)], c_ref[:, pl.ds(off, tile)]))[0]

    h_scr[...] = jax.lax.fori_loop(0, chunk // tile, over_tile, h_scr[...])


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, hs_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, g_scr, hist,
                *, chunk: int, tile: int):
    """One chunk, the chunks in reverse. ``hist[t]`` is the state BEFORE
    step ``t``, recomputed from the saved chunk start; ``g_scr`` carries
    ``a_{t+1} * dL/dh_{t+1}`` into the chunk before."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    a = a_ref[...]
    bd = a.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (_STEPS, bd), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (a.shape[0], tile), 1)
    tiles, groups = chunk // tile, tile // _STEPS

    def recompute_tile(k, h):
        off = pl.multiple_of(k * tile, tile)

        def over_group(g, carry):
            h, bt = carry
            row = pl.multiple_of(off + g * _STEPS, _STEPS)
            xs = x_ref[pl.ds(row, _STEPS), :].astype(_F32)
            dts = dt_ref[pl.ds(row, _STEPS), :]
            for u in range(_STEPS):
                hist[row + u] = h
                _, h = _step(h, dts[u:u + 1, :], xs[u:u + 1, :], bt[:, u:u + 1], a)
            return h, pltpu.roll(bt, tile - _STEPS, 1)

        return jax.lax.fori_loop(0, groups, over_group,
                                 (h, b_ref[:, pl.ds(off, tile)]))[0]

    jax.lax.fori_loop(0, tiles, recompute_tile, hs_ref[...])

    def reverse_tile(k, carry):
        gn, da = carry
        off = pl.multiple_of((tiles - 1 - k) * tile, tile)

        def over_group(j, carry):
            gn, da, bt, ct, dbt, dct = carry
            g = groups - 1 - j
            row = pl.multiple_of(off + g * _STEPS, _STEPS)
            # the group's eight timesteps now sit on lanes 0..7
            bt, ct = pltpu.roll(bt, _STEPS, 1), pltpu.roll(ct, _STEPS, 1)
            xs = x_ref[pl.ds(row, _STEPS), :].astype(_F32)
            dts = dt_ref[pl.ds(row, _STEPS), :]
            dys = dy_ref[pl.ds(row, _STEPS), :]
            dxs, ddts = jnp.zeros_like(xs), jnp.zeros_like(xs)
            for u in reversed(range(_STEPS)):
                dt_u, x_u, dy_u = dts[u:u + 1, :], xs[u:u + 1, :], dys[u:u + 1, :]
                b_u, c_u = bt[:, u:u + 1], ct[:, u:u + 1]
                hp = hist[row + u]
                bx = dt_u * x_u
                decay, h = _step(hp, dt_u, x_u, b_u, a)
                gh = dy_u * c_u + gn  # dL/dh_t
                col = g * _STEPS + u
                dct = jnp.where(lanes == col,
                                jnp.sum(h * dy_u, axis=1, keepdims=True), dct)
                dbt = jnp.where(lanes == col,
                                jnp.sum(gh * bx, axis=1, keepdims=True), dbt)
                s = jnp.sum(gh * b_u, axis=0, keepdims=True)
                gn = gh * decay
                w = gn * hp  # dL/d(dt*A), elementwise
                dxs = _row_select(rows, u, dt_u * s, dxs)
                ddts = _row_select(
                    rows, u, x_u * s + jnp.sum(w * a, axis=0, keepdims=True), ddts)
                da = da + w * dt_u
            dx_ref[pl.ds(row, _STEPS), :] = dxs.astype(dx_ref.dtype)
            ddt_ref[pl.ds(row, _STEPS), :] = ddts
            return gn, da, bt, ct, dbt, dct

        zeros = jnp.zeros((a.shape[0], tile), _F32)
        gn, da, _, _, dbt, dct = jax.lax.fori_loop(
            0, groups, over_group,
            (gn, da, b_ref[:, pl.ds(off, tile)], c_ref[:, pl.ds(off, tile)],
             zeros, zeros))
        db_ref[:, pl.ds(off, tile)] = dbt
        dc_ref[:, pl.ds(off, tile)] = dct
        return gn, da

    gn, da = jax.lax.fori_loop(0, tiles, reverse_tile,
                               (g_scr[...], jnp.zeros_like(a)))
    g_scr[...] = gn
    da_ref[...] += da


def _sizes(T: int, di: int, chunk: int, block: int):
    """(chunk, tile, block, padded T) for a sequence of T and di channels."""
    chunk = min(chunk, -(-T // _STEPS) * _STEPS)
    tile = math.gcd(chunk, 128)
    if tile % _STEPS:
        raise ValueError(f"selective_scan: chunk {chunk} must be a multiple of {_STEPS}")
    block = next((b for b in (block, 512, 256, 128) if b <= block and di % b == 0), di)
    return chunk, tile, block, -(-T // chunk) * chunk


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params():
    if _interpret():
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 * 2**20)}


def _forward(x, dt, a_t, b_t, c_t, chunk, block):
    """x [B,T,di], dt [B,T,di] f32, a_t [N,di], b_t/c_t [B,N,T] f32, T a
    multiple of ``chunk`` -> (y [B,T,di] f32, the state at every chunk's
    start [B,T/chunk,N,di] f32)."""
    (nb, T, di), N = x.shape, a_t.shape[0]
    chunk, tile, bd, _ = _sizes(T, di, chunk, block)
    grid = (nb, di // bd, T // chunk)
    seq = pl.BlockSpec((None, chunk, bd), lambda b, j, c: (b, c, j))
    bc = pl.BlockSpec((None, N, chunk), lambda b, j, c: (b, 0, c))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, tile=tile),
        grid=grid,
        in_specs=[seq, seq, pl.BlockSpec((N, bd), lambda b, j, c: (0, j)), bc, bc],
        out_specs=[seq, pl.BlockSpec((None, None, N, bd), lambda b, j, c: (b, c, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((nb, T, di), _F32),
                   jax.ShapeDtypeStruct((nb, T // chunk, N, di), _F32)],
        scratch_shapes=[pltpu.VMEM((N, bd), _F32)],
        name="selective_scan_fwd",
        **_params(),
    )(x, dt, a_t, b_t, c_t)


def _backward(x, dt, a_t, b_t, c_t, hs, dy, chunk, block):
    (nb, T, di), N = x.shape, a_t.shape[0]
    chunk, tile, bd, _ = _sizes(T, di, chunk, block)
    nd, nc = di // bd, T // chunk
    grid = (nb, nd, nc)
    seq = pl.BlockSpec((None, chunk, bd), lambda b, j, c: (b, nc - 1 - c, j))
    bc = pl.BlockSpec((None, N, chunk), lambda b, j, c: (b, 0, nc - 1 - c))
    part = pl.BlockSpec((None, None, N, chunk), lambda b, j, c: (b, j, 0, nc - 1 - c))
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, tile=tile),
        grid=grid,
        in_specs=[seq, seq, pl.BlockSpec((N, bd), lambda b, j, c: (0, j)), bc, bc,
                  pl.BlockSpec((None, None, N, bd),
                               lambda b, j, c: (b, nc - 1 - c, 0, j)), seq],
        out_specs=[seq, seq, pl.BlockSpec((None, N, bd), lambda b, j, c: (b, 0, j)),
                   part, part],
        out_shape=[jax.ShapeDtypeStruct((nb, T, di), x.dtype),
                   jax.ShapeDtypeStruct((nb, T, di), _F32),
                   jax.ShapeDtypeStruct((nb, N, di), _F32),
                   jax.ShapeDtypeStruct((nb, nd, N, T), _F32),
                   jax.ShapeDtypeStruct((nb, nd, N, T), _F32)],
        scratch_shapes=[pltpu.VMEM((N, bd), _F32), pltpu.VMEM((chunk, N, bd), _F32)],
        name="selective_scan_bwd",
        **_params(),
    )(x, dt, a_t, b_t, c_t, hs, dy)
    # channels' blocks each saw their own share of B's and C's cotangent;
    # batches each their own of A's
    return dx, ddt, da.sum(axis=0), db.sum(axis=1), dc.sum(axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan(x, dt, a_t, b_t, c_t, chunk, block):
    return _forward(x, dt, a_t, b_t, c_t, chunk, block)[0]


def _scan_fwd(x, dt, a_t, b_t, c_t, chunk, block):
    y, hs = _forward(x, dt, a_t, b_t, c_t, chunk, block)
    return y, (x, dt, a_t, b_t, c_t, hs)


def _scan_bwd(chunk, block, saved, dy):
    return _backward(*saved, dy, chunk, block)


_scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan(
    x: jax.Array,
    dt: jax.Array,
    A: jax.Array,
    B: jax.Array,
    C: jax.Array,
    D: Optional[jax.Array] = None,
    z: Optional[jax.Array] = None,
    *,
    chunk: int = CHUNK,
    block: int = BLOCK,
) -> jax.Array:
    """x, dt, z [batch, T, d_inner]; A [d_inner, d_state] (negative); B, C
    [batch, T, d_state]; D [d_inner] -> y [batch, T, d_inner] in x's dtype.
    ``dt`` is the step size itself (after the softplus). Any T: the
    sequence is padded to whole chunks with steps of size zero, which leave
    the state as it is."""
    T = x.shape[1]
    chunk, _, block, padded = _sizes(T, x.shape[2], chunk, block)
    time_last = lambda m: jnp.swapaxes(m.astype(_F32), 1, 2)  # noqa: E731
    args = [x, dt.astype(_F32), B, C]
    if padded != T:
        args = [jnp.pad(m, ((0, 0), (0, padded - T), (0, 0))) for m in args]
    xs, dts, Bs, Cs = args
    y = _scan(xs, dts, A.astype(_F32).T, time_last(Bs), time_last(Cs),
              chunk, block)[:, :T]
    if D is not None:
        y = y + D.astype(_F32) * x.astype(_F32)
    if z is not None:
        y = y * jax.nn.silu(z.astype(_F32))
    return y.astype(x.dtype)


def selective_scan_reference(x, dt, A, B, C, D=None, z=None):
    """The same function as a ``lax.scan`` over positions in float32: the
    kernels' test oracle, never the program's path."""
    f = lambda m: m.astype(_F32)  # noqa: E731
    x32, dt32, A32 = f(x), f(dt), f(A)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp  # [B,di], [B,di], [B,N], [B,N]
        h = jnp.exp(dt_t[..., None] * A32) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)

    h0 = jnp.zeros((x.shape[0], x.shape[2], A.shape[1]), _F32)
    _, y = jax.lax.scan(step, h0, tuple(jnp.swapaxes(m, 0, 1)
                                        for m in (x32, dt32, f(B), f(C))))
    y = jnp.swapaxes(y, 0, 1)
    if D is not None:
        y = y + f(D) * x32
    if z is not None:
        y = y * jax.nn.silu(f(z))
    return y.astype(x.dtype)
