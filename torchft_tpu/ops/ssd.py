"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060) in its
chunked form, for the training hot path.

For every head ``h`` of ``P`` channels, in group ``g = h // (H / G)``, with
a state ``S`` of ``[P, N]`` float32 that starts at zero::

    S_t = exp(dt_t a) S_{t-1} + dt_t x_t (x) B_t[g]
    y_t = S_t C_t[g]

ONE scalar decay a head and position (``ops/selective_scan.py``: one per
(channel, state); ``ops/kda.py``: one per key channel under a delta rule),
``B`` and ``C`` shared by the heads of a group. ``D x``, the gate and the
grouped norm are the caller's, in plain XLA.

A chunk of ``CHUNK`` positions in matrix products, with ``c`` the running
sum of ``dt a`` inside it (``c <= 0`` and falling) and ``S0`` the state it
starts from::

    L[i, j]  = exp(c_i - c_j)  for j <= i, else 0     # the DIFFERENCE is
    y        = ((C B^T) o L) (dt o x) + exp(c) o (C S0)   # masked before exp
    S1       = exp(c_end) S0 + (B o exp(c_end - c) dt)^T x

so every exponent is <= 0 and nothing overflows; ``exp(c_i) / exp(c_j)``
would be 0 / 0 after some thousand positions' worth of decay in one chunk.
``C B^T`` is computed once a group and used by its heads.

A Pallas kernel pair under ``jax.custom_vjp`` (``ssd_fwd`` / ``ssd_bwd`` in
a device trace): grid over batch, groups (a group's heads together: their
``B`` and ``C`` are one block, and the cotangents of both are summed over
the heads inside the kernel) and chunks, the chunk axis last and sequential,
the group's states in VMEM scratch across chunks, so ``[T, H, P, N]`` never
reaches HBM. The forward pass writes the state every chunk starts from (268
MB a layer at 2 x 8,192 positions, 64 heads of 64 x 128); the backward pass
walks the chunks in reverse with the state's cotangent in scratch and
differentiates the one chunk function (:func:`_chunk`) inside the kernel, so
forward and backward cannot drift apart. The state, ``c``, ``L`` and every
accumulation are float32 whatever ``x`` is (``Precision.HIGHEST`` on the
MXU); ``C B^T`` multiplies the operands as they come, accumulated in float32.
Off the TPU the same kernels run interpreted, as the other kernels do.

Inside the kernel the heads lie along lanes (``x`` as ``[T, H * P]``) and
their step sizes along sublanes (``dt`` as ``[H / G, T]`` a group): a head's
``[CHUNK, 1]`` column is taken from its row through a masked sum, and two
heads of 64 channels are multiplied as one 128-lane block, each by its own
``L``, and told apart by a lane mask: every slice is whole vector registers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd", "ssd_reference", "CHUNK"]

CHUNK = 128  # positions one set of matrix products covers (``chunk_size``)
_F32 = jnp.float32
# What the state and the running log-decay are rounded to after every chunk
# (the reference: the state after every step). float32 is the only value the
# program runs with; the tests and benchmarks/nemotron_h_check_faults.py set
# bfloat16 here to show that the checks refuse it.
STATE_DTYPE = jnp.float32

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _lanes(P: int, width: int) -> int:
    """Lanes multiplied as one block: whole heads, 128 where they fit."""
    return min(width, max(P, 128))


def _chunk(x, dt, dta, bm, cm, st, P):
    """One chunk of one group. x [Q, hb*P] (the group's heads along lanes);
    dt, dta = dt * a [hb, Q] float32; bm, cm [Q, N]; st [N, hb*P] float32,
    each head's state transposed -> (y [Q, hb*P] float32, the states after
    the chunk)."""
    Q, width = x.shape
    hb, lanes = width // P, _lanes(P, width)
    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    c = _dot(dta, (cols >= rows).astype(_F32))  # [hb, Q]: running sum, inclusive
    c = c.astype(STATE_DTYPE).astype(_F32)
    c_end = jnp.sum(dta, axis=1, keepdims=True).astype(STATE_DTYPE).astype(_F32)  # [hb, 1]
    w = jnp.exp(c_end - c) * dt  # [hb, Q]: what a position adds to the chunk's last state

    def column(row):  # [1, Q] -> [Q, 1]
        return jnp.sum(jnp.where(rows == cols, row, 0.0), axis=1, keepdims=True)

    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=_F32)  # [Q, Q], once a group
    x32, from_state = x.astype(_F32), _dot(cm.astype(_F32), st)  # [Q, hb*P]
    head_of = jax.lax.broadcasted_iota(jnp.int32, (Q, lanes), 1) // P
    ys, xs = [], []
    for lo in range(0, width, lanes):
        xk = x32[:, lo:lo + lanes]
        yk, decay, weight = (jnp.zeros((Q, lanes), _F32),) * 3
        for i in range(lanes // P):
            h = lo // P + i
            c_col = column(c[h:h + 1])
            grew = jnp.exp(jnp.where(rows >= cols, c_col - c[h:h + 1], -jnp.inf))  # L
            mine = head_of == i
            yk = jnp.where(mine, _dot(cb * grew * dt[h:h + 1], xk), yk)
            decay = jnp.where(mine, jnp.exp(c_col), decay)
            weight = jnp.where(mine, column(w[h:h + 1]), weight)
        ys.append(yk + decay * from_state[:, lo:lo + lanes])
        xs.append(xk * weight)
    y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    xs = xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=1)
    head_at = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // P
    kept = jnp.zeros((1, width), _F32)
    for h in range(hb):
        kept = jnp.where(head_at == h, jnp.exp(c_end[h:h + 1]), kept)
    st = st * kept + _dot(bm.astype(_F32), xs, _TN)
    return y, st.astype(STATE_DTYPE).astype(_F32)


def _fwd_kernel(P, x_ref, dt_ref, dta_ref, b_ref, c_ref, y_ref, hs_ref, st_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_scr[...] = jnp.zeros_like(st_scr)

    st = st_scr[...]
    hs_ref[...] = st  # the states this chunk starts from
    y, st = _chunk(x_ref[...], dt_ref[...], dta_ref[...], b_ref[...], c_ref[...], st, P)
    y_ref[...] = y.astype(y_ref.dtype)
    st_scr[...] = st


def _bwd_kernel(P, x_ref, dt_ref, dta_ref, b_ref, c_ref, hs_ref, dy_ref,
                dx_ref, ddt_ref, ddta_ref, db_ref, dc_ref, dst_scr):
    """One chunk, the chunks in reverse: the chunk is computed again from
    the saved states and differentiated; ``dst_scr`` carries the cotangent
    of the states a chunk ends with into the chunk before."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_scr[...] = jnp.zeros_like(dst_scr)

    _, pullback = jax.vjp(
        functools.partial(_chunk, P=P), x_ref[...], dt_ref[...], dta_ref[...],
        b_ref[...], c_ref[...], hs_ref[...])
    dx, ddt, ddta, db, dc, dst = pullback((dy_ref[...].astype(_F32), dst_scr[...]))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    ddt_ref[...] = ddt
    ddta_ref[...] = ddta
    db_ref[...] = db.astype(db_ref.dtype)
    dc_ref[...] = dc.astype(dc_ref.dtype)
    dst_scr[...] = dst


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params():
    if _interpret():
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        # the backward kernel's pullback keeps every head's L and its
        # cotangent; the default scope is 16 of the v5e's 128
        vmem_limit_bytes=64 * 2**20)}


def _specs(hb, P, N, nc, reverse):
    """Block specs of (x [B, T, H*P], dt [B, G, hb, T], B or C [B, T, G*N],
    the saved states [B, G, T/CHUNK, N, hb*P]) on the grid (batch, group,
    chunk), the chunks in reverse for the backward pass."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    return (pl.BlockSpec((None, CHUNK, hb * P), lambda b, g, c: (b, at(c), g)),
            pl.BlockSpec((None, None, hb, CHUNK), lambda b, g, c: (b, g, 0, at(c))),
            pl.BlockSpec((None, CHUNK, N), lambda b, g, c: (b, at(c), g)),
            pl.BlockSpec((None, None, None, N, hb * P), lambda b, g, c: (b, g, at(c), 0, 0)))


def _forward(x, dt, dta, bm, cm, P):
    """x [B, T, H*P]; dt, dta [B, G, H/G, T] f32; bm, cm [B, T, G*N]; T
    whole chunks -> (y [B, T, H*P] in x's dtype, the states every chunk
    starts from [B, G, T/CHUNK, N, (H/G)*P] f32)."""
    (B, T, width), (_, G, hb, _) = x.shape, dt.shape
    N, nc = bm.shape[2] // G, T // CHUNK
    xs, ds, bs, hs = _specs(hb, P, N, nc, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P), grid=(B, G, nc),
        in_specs=[xs, ds, ds, bs, bs], out_specs=[xs, hs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, G, nc, N, width // G), _F32)],
        scratch_shapes=[pltpu.VMEM((N, width // G), _F32)],
        name="ssd_fwd", **_params(),
    )(x, dt, dta, bm, cm)


def _backward(x, dt, dta, bm, cm, hs, dy, P):
    (B, T, width), (_, G, hb, _) = x.shape, dt.shape
    N, nc = bm.shape[2] // G, T // CHUNK
    xs, ds, bs, st = _specs(hb, P, N, nc, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, P), grid=(B, G, nc),
        in_specs=[xs, ds, ds, bs, bs, st, xs], out_specs=[xs, ds, ds, bs, bs],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dta.shape, _F32),
                   jax.ShapeDtypeStruct(bm.shape, bm.dtype),
                   jax.ShapeDtypeStruct(cm.shape, cm.dtype)],
        scratch_shapes=[pltpu.VMEM((N, width // G), _F32)],
        name="ssd_bwd", **_params(),
    )(x, dt, dta, bm, cm, hs, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd(x, dt, dta, bm, cm, P):
    return _forward(x, dt, dta, bm, cm, P)[0]


def _ssd_fwd(x, dt, dta, bm, cm, P):
    y, hs = _forward(x, dt, dta, bm, cm, P)
    return y, (x, dt, dta, bm, cm, hs)


def _ssd_bwd(P, saved, dy):
    return tuple(_backward(*saved, dy, P))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array,
        cm: jax.Array) -> jax.Array:
    """x [B, T, H, P]; dt [B, T, H] (> 0); a [H] (< 0); bm, cm [B, T, G, N]
    -> y [B, T, H, P] in x's dtype. Any T: the sequence is padded to whole
    chunks with positions of dt = 0, which leave the state as it is."""
    B, T, H, P = x.shape
    G, N = bm.shape[2:]
    if H % G or (H // G * P) % _lanes(P, H // G * P) or 128 % min(P, 128):
        raise ValueError(f"ssd: {H} heads of {P} in {G} groups")
    dt = dt.astype(_F32)
    args = [x.reshape(B, T, H * P), dt, dt * a.astype(_F32),
            bm.reshape(B, T, G * N), cm.reshape(B, T, G * N)]
    pad = -T % CHUNK
    if pad:
        args = [jnp.pad(m, ((0, 0), (0, pad), (0, 0))) for m in args]
    rows = lambda m: jnp.transpose(m.reshape(B, -1, G, H // G), (0, 2, 3, 1))  # noqa: E731
    y = _ssd(args[0], rows(args[1]), rows(args[2]), args[3], args[4], P)
    return y[:, :T].reshape(B, T, H, P)


def ssd_reference(x, dt, a, bm, cm):
    """The same function as a ``lax.scan`` over positions in float32: the
    kernels' test oracle, never the program's path."""
    B, T, H, P = x.shape
    G = bm.shape[2]
    f = lambda m: jnp.swapaxes(m.astype(_F32), 0, 1)  # noqa: E731  time first
    heads = lambda m: jnp.repeat(m, H // G, axis=2)  # noqa: E731  a head reads its group's

    def step(S, inp):  # S [B,H,P,N]
        x_t, dt_t, b_t, c_t = inp
        S = jnp.exp(dt_t * a)[..., None, None] * S \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        S = S.astype(STATE_DTYPE).astype(_F32)
        return S, jnp.einsum("bhpn,bhn->bhp", S, c_t)

    S0 = jnp.zeros((B, H, P, bm.shape[-1]), _F32)
    with jax.default_matmul_precision("highest"):
        _, y = jax.lax.scan(step, S0, (f(x), f(dt), f(heads(bm)), f(heads(cm))))
    return jnp.swapaxes(y, 0, 1).astype(x.dtype)
