"""The KDA mixer's element-wise passes round the delta-rule kernels
(``models/kda.py``), for the training hot path: two fused kernel pairs.

Before the delta rule (:func:`kda_qkg`; ``kda_qkg_fwd`` / ``kda_qkg_bwd`` in
a device trace), for every head of ``d_k`` channels of the convolutions'
``q`` and ``k`` [B, S, H d_k] and of the decay projection's float32 result
``f``::

    q = l2norm(q) / sqrt(d_k);  k = l2norm(k)                 # float32, rounded once
    g = decay_floor * sigmoid(exp(A_log) * (f + dt_bias))     # in (decay_floor, 0), or
    g = -exp(A_log) * softplus(f + dt_bias)                   # decay_floor None; float32

After it (:func:`kda_gate`; ``kda_gate_fwd`` / ``kda_gate_bwd``), from the
delta rule's ``o`` [B, S, H d_k] and the output gate's LOGITS, one a head
in float32 [B, S, H] or one a channel in the activations' dtype
[B, S, H d_k]::

    out = rmsnorm_head(o) * o_norm * sigmoid(logits)          # float32, rounded once

The work is bound by memory: a pass that reads every row once and writes it
once moves 16 bytes an element before the delta rule (24 backward) and 6
after it (10 backward; 8 and 14 with a gate a channel as the leaves give it,
10 and 18 as the kernels take it: the entry widens the logits to float32
first, at 2 bytes an element a pass and a quarter of a GiB at the unbounded
kind's compiled peak. That quarter keeps the kind's step over the XLA
scheduler's memory limit, where its code is 180 MB smaller and
``peak_hbm_gib``, which counts the code, reads the parent's: PERF.md
section 7, PR 66 (a), has the readings and what lets it go). Written in
``jax.numpy`` and differentiated by XLA (:func:`qkg_reference`,
:func:`gate_reference`: what ``kda_mixer`` ran until PR 66) the float32
``[S, H d_k]`` temporaries of the norms, the sigmoids and their pullbacks go
to HBM and come back, forward, again under remat and backward (PERF.md
section 6, PR 66).

So: Pallas kernel pairs under ``jax.custom_vjp``, as ``ops/short_conv.py``
is built. A grid step owns a tile of positions of whole heads in the rows'
own dtype and walks every head ``_ROWS`` positions at a time: the rows are
widened to float32 in registers, a head's sum of squares is one reduction
over its lanes, and all the arithmetic and the one narrowing happen before
anything is stored. The backward kernels keep the INPUTS only (arrays that
are live anyway) and compute the forward values again in VMEM; ``dt_bias``',
the rate's and ``o_norm``'s gradients are summed in float32 over the batch
and the sequence in their output blocks (the channel axis of the grid is the
outer one), a row a channel, and folded onto the leaves' own shapes by XLA
outside. A gate a head takes its tile over EVERY head (a block of ``[S, H]``
cannot be cut along ``H``), any other call ``_LANES`` channels.

:func:`kda_qkg` and :func:`kda_gate` are the two entries: the kernels where
the shape tiles (heads of whole lanes of 128, ``S`` whole row groups), the
``jax.numpy`` forms otherwise (the debug configurations' widths). Off the
TPU the kernels run interpreted, as the other kernels do; nothing else
selects a path.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from torchft_tpu.ops.short_conv import _params

__all__ = ["kda_qkg", "kda_gate", "qkg_reference", "gate_reference", "tiles", "L2_EPS"]

L2_EPS = 1e-6
_ELEMS = 2 ** 18  # elements of a row array a grid step owns
_LANES = 512  # channels a grid step owns, where no gate a head asks for all
_ROWS = 256  # positions of a head widened, reduced and stored at a time
_F32 = jnp.float32


# ---- the jax.numpy forms ---------------------------------------------------


def _l2norm(x: jax.Array) -> jax.Array:
    x32 = x.astype(_F32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + L2_EPS)


def qkg_reference(q: jax.Array, k: jax.Array, f: jax.Array, dt_bias: jax.Array,
                  A_log: jax.Array, decay_floor: Optional[float]
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q, k [B, S, H d_k]; f [B, S, H d_k] float32; dt_bias [H d_k]; A_log
    [H] -> (q, k as they came, g float32), each [B, S, H d_k]: what a shape
    that does not tile runs, and the kernels' test oracle."""
    heads = lambda m: m.reshape(*m.shape[:2], A_log.shape[0], -1)  # noqa: E731
    f, rate = heads(f + dt_bias), jnp.exp(A_log)[:, None]
    g = (-rate * jax.nn.softplus(f) if decay_floor is None
         else decay_floor * jax.nn.sigmoid(f * rate))
    dk = f.shape[-1]
    return ((_l2norm(heads(q)) * dk ** -0.5).astype(q.dtype).reshape(q.shape),
            _l2norm(heads(k)).astype(k.dtype).reshape(k.shape), g.reshape(q.shape))


def gate_reference(o: jax.Array, logits: jax.Array, o_norm: jax.Array, eps: float) -> jax.Array:
    """o [B, S, H d_k]; logits [B, S, H] float32 or [B, S, H d_k] as o;
    o_norm [d_k] -> [B, S, H d_k] as o, rounded after the norm, after the
    weight and after the gate."""
    x32 = o.reshape(*o.shape[:2], -1, o_norm.shape[0]).astype(_F32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    normed = (x32 * rms).astype(o.dtype) * o_norm
    gate = jax.nn.sigmoid(logits)
    if logits.shape == o.shape:
        return normed.reshape(o.shape) * gate
    return (normed * gate.astype(o.dtype)[..., None]).reshape(o.shape)


# ---- the kernels -----------------------------------------------------------


def tiles(x: jax.Array, head_dim: int) -> bool:
    """Heads of whole lanes and a sequence of whole row groups."""
    return x.ndim == 3 and head_dim % 128 == 0 and x.shape[2] % head_dim == 0 \
        and x.shape[1] % _ROWS == 0 and jnp.issubdtype(x.dtype, jnp.floating)


def _blocks(S: int, width: int, head_dim: int, whole: bool) -> Tuple[int, int]:
    """(positions, channels) a grid step owns: every channel (``whole``) or
    the most whole heads under ``_LANES`` that divide the width, and
    ``_ROWS`` positions doubled while they divide ``S`` and the block stays
    under ``_ELEMS``."""
    lanes = width if whole else next(
        c for c in range(max(min(_LANES, width) // head_dim, 1) * head_dim, 0, -head_dim)
        if width % c == 0)
    tile = _ROWS
    while S % (2 * tile) == 0 and 2 * tile * lanes <= _ELEMS:
        tile *= 2
    return tile, lanes


def _sum(x):
    """x [rows, d_k] -> one sum a row, [rows, 1]."""
    return jnp.sum(x, axis=-1, keepdims=True)


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _walk(tile: int, width: int, dk: int, body) -> None:
    """``body(rows, cols, head)`` for every head of the block (static) and
    every ``_ROWS`` positions of it (a loop)."""
    for h in range(width // dk):
        cols = slice(h * dk, (h + 1) * dk)

        def over_rows(i, _, cols=cols, h=h):
            body(pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS), cols, h)
            return 0

        jax.lax.fori_loop(0, tile // _ROWS, over_rows, 0)


def _first_step(*accumulators) -> None:
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        for ref in accumulators:
            ref[...] = jnp.zeros_like(ref)


def _qkg_fwd_kernel(floor, dk, q_ref, k_ref, f_ref, b_ref, r_ref, qo_ref, ko_ref, g_ref):
    def body(rows, cols, _):
        q, k = q_ref[rows, cols].astype(_F32), k_ref[rows, cols].astype(_F32)
        qo_ref[rows, cols] = (q * jax.lax.rsqrt(_sum(q * q) + L2_EPS)
                              * dk ** -0.5).astype(qo_ref.dtype)
        ko_ref[rows, cols] = (k * jax.lax.rsqrt(_sum(k * k) + L2_EPS)).astype(ko_ref.dtype)
        x, rate = f_ref[rows, cols] + b_ref[:, cols], r_ref[:, cols]
        g_ref[rows, cols] = (-rate * _softplus(x) if floor is None
                             else floor * jax.nn.sigmoid(x * rate))

    _walk(*q_ref.shape, dk, body)


def _qkg_bwd_kernel(floor, dk, q_ref, k_ref, f_ref, b_ref, r_ref, dqo_ref, dko_ref, dg_ref,
                    dq_ref, dk_ref, df_ref, db_ref, dr_ref):
    _first_step(db_ref, dr_ref)

    def unnorm(x_ref, dy_ref, dx_ref, rows, cols, scale):
        # y = scale x r, r = (sum x^2 + eps)^-1/2: dx = scale r (dy - x r^2 sum(dy x))
        x, dy = x_ref[rows, cols].astype(_F32), dy_ref[rows, cols].astype(_F32)
        r = jax.lax.rsqrt(_sum(x * x) + L2_EPS)
        dx_ref[rows, cols] = ((dy - x * (r * r * _sum(dy * x))) * (r * scale)).astype(dx_ref.dtype)

    def body(rows, cols, _):
        unnorm(q_ref, dqo_ref, dq_ref, rows, cols, dk ** -0.5)
        unnorm(k_ref, dko_ref, dk_ref, rows, cols, 1.0)
        x, rate, dg = f_ref[rows, cols] + b_ref[:, cols], r_ref[:, cols], dg_ref[rows, cols]
        if floor is None:  # g = -rate softplus(x)
            dx, drate = -dg * rate * jax.nn.sigmoid(x), -dg * _softplus(x)
        else:  # g = floor sigmoid(rate x)
            s = jax.nn.sigmoid(x * rate)
            dz = dg * (floor * s * (1.0 - s))
            dx, drate = dz * rate, dz * x
        df_ref[rows, cols] = dx
        db_ref[:, cols] += jnp.sum(dx, axis=0, keepdims=True)
        dr_ref[:, cols] += jnp.sum(drate, axis=0, keepdims=True)

    _walk(*q_ref.shape, dk, body)


def _gate_fwd_kernel(eps, dk, o_ref, l_ref, w_ref, out_ref):
    per_head = l_ref.shape != o_ref.shape  # one logit a head, every head in the block

    def body(rows, cols, h):
        o = o_ref[rows, cols].astype(_F32)
        logits = l_ref[rows, h:h + 1] if per_head else l_ref[rows, cols]
        rms = jax.lax.rsqrt(_sum(o * o) / dk + eps)
        out_ref[rows, cols] = (o * rms * w_ref[:, cols]
                               * jax.nn.sigmoid(logits)).astype(out_ref.dtype)

    _walk(*o_ref.shape, dk, body)


def _gate_bwd_kernel(eps, dk, o_ref, l_ref, w_ref, dout_ref, do_ref, dl_ref, dw_ref):
    _first_step(dw_ref)
    per_head = l_ref.shape != o_ref.shape

    def body(rows, cols, h):
        o, dout, w = o_ref[rows, cols].astype(_F32), dout_ref[rows, cols].astype(_F32), \
            w_ref[:, cols]
        logits = l_ref[rows, h:h + 1] if per_head else l_ref[rows, cols]
        rms, s = jax.lax.rsqrt(_sum(o * o) / dk + eps), jax.nn.sigmoid(logits)
        # out = o rms w s: with dn = dout w s the cotangent of the normed rows,
        # do = rms (dn - o rms^2 sum(dn o) / d_k)
        dn, at_gate = dout * w * s, dout * o * rms
        do_ref[rows, cols] = ((dn - o * (rms * rms * _sum(dn * o) / dk)) * rms).astype(do_ref.dtype)
        at_logits, slope = at_gate * w, s * (1.0 - s)
        if per_head:
            dl_ref[rows, h:h + 1] = _sum(at_logits) * slope
        else:
            dl_ref[rows, cols] = at_logits * slope
        dw_ref[:, cols] += jnp.sum(at_gate * s, axis=0, keepdims=True)

    _walk(*o_ref.shape, dk, body)


def _specs(blocks):
    """A tile of the row arrays, and a row of one value a channel."""
    tile, lanes = blocks
    return (pl.BlockSpec((None, tile, lanes), lambda c, n, t: (n, t, c)),
            pl.BlockSpec((1, lanes), lambda c, n, t: (0, c)))


def _grid(x, blocks):
    return (x.shape[2] // blocks[1], x.shape[0], x.shape[1] // blocks[0])


_FREE = ("parallel", "parallel", "parallel")
_SUMMED = ("parallel", "arbitrary", "arbitrary")


# Jitted, so that a program's calls of one shape share one traced and lowered
# kernel, as ``ops/short_conv.py``'s.
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _qkg_forward(q, k, f, bias, rate, floor, dk, blocks):
    """q, k [B, S, H dk]; f as them, float32; bias, rate [1, H dk] float32."""
    rows, row = _specs(blocks)
    return pl.pallas_call(
        functools.partial(_qkg_fwd_kernel, floor, dk), grid=_grid(q, blocks),
        in_specs=[rows, rows, rows, row, row], out_specs=[rows, rows, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(f.shape, _F32)],
        name="kda_qkg_fwd", **_params(_FREE))(q, k, f, bias, rate)


@functools.partial(jax.jit, static_argnums=(8, 9, 10))
def _qkg_backward(q, k, f, bias, rate, dq, dk_, dg, floor, dk, blocks):
    """-> (dq, dk as q, k; df float32; dbias, drate [1, H dk] float32)."""
    rows, row = _specs(blocks)
    return pl.pallas_call(
        functools.partial(_qkg_bwd_kernel, floor, dk), grid=_grid(q, blocks),
        in_specs=[rows, rows, rows, row, row, rows, rows, rows],
        out_specs=[rows, rows, rows, row, row],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(f.shape, _F32)]
        + [jax.ShapeDtypeStruct(bias.shape, _F32)] * 2,
        name="kda_qkg_bwd", **_params(_SUMMED))(q, k, f, bias, rate, dq, dk_, dg)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _qkg(q, k, f, bias, rate, floor, dk, blocks):
    return tuple(_qkg_forward(q, k, f, bias, rate, floor, dk, blocks))


def _qkg_fwd(q, k, f, bias, rate, floor, dk, blocks):
    return tuple(_qkg_forward(q, k, f, bias, rate, floor, dk, blocks)), (q, k, f, bias, rate)


def _qkg_bwd(floor, dk, blocks, saved, cotangents):
    return tuple(_qkg_backward(*saved, *cotangents, floor, dk, blocks))


_qkg.defvjp(_qkg_fwd, _qkg_bwd)


def _logits_spec(o, logits, blocks):
    """The gate's logits beside a tile of ``o``: the same tile where they
    are one a channel, the tile's positions of every head where one a head."""
    if logits.shape == o.shape:
        return _specs(blocks)[0]
    return pl.BlockSpec((None, blocks[0], logits.shape[2]), lambda c, n, t: (n, t, 0))


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _gate_forward(o, logits, w, eps, dk, blocks):
    """o [B, S, H dk]; logits [B, S, H dk] or [B, S, H], w [1, H dk], float32."""
    rows, row = _specs(blocks)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, eps, dk),
        grid=_grid(o, blocks), in_specs=[rows, _logits_spec(o, logits, blocks), row],
        out_specs=rows, out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        name="kda_gate_fwd", **_params(_FREE))(o, logits, w)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _gate_backward(o, logits, w, dout, eps, dk, blocks):
    """-> (do as o, dlogits as logits, dw [1, H dk] float32)."""
    rows, row = _specs(blocks)
    gates = _logits_spec(o, logits, blocks)
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, eps, dk),
        grid=_grid(o, blocks), in_specs=[rows, gates, row, rows], out_specs=[rows, gates, row],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(logits.shape, _F32), jax.ShapeDtypeStruct(w.shape, _F32)],
        name="kda_gate_bwd", **_params(_SUMMED))(o, logits, w, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gate(o, logits, w, eps, dk, blocks):
    return _gate_forward(o, logits, w, eps, dk, blocks)


def _gate_fwd(o, logits, w, eps, dk, blocks):
    return _gate_forward(o, logits, w, eps, dk, blocks), (o, logits, w)


def _gate_bwd(eps, dk, blocks, saved, dout):
    return tuple(_gate_backward(*saved, dout, eps, dk, blocks))


_gate.defvjp(_gate_fwd, _gate_bwd)


# ---- the entries -----------------------------------------------------------


@jax.custom_vjp
def _widened(x):
    """x in float32 with the digits of its own dtype, and its cotangent
    rounded to them: XLA on the TPU drops a pair of converts, so ``astype``
    alone would hand the kernels unrounded logits and the projections
    unrounded cotangents, and the check's ``grad_norm_rel`` sees that
    (PERF.md section 6, PR 66)."""
    kind = jnp.finfo(x.dtype)
    return jax.lax.reduce_precision(x.astype(_F32), kind.nexp, kind.nmant)


def _widened_fwd(x):
    return _widened(x), jnp.zeros((0,), x.dtype)


def _widened_bwd(like, ct):
    kind = jnp.finfo(like.dtype)
    return (jax.lax.reduce_precision(ct, kind.nexp, kind.nmant).astype(like.dtype),)


_widened.defvjp(_widened_fwd, _widened_bwd)


def kda_qkg(q: jax.Array, k: jax.Array, f: jax.Array, dt_bias: jax.Array, A_log: jax.Array,
            decay_floor: Optional[float]) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The convolutions' q and k [B, S, H d_k], L2-normalised a head (q over
    ``sqrt(d_k)`` too), and the step's log decay ``g`` (float32) from the
    decay projection's float32 result ``f``, ``dt_bias`` [H d_k] and
    ``A_log`` [H], in the bounded form (``decay_floor``) or the unbounded one
    (None): the kernels where the heads are whole lanes and ``S`` whole row
    groups, :func:`qkg_reference` otherwise."""
    dk = q.shape[2] // A_log.shape[0]
    if not tiles(q, dk):
        return qkg_reference(q, k, f, dt_bias, A_log, decay_floor)
    # the leaves' own arithmetic stays XLA's: H exponents, and a row a channel
    rate = jnp.repeat(jnp.exp(A_log).astype(_F32), dk)[None]
    return _qkg(q, k, f, dt_bias.astype(_F32)[None], rate, decay_floor, dk,
                _blocks(q.shape[1], q.shape[2], dk, False))


def kda_gate(o: jax.Array, logits: jax.Array, o_norm: jax.Array, eps: float) -> jax.Array:
    """The delta rule's ``o`` [B, S, H d_k], RMS-normalised a head, times
    ``o_norm`` [d_k] and the sigmoid of the gate's ``logits`` (one a head,
    [B, S, H] float32, or one a channel, as ``o``), computed in float32 and
    rounded once: the kernels where the shape tiles, :func:`gate_reference`
    (which rounds after every factor) otherwise."""
    dk = o_norm.shape[0]
    if not tiles(o, dk):
        return gate_reference(o, logits, o_norm, eps)
    w = jnp.tile(o_norm.astype(_F32), o.shape[2] // dk)[None]
    # the kernels read float32 logits whatever the leaves gave: see the module's note
    return _gate(o, logits if logits.dtype == _F32 else _widened(logits), w, eps, dk,
                 _blocks(o.shape[1], o.shape[2], dk, logits.shape != o.shape))
