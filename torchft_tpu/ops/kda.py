"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692) for the training hot path.

For every head, with a state ``S`` of ``[d_k, d_v]`` float32 that starts at
zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t`` [d_k] is the log of the decay, in ``[-5, 0]`` (the published
``kda_lower_bound``: what keeps the factors below finite), ``beta_t`` a
scalar a head.

The recurrence is a Pallas kernel pair under ``jax.custom_vjp`` (``kda_fwd``
/ ``kda_bwd`` in a device trace): grid over batch, heads and blocks of
``BLOCK`` positions (four chunks of ``CHUNK``), the block axis last and
sequential, the state in VMEM scratch across blocks, so the ``[T, d_k,
d_v]`` states never reach HBM: the forward pass writes the state at every
block's start (256 MB a layer at 32k and 32 heads), the backward pass walks
the blocks in reverse with the state's cotangent in scratch. A chunk is ONE
definition in two halves, :func:`_state_free` (decays, ``A``, ``P``, the
chunk inverse: what no state enters) and :func:`_through_state`; the forward
kernel runs their composition (:func:`_chunk`) and the backward kernel
differentiates the same two inside the kernel, so forward and backward
cannot drift apart. In a block's backward pass every piece is computed once:
the state-free half is linearised once for the block's four chunks together
(``jax.vmap``: four independent chains of small dependent products, which
the MXU overlaps where one chain leaves it waiting), the state half once a
chunk from the saved state on, which also gives the states the later chunks
start from; the walk back uses both linearisations and nothing is run a
second time. The inverse's derivative is taken in closed form,
``dX = -M^T dM M^T`` for ``M = (I + X)^-1`` (:func:`_inverse`): exact,
because the product form below IS the inverse (the powers it drops are
zero), two products where autodiff through them takes twenty, and closer to
the float64 answer where a chunk's keys are nearly one vector. Off the TPU
the same kernels run interpreted, as ``ops/selective_scan.py``'s do.

A chunk (``C`` positions, ``G`` the running sum of ``g`` inside it, ``S0``
the state it starts from) in matrix products: with
``A[t,s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for ``s < t`` and
``P[t,s]`` the same with ``q_t`` for ``s <= t``,

    U  = (I + Diag(beta) A)^-1 Diag(beta) (V - (K exp(G)) S0)
    O  = (Q exp(G)) S0 + P U
    S1 = Diag(exp(G_C)) S0 + (K exp(G_C - G))^T U

``exp(G_t - G_s)`` is taken as ``exp(G_t - R) exp(R - G_s)`` with ``R`` the
running sum at the MIDDLE of ``t``'s sub-block of ``SUB`` rows: both factors
then lie within ``exp(+-SUB / 2 * 5)`` = e^+-40 where they are used (``s``
in the same sub-block; before it the second only shrinks), and the second
is cut off at ``exp(_LIMIT)`` where the mask drops it. (Taken from the
sub-block's start the factors reach e^-80 and e^80: finite, but in the
backward pass a cotangent times e^-80 is a denormal, a TPU flushes it to
zero, and the e^80 that should have brought it back multiplies nothing: the
decay's gradient was 1% off at decays near the bound.) ``(I + X)^-1`` for the strictly lower triangular ``X`` is taken in two
steps of the product form ``(I + Y)^-1 = (I - Y)(I + Y^2)(I + Y^4)...``
(exact where a power of ``Y`` is zero; matrix products and no substitution
loop): first of ``X``'s diagonal sub-blocks of ``SUB`` rows, ``D``, then of
``(I + D)^-1 L`` with ``L = X - D``, which is zero from its fourth power on:
``(I + X)^-1 = (I + (I + D)^-1 L)^-1 (I + D)^-1``, ten products of 64 cubed.
In one step over all 64 rows the powers of ``X`` hold binomial coefficients
up to 1e18 where a chunk's keys are nearly one vector under ``beta`` near 1
and little decay, their products pass float32's range and the output is not
finite: one freshly initialised model in six had such a head behind its
attention layer on the chip (PERF.md section 6, PR 40). Over 16 rows they
stay under 1e4. The state, the decays and every product are float32
(``Precision.HIGHEST`` on the MXU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda", "kda_reference"]

CHUNK = 64  # positions one set of matrix products covers
BLOCK = 4 * CHUNK  # positions a grid step owns; the state is saved at its start
SUB = 16  # rows whose decays share a reference point, at their middle
_LIMIT = 45.0  # SUB / 2 * 5 and room; exp() of twice as much still squares
_F32 = jnp.float32
# What the state is rounded to after every chunk (the reference: after
# every step). float32 is the only value the program runs with; the tests
# and benchmarks/ling_check_faults.py set bfloat16 here to show that the
# checks refuse it.
STATE_DTYPE = jnp.float32

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _products_inverse(x):
    """``(I + x)^-1`` of a strictly lower triangular ``x`` [C, C] in the two
    steps of the product form the module docstring describes."""
    C = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    eye = (rows == cols).astype(_F32)

    def inverse(y, order):  # (I + y)^-1 for y^order = 0: (I - y)(I + y^2)(I + y^4)...
        inv, power, n = eye - y, y, 2
        while n < order:
            power = _dot(power, power)
            inv = inv + _dot(inv, power)
            n *= 2
        return inv

    # I + X = (I + D)(I + (I + D)^-1 L), D the diagonal sub-blocks of X
    d = jnp.where(rows // SUB == cols // SUB, x, 0.0)
    of_blocks = inverse(d, SUB)
    return _dot(inverse(_dot(of_blocks, x - d), C // SUB), of_blocks)


@jax.custom_vjp
def _inverse(x):
    """:func:`_products_inverse` with the inverse's own derivative: the
    products ARE ``M = (I + x)^-1`` (every power they drop is zero), so
    ``dx = -M^T dM M^T``, two products where autodiff through the ten takes
    twenty."""
    return _products_inverse(x)


def _inverse_fwd(x):
    inv = _products_inverse(x)
    return inv, inv


def _inverse_bwd(inv, dinv):
    return (-_dot(_dot(inv, dinv, _TN), inv, _NT),)


_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _state_free(q, k, g, beta):
    """The half of a chunk that no state enters. q, k, g [C, dk]; beta [C,
    1]; float32 -> (``P`` masked [C, C], the chunk inverse [C, C], ``K
    exp(G)``, ``Q exp(G)``, ``K exp(G_C - G)`` [C, dk], ``exp(G_C)`` [1,
    dk])."""
    C = q.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G = _dot((rows >= cols).astype(_F32), g)  # running sum of g, inclusive
    a_rows, p_rows = [], []
    for lo in range(0, C, SUB):
        at = slice(lo, lo + SUB)
        mid = lo + SUB // 2
        ref = G[mid:mid + 1] - g[mid:mid + 1]  # the sum over the rows before ``mid``
        up = jnp.exp(G[at] - ref)  # within e^+-40
        down = k * jnp.exp(jnp.minimum(ref - G, _LIMIT))
        both = _dot(jnp.concatenate([k[at] * up, q[at] * up]), down, _NT)
        a_rows.append(both[:SUB])
        p_rows.append(both[SUB:])
    inv = _inverse(beta * jnp.where(rows > cols, jnp.concatenate(a_rows), 0.0))
    p = jnp.where(rows >= cols, jnp.concatenate(p_rows), 0.0)
    decay = jnp.exp(G)
    g_end = jnp.sum(g, axis=0, keepdims=True)  # [1, dk]
    return p, inv, k * decay, q * decay, k * jnp.exp(g_end - G), jnp.exp(g_end)


def _through_state(p, inv, k_in, q_in, k_out, decay, v, beta, st):
    """The half that the state enters: :func:`_state_free`'s six, v [C, dv],
    beta [C, 1] and st [dv, dk], the state TRANSPOSED (its decay is then a
    row broadcast over sublanes) -> (o [C, dv], the state after the
    chunk)."""
    C = v.shape[0]
    from_state = _dot(jnp.concatenate([k_in, q_in]), st, _NT)  # [2C, dv]
    u = _dot(inv, beta * (v - from_state[:C]))
    o = from_state[C:] + _dot(p, u)
    st = st * decay + _dot(u, k_out, _TN)
    return o, st.astype(STATE_DTYPE).astype(_F32)


def _chunk(q, k, v, g, beta, st):
    """One chunk of one head, all float32: the two halves composed."""
    return _through_state(*_state_free(q, k, g, beta), v, beta, st)


def _turned(x, axis):
    """A [1, n] row as an [n, 1] column (``axis`` 1) or back (``axis`` 0):
    ``beta`` lies along lanes in HBM, where a [T, 1] column would be padded
    to 128 lanes a position (512 MB a layer at 32k)."""
    n = max(x.shape)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, x, 0.0), axis=axis, keepdims=True)


def _chunks(q_ref, k_ref, v_ref, g_ref, b_ref):
    """The block as its chunks, float32: q, k, g [n, CHUNK, dk]; v [n, CHUNK,
    dv]; beta [n, CHUNK, 1]."""
    return tuple(m.reshape(BLOCK // CHUNK, CHUNK, m.shape[-1]) for m in (
        q_ref[...].astype(_F32), k_ref[...].astype(_F32), v_ref[...].astype(_F32),
        g_ref[...], _turned(b_ref[...], 1)))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, hs_ref, st_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_scr[...] = jnp.zeros_like(st_scr)

    st = st_scr[...]
    hs_ref[...] = st  # the state this block starts from
    for i, args in enumerate(zip(*_chunks(q_ref, k_ref, v_ref, g_ref, b_ref))):
        o, st = _chunk(*args, st)
        o_ref[i * CHUNK:(i + 1) * CHUNK, :] = o.astype(o_ref.dtype)
    st_scr[...] = st


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, hs_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dst_scr):
    """One block, the blocks in reverse, each half of a chunk linearised
    ONCE: the state-free half of the block's chunks together (``vmap``: four
    independent chains of small products, side by side where the MXU can
    overlap them), then the state half chunk by chunk from the saved state on,
    which is the states pass (no ``o`` is taken). The walk goes back through
    the state halves, ``dst_scr`` carrying the cotangent of the state a block
    ends with into the block before, and hands what it gathered to the
    state-free half's one pullback."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_scr[...] = jnp.zeros_like(dst_scr)

    q, k, v, g, beta = _chunks(q_ref, k_ref, v_ref, g_ref, b_ref)
    free, into_free = jax.vjp(jax.vmap(_state_free), q, k, g, beta)
    st, into_states = hs_ref[...], []
    for i in range(len(v)):
        (_, st), into = jax.vjp(_through_state, *(m[i] for m in free), v[i], beta[i], st)
        into_states.append(into)
    dst, back = dst_scr[...], []
    for i in reversed(range(len(v))):
        *rest, dst = into_states[i]((do_ref[i * CHUNK:(i + 1) * CHUNK, :].astype(_F32), dst))
        back.append(rest)
    *dfree, dv, db = (jnp.stack(m[::-1]) for m in zip(*back))
    dq, dk, dg, db_free = into_free(tuple(dfree))
    for ref, m in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv), (dg_ref, dg)):
        ref[...] = m.reshape(ref.shape).astype(ref.dtype)
    db_ref[...] = _turned((db + db_free).reshape(BLOCK, 1), 0)
    dst_scr[...] = dst


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params():
    if _interpret():
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        # the backward kernel's unrolled chunks keep 12 MB of temporaries
        # under a default scope of 16 of the v5e's 128: room, not need
        vmem_limit_bytes=64 * 2**20)}


def _specs(H, dk, dv, nc, reverse):
    """Block specs of (a [B, T, H*dk] array, a [B, T, H*dv] one, beta
    [B, H, T/BLOCK, 1, BLOCK], the saved states [B, H, T/BLOCK, dv, dk]) on
    the grid (batch, head, block), the blocks in reverse for the backward
    pass."""
    at = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    return (pl.BlockSpec((None, BLOCK, dk), lambda b, h, c: (b, at(c), h)),
            pl.BlockSpec((None, BLOCK, dv), lambda b, h, c: (b, at(c), h)),
            pl.BlockSpec((None, None, None, 1, BLOCK),
                         lambda b, h, c: (b, h, at(c), 0, 0)),
            pl.BlockSpec((None, None, None, dv, dk), lambda b, h, c: (b, h, at(c), 0, 0)))


def _forward(q, k, v, g, beta, H):
    """q, k [B, T, H*dk]; v [B, T, H*dv]; g [B, T, H*dk] f32; beta [B, H,
    T/BLOCK, 1, BLOCK] f32; T whole blocks -> (o [B, T, H*dv] in v's dtype,
    the state at every block's start [B, H, T/BLOCK, dv, dk] f32)."""
    B, T, dk, dv = q.shape[0], q.shape[1], q.shape[2] // H, v.shape[2] // H
    nc = T // BLOCK
    qk, vo, bt, hs = _specs(H, dk, dv, nc, reverse=False)
    return pl.pallas_call(
        _fwd_kernel, grid=(B, H, nc),
        in_specs=[qk, qk, vo, qk, bt], out_specs=[vo, hs],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, nc, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        name="kda_fwd", **_params(),
    )(q, k, v, g, beta)


def _backward(q, k, v, g, beta, hs, do, H):
    B, T, dk, dv = q.shape[0], q.shape[1], q.shape[2] // H, v.shape[2] // H
    qk, vo, bt, st = _specs(H, dk, dv, T // BLOCK, reverse=True)
    return pl.pallas_call(
        _bwd_kernel, grid=(B, H, T // BLOCK),
        in_specs=[qk, qk, vo, qk, bt, st, vo], out_specs=[qk, qk, vo, qk, bt],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        name="kda_bwd", **_params(),
    )(q, k, v, g, beta, hs, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, H):
    return _forward(q, k, v, g, beta, H)[0]


def _kda_fwd(q, k, v, g, beta, H):
    o, hs = _forward(q, k, v, g, beta, H)
    return o, (q, k, v, g, beta, hs)


def _kda_bwd(H, saved, do):
    return tuple(_backward(*saved, do, H))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array) -> jax.Array:
    """q, k, g [B, T, H, d_k]; v [B, T, H, d_v]; beta [B, T, H] -> o [B, T,
    H, d_v] in v's dtype. ``g`` within ``[-5, 0]``. Any T: the sequence is
    padded to whole blocks with positions of k = 0, g = 0 and beta = 0,
    which leave the state as it is."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    flat = lambda m: m.reshape(B, T, -1)  # noqa: E731
    args = [flat(q), flat(k), flat(v), flat(g.astype(_F32)), beta.astype(_F32)]
    pad = -T % BLOCK
    if pad:
        args = [jnp.pad(m, ((0, 0), (0, pad), (0, 0))) for m in args]
    q2, k2, v2, g2, b2 = args
    o = _kda(q2, k2, v2, g2,
             jnp.swapaxes(b2, 1, 2).reshape(B, H, -1, 1, BLOCK), H)
    return o[:, :T].reshape(B, T, H, dv)


def kda_reference(q, k, v, g, beta):
    """The same function as a ``lax.scan`` over positions in float32: the
    kernels' test oracle, never the program's path."""
    f = lambda m: jnp.swapaxes(m.astype(_F32), 0, 1)  # noqa: E731  time first

    def step(S, inp):  # S [B,H,dk,dv]
        q_t, k_t, v_t, g_t, b_t = inp
        S = jnp.exp(g_t)[..., None] * S
        S = S + (b_t[..., None] * k_t)[..., None] * (
            v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))[..., None, :]
        S = S.astype(STATE_DTYPE).astype(_F32)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

    B, _, H, dk = q.shape
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), _F32)
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(step, S0, (f(q), f(k), f(v), f(g), f(beta)))
    return jnp.swapaxes(o, 0, 1).astype(v.dtype)
