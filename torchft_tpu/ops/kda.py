"""Gated delta-rule linear attention with a per-channel decay (Kimi Delta
Attention, arXiv:2510.26692) for the training hot path.

For every head, with a state ``S`` of ``[d_k, d_v]`` float32 that starts at
zero::

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``g_t`` [d_k] is the log of the decay, ANY value ``<= 0`` (Kimi Linear's own
``-exp(A_log) softplus(.)`` has no floor), ``beta_t`` a scalar a head in
``[0, 2]`` (``allow_neg_eigval``, arXiv:2411.12537: at ``beta`` > 1 the
transition ``I - beta k k^T`` has a negative eigenvalue). The kernels are
finite and the recurrence's for all of them: ``tests/test_solar_kernels.py``
holds them to the recurrence in float64 at -80 a step, at a chunk's sum past
-700 and at ``beta`` = 1.999 on keys within 1e-3 of one vector. A model whose
form of ``g`` and ``beta`` promises more (``g >= -5`` and ``beta <= 1``:
``models/ling.py``'s published ``kda_lower_bound``) says so where the step is
traced (``kda``'s ``decay_floor``, ``beta_max``) and gets the BOUNDED body,
which is the same function at two thirds of the time (below).

The recurrence is a Pallas kernel pair under ``jax.custom_vjp`` (``kda_fwd``
/ ``kda_bwd`` in a device trace): grid over batch, heads and blocks of
``BLOCK`` positions (four chunks of ``CHUNK``), the block axis last and
sequential, the state in VMEM scratch across blocks, so the ``[T, d_k,
d_v]`` states never reach HBM: the forward pass writes the state at every
block's start (256 MB a layer at 32k and 32 heads), the backward pass walks
the blocks in reverse with the state's cotangent in scratch. A chunk is ONE
definition in two halves, :func:`_state_free` (decays, ``A``, ``P``, the
chunk inverse: what no state enters) and :func:`_through_state`; the forward
kernel runs the two and the backward kernel differentiates the same two
inside the kernel, so forward and backward cannot drift apart. Both kernels
take the state-free half of a block's four chunks together, as ONE batch
before their walk over the chunks (``jax.vmap``: four independent chains of
small dependent products, which the MXU overlaps where one chain leaves it
waiting; these kernels are bound by the latency of such products and not by
their FLOPs), and only the state half, three dependent products a chunk,
goes chunk by chunk from the block's state on. In a block's backward pass
every piece is computed once: the state-free half is linearised once for the
batch, the state half once a chunk from the saved state on, which also gives
the states the later chunks start from; the walk back uses both
linearisations and nothing is run a second time. The inverse's derivative is
taken in closed form,
``dX = -M^T dM M^T`` for ``M = (I + X)^-1`` (:func:`_inverse`): exact,
because the products below ARE the inverse (either body's), two products
where autodiff through them takes twenty, and closer to
the float64 answer where a chunk's keys are nearly one vector. Off the TPU
the same kernels run interpreted, as ``ops/selective_scan.py``'s do.

A chunk (``C`` positions, ``G`` the running sum of ``g`` inside it, ``S0``
the state it starts from) in matrix products: with
``A[t,s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for ``s < t`` and
``P[t,s]`` the same with ``q_t`` for ``s <= t``,

    U  = (I + Diag(beta) A)^-1 Diag(beta) (V - (K exp(G)) S0)
    O  = (Q exp(G)) S0 + P U
    S1 = Diag(exp(G_C)) S0 + (K exp(G_C - G))^T U

**The general body** (:func:`_state_free`, :func:`_products_inverse`). No
exponent it takes is above 0, so nothing overflows, and a factor that
underflows to 0 stands for a product that is under 1e-38 too. Between
sub-blocks of ``SUB`` rows ``exp(G_t - G_s)`` is ``exp(G_t - R) exp(R - G_s)``
with ``R`` the running sum over the rows BEFORE ``t``'s sub-block: ``s`` lies
before it, so both factors only shrink (one product of ``[2 SUB, d_k] x [d_k,
C]`` a sub-block on the MXU). Inside a sub-block no one point serves every
pair (after a row that decays by e^-80 the rows behind it hold on, or not: a
factor taken from any fixed row is then e^+640 for one pair or 0 for a pair
whose product is 1), so the exponents are taken pair by pair, ``[SUB, SUB,
d_k]`` differences under the mask ``s <= t``, an ``exp``, two multiplies and
a sum over the channels on the VPU: that is the body's cost. (A mask, not a
``minimum`` at 0: where no decay lies between two rows the difference is 0
exactly, and a minimum's tie halves the gradient.) ``(I + X)^-1`` for the
strictly lower triangular ``X`` is taken by DOUBLING: ``I - X`` is the
inverse of ``I + X``'s diagonal blocks of 2 rows, and from the inverses
``M`` of the blocks of ``n`` rows those of ``2n`` are ``M - M L M``, ``L`` the
part of ``X`` below the diagonal blocks of ``n`` inside those of ``2n``
(``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``): five levels,
ten products of 64 cubed, every intermediate an entry of a true inverse times
an entry of ``X``. What the chunked form itself limits: the exponents are
differences of a float32 running sum over a chunk's 64 rows, so their
absolute error is 6e-8 x the chunk's largest ``|G|`` (read 2e-5 in the output
where resets of -80 alternate with steps of -1e-3: ``|G|`` 700).

**The bounded body** (:func:`_state_free_bounded`, :func:`_power_inverse`),
for ``g`` in ``[-5, 0]`` and ``beta <= 1`` alone: ``R`` is the running sum at
the MIDDLE of ``t``'s sub-block, for every ``s``: both factors then lie
within ``exp(+-SUB / 2 * 5)`` = e^+-40 where they are used (``s`` in the same
sub-block; before it the second only shrinks), and the second is cut off at
``exp(_LIMIT)`` where the mask drops it, so a sub-block is one product and no
pair-by-pair work. (Taken from the sub-block's start the factors reach e^-80
and e^80: finite, but in the backward pass a cotangent times e^-80 is a
denormal, a TPU flushes it to zero, and the e^80 that should have brought it
back multiplies nothing: the decay's gradient was 1% off at decays near the
bound.) Its inverse is two steps of the product form ``(I + Y)^-1 = (I - Y)(I
+ Y^2)(I + Y^4)...`` (exact where a power of ``Y`` is zero): first of ``X``'s
diagonal sub-blocks of ``SUB`` rows, ``D``, then of ``(I + D)^-1 L`` with ``L
= X - D``, which is zero from its fourth power on: ``(I + X)^-1 = (I + (I +
D)^-1 L)^-1 (I + D)^-1``, ten products too. In one step over all 64 rows the
powers of ``X`` hold binomial coefficients up to 1e18 where a chunk's keys are
nearly one vector under ``beta`` near 1 and little decay, and the output is
not finite: one freshly initialised model in six had such a head behind its
attention layer on the chip (PERF.md section 6, PR 40). Over 16 rows at
``beta <= 1`` the powers stay under 1e4 and cancel to an inverse of O(1):
four digits lost, 1.1e-4 in the output on keys within 1e-3 of one vector
where the doubling reads 1.2e-6. At ``beta`` = 2 they reach 256 x C(14, 7) =
9e5 and the product form is off by a fifth (``tests/test_solar_kernels.py``),
which is why this body is held to ``beta <= 1``.

**Why two, and what the bounded body's own inverse is still worth.** On a
v5e at 32 heads x 32,768 positions (Ling's shape; the same to three digits at
64 x 16,384) one jitted call of ``kda``, timed on the host's clock round the
whole call with its layout turns inside (``benchmarks/kda_bodies.py``, my chip
run, PR 65, call A, parent and change back to back: a standalone timing, not
a step's; in Ling's cell's device trace ``kda_fwd`` reads 0.0270 s a call
where that script reads 0.0340), takes forward 0.03398 s bounded and 0.05011
s general, and forward with backward 0.10334 s and 0.14641 s: the
pair-by-pair exponents cost 1.47 x and 1.42 x, which is why there are two
bodies. Before the forward kernel took a block's state-free halves as one
batch (PR 65) the same calls read 0.04632 / 0.06697 forward and 0.11559 /
0.16337 with backward: the batch took 12.3 ms off the bounded forward pass
and 16.9 ms off the general one, the chains of small dependent products that
each chunk had waited on. The INVERSES no longer differ: the bounded body's
exponents over the DOUBLING inverse read 0.03395 s forward and 0.10308 s with
backward, the product form's 0.03398 and 0.10334. One chunk at a time the
doubling cost the forward kernel 5.8 ms a pass (0.05217 against 0.04632: ten
products in one chain where the product form's longest is eight), 2.3% of
Ling's step; with four chunks' chains side by side the longer chain is hidden
as it always was in the backward kernel. So ``_power_inverse``,
``_inverse_bounded`` and ``beta_max`` are no longer paid for by a reading,
and a ``simplicity`` PR may take them out (ROADMAP D14): the bounded body
would then be the general body's inverse under its own exponents, right for
``beta`` to 2, and ``decay_floor`` alone would choose the body. They stay
here because Ling's lowered step would change with them. Whether a
configuration bounds its decay is a property of the model known when the
step is traced, not an option of the program.

The state, the decays and every product are float32 (``Precision.HIGHEST`` on
the MXU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda", "kda_reference"]

CHUNK = 64  # positions one set of matrix products covers
BLOCK = 4 * CHUNK  # positions a grid step owns; the state is saved at its start
SUB = 16  # rows of a sub-block: pair by pair inside it, or (bounded) one point at its middle
BOUNDED_FLOOR = -5.0  # the least log decay a step the bounded body is finite and right at
_LIMIT = 45.0  # bounded: SUB / 2 * 5 and room; exp() of twice as much still squares
_F32 = jnp.float32
# What the state is rounded to after every chunk (the reference: after
# every step). float32 is the only value the program runs with; the tests
# and benchmarks/ling_check_faults.py and solar_check_faults.py set bfloat16
# here to show that the checks refuse it.
STATE_DTYPE = jnp.float32

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _products_inverse(x):
    """``(I + x)^-1`` of a strictly lower triangular ``x`` [C, C] by
    doubling, as the module docstring describes: ten products, none of them
    of a power of ``x``."""
    C = x.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    inv = (rows == cols).astype(_F32) - jnp.where(rows // 2 == cols // 2, x, 0.0)
    n = 2
    while n < C:  # inv: the inverses of I + x's diagonal blocks of n rows
        below = jnp.where((rows // (2 * n) == cols // (2 * n)) & (rows // n != cols // n), x, 0.0)
        inv = inv - _dot(inv, _dot(below, inv))
        n *= 2
    return inv


def _power_inverse(x):
    """``(I + x)^-1`` in the two steps of the product form of powers the
    module docstring describes: the bounded body's."""
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


def _with_closed_pullback(products):
    """``products`` (one of the two above) with the inverse's own derivative:
    the products ARE ``M = (I + x)^-1``, so ``dx = -M^T dM M^T``, two
    products where autodiff through the ten takes twenty."""
    inverse = jax.custom_vjp(lambda x: products(x))

    def fwd(x):
        inv = products(x)
        return inv, inv

    inverse.defvjp(fwd, lambda inv, dinv: (-_dot(_dot(inv, dinv, _TN), inv, _NT),))
    return inverse


_inverse = _with_closed_pullback(_products_inverse)
_inverse_bounded = _with_closed_pullback(_power_inverse)


def _running_sum(g):
    """g [C, dk] -> (its running sum down the rows, inclusive, and two iotas
    [C, C])."""
    C = g.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return _dot((rows >= cols).astype(_F32), g), rows, cols


def _the_six(a_rows, p_rows, q, k, g, G, beta, rows, cols, inverse):
    """What either state-free half hands on, from the rows of ``A`` and of
    ``P`` a sub-block at a time."""
    inv = inverse(beta * jnp.where(rows > cols, jnp.concatenate(a_rows), 0.0))
    p = jnp.where(rows >= cols, jnp.concatenate(p_rows), 0.0)
    decay = jnp.exp(G)
    g_end = jnp.sum(g, axis=0, keepdims=True)  # [1, dk]
    return p, inv, k * decay, q * decay, k * jnp.exp(g_end - G), jnp.exp(g_end)


def _state_free(q, k, g, beta):
    """The half of a chunk that no state enters, for every ``g <= 0``. q, k,
    g [C, dk]; beta [C, 1]; float32 -> (``P`` masked [C, C], the chunk
    inverse [C, C], ``K exp(G)``, ``Q exp(G)``, ``K exp(G_C - G)`` [C, dk],
    ``exp(G_C)`` [1, dk]). No exponent taken here is above 0."""
    C, dk = q.shape
    G, rows, cols = _running_sum(g)
    n = C // SUB
    # inside a sub-block of SUB rows: the exponents pair by pair
    Gs, ks, qs = (m.reshape(n, SUB, dk) for m in (G, k, q))
    at_or_before = (jax.lax.broadcasted_iota(jnp.int32, (n, SUB, SUB, dk), 1)
                    >= jax.lax.broadcasted_iota(jnp.int32, (n, SUB, SUB, dk), 2))
    # a mask and no minimum: where no decay lies between two rows the
    # difference is 0 exactly, and a minimum's tie halves the gradient
    pair = ks[:, None] * jnp.exp(
        jnp.where(at_or_before, Gs[:, :, None] - Gs[:, None], 0.0))  # [n,t,s,dk]
    within = jnp.concatenate([jnp.sum(ks[:, :, None] * pair, axis=-1),
                              jnp.sum(qs[:, :, None] * pair, axis=-1)]).reshape(2 * C, SUB)
    # [2C, SUB] -> [2C, C], each sub-block's columns under its own rows
    tiled = _dot(within, (jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 0)
                          == jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 1) % SUB).astype(_F32))
    a_rows, p_rows = [tiled[:SUB]], [tiled[C:C + SUB]]
    for lo in range(SUB, C, SUB):
        # the sub-blocks before this one: from the sum over the rows before it
        at = slice(lo, lo + SUB)
        ref = G[lo - 1:lo]
        up = jnp.exp(G[at] - ref)
        down = k * jnp.exp(jnp.where(  # from ``lo`` on the mask below drops it
            jax.lax.broadcasted_iota(jnp.int32, G.shape, 0) < lo, ref - G, 0.0))
        both = _dot(jnp.concatenate([k[at] * up, q[at] * up]), down, _NT)
        before = jax.lax.broadcasted_iota(jnp.int32, (SUB, C), 1) < lo
        a_rows.append(jnp.where(before, both[:SUB], tiled[at]))
        p_rows.append(jnp.where(before, both[SUB:], tiled[C + lo:C + lo + SUB]))
    return _the_six(a_rows, p_rows, q, k, g, G, beta, rows, cols, _inverse)


def _state_free_bounded(q, k, g, beta):
    """:func:`_state_free` for ``g`` in ``[-5, 0]`` alone: a sub-block's
    exponents from one point at its middle, which is one product a sub-block
    and no pair-by-pair work."""
    C = q.shape[0]
    G, rows, cols = _running_sum(g)
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
    return _the_six(a_rows, p_rows, q, k, g, G, beta, rows, cols, _inverse_bounded)


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


def _fwd_kernel(free, q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, hs_ref, st_scr):
    @pl.when(pl.program_id(2) == 0)
    def _():
        st_scr[...] = jnp.zeros_like(st_scr)

    st = st_scr[...]
    hs_ref[...] = st  # the state this block starts from
    q, k, v, g, beta = _chunks(q_ref, k_ref, v_ref, g_ref, b_ref)
    six = jax.vmap(free)(q, k, g, beta)  # no state enters: the block's chunks side by side
    for i in range(len(v)):
        o, st = _through_state(*(m[i] for m in six), v[i], beta[i], st)
        o_ref[i * CHUNK:(i + 1) * CHUNK, :] = o.astype(o_ref.dtype)
    st_scr[...] = st


def _bwd_kernel(free, q_ref, k_ref, v_ref, g_ref, b_ref, hs_ref, do_ref,
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
    six, into_free = jax.vjp(jax.vmap(free), q, k, g, beta)
    st, into_states = hs_ref[...], []
    for i in range(len(v)):
        (_, st), into = jax.vjp(_through_state, *(m[i] for m in six), v[i], beta[i], st)
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


def _forward(q, k, v, g, beta, H, free):
    """q, k [B, T, H*dk]; v [B, T, H*dv]; g [B, T, H*dk] f32; beta [B, H,
    T/BLOCK, 1, BLOCK] f32; T whole blocks; ``free``: the state-free half to
    run -> (o [B, T, H*dv] in v's dtype, the state at every block's start
    [B, H, T/BLOCK, dv, dk] f32)."""
    B, T, dk, dv = q.shape[0], q.shape[1], q.shape[2] // H, v.shape[2] // H
    nc = T // BLOCK
    qk, vo, bt, hs = _specs(H, dk, dv, nc, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, free), grid=(B, H, nc),
        in_specs=[qk, qk, vo, qk, bt], out_specs=[vo, hs],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, nc, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        name="kda_fwd", **_params(),
    )(q, k, v, g, beta)


def _backward(q, k, v, g, beta, hs, do, H, free):
    B, T, dk, dv = q.shape[0], q.shape[1], q.shape[2] // H, v.shape[2] // H
    qk, vo, bt, st = _specs(H, dk, dv, T // BLOCK, reverse=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, free), grid=(B, H, T // BLOCK),
        in_specs=[qk, qk, vo, qk, bt, st, vo], out_specs=[qk, qk, vo, qk, bt],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), _F32)],
        name="kda_bwd", **_params(),
    )(q, k, v, g, beta, hs, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, H, free):
    return _forward(q, k, v, g, beta, H, free)[0]


def _kda_fwd(q, k, v, g, beta, H, free):
    o, hs = _forward(q, k, v, g, beta, H, free)
    return o, (q, k, v, g, beta, hs)


def _kda_bwd(H, free, saved, do):
    return tuple(_backward(*saved, do, H, free))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array, *,
        decay_floor: Optional[float] = None, beta_max: float = 2.0) -> jax.Array:
    """q, k, g [B, T, H, d_k]; v [B, T, H, d_v]; beta [B, T, H] -> o [B, T,
    H, d_v] in v's dtype. Any ``g <= 0`` and any ``beta`` in [0, 2]. What
    the model's own form of ``g`` and ``beta`` promises beyond that is said
    where the step is traced: ``decay_floor`` (``g >= decay_floor``
    everywhere) and ``beta_max``; at a floor of -5 or above under ``beta <=
    1`` the bounded body runs (the module docstring), for the same answer.
    Any T: the sequence is padded to whole blocks with positions of k = 0,
    g = 0 and beta = 0, which leave the state as it is."""
    bounded = decay_floor is not None and decay_floor >= BOUNDED_FLOOR and beta_max <= 1.0
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    flat = lambda m: m.reshape(B, T, -1)  # noqa: E731
    args = [flat(q), flat(k), flat(v), flat(g.astype(_F32)), beta.astype(_F32)]
    pad = -T % BLOCK
    if pad:
        args = [jnp.pad(m, ((0, 0), (0, pad), (0, 0))) for m in args]
    q2, k2, v2, g2, b2 = args
    o = _kda(q2, k2, v2, g2,
             jnp.swapaxes(b2, 1, 2).reshape(B, H, -1, 1, BLOCK), H,
             _state_free_bounded if bounded else _state_free)
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
