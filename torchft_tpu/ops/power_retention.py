"""Power retention (degree-2 symmetric-power linear attention under a learned
scalar decay; "Scaling Context Requires Rethinking Attention", Manifest AI,
arXiv:2507.04239) in its chunked form, for the training hot path.

For every key/value head ``j`` and each of the ``rep`` query heads ``i`` that
read it, with ``s`` the scale, ``g_t <= 0`` the log of the decay (one scalar
a key/value head and position) and ``G_t`` its running sum::

    a[t, r] = exp(G_t - G_r) (s q_t[i] . k_r[j])^2     for r <= t, else 0
    y_t[i]  = sum_r a[t, r] v_r[j] / (sum_r a[t, r] + eps)

``(u . w)^2 = phi(u) . phi(w)`` with ``phi(u) = (u_a u_b (sqrt 2 if a < b
else 1))_{a <= b}``, ``d (d + 1) / 2`` wide (8,256 at ``d`` = 128), so the
same function is a recurrence over a state ``S`` [phi, d_v] and a normaliser
``z`` [phi], float32, from zero::

    S_t = exp(g_t) S_{t-1} + phi(k_t) v_t^T     z_t = exp(g_t) z_{t-1} + phi(k_t)
    y_t[i] = phi(s q_t[i])^T S_t / (phi(s q_t[i]) . z_t + eps)

and in chunks of ``C`` positions (``G`` the running sum inside the chunk,
``S0`` the state it starts from; ``z`` rides along as one more column of
``v``, a column of ones)::

    num = exp(G) o (phi(Q) S0) + (D o (Q K^T)^2) V      D[t, r] = exp(G_t - G_r), r <= t
    S1  = exp(G_C) S0 + phi(K)^T (exp(G_C - G) o V)

The decay is ONE scalar a head and ``g <= 0``, so every exponent above is
the sum of some ``g`` and ``<= 0``: ``exp(G_t - G_r)`` for ``r <= t``,
``exp(G_t)``, ``exp(G_C - G_r)`` all lie in (0, 1], where they are used the
difference is taken BEFORE the exponential, and nothing can overflow: none
of ``ops/kda.py``'s care for a reference point is needed (there the decay is
a vector a head and the factors are split between the two operands of a
product).

A Pallas kernel pair under ``jax.custom_vjp`` (``power_retention_fwd`` /
``power_retention_bwd`` in a device trace): grid over batch, key/value heads
and blocks of ``BLOCK`` positions (``BLOCK / CHUNK`` chunks), the block axis
last and sequential, the state with its normaliser in VMEM scratch across
blocks (4.8 MB a head at ``d`` = 128), the ``rep`` query heads of a group
side by side against the one state. ``phi`` exists in VMEM only, a tile of
``d`` features at a time: written to HBM ``phi(K)`` would be 16.5 KB a token
a head. The forward pass writes the state every BLOCK starts from (not every
chunk: 0.6 GB a layer at 16k and 8 heads) and, where a gradient follows,
what every chunk READS from the state it starts from (``phi(Q) S0`` with the
normaliser's row, before the decay: 0.38 GB a layer); the backward pass walks
the blocks in reverse with the state's cotangent in scratch, computes the
states inside a block again from the saved one, loads each chunk's read
where it would multiply the state's tiles by the queries' a second time, and
takes the chunk apart with the SAME functions the forward pass is made of
(:func:`_prepare`, :func:`_intra`, :func:`_tile`), so forward and backward
cannot drift apart. The state, the
normaliser, the decays and the division are float32; a product of float32
operands is three passes of the MXU over their bfloat16 halves
(:func:`_dot`), the running sums of the log-decays six. Off the TPU the same
kernels run interpreted, as the other kernels do.

Inside the kernel everything is TRANSPOSED: features lie along sublanes,
positions along lanes, so that ``u_a`` times the row ``u`` is a sublane
broadcast. The symmetric features are laid out as ``d / 2 + 1`` tiles of
``d`` rows: tile ``a < d / 2`` holds ``u_a u_b`` for the ``d / 2`` ``b``
that follow ``a`` round the circle (rows below ``d / 2``) and ``u_a' u_b``
for those that follow ``a' = a + d / 2`` (rows from ``d / 2``): every
unordered pair ``a != b`` once with weight sqrt 2, except the ``d / 2``
pairs half a circle apart, which come up from both sides and weigh 1 each
(1 + 1 = sqrt 2 squared); the last tile holds the squares ``u_a^2``. The
inner product of two such vectors is ``(u . w)^2`` exactly; 8,320 rows are
stored for 8,256 distinct products.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["power_retention", "power_retention_reference", "CHUNK", "BLOCK"]

CHUNK = 256  # positions one set of matrix products covers
BLOCK = 1024  # positions a grid step owns; the state is saved at its start
EPS = 1e-6  # added to the normaliser before the division
PAD = 16  # rows the normaliser's column of ones adds to v: one, and whole bfloat16 tiles
_F32 = jnp.float32
# What the state and the normaliser are rounded to after every chunk (the
# reference: after every step). float32 is the only value the program runs
# with; the tests and benchmarks/brumby_check_faults.py set bfloat16 here to
# show that the checks refuse it.
STATE_DTYPE = jnp.float32
# phi's weight of a product u_a u_b, a != b; sqrt 2 is the only value the
# program runs with (the fault script sets 1: "phi without the sqrt 2").
CROSS = math.sqrt(2.0)

# Whether the output is divided by the running sum of its weights; True is
# the only value the program runs with (the fault script sets False: "the
# normaliser left out").
NORMALISED = True

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _halves(x):
    """float32 ``x`` as (its bfloat16 rounding, the bfloat16 rounding of what
    that left); a pair is handed on as it is: an operand many tiles multiply
    is split once."""
    if isinstance(x, tuple):
        return x
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(_F32)).astype(jnp.bfloat16)


def _exact(a, b, dims=_NN):
    """Six passes of the MXU (``Precision.HIGHEST``): the running sums of
    the log-decays, which stand in exponents."""
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=_F32)


def _dot(a, b, dims=_NN):
    """A float32 product in THREE passes of the MXU: each operand split into
    two bfloat16 halves, the three products that matter summed in float32 (16
    mantissa bits an operand, an error of 2^-17 a product: a hundredth of
    what rounding the state to bfloat16 costs; Mosaic has no
    ``Precision.HIGH``, and six passes took 1.5 times as long on the chip:
    PERF.md section 6, PR 56)."""
    (ah, al), (bh, bl) = _halves(a), _halves(b)
    dot = functools.partial(jax.lax.dot_general, dimension_numbers=dims,
                            preferred_element_type=_F32)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _roll(x, shift):
    """``x`` [d, n] turned down its rows: row ``r`` of the result is row
    ``r - shift`` of ``x``, round the circle; ``shift`` in [0, d)."""
    if _interpret():
        return jnp.roll(x, shift, axis=0)
    return pltpu.roll(x, shift, 0)


def _turned(x, axis):
    """A [1, n] row as an [n, 1] column (``axis`` 1) or back (``axis`` 0)."""
    n = max(x.shape)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(eye, x, 0.0), axis=axis, keepdims=True)


def _weights(d, n):
    """[d, n]: phi's weight of each row of a tile (see the module's text)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (d, n), 0)
    half = (rows == d // 2 - 1) | (rows == d - 1)
    return jnp.where(half, CROSS / math.sqrt(2.0), CROSS).astype(_F32)


def _tile(x_ref, a, weights):
    """Tile ``a < d / 2`` of phi of the vectors in ``x_ref`` [d, n] (one a
    column) as its two factors: (the row that multiplies, the rows
    multiplied with their weights, ``_weights(d, n)``, made once outside the
    loop over tiles): the tile is their product."""
    d, n = x_ref.shape
    sel = jnp.concatenate([jnp.broadcast_to(x_ref[pl.ds(a, 1), :], (d // 2, n)),
                           jnp.broadcast_to(x_ref[pl.ds(a + d // 2, 1), :], (d // 2, n))])
    return sel, _roll(x_ref[...], d - 1 - a) * weights


def _tile_back(dx_ref, a, sel, win, dt, weights):
    """The cotangent ``dt`` of tile ``a`` (factors ``sel``, ``win`` of
    :func:`_tile`) added to the vectors' cotangent ``dx_ref`` [d, n]."""
    d = dx_ref.shape[0]
    dx_ref[...] += _roll(dt * sel * weights, a + 1)
    dsel = dt * win
    dx_ref[pl.ds(a, 1), :] += jnp.sum(dsel[:d // 2], axis=0, keepdims=True)
    dx_ref[pl.ds(a + d // 2, 1), :] += jnp.sum(dsel[d // 2:], axis=0, keepdims=True)


def _tiled(row, rep):
    """[., C] -> [., rep * C]: the same for every query head of the group."""
    return row if rep == 1 else jnp.concatenate([row] * rep, axis=1)


def _folded(row, rep):
    """[., rep * C] -> [., C]: summed over the query heads of the group."""
    C = row.shape[1] // rep
    return sum(row[:, i * C:(i + 1) * C] for i in range(rep))


def _heads_t(x, rep):
    """[C, rep * d] (heads along lanes) -> float32 [d, rep * C]: each head
    transposed, the heads side by side."""
    d = x.shape[1] // rep
    x = x.astype(_F32)
    return jnp.concatenate([x[:, i * d:(i + 1) * d].T for i in range(rep)], axis=1)


def _heads_back(xt, rep):
    """:func:`_heads_t`'s inverse: [d, rep * C] -> [C, rep * d]."""
    C = xt.shape[1] // rep
    return jnp.concatenate([xt[:, i * C:(i + 1) * C].T for i in range(rep)], axis=1)


def _prepare(k, v, g):
    """A chunk's keys, values and log-decays as the kernels use them: k
    float32 [C, d]; vz [d_v + PAD, C], the values transposed over a row of
    ones (the normaliser's) and zeros; G [1, C] the running sum of g [1, C];
    dec [C, C] = exp(G_t - G_r) at [r, t] for r <= t, else 0; omega [1, C] =
    exp(G_C - G_r); gamma [1, 1] = exp(G_C)."""
    C = k.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G = _exact(g, (rows <= cols).astype(_F32))
    total = jnp.sum(g, axis=1, keepdims=True)
    dec = jnp.where(rows <= cols, jnp.exp(jnp.minimum(G - _turned(G, 1), 0.0)), 0.0)
    ones = (jax.lax.broadcasted_iota(jnp.int32, (PAD, C), 0) == 0).astype(_F32)
    vz = jnp.concatenate([v.astype(_F32).T, ones], axis=0)
    return k.astype(_F32), vz, G, dec, jnp.exp(total - G), jnp.exp(total)


def _intra(kf, x, dec, rep):
    """The chunk's own pairs: p [C, rep * C] = k_r . q_t at [r, (i, t)] and
    a = D o p^2."""
    p = _dot(kf, x)
    return p, _tiled(dec, rep) * p * p


def _rounded(w):
    return w.astype(STATE_DTYPE).astype(_F32)


def _advance(w_from, w_to, kx_ref, vzw, gamma):
    """``w_to`` = the state after a chunk that starts from ``w_from`` (refs
    [tiles, d_v + PAD, d], or index functions of a tile): gamma S0 +
    (omega o vz) phi(K)^T, tile by tile; ``kx_ref`` [d, C] the keys."""
    d = kx_ref.shape[0]
    weights = _weights(*kx_ref.shape)

    def tile(a, _):
        sel, win = _tile(kx_ref, a, weights)
        w_to(a, _rounded(gamma * w_from(a) + _dot(vzw, sel * win, _NT)))
        return 0

    jax.lax.fori_loop(0, d // 2, tile, 0)
    kx = kx_ref[...]
    w_to(d // 2, _rounded(gamma * w_from(d // 2) + _dot(vzw, kx * kx, _NT)))


def _fwd_kernel(rep, C, scale, valid, q_ref, k_ref, v_ref, g_ref,
                y_ref, hs_ref, dmin_ref, *rest):
    """One block. ``rest``: ``reads_ref`` [block / C, d_v + PAD, rep * C],
    what each chunk reads from the state it starts from, where the backward
    pass follows (the plain call has no such output), then the scratch."""
    *reads_ref, w_scr, x_scr, kx_scr, out_scr = rest
    block = q_ref.shape[0]
    d, dv = k_ref.shape[1], v_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        w_scr[...] = jnp.zeros_like(w_scr)

    first = pl.program_id(2) * block  # the block's first position
    wq, wk = _weights(*x_scr.shape), _weights(*kx_scr.shape)

    def save(a, _):  # the state this block starts from
        hs_ref[a] = w_scr[a]
        return 0

    jax.lax.fori_loop(0, d // 2 + 1, save, 0)

    def chunk(c, dmin):
        at = pl.ds(pl.multiple_of(c * C, C), C)
        kf, vz, G, dec, omega, gamma = _prepare(k_ref[at, :], v_ref[at, :], g_ref[c])
        x_scr[...] = _heads_t(q_ref[at, :], rep) * scale
        kx_scr[...] = kf.T
        out_scr[...] = jnp.zeros_like(out_scr)
        vzw = _halves(vz * omega)

        def tile(a, _):
            sel, win = _tile(x_scr, a, wq)
            w = w_scr[a]
            out_scr[...] += _dot(w, sel * win)
            ksel, kwin = _tile(kx_scr, a, wk)
            w_scr[a] = _rounded(gamma * w + _dot(vzw, ksel * kwin, _NT))
            return 0

        jax.lax.fori_loop(0, d // 2, tile, 0)
        x, kx, w = x_scr[...], kx_scr[...], w_scr[d // 2]
        from_state = out_scr[...] + _dot(w, x * x)
        for ref in reads_ref:
            ref[c] = from_state
        w_scr[d // 2] = _rounded(gamma * w + _dot(vzw, kx * kx, _NT))
        out = _tiled(jnp.exp(G), rep) * from_state + _dot(vz, _intra(kf, x, dec, rep)[1])
        den = out[dv:dv + 1]
        y = out[:dv] / (den + EPS) if NORMALISED else out[:dv]
        y_ref[at, :] = _heads_back(y, rep).astype(y_ref.dtype)
        pos = (first + c * C
               + _tiled(jax.lax.broadcasted_iota(jnp.int32, (1, C), 1), rep))
        return jnp.minimum(dmin, jnp.where(pos < valid, den, jnp.inf))

    dmin = jax.lax.fori_loop(0, block // C, chunk, jnp.full((1, rep * C), jnp.inf, _F32))
    dmin_ref[...] = jnp.broadcast_to(jnp.min(dmin, axis=1, keepdims=True), dmin_ref.shape)


def _bwd_kernel(rep, C, scale, q_ref, k_ref, v_ref, g_ref, hs_ref, reads_ref, dy_ref,
                dq_ref, dk_ref, dv_ref, dg_ref,
                wc_scr, dw_scr, x_scr, kx_scr, dx_scr, dkx_scr, u_scr, gam_scr):
    """One block, the blocks in reverse: the states its later chunks start
    from are computed again from the saved one, then the chunks are walked
    backwards, each with what the forward pass read from the state for it
    (``reads_ref``); ``dw_scr`` carries the cotangent of the state a block
    ends with into the block before."""
    block = q_ref.shape[0]
    d, dv = k_ref.shape[1], v_ref.shape[1]
    n_chunks, tiles = block // C, d // 2 + 1
    wq, wk = _weights(*x_scr.shape), _weights(*kx_scr.shape)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dw_scr[...] = jnp.zeros_like(dw_scr)

    def load(a, _):
        wc_scr[0, a] = hs_ref[a]
        return 0

    jax.lax.fori_loop(0, tiles, load, 0)

    def again(c, _):  # the state chunk c + 1 starts from
        at = pl.ds(pl.multiple_of(c * C, C), C)
        kf, vz, _, _, omega, gamma = _prepare(k_ref[at, :], v_ref[at, :], g_ref[c])
        kx_scr[...] = kf.T

        def put(a, w):
            wc_scr[c + 1, a] = w

        _advance(lambda a: wc_scr[c, a], put, kx_scr, _halves(vz * omega), gamma)
        return 0

    jax.lax.fori_loop(0, n_chunks - 1, again, 0)

    def chunk(i, _):
        c = n_chunks - 1 - i
        at = pl.ds(pl.multiple_of(c * C, C), C)
        kf, vz, G, dec, omega, gamma = _prepare(k_ref[at, :], v_ref[at, :], g_ref[c])
        x_scr[...] = _heads_t(q_ref[at, :], rep) * scale
        kx_scr[...] = kf.T
        x, kx = x_scr[...], kx_scr[...]
        vzw = _halves(vz * omega)

        # the chunk again, as the forward pass computed it
        from_state = reads_ref[c]
        p, a_mat = _intra(kf, x, dec, rep)
        grew = _tiled(jnp.exp(G), rep)
        out = grew * from_state + _dot(vz, a_mat)
        den = out[dv:dv + 1] + EPS if NORMALISED else jnp.ones_like(out[dv:dv + 1])
        y = out[:dv] / den if NORMALISED else jnp.zeros_like(out[:dv])  # no den, no term

        # the division, the decay of what came from the state, the chunk's pairs
        dy = _heads_t(dy_ref[at, :], rep)
        first = jax.lax.broadcasted_iota(jnp.int32, (PAD, dy.shape[1]), 0) == 0
        dout = jnp.concatenate(
            [dy / den, jnp.where(first, -jnp.sum(dy * y, axis=0, keepdims=True) / den, 0.0)],
            axis=0)
        dfrom = dout * grew
        dG_t = jnp.sum(dfrom * from_state, axis=0, keepdims=True)  # [1, rep * C]
        dfrom = _halves(dfrom)
        da = _dot(vz, dout, _TN)  # [C, rep * C]
        dvz = _dot(dout, a_mat, _NT)  # [d_v + PAD, C]
        pairs = da * a_mat
        dp = 2.0 * da * _tiled(dec, rep) * p
        dG = (_folded(dG_t + jnp.sum(pairs, axis=0, keepdims=True), rep)
              - _turned(jnp.sum(pairs, axis=1, keepdims=True), 0))
        dx_scr[...] = _dot(kf, dp, _TN)
        dk_rows = _dot(dp, x, _NT)  # [C, d]

        # tile by tile: the state's cotangent and phi's
        dkx_scr[...] = jnp.zeros_like(dkx_scr)
        u_scr[...] = jnp.zeros_like(u_scr)
        gam_scr[...] = jnp.zeros_like(gam_scr)

        def back(a, _):
            sel, win = _tile(x_scr, a, wq)
            ksel, kwin = _tile(kx_scr, a, wk)
            w0, dw1 = wc_scr[c, a], dw_scr[a]
            gam_scr[...] += dw1 * w0
            dw1h = _halves(dw1)
            u_scr[...] += _dot(dw1h, ksel * kwin)
            _tile_back(dkx_scr, a, ksel, kwin, _dot(dw1h, vzw, _TN), wk)
            dw_scr[a] = gamma * dw1 + _dot(dfrom, sel * win, _NT)
            _tile_back(dx_scr, a, sel, win, _dot(w0, dfrom, _TN), wq)
            return 0

        jax.lax.fori_loop(0, d // 2, back, 0)
        w0, dw1 = wc_scr[c, d // 2], dw_scr[d // 2]
        dgamma = jnp.sum(jnp.sum(gam_scr[...] + dw1 * w0, axis=0, keepdims=True),
                         axis=1, keepdims=True)  # [1, 1]
        dw1h = _halves(dw1)
        u = u_scr[...] + _dot(dw1h, kx * kx)
        dkx = dkx_scr[...] + 2.0 * kx * _dot(dw1h, vzw, _TN)
        dw_scr[d // 2] = gamma * dw1 + _dot(dfrom, x * x, _NT)
        dx = dx_scr[...] + 2.0 * x * _dot(w0, dfrom, _TN)

        dvz = dvz + u * omega
        domega = jnp.sum(u * vz, axis=0, keepdims=True) * omega  # [1, C], times omega
        dG = dG - domega
        last = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1) == C - 1
        dG = dG + jnp.where(
            last, dgamma * gamma + jnp.sum(domega, axis=1, keepdims=True), 0.0)
        rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        dg_ref[c] = _exact(dG, (rows >= cols).astype(_F32))
        dq_ref[at, :] = (_heads_back(dx, rep) * scale).astype(dq_ref.dtype)
        dk_ref[at, :] = (dk_rows + dkx.T).astype(dk_ref.dtype)
        dv_ref[at, :] = dvz[:dv].T.astype(dv_ref.dtype)
        return 0

    jax.lax.fori_loop(0, n_chunks, chunk, 0)


def _params():
    if _interpret():
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        # the backward kernel holds the states its chunks start from (4.8 MB
        # each at d = 128) and the cotangent; the default scope is 16 of the
        # v5e's 128
        vmem_limit_bytes=100 * 2**20)}


def _specs(rep, d, dv, block, C, nb, reverse):
    """Block specs of (q [B, T, Hq*d], k [B, T, H*d], v [B, T, H*dv], g [B, H,
    T/block, block/C, 1, C], the saved states [B, H, T/block, tiles, dv + PAD,
    d], the saved reads [B, H, T/block, block/C, dv + PAD, rep*C]) on the grid
    (batch, key/value head, block), the blocks in reverse for the backward
    pass."""
    at = (lambda c: nb - 1 - c) if reverse else (lambda c: c)
    return (pl.BlockSpec((None, block, rep * d), lambda b, h, c: (b, at(c), h)),
            pl.BlockSpec((None, block, d), lambda b, h, c: (b, at(c), h)),
            pl.BlockSpec((None, block, dv), lambda b, h, c: (b, at(c), h)),
            pl.BlockSpec((None, None, None, block // C, 1, C),
                         lambda b, h, c: (b, h, at(c), 0, 0, 0)),
            pl.BlockSpec((None, None, None, d // 2 + 1, dv + PAD, d),
                         lambda b, h, c: (b, h, at(c), 0, 0, 0)),
            pl.BlockSpec((None, None, None, block // C, dv + PAD, rep * C),
                         lambda b, h, c: (b, h, at(c), 0, 0, 0)))


def _forward(q, k, v, g, cfg, save_reads):
    """q [B, T, Hq*d]; k [B, T, H*d]; v [B, T, H*dv]; g [B, H, T/block,
    block/C, 1, C] f32; ``cfg`` = (H, scale, C, block, valid positions) ->
    (y [B, T, Hq*dv] in v's dtype, the state at every block's start, the
    smallest normaliser of every block [B, H, T/block, 1, 128], and with
    ``save_reads`` what every chunk reads from the state it starts from)."""
    H, scale, C, block, valid = cfg
    B, T = q.shape[:2]
    d, dv, rep = k.shape[2] // H, v.shape[2] // H, q.shape[2] // k.shape[2]
    nb, tiles, rows = T // block, d // 2 + 1, dv + PAD
    qs, ks, vs, gs, hs, rs = _specs(rep, d, dv, block, C, nb, reverse=False)
    outs = [(pl.BlockSpec((None, block, rep * dv), lambda b, h, c: (b, c, h)),
             jax.ShapeDtypeStruct((B, T, rep * H * dv), v.dtype)),
            (hs, jax.ShapeDtypeStruct((B, H, nb, tiles, rows, d), _F32)),
            (pl.BlockSpec((None, None, None, 1, 128), lambda b, h, c: (b, h, c, 0, 0)),
             jax.ShapeDtypeStruct((B, H, nb, 1, 128), _F32)),
            (rs, jax.ShapeDtypeStruct((B, H, nb, block // C, rows, rep * C), _F32))]
    outs = outs if save_reads else outs[:-1]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rep, C, scale, valid), grid=(B, H, nb),
        in_specs=[qs, ks, vs, gs],
        out_specs=[spec for spec, _ in outs], out_shape=[shape for _, shape in outs],
        scratch_shapes=[pltpu.VMEM((tiles, rows, d), _F32), pltpu.VMEM((d, rep * C), _F32),
                        pltpu.VMEM((d, C), _F32), pltpu.VMEM((rows, rep * C), _F32)],
        name="power_retention_fwd", **_params(),
    )(q, k, v, g)


def _backward(q, k, v, g, hs, reads, dy, cfg):
    H, scale, C, block, _ = cfg
    B, T = q.shape[:2]
    d, dv, rep = k.shape[2] // H, v.shape[2] // H, q.shape[2] // k.shape[2]
    nb, tiles, rows = T // block, d // 2 + 1, dv + PAD
    qs, ks, vs, gs, st, rs = _specs(rep, d, dv, block, C, nb, reverse=True)
    ys = pl.BlockSpec((None, block, rep * dv), lambda b, h, c: (b, nb - 1 - c, h))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, rep, C, scale), grid=(B, H, nb),
        in_specs=[qs, ks, vs, gs, st, rs, ys], out_specs=[qs, ks, vs, gs],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((block // C, tiles, rows, d), _F32),
                        pltpu.VMEM((tiles, rows, d), _F32),
                        pltpu.VMEM((d, rep * C), _F32), pltpu.VMEM((d, C), _F32),
                        pltpu.VMEM((d, rep * C), _F32),
                        pltpu.VMEM((d, C), _F32), pltpu.VMEM((rows, C), _F32),
                        pltpu.VMEM((rows, d), _F32)],
        name="power_retention_bwd", **_params(),
    )(q, k, v, g, hs, reads, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _retention(q, k, v, g, cfg):
    y, _, dmin = _forward(q, k, v, g, cfg, save_reads=False)
    return y, dmin


def _retention_fwd(q, k, v, g, cfg):
    y, hs, dmin, reads = _forward(q, k, v, g, cfg, save_reads=True)
    return (y, dmin), (q, k, v, g, hs, reads)


def _retention_bwd(cfg, saved, cotangents):
    return tuple(_backward(*saved, cotangents[0], cfg))


_retention.defvjp(_retention_fwd, _retention_bwd)


def power_retention(q: jax.Array, k: jax.Array, v: jax.Array, log_g: jax.Array,
                    scale: float | None = None, chunk: int = CHUNK, block: int = BLOCK,
                    with_den_min: bool = False):
    """q [B, T, Hq, d]; k [B, T, H, d]; v [B, T, H, d_v]; log_g [B, T, H] (<=
    0) -> y [B, T, Hq, d_v] in v's dtype; query head ``i`` reads key/value
    head ``i // (Hq / H)``. ``scale`` (d^-1/2) stands inside the square.
    ``with_den_min``: also the smallest normaliser (before eps) over all
    positions and heads, a float32 scalar without a gradient. Any T: the
    sequence is padded to whole blocks with positions of k = 0 and g = 0,
    which leave the state as it is. ``d`` even; on the TPU whole tiles (``d``
    and ``d_v`` multiples of 128, ``chunk`` too)."""
    B, T, Hq, d = q.shape
    H, dv = k.shape[2], v.shape[3]
    if Hq % H or d % 2 or block % chunk:
        raise ValueError(f"power_retention: {Hq} heads over {H} of {d}, blocks of "
                         f"{block} in chunks of {chunk}")
    block = min(block, -(-T // chunk) * chunk)
    flat = lambda m: m.reshape(B, T, -1)  # noqa: E731
    args = [flat(q), flat(k), flat(v), log_g.astype(_F32)]
    pad = -T % block
    if pad:
        args = [jnp.pad(m, ((0, 0), (0, pad), (0, 0))) for m in args]
    q2, k2, v2, g2 = args
    g2 = jnp.swapaxes(g2, 1, 2).reshape(B, H, -1, block // chunk, 1, chunk)
    cfg = (H, float(d ** -0.5 if scale is None else scale), chunk, block, T)
    y, dmin = _retention(q2, k2, v2, g2, cfg)
    y = y[:, :T].reshape(B, T, Hq, dv)
    return (y, jax.lax.stop_gradient(jnp.min(dmin))) if with_den_min else y


def power_retention_reference(q, k, v, log_g, scale=None):
    """The same function as the recurrence, a ``lax.scan`` over positions in
    float32 over the state [phi, d_v] and the normaliser [phi] with phi
    written out (d (d + 1) / 2 wide): the kernels' test oracle, never the
    program's path."""
    B, T, Hq, d = q.shape
    H = k.shape[2]
    scale = d ** -0.5 if scale is None else scale
    ia, ib = jnp.triu_indices(d)
    weight = jnp.where(ia == ib, 1.0, CROSS).astype(_F32)

    def phi(u):  # [..., d] -> [..., d (d + 1) / 2]
        return u[..., ia] * u[..., ib] * weight

    f = lambda m: jnp.swapaxes(m.astype(_F32), 0, 1)  # noqa: E731  time first

    def step(carry, inp):  # S [B,H,phi,dv], z [B,H,phi]
        S, z = carry
        q_t, k_t, v_t, g_t = inp
        pk, decay = phi(k_t), jnp.exp(g_t)
        S = decay[..., None, None] * S + pk[..., None] * v_t[..., None, :]
        z = decay[..., None] * z + pk
        S, z = _rounded(S), _rounded(z)
        pq = phi(scale * q_t).reshape(B, H, Hq // H, -1)
        num = jnp.einsum("bhrp,bhpv->bhrv", pq, S)
        den = jnp.einsum("bhrp,bhp->bhr", pq, z)
        return (S, z), (num / (den[..., None] + EPS)).reshape(B, Hq, -1)

    n = ia.shape[0]
    init = (jnp.zeros((B, H, n, v.shape[-1]), _F32), jnp.zeros((B, H, n), _F32))
    with jax.default_matmul_precision("highest"):
        _, y = jax.lax.scan(step, init, (f(q), f(k), f(v), f(log_g)))
    return jnp.swapaxes(y, 0, 1).astype(v.dtype)
