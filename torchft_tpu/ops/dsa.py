"""The alignment loss of a learned sparse-attention scorer (DeepSeek sparse
attention's "lightning indexer", dense warm-up stage) against the attention
it is to imitate, without either ``[T, T]`` matrix ever standing in memory.

For one sequence, ``H`` main heads and ``HI`` scorer heads::

    P_h[t, s] = softmax_s<=t(scale q[t, h] . k[s, h])          # the model's attention
    p[t, s]   = sum_h P_h[t, s] / H                            # the target: rows sum to 1
    I[t, s]   = sum_j w[t, j] relu(qI[t, j] . kI[s])           # the scorer, s <= t
    kl[t]     = KL(p[t, :t+1] || softmax(I[t, :t+1]))
              = sum_s p log p - sum_s p I + logsumexp_s<=t I[t, s]

:func:`index_kl` returns ``kl`` [B, T] with a custom VJP into ``(qI, kI,
w)`` alone: the target is a constant of the stage (every leaf it reads is
frozen) and ``q`` and ``k`` get no cotangent.

Three Pallas kernels, a sequence at a time, all on a grid (block of queries,
block of keys, group of ``HEADS`` main heads), the group innermost so that a block's head
sum is accumulated in VMEM, and blocks above the diagonal neither loaded nor
computed:

- ``dsa_kl_fwd_lse``: every main head's log-sum-exp a row, online over the
  key blocks (the normaliser ``p`` needs BEFORE a block's head sum can be
  formed: ``log p`` is not additive over heads).
- ``dsa_kl_fwd``: a block's ``sum_h exp(s_h - lse_h)`` head by head, then,
  at the group's last step, the scorer's block (a loop over its ``HI`` heads
  against the one shared key) and the three row sums above, the scorer's
  log-sum-exp online. Hands out ``kl`` and the scorer's log-sum-exp.
- ``dsa_kl_bwd``: the same head sum again, the scorer's block again, ``dI =
  g (softmax(I) - p)`` for that block, and at once its contraction into
  ``dw``, ``dqI`` (accumulated in VMEM over a row of blocks) and ``dkI``
  (one partial a block of queries, summed outside: 8 MB a partial at 16,384).

What is computed more than once, and why. The main heads' scores are
computed THREE times (log-sum-exp, forward, backward: 6.6e12 operations a
layer each at 16,384 positions and 128 heads of 192 padded to 256). Kept
instead, ``p`` alone would be 16,384^2 float32 = 1 GiB a layer, and the
stage's step is sized so that it does not fit beside the frozen weights;
the per-head probabilities are 128 times that. The scorer's scores are
computed once forward and twice backward (``dI`` needs the whole of ``I[t,
s]`` over the ``HI`` heads before any head's ReLU can be pulled back).

Everything inside a kernel is TRANSPOSED: keys along sublanes, queries along
lanes, so that what is one number a query (a log-sum-exp, a weight ``w[t,
j]``, ``kl``) is a lane-major row broadcast over sublanes. Probabilities,
the head sum, ``I`` and both log-sum-exps are float32; the score products
and the three gradient contractions take bfloat16 operands (as they are
given) and accumulate in float32. Off the TPU the kernels run interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["index_kl", "index_kl_reference", "BLOCK", "HEADS"]

BLOCK = 512  # queries and keys of one block
HEADS = 8  # main heads a grid step multiplies
_F32 = jnp.float32
_NEG = -1e30  # a masked score: exp of it less any row's maximum is 0

_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))

# What the target and the scorer's block are rounded to before the logarithm
# and the exponential; float32 is the only value the program runs with (the
# tests and benchmarks/dsa_check_faults.py set bfloat16 to show that the
# checks refuse it).
P_DTYPE = jnp.float32
I_DTYPE = jnp.float32
# Two more faults the same script puts in: the scorer's ReLU (True is the
# only value the program runs with) and the number of main heads the target
# is summed over (None: all of them).
RELU = True
TARGET_HEADS = None


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _params():
    if _interpret():
        return {"interpret": True}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        # the scorer's queries of a block ([HI, BLOCK, 128] bf16, 8 MB at 64
        # heads) and, backwards, their gradient's float32 accumulator stay in
        # VMEM over a row of blocks; the default scope is 16 MB of 128
        vmem_limit_bytes=100 * 2**20)}


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _round(x, dtype):
    return x if dtype == _F32 else x.astype(dtype).astype(_F32)


def _allowed(i, j, block):
    """[keys, queries] of block (i, j): key ``s`` <= query ``t``."""
    s = j * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    t = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    return s <= t


def _head_scores(q_ref, k_ref, h):
    """Head ``h`` of the group: k q^T, [keys, queries] float32."""
    return _dot(k_ref[h], q_ref[h], _NT)


def _lse_kernel(q_ref, k_ref, lse_ref, m_ref, l_ref):
    """lse_ref [groups, HEADS, block]: written at the diagonal block; m_ref,
    l_ref: the running maximum and sum of every head, same shape."""
    i, j, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_ref[n] = jnp.full(m_ref.shape[1:], _NEG, _F32)
        l_ref[n] = jnp.zeros(l_ref.shape[1:], _F32)

    @pl.when(j <= i)
    def _():
        seen = _allowed(i, j, block)
        for h in range(HEADS):
            s = jnp.where(seen, _head_scores(q_ref, k_ref, h), _NEG)
            m0 = m_ref[n, h:h + 1, :]
            m1 = jnp.maximum(m0, jnp.max(s, axis=0, keepdims=True))
            l_ref[n, h:h + 1, :] = (l_ref[n, h:h + 1, :] * jnp.exp(m0 - m1)
                                    + jnp.sum(jnp.exp(s - m1), axis=0, keepdims=True))
            m_ref[n, h:h + 1, :] = m1

    @pl.when(j == i)
    def _():
        lse_ref[n] = m_ref[n] + jnp.log(l_ref[n])


def _head_sum(n, q_ref, k_ref, lse_ref, acc_ref):
    """acc_ref [keys, queries] += sum over group ``n``'s heads of exp(s -
    lse) (zeroed at the first group)."""
    @pl.when(n == 0)
    def _():
        acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    acc = acc_ref[...]
    for h in range(HEADS):
        e = jnp.exp(_head_scores(q_ref, k_ref, h) - lse_ref[h:h + 1, :])
        acc = acc + (e if TARGET_HEADS is None else jnp.where(n * HEADS + h < TARGET_HEADS, e, 0.0))
    acc_ref[...] = acc


def _target(acc_ref, seen, H):
    """p^T [keys, queries]: the head sum over the heads it was taken over,
    zero where the query does not see the key."""
    return _round(jnp.where(seen, acc_ref[...] * (1.0 / (TARGET_HEADS or H)), 0.0), P_DTYPE)


def _relu(z):
    return jnp.maximum(z, 0.0) if RELU else z


def _scorer_block(qI_ref, kI_ref, w_ref):
    """I^T [keys, queries] float32 of the block: sum over the scorer's heads
    of w[j] relu(kI qI[j]^T)."""
    kI = kI_ref[...]

    def head(j, acc):
        z = _dot(kI, qI_ref[j], _NT)
        return acc + w_ref[j] * _relu(z)

    shape = (kI.shape[0], qI_ref.shape[1])
    return jax.lax.fori_loop(0, qI_ref.shape[0], head, jnp.zeros(shape, _F32))


def _fwd_kernel(H, q_ref, k_ref, lse_ref, qI_ref, kI_ref, w_ref,
                kl_ref, lseI_ref, acc_ref, a_ref, b_ref, m_ref, l_ref):
    i, j, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block = q_ref.shape[1]
    last = n == pl.num_programs(2) - 1

    @pl.when((j == 0) & (n == 0))
    def _():
        a_ref[...] = jnp.zeros(a_ref.shape, _F32)
        b_ref[...] = jnp.zeros(b_ref.shape, _F32)
        m_ref[...] = jnp.full(m_ref.shape, _NEG, _F32)
        l_ref[...] = jnp.zeros(l_ref.shape, _F32)

    @pl.when(j <= i)
    def _():
        _head_sum(n, q_ref, k_ref, lse_ref, acc_ref)

    @pl.when((j <= i) & last)
    def _():
        seen = _allowed(i, j, block)
        p = _target(acc_ref, seen, H)
        I = _round(_scorer_block(qI_ref, kI_ref, w_ref), I_DTYPE)
        a_ref[...] += jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0),
                              axis=0, keepdims=True)
        b_ref[...] += jnp.sum(p * I, axis=0, keepdims=True)
        I = jnp.where(seen, I, _NEG)
        m0 = m_ref[...]
        m1 = jnp.maximum(m0, jnp.max(I, axis=0, keepdims=True))
        l_ref[...] = l_ref[...] * jnp.exp(m0 - m1) + jnp.sum(jnp.exp(I - m1), axis=0,
                                                             keepdims=True)
        m_ref[...] = m1

    @pl.when((j == i) & last)
    def _():
        lseI = m_ref[...] + jnp.log(l_ref[...])
        lseI_ref[...] = lseI
        kl_ref[...] = a_ref[...] - b_ref[...] + lseI


def _bwd_kernel(H, q_ref, k_ref, lse_ref, qI_ref, kI_ref, w_ref, lseI_ref, g_ref,
                dqI_ref, dkI_ref, dw_ref, acc_ref):
    """dqI_ref [HI, block, dI] and dw_ref [HI, 1, block] float32 are the
    row of blocks' accumulators (their block does not move with ``j``);
    dkI_ref [block, dI] is this block's own partial."""
    i, j, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block = q_ref.shape[1]
    last = n == pl.num_programs(2) - 1

    @pl.when((j == 0) & (n == 0))
    def _():
        dqI_ref[...] = jnp.zeros(dqI_ref.shape, _F32)
        dw_ref[...] = jnp.zeros(dw_ref.shape, _F32)

    @pl.when(j <= i)
    def _():
        _head_sum(n, q_ref, k_ref, lse_ref, acc_ref)

    @pl.when((j <= i) & last)
    def _():
        seen = _allowed(i, j, block)
        p = _target(acc_ref, seen, H)
        I = _round(_scorer_block(qI_ref, kI_ref, w_ref), I_DTYPE)
        soft = jnp.exp(jnp.where(seen, I, _NEG) - lseI_ref[...])
        dI = (soft - p) * g_ref[...]  # [keys, queries]; zero above the diagonal
        kI = kI_ref[...]

        def head(jj, dk):
            qj = qI_ref[jj]
            z = _dot(kI, qj, _NT)
            dw_ref[jj] += jnp.sum(dI * _relu(z), axis=0, keepdims=True)
            dz = dI * w_ref[jj]
            dz = (jnp.where(z > 0, dz, 0.0) if RELU else dz).astype(kI.dtype)
            dqI_ref[jj] += _dot(dz, kI, _TN)
            return dk + _dot(dz, qj, _NN)

        dkI_ref[...] = jax.lax.fori_loop(0, qI_ref.shape[0], head,
                                         jnp.zeros(dkI_ref.shape, _F32))


def _main_specs(D, block):
    """Block specs of q, k [H, T, D] and lse [groups, HEADS, T] on the grid
    (i, j, n); a block above the diagonal maps to the diagonal's, so
    nothing is loaded for it."""
    return (pl.BlockSpec((HEADS, block, D), lambda i, j, n: (n, i, 0)),
            pl.BlockSpec((HEADS, block, D),
                         lambda i, j, n: (n, jnp.minimum(j, i), 0)),
            pl.BlockSpec((None, HEADS, block), lambda i, j, n: (n, 0, i)))


def _scorer_specs(HI, dI, block):
    """Block specs of qI [HI, T, dI], kI [T, dI], w [HI, 1, T] and of one
    number a query [1, T]."""
    return (pl.BlockSpec((HI, block, dI), lambda i, j, n: (0, i, 0)),
            pl.BlockSpec((block, dI), lambda i, j, n: (jnp.minimum(j, i), 0)),
            pl.BlockSpec((HI, 1, block), lambda i, j, n: (0, 0, i)),
            pl.BlockSpec((1, block), lambda i, j, n: (0, i)))


def _lse(q, k, block):
    H, T, D = q.shape
    groups, nb = H // HEADS, T // block
    qs, ks, _ = _main_specs(D, block)
    whole = pl.BlockSpec((groups, HEADS, block), lambda i, j, n: (0, 0, i))
    return pl.pallas_call(
        _lse_kernel, grid=(nb, nb, groups),
        in_specs=[qs, ks], out_specs=whole,
        out_shape=jax.ShapeDtypeStruct((groups, HEADS, T), _F32),
        scratch_shapes=[pltpu.VMEM((groups, HEADS, block), _F32)] * 2,
        name="dsa_kl_fwd_lse", **_params(),
    )(q, k)


def _forward(q, k, lse, qI, kI, w, block):
    H, T, D = q.shape
    groups, nb = H // HEADS, T // block
    HI, dI = qI.shape[0], qI.shape[2]
    row = jax.ShapeDtypeStruct((1, T), _F32)
    one = _scorer_specs(HI, dI, block)[3]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, H), grid=(nb, nb, groups),
        in_specs=[*_main_specs(D, block), *_scorer_specs(HI, dI, block)[:3]],
        out_specs=[one, one], out_shape=[row, row],
        scratch_shapes=[pltpu.VMEM((block, block), _F32)] + [pltpu.VMEM((1, block), _F32)] * 4,
        name="dsa_kl_fwd", **_params(),
    )(q, k, lse, qI, kI, w)


def _backward(q, k, lse, qI, kI, w, lseI, g, block):
    H, T, D = q.shape
    groups, nb = H // HEADS, T // block
    HI, dI = qI.shape[0], qI.shape[2]
    qIs, kIs, ws, one = _scorer_specs(HI, dI, block)
    part = pl.BlockSpec((None, block, dI),
                        lambda i, j, n: (i, jnp.minimum(j, i), 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, H), grid=(nb, nb, groups),
        in_specs=[*_main_specs(D, block), qIs, kIs, ws, one, one],
        out_specs=[qIs, part, ws],
        out_shape=[jax.ShapeDtypeStruct(qI.shape, _F32),
                   jax.ShapeDtypeStruct((nb, T, dI), _F32),
                   jax.ShapeDtypeStruct(w.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((block, block), _F32)],
        name="dsa_kl_bwd", **_params(),
    )(q, k, lse, qI, kI, w, lseI, g)


def _laid_out(q, k, scale, qI, kI, w, block):
    """The arguments as the kernels read them: heads before positions (the
    main heads' exactly as ``ops/attention.py`` hands them to its kernels,
    ``scale`` on ``q`` in ``q``'s dtype, so that a program that runs both
    keeps ONE such copy of each), the sequence padded with zeros to whole
    blocks (a padded key is after every true query; a padded query's row is
    finite and is cut off)."""
    pad = -q.shape[1] % block
    seq = lambda x, axis: jnp.pad(x, [(0, pad if a == axis else 0)  # noqa: E731
                                      for a in range(x.ndim)])
    return (seq(jnp.swapaxes(q, 1, 2) * jnp.asarray(scale, q.dtype), 2),
            seq(jnp.swapaxes(k, 1, 2), 2), seq(jnp.swapaxes(qI, 1, 2), 2), seq(kI, 1),
            seq(jnp.swapaxes(w.astype(_F32), 1, 2)[:, :, None, :], 3))


def _check(q, k, qI, kI, w):
    (B, T, H, D), HI = q.shape, qI.shape[2]
    if k.shape != q.shape or qI.shape[:2] != (B, T) or kI.shape != (B, T, qI.shape[3]) \
            or w.shape != (B, T, HI):
        raise ValueError(f"index_kl: q {q.shape}, k {k.shape}, qI {qI.shape}, kI {kI.shape}, "
                         f"w {w.shape}")
    if H % HEADS:
        raise ValueError(f"index_kl: {H} main heads, in groups of {HEADS}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def index_kl(q: jax.Array, k: jax.Array, scale: float, qI: jax.Array, kI: jax.Array,
             w: jax.Array) -> jax.Array:
    """q, k [B, T, H, D]: the main heads' queries and keys as the attention
    multiplies them (rotated; ``scale`` on ``q`` in its own dtype before the
    product, as ``ops/attention.py`` folds it in); qI [B, T, HI, dI],
    kI [B, T, dI], w [B, T, HI] (float32): the scorer's -> kl [B, T] float32,
    row ``t``'s KL divergence of the scorer's softmax over ``s <= t`` from
    the head-mean attention. Differentiable in ``qI``, ``kI`` and ``w``."""
    return _index_kl_fwd(q, k, scale, qI, kI, w)[0]


def _block_for(T: int) -> int:
    """``BLOCK``, or a shorter sequence's length in whole lanes."""
    return min(BLOCK, -(-T // 128) * 128)


def _index_kl_fwd(q, k, scale, qI, kI, w):
    _check(q, k, qI, kI, w)
    T = q.shape[1]
    block = _block_for(T)
    laid = _laid_out(q, k, scale, qI, kI, w, block)
    # a sequence at a time, as ``splash_attention_tpu`` maps its kernel: one
    # sequence is then the same call on the same arrays as the attention's
    lse = jax.vmap(functools.partial(_lse, block=block))(*laid[:2])
    kl, lseI = jax.vmap(functools.partial(_forward, block=block))(*laid[:2], lse, *laid[2:])
    return kl[:, 0, :T], (laid, lse, lseI)


def _index_kl_bwd(scale, res, g):
    laid, lse, lseI = res
    (qI, kI, _), T = laid[2:], g.shape[1]
    block = _block_for(T)
    g = jnp.pad(g.astype(_F32), ((0, 0), (0, kI.shape[1] - T)))[:, None, :]
    dqI, parts, dw = jax.vmap(functools.partial(_backward, block=block))(
        *laid[:2], lse, *laid[2:], lseI, g)
    nb = parts.shape[1]
    # a partial exists where the block of queries is at or after the keys'
    written = (jnp.arange(nb)[:, None] >= jnp.arange(nb)[None, :])[None, :, :, None, None]
    dkI = jnp.sum(jnp.where(written, parts.reshape(parts.shape[0], nb, nb, block, -1), 0.0),
                  axis=1).reshape(parts.shape[0], nb * block, -1)
    return (None, None, jnp.swapaxes(dqI, 1, 2)[:, :T].astype(qI.dtype),
            dkI[:, :T].astype(kI.dtype), jnp.swapaxes(dw[:, :, 0, :], 1, 2)[:, :T])


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)


def index_kl_reference(q, k, scale, qI, kI, w):
    """:func:`index_kl` by its definition in float32 ``jax.numpy``, every
    ``[T, T]`` matrix in memory: what the kernels are tested against."""
    T = q.shape[1]
    seen = jnp.tril(jnp.ones((T, T), bool))
    q = q * jnp.asarray(scale, q.dtype)  # as the kernels, and the attention's, do
    s = jnp.einsum("bthd,bshd->bhts", q.astype(_F32), k.astype(_F32))
    p = jnp.mean(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), axis=1)  # [B,T,T]
    z = jnp.einsum("btjd,bsd->btjs", qI.astype(_F32), kI.astype(_F32))
    I = jnp.einsum("btj,btjs->bts", w.astype(_F32), jnp.maximum(z, 0.0))
    logq = jax.nn.log_softmax(jnp.where(seen, I, -jnp.inf), axis=-1)
    return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(p > 0, p, 1.0))
                                        - jnp.where(seen, logq, 0.0)), 0.0), axis=-1)
