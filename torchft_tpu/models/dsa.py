"""DeepSeek sparse attention's learned scorer (the "lightning indexer" of
DeepSeek-V3.2-Exp) beside a latent-attention layer, and the DENSE WARM-UP
stage of its training: the model keeps dense attention, every parameter but
the indexers is frozen, and each layer's indexer learns the layer's own
attention by a KL divergence.

The indexer of one layer (``HI`` = ``index_n_heads`` heads of ``dI`` =
``index_head_dim``; ``u`` the layer's normalised input, ``cq`` the queries'
normalised latent, both the mixer's own)::

    qI = cq @ w_iq              -> [T, HI, dI]   rotary on [..., :dr], in halves
    kI = layernorm(u @ w_ik)    -> [T, dI]       weight and bias, float32; ONE key; rotary on [:dr]
    w  = (u @ w_iw) HI^-0.5 dI^-0.5  -> [T, HI]  float32
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])          s <= t

The stage's loss of one layer, ``P_h`` the layer's attention probabilities::

    p[t, :] = sum_h P_h[t, :] / H        L_layer = mean_t KL(p[t, :t+1] || softmax(I[t, :t+1]))

through ``ops/dsa.py``'s kernels: no ``[T, T]`` matrix stands in memory.

**A layer's gradient is taken inside its forward pass.** ``u``, ``cq`` and
the attention's queries and keys come from frozen leaves, so no cotangent
crosses a layer: ``L_layer``'s gradient reaches the layer's own five indexer
leaves and nothing else. :func:`layer_kl` is a ``custom_vjp`` whose forward
rule runs the layer's backward pass at once and keeps the five gradients (28
MB) as its residual; its backward rule scales them. The queries and keys
the kernels read a second time (2 GB a layer at 16,384 positions and 128
heads) are then dead when the layer ends, where the residuals of an ordinary
backward pass would hold every layer's until the last one is through: five
layers' do not fit a chip beside the frozen weights. The cotangents of
``u``, ``cq``, ``q`` and ``k`` are None: the stage declares them constants.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.mellum import _rotate
from torchft_tpu.ops.dsa import index_kl

__all__ = ["indexer_leaves", "indexer_init", "indexer", "layer_kl", "topk_mass",
           "MASS_ROWS"]

_F32 = jnp.float32
MASS_ROWS = 64  # query rows a layer's ``topk_mass`` is sampled on
MASS_HEADS = 16  # main heads whose sampled rows stand in memory at once


def indexer_leaves(cfg: Any) -> Dict[str, Tuple[Tuple[int, ...], Optional[int], Any, Any]]:
    """One layer's indexer: leaf -> (its shape without the layers' axis, its
    fan-in (None: the LayerNorm's weight, ones, or its bias, zeros), its
    dtype, its PartitionSpec with the layers' axis). The LayerNorm's two
    vectors are float32: they stand before a softmax's exponent."""
    from jax.sharding import PartitionSpec as P

    d, rq, HI, dI = cfg.dim, cfg.q_lora_rank, cfg.index_n_heads, cfg.index_head_dim
    rep = P(None, None)
    return {"w_iq": ((rq, HI * dI), rq, cfg.dtype, P(None, None, "tp")),
            "w_ik": ((d, dI), d, cfg.dtype, P(None, "fsdp", None)),
            "k_norm": ((dI,), None, _F32, rep), "k_bias": ((dI,), None, _F32, rep),
            "w_iw": ((d, HI), d, cfg.dtype, P(None, "fsdp", None))}


def indexer_init(key: jax.Array, cfg: Any, L: int) -> Dict[str, jax.Array]:
    """:func:`indexer_leaves` stacked over ``L`` layers: the matrices normal
    over the root of their fan-in, the LayerNorm at (1, 0)."""
    out = {}
    for k, (name, (shape, fan_in, dtype, _)) in zip(
            jax.random.split(key, 5), indexer_leaves(cfg).items()):
        if fan_in is None:
            out[name] = (jnp.ones if name == "k_norm" else jnp.zeros)((L, *shape), dtype)
        else:
            out[name] = (jax.random.normal(k, (L, *shape), _F32)
                         / jnp.sqrt(fan_in)).astype(dtype)
    return out


def _layernorm(x: jax.Array, weight: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    x = x.astype(_F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def _turn_first(x: jax.Array, table: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """x [B, T, h, dI]: its first ``2 x table width`` values turned by the
    rotary table, as two halves; the rest as they are."""
    dr = 2 * table[0].shape[-1]
    return jnp.concatenate([_rotate(x[..., :dr], table), x[..., dr:]], axis=-1)


def indexer(ix: Dict[str, jax.Array], u: jax.Array, cq: jax.Array, cfg: Any,
            table: Tuple[jax.Array, jax.Array]) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One layer's indexer from the mixer's ``u`` [B,T,d] and ``cq``
    [B,T,q_lora_rank] -> (qI [B,T,HI,dI], kI [B,T,dI], both in ``u``'s dtype
    and turned, w [B,T,HI] float32)."""
    B, T, _ = u.shape
    HI, dI = cfg.index_n_heads, cfg.index_head_dim
    with jax.named_scope("dsa/index_q"):
        qI = _turn_first((cq @ ix["w_iq"]).reshape(B, T, HI, dI), table)
    with jax.named_scope("dsa/index_k"):
        kI = _layernorm(jnp.matmul(u, ix["w_ik"], preferred_element_type=_F32),
                        ix["k_norm"], ix["k_bias"], cfg.index_norm_eps)
        kI = _turn_first(kI[:, :, None, :], table)[:, :, 0].astype(u.dtype)
    with jax.named_scope("dsa/index_w"):
        w = jnp.matmul(u, ix["w_iw"], preferred_element_type=_F32) * (
            HI ** -0.5 * dI ** -0.5)
    return qI, kI, w


def _kl_of(ix, u, cq, q, k, table, cfg, scale):
    qI, kI, w = indexer(ix, u, cq, cfg, table)
    with jax.named_scope("dsa/kl"):
        return jnp.mean(index_kl(q, k, scale, qI, kI, w))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def layer_kl(ix: Dict[str, jax.Array], u: jax.Array, cq: jax.Array, q: jax.Array,
             k: jax.Array, table: Tuple[jax.Array, jax.Array], cfg: Any,
             scale: float) -> jax.Array:
    """``L_layer`` (a float32 scalar) of the layer whose mixer handed out
    ``cq``, ``q``, ``k`` and ``scale`` (``mla_mixer(..., hand_out=True)``),
    differentiable in the indexer's leaves ``ix`` ALONE (the module's text)."""
    return _kl_of(ix, u, cq, q, k, table, cfg, scale)


def _layer_kl_fwd(ix, u, cq, q, k, table, cfg, scale):
    # the value waits for the gradients: whoever holds the layer back until
    # its loss is there (the layer body does) holds it back for these too
    return jax.lax.optimization_barrier(
        jax.value_and_grad(_kl_of)(ix, u, cq, q, k, table, cfg, scale))


def _layer_kl_bwd(cfg, scale, grads, ct):
    return (jax.tree_util.tree_map(lambda g: (ct * g).astype(g.dtype), grads),
            None, None, None, None, None)


layer_kl.defvjp(_layer_kl_fwd, _layer_kl_bwd)


def topk_mass(ix: Dict[str, jax.Array], u: jax.Array, cq: jax.Array, q: jax.Array,
              k: jax.Array, table: Tuple[jax.Array, jax.Array], cfg: Any,
              scale: float) -> jax.Array:
    """The share of the target's mass that lies on the ``cfg.index_topk``
    keys the indexer scores highest, the mean over ``MASS_ROWS`` evenly
    spaced query rows of the first sequence (a row with fewer keys than
    that reads 1): what says when the warm-up has done its work. float32,
    off the kernels' path, and no ``[T, T]``: the sampled rows alone stand
    in memory, ``MASS_HEADS`` main heads at a time."""
    T, H = q.shape[1:3]
    rows = (jnp.arange(1, MASS_ROWS + 1) * T) // MASS_ROWS - 1 if T >= MASS_ROWS \
        else jnp.arange(T)
    seen = jnp.arange(T)[None, :] <= rows[:, None]  # [rows, T]
    qI, kI, w = indexer(ix, u[:1], cq[:1], cfg, table)
    z = jnp.einsum("rjd,sd->rjs", qI[0, rows], kI[0], preferred_element_type=_F32)
    I = jnp.where(seen, jnp.einsum("rj,rjs->rs", w[0, rows], jnp.maximum(z, 0.0)), -jnp.inf)
    step, q_rows = math.gcd(H, MASS_HEADS), q[0, rows]

    def some(first):  # ``step`` heads from ``first``: their rows of the attention, summed
        s = jnp.einsum("rhd,shd->hrs", jax.lax.dynamic_slice_in_dim(q_rows, first, step, 1),
                       jax.lax.dynamic_slice_in_dim(k[0], first, step, 1),
                       preferred_element_type=_F32) * scale
        return jnp.sum(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), axis=0)

    p = jnp.sum(jax.lax.map(some, jnp.arange(0, H, step)), axis=0) / H
    if cfg.index_topk >= T:
        return jnp.ones((), _F32)
    kth = -jnp.sort(-I, axis=-1)[:, cfg.index_topk - 1:cfg.index_topk]  # the k-th largest
    return jnp.mean(jnp.sum(jnp.where(I >= kth, p, 0.0), axis=-1))
