"""Latent attention (MLA), the one mixer every kind with such a layer calls
(``models/ling.py``'s one layer in seven, ``models/deepseek.py``'s every
layer): keys and values expanded from one normalised low-rank latent, a
rotary part beside a part without position, the rotary key one for all
heads::

    q        = u @ wq                                  # or, through a latent:
    q        = rmsnorm(u @ w_dq, q_norm) @ w_uq        # where the layer has ``w_dq``
    c, k_r   = split(u @ w_kva);  c = rmsnorm(c, kv_norm)
    k_n, v   = split(c @ w_kvb)                        # a head: nope + v
    q_r, k_r = rotary(q's last dr), rotary(k_r)        # stored pairs (0,1), (2,3)..
    o        = causal_attention([q_n, q_r], [k_n, k_r], v)   # 1 / sqrt(dn + dr) x factor
    out      = (o * sigmoid(u @ w_g)) @ wo             # the gate where the layer has ``w_g``

What differs between the kinds is what the layer's leaves and the caller
say: the queries' latent (``w_dq``, ``q_norm``, ``w_uq`` in place of ``wq``),
the head-wise output gate (``w_g``), the rotary turn (``rotate``: the plain
table, or YaRN's), a factor on the softmax scale (YaRN's ``mscale`` squared)
and THE HEADS HELD: ``heads`` of them, where this chip holds a share of the
layer's heads. The leaves are then the held heads' own (``w_uq`` / ``wq``
``[.., heads x (dn + dr)]``, ``w_kvb`` ``[.., heads x (dn + dv)]``, ``wo``
``[heads x dv, ..]``; the latents' projections and norms whole, as every
tensor-parallel layout of the layer keeps them) and the output is the held
heads' part of the sum over heads: what the other heads would add is
computed by nobody, as ``moe_ffn``'s absent experts.

The attention goes through the dispatcher every kind uses: the ``dn + dr``
wide queries and keys padded with zeros to the next width the kernels tile
(64, 128 or 256), the values left at ``dv``.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.models.llama import _rmsnorm
from torchft_tpu.models.remat import ATTN_OUT_NAME

__all__ = ["mla_mixer"]

_F32 = jnp.float32


def _head_gate(o: jax.Array, u: jax.Array, w_g: jax.Array) -> jax.Array:
    """o [B,S,H,dv] times the sigmoid of one value a head -> [B,S,H*dv]."""
    gate = jax.nn.sigmoid(jnp.matmul(u, w_g, preferred_element_type=_F32))
    return (o * gate.astype(o.dtype)[..., None]).reshape(*o.shape[:2], -1)


def _pairs_apart(x: jax.Array) -> jax.Array:
    """``rope_interleave``: the stored rotary values pair (0, 1), (2, 3)...;
    -> the first of every pair, then the second, which is how a rotary turn
    of halves pairs them."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def mla_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: Any, attention: Any,
              rotate: Callable[[jax.Array], jax.Array], heads: int,
              softmax_factor: float = 1.0, hand_out: bool = False,
              exact_scale: bool = False) -> Any:
    """One layer's latent attention from its normalised input ``u`` [B,S,d]
    to ``wo``'s output: the part of it that the ``heads`` heads of ``w``
    give. ``rotate``: x [B,S,h,dr], its halves paired -> turned by the
    layer's rotary table; ``softmax_factor``: on ``1 / sqrt(dn + dr)``.

    ``hand_out``: -> (the output, what a loss over the attention's own
    probabilities reads: ``cq`` [B,S,q_lora_rank], the queries' normalised
    latent (None without ``w_dq``); ``q``, ``k`` [B,S,H,width], the queries
    and keys exactly as the attention kernel was given them (rotated, padded,
    the softmax factor on ``q``) and ``scale``, what the dispatcher multiplies
    their product by). The compiled mixer is the same either way.

    ``exact_scale``: the factor on the queries (``sqrt(width / (dn + dr)) x
    softmax_factor``) is applied in float32 and the PRODUCT rounded to
    ``u``'s dtype. False, what every kind that stood before the flag keeps
    (their lowered steps are pinned), rounds the CONSTANT to ``u``'s dtype
    first: +0.02% of every score at DeepSeek-V2's constants, +0.13% at
    Ling's, -0.35% at DeepSeek-V3.2's (2.16374 -> 2.15625 in bfloat16), a
    softmax temperature that a loss over the attention's own probabilities
    reads as 0.56% of itself (PERF.md section 6, PR 67)."""
    (B, S, _), H = u.shape, heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                     cfg.kv_lora_rank)
    rope = lambda m: rotate(_pairs_apart(m))  # noqa: E731
    cq = None
    with jax.named_scope("mla/q"):
        if "w_dq" in w:
            cq = _rmsnorm(u @ w["w_dq"], w["q_norm"], cfg.norm_eps)
            q = cq @ w["w_uq"]
        else:
            q = u @ w["wq"]
        q = q.reshape(B, S, H, dn + dr)
        q_r = rope(q[..., dn:])
    with jax.named_scope("mla/kv"):
        ckr = u @ w["w_kva"]
        c = _rmsnorm(ckr[..., :r], w["kv_norm"], cfg.norm_eps)
        k_r = rope(ckr[..., None, r:])  # [B,S,1,dr]: one for all heads
        kv = (c @ w["w_kvb"]).reshape(B, S, H, dn + dv)
    with jax.named_scope("mla/attn"):
        # the dispatcher scales by 1 / sqrt(the width it is given)
        width = next(n for n in (64, 128, 256) if n >= dn + dr)  # what the kernels tile
        zeros = jnp.zeros((B, S, H, width - dn - dr), u.dtype)
        scale = jnp.asarray(math.sqrt(width / (dn + dr)) * softmax_factor,
                            _F32 if exact_scale else u.dtype)
        qq = (jnp.concatenate([q[..., :dn], q_r, zeros], axis=-1) * scale).astype(u.dtype)
        kk = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, H, dr)), zeros], axis=-1)
        attn = jax.ad_checkpoint.checkpoint_name(
            attention(qq, kk, kv[..., dn:], cfg), ATTN_OUT_NAME)
    with jax.named_scope("mla/out"):
        out = (_head_gate(attn, u, w["w_g"]) if "w_g" in w
               else attn.reshape(B, S, H * dv)) @ w["wo"]
    if hand_out:
        return out, {"cq": cq, "q": qq, "k": kk, "scale": 1.0 / math.sqrt(width)}
    return out
