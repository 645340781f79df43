"""Ring attention: context/sequence parallelism for long sequences.

Not present in the reference (SURVEY.md §5: sequence scaling is delegated to
torchtitan) but first-class here: causal flash-style attention where the KV
shards rotate around the ``sp`` mesh axis via ``ppermute`` while each device
keeps its Q shard, with online-softmax accumulation — compute overlaps the
ICI transfer and per-device memory stays O(S/P).

Use ``make_ring_attention_fn(mesh)`` as the ``attention_fn`` of
``llama_forward``; it shard_maps over (dp, fsdp, sp, tp) and runs
``ring_attention`` per shard.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["ring_attention", "make_ring_attention_fn", "make_sp_attention_fn"]


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "sp",
) -> jax.Array:
    """Causal ring attention over ``axis_name``.

    Call inside shard_map. q: [B, S_loc, Hq, hd]; k/v: [B, S_loc, Hkv, hd]
    (local sequence shards; global position = axis_index * S_loc + offset).
    Returns [B, S_loc, Hq, hd] in q's dtype.
    """
    P_ = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    groups = Hq // Hkv

    q32 = q.astype(jnp.float32)
    q_pos = my_idx * S + jnp.arange(S)  # [S]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    # online softmax accumulators
    m0 = jnp.full((B, Hq, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hq, S), jnp.float32)
    o0 = jnp.zeros((B, S, Hq, hd), jnp.float32)

    def body(i, carry):
        m, l, o, k_blk, v_blk = carry
        kv_idx = (my_idx - i) % P_
        kv_pos = kv_idx * S + jnp.arange(S)  # [S]

        k_rep = jnp.repeat(k_blk, groups, axis=2).astype(jnp.float32)
        v_rep = jnp.repeat(v_blk, groups, axis=2).astype(jnp.float32)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, k_rep) * scale
        causal = q_pos[:, None] >= kv_pos[None, :]  # [Sq, Sk]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
        # fully-masked rows keep m_new == -inf; use a zero surrogate so the
        # exps below stay finite (their probabilities are zeroed by `causal`)
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(
            jnp.isneginf(scores), 0.0, jnp.exp(scores - m_safe[..., None])
        )  # [B,H,Sq,Sk]
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))  # [B,H,Sq]
        l = alpha * l + jnp.sum(p, axis=-1)
        o = alpha.transpose(0, 2, 1)[..., None] * o + jnp.einsum(
            "bhqk,bkhd->bqhd", p, v_rep
        )
        m = m_new

        # rotate the KV shard to the next device on the ring
        perm = [(j, (j + 1) % P_) for j in range(P_)]
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        return m, l, o, k_blk, v_blk

    m, l, o, _, _ = jax.lax.fori_loop(0, P_, body, (m0, l0, o0, k, v))
    l_t = l.transpose(0, 2, 1)[..., None]  # [B,S,H,1]
    out = jnp.where(l_t > 0, o / jnp.maximum(l_t, 1e-20), 0.0)
    return out.astype(q.dtype)


def make_sp_attention_fn(mesh: Mesh, kernel):
    """Shared shard_map wrapper for the sequence-parallel attention
    strategies: ``kernel(q, k, v, cfg)`` runs per shard under the one
    (dp, fsdp) x sp x tp sharding contract, so ring and ulysses cannot
    drift apart on specs. What a layer asks for beyond that (``window=``)
    goes to the kernel as it came: the dispatcher of ops/attention.py takes
    it, the ring and ulysses kernels have no window and say so."""
    qspec = P(("dp", "fsdp"), "sp", "tp", None)

    def attention_fn(q, k, v, cfg, **asked):
        fn = shard_map(
            partial(kernel, cfg=cfg, **asked),
            mesh=mesh,
            in_specs=(qspec, qspec, qspec),
            out_specs=qspec,
            check_vma=False,
        )
        return fn(q, k, v)

    return attention_fn


def make_ring_attention_fn(mesh: Mesh):
    """Attention fn for llama_forward: shard_map of ring_attention.

    Sharding: batch over (dp, fsdp), sequence over sp, heads over tp
    (tp must divide n_kv_heads).
    """
    def kernel(q, k, v, cfg):
        return ring_attention(q, k, v, axis_name="sp")

    return make_sp_attention_fn(mesh, kernel)
