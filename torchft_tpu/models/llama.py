"""Llama-3-family transformer, TPU-first functional JAX.

The flagship model family for fault-tolerant HSDP/DiLoCo training (the
reference trains Llama-3-8B via torchtitan, examples/slurm/runner.py:23-60;
here the model is in-tree because the rebuild is a standalone framework).

Design for the TPU:
- params and activations in bfloat16, RMSNorm/softmax accumulation in f32
  (MXU-friendly matmuls, VPU-safe reductions)
- GQA attention with RoPE; SwiGLU MLP; pre-norm; the head is never tied here
  (``lm_head`` is a leaf of its own and ``num_params`` counts it): the tied
  case belongs to the hybrid's config object (models/jamba.py), which reads
  ``embed`` twice through :func:`head_loss`
- pure functions of a params pytree: `jit`/`pjit` them under any Mesh; the
  sharding rules for tp/fsdp axes live in torchft_tpu/parallel/mesh.py
- no data-dependent Python control flow — everything traces once
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Any, Dict, Optional

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.remat import ATTN_OUT_NAME, remat_wrap
from torchft_tpu.models.staged import Stages

__all__ = [
    "LlamaConfig",
    "llama_init",
    "llama_hidden",
    "llama_forward",
    "llama_loss",
    "llama_stages",
    "head_loss",
    "token_ce",
    "sum_over_chunks",
    "swiglu",
    "CONFIGS",
]


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    ffn_hidden: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def num_params(self) -> int:
        d, h, v, L = self.dim, self.ffn_hidden, self.vocab_size, self.n_layers
        kv = self.n_kv_heads * self.head_dim
        per_layer = d * d + 2 * d * kv + d * d + 3 * d * h + 2 * d
        return L * per_layer + 2 * v * d + d


CONFIGS: Dict[str, LlamaConfig] = {
    # debug/tiny for tests and compile checks
    "debug": LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=128, dtype=jnp.float32,
    ),
    "tiny": LlamaConfig(
        vocab_size=2048, dim=256, n_layers=4, n_heads=8, n_kv_heads=4,
        ffn_hidden=688, max_seq_len=1024,
    ),
    # ~349M params: single-v5e-chip bench config. head_dim is 128 — the MXU
    # lane width — so the flash kernel's QK/PV matmuls use the full systolic
    # array (head_dim 64 halves attention throughput on TPU; measured 2.2x
    # slower fwd+bwd). Same dim/param count as an n_heads=16, hd=64 layout.
    "bench_350m": LlamaConfig(
        vocab_size=32000, dim=1024, n_layers=24, n_heads=8, n_kv_heads=4,
        ffn_hidden=2816, max_seq_len=2048,
    ),
    # ~1.07B params: the round-5 FLAGSHIP bench config (dim 2048 tiles the
    # 128x128 MXU 16-wide; ffn matmuls are 2048x5632; 0.533 MFU at batch 4,
    # the measured peak of the model/batch matrix, vs the 350M config's
    # 0.458 plateau - small-matmul overhead, not a bandwidth floor, see
    # docs/performance.md). Pure-bf16 adamw state is ~6.0 GiB of 16 GiB
    # HBM. bench.py headlines this config at batch 4 and re-measures
    # bench_350m at batch 8 on the same artifact line so rounds <=4 stay
    # directly comparable.
    "bench_1b": LlamaConfig(
        vocab_size=32000, dim=2048, n_layers=20, n_heads=16, n_kv_heads=8,
        ffn_hidden=5632, max_seq_len=2048,
    ),
    # ~1.49B params: the next MXU-width step (dim 2560 = 20 tiles of 128;
    # ffn matmuls 2560x7040). ~8.3 GiB pure-bf16 adamw state. Probes
    # whether the matmul-amortization gain continues past bench_1b on a
    # single 16 GiB chip (docs/performance.md scaling curve).
    "bench_2b": LlamaConfig(
        vocab_size=32000, dim=2560, n_layers=18, n_heads=20, n_kv_heads=10,
        ffn_hidden=7040, max_seq_len=2048,
    ),
    # Llama-3-8B (reference target config, examples/slurm/runner.py)
    "llama3_8b": LlamaConfig(
        vocab_size=128256, dim=4096, n_layers=32, n_heads=32, n_kv_heads=8,
        ffn_hidden=14336, max_seq_len=8192,
    ),
    # Llama-3-70B (reference v5p-256 config)
    "llama3_70b": LlamaConfig(
        vocab_size=128256, dim=8192, n_layers=80, n_heads=64, n_kv_heads=8,
        ffn_hidden=28672, max_seq_len=8192,
    ),
}


def llama_init(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Initialize the parameter pytree.

    Layers are stacked along a leading axis so the forward pass can
    ``lax.scan`` over them — one compiled layer body regardless of depth
    (fast compiles, friendly to pipeline sharding).
    """
    k_emb, k_out, k_layers = jax.random.split(key, 3)
    d, hd = cfg.dim, cfg.head_dim
    kvd = cfg.n_kv_heads * hd
    L = cfg.n_layers

    def norm_init(*shape):
        return jnp.ones(shape, cfg.dtype)

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(
            cfg.dtype
        )

    ks = jax.random.split(k_layers, 7)
    layers = {
        "attn_norm": norm_init(L, d),
        "wq": dense_init(ks[0], (L, d, cfg.n_heads * hd), d),
        "wk": dense_init(ks[1], (L, d, kvd), d),
        "wv": dense_init(ks[2], (L, d, kvd), d),
        "wo": dense_init(ks[3], (L, cfg.n_heads * hd, d), cfg.n_heads * hd),
        "ffn_norm": norm_init(L, d),
        "w_gate": dense_init(ks[4], (L, d, cfg.ffn_hidden), d),
        "w_up": dense_init(ks[5], (L, d, cfg.ffn_hidden), d),
        "w_down": dense_init(ks[6], (L, cfg.ffn_hidden, d), cfg.ffn_hidden),
    }
    return {
        "embed": dense_init(k_emb, (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": norm_init(d),
        "lm_head": dense_init(k_out, (d, cfg.vocab_size), d),
    }


def _rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * w


def _rope(x: jax.Array, theta: float, positions: jax.Array) -> jax.Array:
    """Rotary embeddings; x: [B, S, H, hd]."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs  # [B,S,1,hd/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _attention(
    q: jax.Array, k: jax.Array, v: jax.Array, cfg: LlamaConfig,
    window: Optional[int] = None,
) -> jax.Array:
    """Default causal GQA attention: Pallas flash kernel on TPU, XLA
    elsewhere (torchft_tpu/ops/attention.py); ``window``: over each query's
    last ``window`` positions (a kind with window layers passes it)."""
    from torchft_tpu.ops.attention import causal_attention

    return causal_attention(q, k, v, cfg, window=window)


def swiglu(x: jax.Array, layer_params: Dict[str, Any], block: int = 0) -> jax.Array:
    """``(silu(x w_gate) * (x w_up)) w_down`` of x [B, S, dim]. ``block`` > 0
    (a kind at the HBM edge: models/brumby.py's ``ffn_block``) runs it over
    blocks of that many positions, each rematerialised, so that the three
    [S, ffn_hidden] temporaries exist a block at a time, forward and backward;
    a position's result is the same products either way."""
    def whole(x):
        gated = jax.nn.silu(x @ layer_params["w_gate"]) * (x @ layer_params["w_up"])
        return gated @ layer_params["w_down"]

    B, S, _ = x.shape
    if block <= 0 or S <= block:
        return whole(x)
    if S % block != 0:
        raise ValueError(f"ffn_block {block} must divide seq len {S}")
    blocks = jnp.swapaxes(x.reshape(B, S // block, block, -1), 0, 1)
    fed = jax.lax.map(jax.checkpoint(whole), blocks)
    return jnp.swapaxes(fed, 0, 1).reshape(B, S, -1)


def make_llama_layer_body(
    cfg: LlamaConfig, attention_fn: Optional[Any] = None, mixer: Optional[Any] = None
):
    """The ONE scanned transformer layer body, shared by every execution
    path (dense scan here, GPipe stages in parallel/pipeline.py) so the
    layer math can never diverge between them. Signature matches lax.scan:
    ``layer(h, layer_params) -> (h, None)`` with h [B, S, dim]. A stack that
    has ``attn_post_norm`` / ``ffn_post_norm`` leaves is a sandwich-norm
    layer (models/ouro.py): each branch is normalised once more before it
    joins the residual stream; one that has ``q_norm`` / ``k_norm`` leaves
    [head_dim] normalises every head's queries and keys before the rotary
    turn.

    ``mixer``: the seam for a kind whose layers mix positions by something
    other than attention over q, k, v alone (models/brumby.py: a retention
    under a gate computed from the layer's normalised input). ``mixer.mix(q,
    k, v, x, layer_params) -> (mixed [B, S, n_heads, head_dim], emitted)``
    stands where attention does and ``emitted`` is what the layer hands out
    beside ``h``; ``mixer.scope(part)`` names the part of the layer that
    follows ("in_proj", "qk_norm_rope", "out_proj", "ffn") in the compiled
    step; ``mixer.ffn_block`` is :func:`swiglu`'s ``block``."""
    attention = attention_fn or _attention
    scope = mixer.scope if mixer is not None else (lambda part: contextlib.nullcontext())
    ffn_block = mixer.ffn_block if mixer is not None else 0

    def layer(h, layer_params):
        B, S = h.shape[0], h.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        x = _rmsnorm(h, layer_params["attn_norm"], cfg.norm_eps)
        with scope("in_proj"):
            q = (x @ layer_params["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
            k = (x @ layer_params["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
            v = (x @ layer_params["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        with scope("qk_norm_rope"):
            if "q_norm" in layer_params:
                q = _rmsnorm(q, layer_params["q_norm"], cfg.norm_eps)
                k = _rmsnorm(k, layer_params["k_norm"], cfg.norm_eps)
            q = _rope(q, cfg.rope_theta, positions)
            k = _rope(k, cfg.rope_theta, positions)
        if mixer is None:
            attn, emitted = attention(q, k, v, cfg), None
        else:
            attn, emitted = mixer.mix(q, k, v, x, layer_params)
        attn = jax.ad_checkpoint.checkpoint_name(attn, ATTN_OUT_NAME).reshape(
            B, S, cfg.n_heads * cfg.head_dim)
        with scope("out_proj"):
            mixed = attn @ layer_params["wo"]
        if "attn_post_norm" in layer_params:
            mixed = _rmsnorm(mixed, layer_params["attn_post_norm"], cfg.norm_eps)
        h = h + mixed
        with scope("ffn"):
            x = _rmsnorm(h, layer_params["ffn_norm"], cfg.norm_eps)
            fed = swiglu(x, layer_params, ffn_block)
        if "ffn_post_norm" in layer_params:
            fed = _rmsnorm(fed, layer_params["ffn_post_norm"], cfg.norm_eps)
        h = h + fed
        return h, emitted

    return layer


def llama_hidden(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    attention_fn: Optional[Any] = None,
    remat: Any = "dots",
) -> jax.Array:
    """tokens: int32 [B, S] -> final-norm hidden states [B, S, dim]
    (everything except the lm_head projection — see `llama_loss`'s chunked
    path, which applies the head per sequence chunk)."""
    h = params["embed"][tokens]  # [B,S,D]
    # scan over stacked layers: one compiled body, L iterations.
    # TORCHFT_TPU_SCAN_UNROLL (benchmark escape hatch, default 1) unrolls
    # the layer loop N-wise — XLA can then overlap across layer boundaries
    # at the cost of N x the body's compile time; benchmarks/mfu_sweep.py
    # is where values compete, training code leaves it unset
    body = remat_wrap(make_llama_layer_body(cfg, attention_fn), remat)
    unroll = int(os.environ.get("TORCHFT_TPU_SCAN_UNROLL", "1"))
    h, _ = jax.lax.scan(body, h, params["layers"], unroll=unroll)
    return _rmsnorm(h, params["final_norm"], cfg.norm_eps)


def llama_stages(cfg: LlamaConfig, attention_fn: Optional[Any] = None) -> Stages:
    """:func:`llama_loss` in the three stages a staged gradient composes
    (models/staged.py): the embedding, the ONE layer body, final norm with
    head and loss."""

    def head(head_params, h, emitted, targets):
        h = _rmsnorm(h, head_params["final_norm"], cfg.norm_eps)
        return head_loss(h, head_params["lm_head"], targets), {}

    return Stages(lambda embed, tokens: embed[tokens],
                  make_llama_layer_body(cfg, attention_fn), head)


def llama_forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    attention_fn: Optional[Any] = None,
    remat: Any = "dots",
) -> jax.Array:
    """tokens: int32 [B, S] -> logits f32 [B, S, vocab].

    ``attention_fn(q, k, v, cfg)`` can be swapped for a sharded/ring variant
    (torchft_tpu/parallel/ring_attention.py) without touching the rest of the
    stack.

    ``remat`` selects the rematerialization mode for the scanned layer body —
    see `torchft_tpu.models.remat.remat_wrap`. Default "dots" saves matmul
    outputs and recomputes the rest, trading HBM for ~25% fewer backward
    FLOPs vs full remat; pass "full" for models at the edge of HBM.
    """
    h = llama_hidden(params, tokens, cfg, attention_fn=attention_fn, remat=remat)
    return (h @ params["lm_head"]).astype(jnp.float32)


def llama_loss(
    params: Dict[str, Any],
    tokens: jax.Array,
    targets: jax.Array,
    cfg: LlamaConfig,
    attention_fn: Optional[Any] = None,
    remat: Any = "dots",
    loss_chunk: int = 0,
) -> jax.Array:
    """Mean next-token cross-entropy.

    Computed as logsumexp(logits) - logits[target] rather than via
    log_softmax: the latter materializes a second [B, S, vocab] f32 array in
    HBM, which at vocab ~2GB per step dominates the loss cost on TPU
    (~6% step-time win on the bench config).

    ``loss_chunk > 0`` scans the loss over sequence chunks of that length
    with per-chunk rematerialization: peak HBM for logits drops from
    [B, S, vocab] f32 to [B, chunk, vocab] (the backward recomputes each
    chunk's logits instead of keeping them all resident). Trades one extra
    lm_head matmul per chunk in backward for vocab-sized activation memory —
    the standard trade for big-vocab models at the HBM edge.
    """
    h = llama_hidden(
        params, tokens, cfg, attention_fn=attention_fn, remat=remat
    )
    return head_loss(h, params["lm_head"], targets, loss_chunk)


def token_ce(h: jax.Array, lm_head: jax.Array, targets: jax.Array) -> jax.Array:
    """The cross-entropy token by token, float32 [...]: logsumexp(logits) -
    logits[target] of h [..., dim] under the head [dim, vocab]."""
    logits = (h @ lm_head).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


def sum_over_chunks(chunk_sum: Any, zero: jax.Array, chunks: Any) -> jax.Array:
    """``zero`` + ``chunk_sum(*chunk)`` over the leading axis of ``chunks``,
    each chunk rematerialised: the backward pass recomputes a chunk's logits
    instead of keeping every chunk's resident."""
    def body(acc, xs):
        return acc + jax.checkpoint(chunk_sum)(*xs), None

    total, _ = jax.lax.scan(body, zero, chunks)
    return total


def head_loss(
    h: jax.Array, lm_head: jax.Array, targets: jax.Array, loss_chunk: int = 0
) -> jax.Array:
    """The cross-entropy of :func:`llama_loss` from final-norm hidden states
    h [B, S, dim] and a head matrix [dim, vocab] (a model with a tied head
    passes ``embed.T``: one leaf read twice, its gradient the sum of both)."""
    if loss_chunk <= 0:
        return jnp.mean(token_ce(h, lm_head, targets))

    B, S = targets.shape
    if S % loss_chunk != 0:
        raise ValueError(f"loss_chunk {loss_chunk} must divide seq len {S}")
    n = S // loss_chunk
    # [n, B, chunk, ...]: scan over sequence chunks
    h_c = jnp.swapaxes(h.reshape(B, n, loss_chunk, -1), 0, 1)
    t_c = jnp.swapaxes(targets.reshape(B, n, loss_chunk), 0, 1)
    total = sum_over_chunks(lambda hc, tc: jnp.sum(token_ce(hc, lm_head, tc)),
                            jnp.zeros((), jnp.float32), (h_c, t_c))
    return total / (B * S)


def _model_fns() -> ModelFns:
    from torchft_tpu.parallel.mesh import llama_param_specs  # it imports this module

    return ModelFns(llama_init, logged(lambda *a, **kw: (llama_loss(*a, **kw), {})),
                    llama_param_specs, llama_stages)


register(LlamaConfig, {}, _model_fns)  # its presets are ``CONFIGS`` itself
