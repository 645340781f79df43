"""A dense decoder whose layers mix positions by POWER RETENTION (Manifest
AI's Brumby, retrained from a Qwen3 decoder; "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239): degree-2 symmetric-power linear
attention under a learned scalar decay, its output normalised by the running
sum of its weights. No softmax, no key/value cache: a layer's memory is one
state of 8,256 x 128 float32 and a normaliser of 8,256 a key/value head at
head size 128, whatever the length.

The layer is the dense kind's (``models/llama.py``'s
``make_llama_layer_body``: RMSNorm, the four projections, RoPE, SwiGLU,
residuals), reached through its ``mixer`` seam, with three things its own
(h [B, S, dim], eps ``norm_eps``)::

    x    = RMSNorm(h; attn_norm);  q, k, v = x wq, x wk, x wv      (no bias)
    q, k = RMSNorm_hd(q; q_norm), RMSNorm_hd(k; k_norm)            one weight of head_dim each
    q, k = RoPE(q), RoPE(k)                                          theta rope_theta
    g    = logsigmoid(x wg + bg)          float32 [B, S, n_kv_heads]: a log-decay a key/value head
    y    = power_retention(q, k, v, g)    ops/power_retention.py: a[t, r] = exp(G_t - G_r)
           (head_dim^-1/2 q_t . k_r)^2 over r <= t, y_t = sum_r a v_r / (sum_r a + 1e-6)
    h    = h + y wo;   h = h + SwiGLU(RMSNorm(h; ffn_norm))

The five query heads of a group read one key/value head's state. The
feed-forward runs over blocks of ``ffn_block`` positions, each
rematerialised (``llama.swiglu``): at hidden 5,120 and 17,408 the three
temporaries are 570 MB each at 16k. The layers are RUNS OF ONE
(``models/decoder.py``): each layer's leaves are their own buffers, so a
layer's gradient is consumed by its update when the backward pass has made
it and the four layers' gradients need not all be alive at once (as the
expert layers' of the routed kinds).

``jax.named_scope``: ``retention/in_proj``, ``retention/qk_norm_rope``,
``retention/gate``, ``retention/kernel``, ``retention/out_proj`` and
``ffn/block`` in the compiled step. Beside the loss: ``den_min`` (the
smallest normaliser before eps over the step: the health of the division)
and ``decay_mean`` (the mean of ``exp(g)``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from torchft_tpu.models.decoder import Decoder, init_tree, runs_of, spec_tree
from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import LlamaConfig, make_llama_layer_body
from torchft_tpu.ops import attention as attention_ops
from torchft_tpu.ops.power_retention import BLOCK, CHUNK, power_retention

__all__ = ["BrumbyConfig", "BRUMBY_CONFIGS", "brumby_init", "brumby_hidden",
           "brumby_forward", "brumby_loss", "brumby_loss_and_stats", "brumby_param_specs"]

_F32 = jnp.float32
# what ``exp(g)`` spans over the key/value heads at initialisation, position
# by position at a zero gate: a head that forgets in tens of positions (1 /
# (1 - 0.9) = 10) beside heads that hold thousands (1 / (1 - 0.9999) = 10,000)
DECAY_SPAN = (0.9, 0.9999)
# what ``ops.attention.LAST_DISPATCH`` reads after a step of this kind
DISPATCH = "power_retention"


@dataclasses.dataclass(frozen=True)
class BrumbyConfig(LlamaConfig):
    head_dim: int = 128  # the configuration's own (here also dim // n_heads)
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    ffn_block: int = 0  # positions a feed-forward block covers (0: the sequence)
    # positions one set of the kernel's products covers; a state is saved
    # every ``BLOCK / CHUNK`` chunks
    retention_chunk: int = CHUNK
    loss_chunk: int = 0  # as ``Lfm2Config.loss_chunk``

    def __post_init__(self) -> None:
        if self.n_heads % self.n_kv_heads or self.head_dim % 2:
            raise ValueError(f"{self.n_heads} heads over {self.n_kv_heads} of {self.head_dim}")
        if self.retention_chunk < 1:
            raise ValueError(f"retention_chunk={self.retention_chunk}")

    def runs(self):
        return runs_of(["retention"] * self.n_layers, merges=lambda kind: False)

    def num_params(self) -> int:
        d, f, v, hd = self.dim, self.ffn_hidden, self.vocab_size, self.head_dim
        q, kv = self.n_heads * hd, self.n_kv_heads * hd
        per_layer = (2 * d * q + 2 * d * kv + (d + 1) * self.n_kv_heads + 3 * d * f
                     + 2 * d + 2 * hd)
        return self.n_layers * per_layer + 2 * v * d + d


BRUMBY_CONFIGS: Dict[str, BrumbyConfig] = {
    "brumby_debug": BrumbyConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
        ffn_hidden=128, max_seq_len=128, dtype=jnp.float32, ffn_block=16,
        retention_chunk=8),
}


def gate_bias(heads: int) -> jax.Array:
    """``bg`` [heads] float32 with ``sigmoid(bg)`` = ``exp(g)`` at a zero
    gate spread over :data:`DECAY_SPAN`, evenly in the log of the memory
    ``1 / (1 - exp(g))``."""
    lo, hi = (1.0 / (1.0 - x) for x in DECAY_SPAN)
    memory = jnp.exp(jnp.linspace(jnp.log(lo), jnp.log(hi), heads, dtype=_F32))
    return jnp.log(memory - 1.0)  # logit(1 - 1 / memory)


def brumby_init(key: jax.Array, cfg: BrumbyConfig) -> Dict[str, Any]:
    """Every matrix normal over the root of its fan-in, the norms' weights
    ones, the gate's bias :func:`gate_bias` (float32, as the gate's matrix:
    they sit under an exponential); a stack of its own a layer."""
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    d, hd, f = cfg.dim, cfg.head_dim, cfg.ffn_hidden
    q, kv = cfg.n_heads * hd, cfg.n_kv_heads * hd

    def dense(key, shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, _F32) / jnp.sqrt(fan_in)).astype(dtype)

    def run(key, kind, L):
        ks = jax.random.split(key, 8)
        ones = lambda *shape: jnp.ones(shape, cfg.dtype)  # noqa: E731  a buffer each
        return {"attn_norm": ones(L, d), "wq": dense(ks[0], (L, d, q), d),
                "wk": dense(ks[1], (L, d, kv), d), "wv": dense(ks[2], (L, d, kv), d),
                "q_norm": ones(L, hd), "k_norm": ones(L, hd),
                "wg": dense(ks[3], (L, d, cfg.n_kv_heads), d, _F32),
                "bg": jnp.tile(gate_bias(cfg.n_kv_heads), (L, 1)),
                "wo": dense(ks[4], (L, q, d), q), "ffn_norm": ones(L, d),
                "w_gate": dense(ks[5], (L, d, f), d), "w_up": dense(ks[6], (L, d, f), d),
                "w_down": dense(ks[7], (L, f, d), f)}

    return {**init_tree(k_emb, k_layers, cfg, run),
            "lm_head": dense(k_head, (d, cfg.vocab_size), d)}


def brumby_param_specs(cfg: BrumbyConfig) -> Dict[str, Any]:
    """The dense decoder's PartitionSpecs a run, the per-head norms and the
    gate's leaves replicated (the kernel owns every head of a group)."""
    from jax.sharding import PartitionSpec as P

    from torchft_tpu.parallel.mesh import llama_param_specs  # it imports models

    dense = llama_param_specs(cfg)["layers"]
    own = {"q_norm": P(None, None), "k_norm": P(None, None),
           "wg": P(None, "fsdp", None), "bg": P(None, None)}
    return {**spec_tree(cfg, lambda kind: {**dense, **own}), "lm_head": P("fsdp", "tp")}


@dataclasses.dataclass(frozen=True)
class _Retention:
    """``make_llama_layer_body``'s ``mixer`` for this kind."""

    cfg: BrumbyConfig

    @property
    def ffn_block(self) -> int:
        return self.cfg.ffn_block

    @staticmethod
    def scope(part: str):
        return jax.named_scope("ffn/block" if part == "ffn" else f"retention/{part}")

    def mix(self, q, k, v, x, w):
        cfg = self.cfg
        with jax.named_scope("retention/gate"):
            # the decay's pre-activation in float32, its matrix unrounded: g is
            # summed over thousands of positions (5,120 x 8: the cost is none)
            g = jax.nn.log_sigmoid(jnp.matmul(
                x, w["wg"], precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=_F32) + w["bg"])
        with jax.named_scope("retention/kernel"):
            y, den_min = power_retention(
                q, k, v, g, chunk=cfg.retention_chunk,
                block=cfg.retention_chunk * (BLOCK // CHUNK), with_den_min=True)
        attention_ops.LAST_DISPATCH = DISPATCH
        return y, jax.lax.stop_gradient(
            {"den_min": den_min, "decay_mean": jnp.mean(jnp.exp(g))})


def _bodies(cfg: BrumbyConfig, seq: int, attention_fn: Optional[Any]):
    layer = make_llama_layer_body(cfg, mixer=_Retention(cfg))
    return lambda kind: lambda h, xs: layer(h, xs[0])


def _counters(stats: Dict[str, jax.Array], tokens: jax.Array, cfg: BrumbyConfig
              ) -> Dict[str, jax.Array]:
    """``den_min`` (the smallest normaliser before eps, over layers, heads
    and positions) and ``decay_mean`` (the mean of ``exp(g)``)."""
    return {"den_min": jnp.min(stats["den_min"]), "decay_mean": jnp.mean(stats["decay_mean"])}


BRUMBY = Decoder(_bodies, _counters)
brumby_hidden, brumby_forward = BRUMBY.hidden, BRUMBY.forward
brumby_loss_and_stats, brumby_loss = BRUMBY.loss_and_stats, BRUMBY.loss

register(BrumbyConfig, BRUMBY_CONFIGS, lambda: ModelFns(
    brumby_init, logged(brumby_loss_and_stats, retention=("den_min", "decay_mean")),
    brumby_param_specs, None))
