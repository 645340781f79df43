"""Linear-attention / latent-attention decoder with a share of its routed
experts (inclusionAI's Ling 3.0 family, ``model_type`` ``bailing_hybrid``) as
a fifth kind of the one trainer's model: most layers mix the sequence with
KDA, a gated delta rule with a per-channel decay (``ops/kda.py``), some
(``layer_types``) with MLA, attention whose keys and values are expanded
from one normalised low-rank latent; the first ``num_dense_layers`` end in a
SwiGLU, the others in ``num_experts`` sigmoid-routed experts chosen
``top_k`` a token inside ``topk_group`` of ``n_group`` groups, beside one
shared expert.

Every layer: ``h = x + mixer(rmsnorm(x))``, then ``h + ffn(rmsnorm(h))``.

The ``kda`` mixer (``models/kda.py``'s, which ``models/solar.py`` calls too;
H heads of ``kda_head_dim``)::

    q, k, v = silu(conv4(u @ wq)), silu(conv4(u @ wk)), silu(conv4(u @ wv))
    q, k    = l2norm(q) / sqrt(d_k), l2norm(k)                  # a head at a time
    g       = kda_lower_bound * sigmoid(exp(A_log) * (u @ w_f + dt_bias))   # (-5, 0)
    beta    = sigmoid(u @ w_beta)                               # one a head
    o       = kda(q, k, v, g, beta)                             # ops/kda.py
    out     = (rmsnorm_head(o) * sigmoid(u @ w_g)) @ wo         # gate: one a head

The ``mla`` mixer (``models/mla.py``'s, which ``models/deepseek.py`` calls
too): ``q = u @ wq`` split a head into 128 without position
and 64 rotary; ``c, k_r = split(u @ w_kva)``, ``c`` RMS-normalised, ``k_n, v
= split(c @ w_kvb)``; RoPE on ``q``'s 64 and on ``k_r`` (which all heads
share), their stored pairs interleaved (``rope_interleave``); causal
attention at ``1 / sqrt(192)`` through the dispatcher every kind uses, the
192-wide queries and keys padded with zeros to 256 (the kernels tile 64, 128
or 256) and the values left at 128; the same head-wise sigmoid gate; ``wo``.

The expert feed-forward is ``models/moe.py``'s dropless block told what this
configuration adds to it: the group limit, ``routed_scaling``, the shared
expert and, where ``held_experts`` says so, that this chip holds a SHARE of
the experts: the router and the decision over all of them, the held experts'
part of the sum computed, the rest computed by nobody (``moe_ffn``).
``expert_bias`` ([expert layers, num_experts] float32, a top-level leaf) is
state and not a parameter, exactly as ``models/lfm2.py``'s and for its
reasons: ``model_fns`` names it under ``frozen``, nothing moves it, and it
is initialised as ``0.01 * normal`` so that a check can see that the
selection reads it.

The vocabulary may be a slice too (``vocab_size`` rows of embedding and of
head: ids, logits and loss over the slice); the head is untied. The family's
multi-token-prediction layer is not built: its published loss weight is 0.

The parameters are one stack per RUN of like layers (dense layers of one
mixer together, an expert layer alone) and ``models/decoder.py`` scans the
runs: this module is the configuration, ``init``, the two mixers, the layer
body, the PartitionSpecs and the counters, and declares them (``LING``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.decoder import Decoder, init_tree, runs_of, spec_tree
from torchft_tpu.models.kda import kda_mixer
from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import _attention, _rmsnorm, _rope
from torchft_tpu.models.mla import mla_mixer
from torchft_tpu.models.moe import (BIAS_INIT_SCALE, MoEConfig, _refuse_dropless_ep,
                                    expert_scalars, ffn_init, ffn_leaves, ffn_specs, moe_ffn)
from torchft_tpu.ops.kda import BOUNDED_FLOOR

__all__ = [
    "LingConfig",
    "LING_CONFIGS",
    "LING_FROZEN",
    "ling_init",
    "ling_hidden",
    "ling_forward",
    "ling_loss",
    "ling_loss_and_stats",
    "ling_param_specs",
]

# the top-level leaves that are state and not parameters
LING_FROZEN = ("expert_bias",)
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LingConfig(MoEConfig):
    # ``ffn_hidden`` is the dense layers' SwiGLU width (``intermediate_size``);
    # ``n_kv_heads`` is not read: MLA expands keys and values for every head
    layer_types: Tuple[str, ...] = ()  # "kda" | "mla"
    num_dense_layers: int = 1
    kda_head_dim: int = 128  # d_k = d_v of a KDA head; n_heads of them
    kda_conv: int = 4  # taps of the short convolutions
    kda_lower_bound: float = -5.0  # at -5 or above ops/kda.py runs its bounded body
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    moe_intermediate_size: int = 768  # one expert's width, the shared one's too
    num_experts: int = 512
    top_k: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling: float = 2.5
    capacity_factor: Optional[float] = None  # dropless
    aux_loss_weight: float = 0.0
    router_score: str = "sigmoid"
    gate_eps: float = 1e-20
    loss_chunk: int = 0  # as ``Lfm2Config.loss_chunk``

    def __post_init__(self) -> None:
        super().__post_init__()
        self._check_layer_types(("kda", "mla"), self.num_dense_layers)
        if not BOUNDED_FLOOR <= self.kda_lower_bound < 0:
            raise ValueError(
                f"kda_lower_bound={self.kda_lower_bound}: this family's decay is "
                f"kda_lower_bound * sigmoid(.), and the bound the family publishes is "
                f"{BOUNDED_FLOOR:g}; ops/kda.py takes every decay <= 0, so a lower bound "
                "is another model's form (models/solar.py has the unbounded one), not a "
                "limit of the kernel")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.num_dense_layers

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def kinds(self) -> List[Tuple[str, str]]:
        """(mixer, feed-forward) of every layer: ("kda" | "mla", "dense" |
        "moe")."""
        return [(t, "dense" if i < self.num_dense_layers else "moe")
                for i, t in enumerate(self.layer_types)]

    def runs(self) -> List[Tuple[str, Tuple[str, str], int]]:
        """Runs of like layers in order, as :meth:`Lfm2Config.runs`."""
        return runs_of(self.kinds(), name="_".join, merges=lambda kind: kind[1] == "dense")

    def num_params(self) -> int:
        """Every leaf this chip holds, ``expert_bias`` among them."""
        d, H = self.dim, self.n_heads
        kd = H * self.kda_head_dim
        mixer = {"kda": 4 * d * kd + kd * d + 2 * d * H + 3 * self.kda_conv * kd
                        + kd + H + self.kda_head_dim,
                 "mla": d * H * self.qk_head_dim
                        + d * (self.kv_lora_rank + self.qk_rope_head_dim)
                        + self.kv_lora_rank
                        + self.kv_lora_rank * H * (self.qk_nope_head_dim + self.v_head_dim)
                        + d * H + H * self.v_head_dim * d}
        one = 3 * d * self.moe_intermediate_size
        ffn = {"dense": 3 * d * self.ffn_hidden,
               "moe": (self.n_held + 1) * one + d * self.num_experts + self.num_experts}
        return (sum(mixer[m] + ffn[f] + 2 * d for m, f in self.kinds())
                + 2 * self.vocab_size * d + d)


LING_CONFIGS: Dict[str, LingConfig] = {
    # every kind of layer: a dense KDA layer, then KDA, MLA and KDA layers
    # with a share of 16 experts in 4 groups; bf16 like the published one, so
    # the float32 routers, decays and bias sit among bf16 leaves in a
    # trainer's bucket plan. The share has room for every pair: a toy batch
    # swings far from the even share.
    "ling_debug": LingConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, ffn_hidden=128,
        max_seq_len=128, rope_theta=6e6, norm_eps=1e-6,
        layer_types=("kda", "kda", "mla", "kda"), num_dense_layers=1,
        kda_head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_intermediate_size=32, num_experts=16, top_k=4,
        n_group=4, topk_group=2, held_experts=(4, 4), share_room=4.0,
    ),
    # inclusionAI/Ling-3.0-flash, one chip's share of the first seven
    # published layers in a deployment of sixteen chips a layer: one leading
    # dense layer, one period KDA KDA KDA MLA KDA KDA of expert layers, 32 of
    # the 512 experts (the first half of the first group), an eighth of the
    # vocabulary
    "ling_3_0_flash_share": LingConfig(
        vocab_size=19648, dim=2560, n_layers=7, n_heads=32, n_kv_heads=32,
        ffn_hidden=6144, max_seq_len=131072, rope_theta=6e6, norm_eps=1e-6,
        layer_types=("kda", "kda", "kda", "kda", "mla", "kda", "kda"),
        held_experts=(0, 32), share_room=6.0, loss_chunk=2048,
    ),
}


def ling_init(key: jax.Array, cfg: LingConfig) -> Dict[str, Any]:
    """Parameter pytree: ``embed``, ``lm_head``, ``final_norm``, ``layers``
    (one stack per run of like layers, :meth:`LingConfig.runs`; the expert
    leaves ``[1, held, ...]``, the router ``[1, dim, num_experts]``) and,
    where there are expert layers, ``expert_bias`` [expert layers,
    num_experts] float32 (state: ``LING_FROZEN``)."""
    k_emb, k_head, k_bias, k_layers = jax.random.split(key, 4)
    d, H = cfg.dim, cfg.n_heads
    kd = H * cfg.kda_head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, _F32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    def mixer(kind, keys, L):
        if kind == "kda":
            taps = lambda k: dense(k, (L, cfg.kda_conv, kd), cfg.kda_conv)  # noqa: E731
            return {"wq": dense(keys[0], (L, d, kd), d), "wk": dense(keys[1], (L, d, kd), d),
                    "wv": dense(keys[2], (L, d, kd), d), "w_f": dense(keys[3], (L, d, kd), d),
                    "conv_q": taps(keys[4]), "conv_k": taps(keys[5]), "conv_v": taps(keys[6]),
                    # the decay's own leaves in float32: they sit in an exponent
                    "A_log": jnp.log(jax.random.uniform(keys[7], (L, H), _F32, 1.0, 2.0)),
                    "dt_bias": jax.random.normal(keys[8], (L, kd), _F32) - 3.0,
                    "w_beta": dense(keys[9], (L, d, H), d),
                    "o_norm": jnp.ones((L, cfg.kda_head_dim), cfg.dtype),
                    "w_g": dense(keys[10], (L, d, H), d),
                    "wo": dense(keys[11], (L, kd, d), kd)}
        r, hv = cfg.kv_lora_rank, H * cfg.v_head_dim
        return {"wq": dense(keys[0], (L, d, H * cfg.qk_head_dim), d),
                "w_kva": dense(keys[1], (L, d, r + cfg.qk_rope_head_dim), d),
                "kv_norm": jnp.ones((L, r), cfg.dtype),
                "w_kvb": dense(keys[2], (L, r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), r),
                "w_g": dense(keys[3], (L, d, H), d),
                "wo": dense(keys[4], (L, hv, d), hv)}

    def run(key, kind, L):
        ks = jax.random.split(key, 20)
        return {"norm": jnp.ones((L, d), cfg.dtype), **mixer(kind[0], ks[:12], L),
                "ffn_norm": jnp.ones((L, d), cfg.dtype),
                **ffn_init(ffn_leaves(cfg, kind[1], shared=True), ks[12:], L, cfg.dtype)}

    params = {**init_tree(k_emb, k_layers, cfg, run),
              "lm_head": dense(k_head, (d, cfg.vocab_size), d)}
    if cfg.n_moe_layers:
        params["expert_bias"] = BIAS_INIT_SCALE * jax.random.normal(
            k_bias, (cfg.n_moe_layers, cfg.num_experts), _F32)
    return params


def _kda_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: LingConfig) -> jax.Array:
    """``models/kda.py``'s mixer as this family has it: the decay above
    ``kda_lower_bound``, its projection one full matrix, ``beta`` up to 1,
    the gate one value a head."""
    return kda_mixer(u, w, cfg, decay_floor=cfg.kda_lower_bound, beta_max=1.0)[0]


def _mla_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: LingConfig,
               attention: Any) -> jax.Array:
    """``models/mla.py``'s mixer as this family has it: every head, the
    plain rotary table, the softmax scale as it is; ``w`` has no queries'
    latent and has the head-wise gate."""
    positions = jnp.broadcast_to(jnp.arange(u.shape[1]), u.shape[:2])
    return mla_mixer(u, w, cfg, attention,
                     lambda m: _rope(m, cfg.rope_theta, positions), cfg.n_heads)


def _layer_body(cfg: LingConfig, kind: Tuple[str, str], attention: Any):
    """The scanned body of a run of ``kind``, as ``models/lfm2.py``'s:
    ``(h, (w, bias, replay)) -> (h, stats)``."""
    mixer, ffn = kind

    def layer(h, xs):
        w, bias, replay = xs
        u = _rmsnorm(h, w["norm"], cfg.norm_eps)
        h = h + (_kda_mixer(u, w, cfg) if mixer == "kda"
                 else _mla_mixer(u, w, cfg, attention))
        x = _rmsnorm(h, w["ffn_norm"], cfg.norm_eps)
        if ffn == "dense":
            return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"], None
        out, stats = moe_ffn(
            x, w["router"], w["w_gate"], w["w_up"], w["w_down"], cfg,
            routing=replay, bias=bias,
            shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))
        stats.pop("prob_sum")  # no auxiliary loss reads it
        return h + out, stats

    return layer


def _bodies(cfg: LingConfig, seq: int, attention_fn: Optional[Any]):
    return lambda kind: _layer_body(cfg, kind, attention_fn or _attention)


def _counters(stats: Dict[str, jax.Array], tokens: jax.Array, cfg: LingConfig
              ) -> Dict[str, jax.Array]:
    """The expert layers' free routing with its margins (``routing``
    [L,T,k], ``p_kth``, ``p_next`` [L,T]) and all seven of
    ``moe.expert_scalars``: ``load_max_over_mean``, ``bias_moved_share``,
    ``groups_hit_mean``, ``held_pair_share``, ``overflow_pairs``,
    ``visited_row_share`` and ``moved_row_share``."""
    return expert_scalars(stats, tokens.size * cfg.top_k)


LING = Decoder(_bodies, _counters, routed=lambda kind: kind[1] == "moe")
ling_hidden, ling_forward = LING.hidden, LING.forward
ling_loss_and_stats, ling_loss = LING.loss_and_stats, LING.loss


def ling_param_specs(cfg: LingConfig, mesh: Optional[Any] = None) -> Dict[str, Any]:
    """PartitionSpecs for the pytree: the mixers' and the feed-forwards'
    matrices over fsdp and tp as the dense decoder's, the experts as
    ``moe_param_specs``' (the dropless block keeps its experts on one device:
    ``ep`` > 1 is refused, a share is one chip's), the small leaves, the
    decay's and ``expert_bias`` replicated."""
    from jax.sharding import PartitionSpec as P

    if mesh is not None:
        _refuse_dropless_ep(cfg, [a for a, n in mesh.shape.items() if n > 1])
    col, row, rep2, rep3 = (P(None, "fsdp", "tp"), P(None, "tp", "fsdp"),
                            P(None, None), P(None, None, None))
    mixer = {
        "kda": {"wq": col, "wk": col, "wv": col, "w_f": col, "conv_q": rep3,
                "conv_k": rep3, "conv_v": rep3, "A_log": rep2, "dt_bias": rep2,
                "w_beta": P(None, "fsdp", None), "o_norm": rep2,
                "w_g": P(None, "fsdp", None), "wo": row},
        "mla": {"wq": col, "w_kva": P(None, "fsdp", None), "kv_norm": rep2,
                "w_kvb": P(None, None, "tp"), "w_g": P(None, "fsdp", None), "wo": row}}
    ffn = {f: ffn_specs(ffn_leaves(cfg, f, shared=True)) for f in ("dense", "moe")}
    specs = {**spec_tree(cfg, lambda kind: {"norm": rep2, **mixer[kind[0]], "ffn_norm": rep2,
                                            **ffn[kind[1]]}), "lm_head": P("fsdp", "tp")}
    if cfg.n_moe_layers:
        specs["expert_bias"] = rep2
    return specs


register(LingConfig, LING_CONFIGS, lambda: ModelFns(
    ling_init, logged(ling_loss_and_stats, moe=(
        "aux_loss", "load_max_over_mean", "bias_moved_share", "held_pair_share", "overflow_pairs",
        "visited_row_share", "moved_row_share", "groups_hit_mean")), ling_param_specs, None, LING_FROZEN))
