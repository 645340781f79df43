"""Short-convolution / attention decoder with routed experts (LiquidAI's
LFM2 mixture-of-experts family, ``model_type`` ``lfm2_moe``) as a fourth kind
of the one trainer's model: most layers mix the sequence with a doubly gated
short convolution, some (``layer_types``) with grouped-query attention whose
heads are normalised one by one; the first ``num_dense_layers`` end in a
SwiGLU, the others in ``num_experts`` routed experts of which a token uses
``top_k``.

Every layer: ``h = x + mixer(rmsnorm(x))``, then ``h + ffn(rmsnorm(h))``.

The ``conv`` mixer::

    B, C, X = split3(u @ in_proj)                       # no bias, [d, 3d]
    c[t]    = k0 * (B*X)[t-2] + k1 * (B*X)[t-1] + k2 * (B*X)[t]   # depthwise, causal
    out     = (C * c) @ out_proj

The ``full_attention`` mixer: q, k, v without bias; an RMSNorm over each
head's ``head_dim`` values of q and of k, each with one learned weight
([head_dim], shared by the heads); RoPE; causal attention at
``1 / sqrt(head_dim)`` through the dispatcher every kind uses
(``ops/attention.py``).

The expert feed-forward is ``models/moe.py``'s dropless block with this
family's routing: scores by sigmoid, the decision by ``scores +
expert_bias``, the gates the scores alone, renormalised over the chosen
(``+ 1e-6``). No shared expert and no auxiliary loss. The published
``routed_scaling_factor`` 1, ``use_expert_bias`` true and the tied head are
what this module computes and no options of it.

``expert_bias`` ([expert layers, num_experts] float32, a top-level leaf) is
STATE and not a parameter: the published code registers it as a buffer, no
gradient reaches it (``moe_ffn`` adds it to the decision alone, and a top-k
has no gradient) and no optimizer may touch it, weight decay included.
``model_fns`` names it under ``frozen``; how the published model moved it
while it was pre-trained is in no public file, and nothing here moves it.
It is initialised as ``0.01 * normal``, not zeros: a bias of zeros decides
nothing, and a check could not tell whether the selection reads it.

The layers are unlike, so the parameters are one stack per RUN of like
layers (``layers["00_conv_dense"]``, ``layers["01_attn_moe"]``,
``layers["02_conv_moe"]`` ...) and ``models/decoder.py`` scans the runs; an
EXPERT layer is always a run of its own (leaves ``[1, ...]``;
``decoder.runs_of`` says why). This module is the configuration, ``init``,
the two mixers, the layer body, the PartitionSpecs and the counters, and
declares them (``LFM2``). The head is tied: ``logits = h @ embed.T``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.models.decoder import Decoder, _causal_conv, init_tree, runs_of, spec_tree
from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import _attention, _rmsnorm, _rope
from torchft_tpu.models.moe import (BIAS_INIT_SCALE, MoEConfig, _refuse_dropless_ep,
                                    expert_scalars, ffn_init, ffn_leaves, ffn_specs, moe_ffn)
from torchft_tpu.models.remat import ATTN_OUT_NAME

__all__ = [
    "Lfm2Config",
    "LFM2_CONFIGS",
    "LFM2_FROZEN",
    "lfm2_init",
    "lfm2_hidden",
    "lfm2_forward",
    "lfm2_loss",
    "lfm2_loss_and_stats",
    "lfm2_param_specs",
]

# the top-level leaves that are state and not parameters
LFM2_FROZEN = ("expert_bias",)


# LFM2-8B-A1B's 24 layers: attention at 2, 6, 10, 14, 18 and 21
_PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2Config(MoEConfig):
    # ``ffn_hidden`` is the dense layers' SwiGLU width (``intermediate_size``)
    layer_types: Tuple[str, ...] = ()
    num_dense_layers: int = 2
    conv_L_cache: int = 3
    moe_intermediate_size: int = 1792  # one expert's width
    num_experts: int = 32
    top_k: int = 4
    capacity_factor: Optional[float] = None  # dropless
    aux_loss_weight: float = 0.0
    router_score: str = "sigmoid"
    gate_eps: float = 1e-6
    # the loss over sequence chunks of this length where it divides the
    # sequence (``JambaConfig.loss_chunk``: 8,192 x 65,536 float32 logits)
    loss_chunk: int = 0

    def __post_init__(self) -> None:
        super().__post_init__()
        self._check_layer_types(("conv", "full_attention"), self.num_dense_layers)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.num_dense_layers

    def kinds(self) -> List[Tuple[str, str]]:
        """(mixer, feed-forward) of every layer: ("conv" | "attn", "dense" |
        "moe")."""
        return [("attn" if t == "full_attention" else "conv",
                 "dense" if i < self.num_dense_layers else "moe")
                for i, t in enumerate(self.layer_types)]

    def runs(self) -> List[Tuple[str, Tuple[str, str], int]]:
        """Runs of like layers in order (``decoder.runs_of``): dense layers of
        one mixer run together, an expert layer runs alone."""
        return runs_of(self.kinds(), name="_".join, merges=lambda kind: kind[1] == "dense")

    def num_params(self) -> int:
        """Every leaf, ``expert_bias`` among them; the tied embedding once."""
        d, hd = self.dim, self.head_dim
        kv = self.n_kv_heads * hd
        mixer = {"conv": d * 3 * d + self.conv_L_cache * d + d * d,
                 "attn": 2 * d * d + 2 * d * kv + 2 * hd}
        E = self.num_experts
        ffn = {"dense": 3 * d * self.ffn_hidden,
               "moe": 3 * E * d * self.moe_intermediate_size + d * E + E}
        return (sum(mixer[m] + ffn[f] + 2 * d for m, f in self.kinds())
                + self.vocab_size * d + d)


LFM2_CONFIGS: Dict[str, Lfm2Config] = {
    # every kind of layer: a dense convolution layer, then attention and
    # convolution layers with experts, the attention layer twice; bf16 like
    # the published one, so the float32 routers and the float32 bias sit
    # among bf16 leaves in a trainer's bucket plan
    "lfm2_debug": Lfm2Config(
        vocab_size=256, dim=64, n_layers=6, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=128, rope_theta=1e6,
        layer_types=("conv", "full_attention", "conv", "conv", "full_attention",
                     "conv"),
        num_dense_layers=1, moe_intermediate_size=32, num_experts=8, top_k=2,
    ),
    # LiquidAI/LFM2-8B-A1B as published
    "lfm2_8b_a1b": Lfm2Config(
        vocab_size=65536, dim=2048, n_layers=24, n_heads=32, n_kv_heads=8,
        ffn_hidden=7168, max_seq_len=128000, rope_theta=1e6, norm_eps=1e-5,
        layer_types=_PUBLISHED_LAYER_TYPES, loss_chunk=2048,
    ),
}


def lfm2_init(key: jax.Array, cfg: Lfm2Config) -> Dict[str, Any]:
    """Parameter pytree: ``embed``, ``final_norm``, ``layers`` (one stack
    per run of like layers, :meth:`Lfm2Config.runs`) and, where there are
    expert layers, ``expert_bias`` [expert layers, E] float32 (state:
    ``LFM2_FROZEN``)."""
    k_emb, k_bias, k_layers = jax.random.split(key, 3)
    d, hd = cfg.dim, cfg.head_dim
    kvd = cfg.n_kv_heads * hd

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(cfg.dtype)

    def mixer(kind, keys, L):
        if kind == "conv":
            return {"in_proj": dense(keys[0], (L, d, 3 * d), d),
                    "conv_w": dense(keys[1], (L, cfg.conv_L_cache, d), cfg.conv_L_cache),
                    "out_proj": dense(keys[2], (L, d, d), d)}
        return {"wq": dense(keys[0], (L, d, cfg.n_heads * hd), d),
                "wk": dense(keys[1], (L, d, kvd), d),
                "wv": dense(keys[2], (L, d, kvd), d),
                "wo": dense(keys[3], (L, cfg.n_heads * hd, d), cfg.n_heads * hd),
                "q_norm": jnp.ones((L, hd), cfg.dtype),
                "k_norm": jnp.ones((L, hd), cfg.dtype)}

    def run(key, kind, L):
        ks = jax.random.split(key, 8)
        return {"norm": jnp.ones((L, d), cfg.dtype), **mixer(kind[0], ks[:4], L),
                "ffn_norm": jnp.ones((L, d), cfg.dtype),
                **ffn_init(ffn_leaves(cfg, kind[1]), ks[4:], L, cfg.dtype)}

    params = init_tree(k_emb, k_layers, cfg, run)
    if cfg.n_moe_layers:
        params["expert_bias"] = BIAS_INIT_SCALE * jax.random.normal(
            k_bias, (cfg.n_moe_layers, cfg.num_experts), jnp.float32)
    return params


def _conv_mixer(u: jax.Array, w: Dict[str, jax.Array]) -> jax.Array:
    with jax.named_scope("conv/in_proj"):
        b, c, x = jnp.split(u @ w["in_proj"], 3, axis=-1)
        bx = b * x
    with jax.named_scope("conv/conv"):
        y = c * _causal_conv(bx, w["conv_w"], None, activation=None)
    with jax.named_scope("conv/out_proj"):
        return y @ w["out_proj"]


def _attn_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: Lfm2Config,
                attention: Any) -> jax.Array:
    B, S = u.shape[0], u.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q = (u @ w["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (u @ w["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (u @ w["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    # a head at a time: the norm is over the head's own values
    q = _rope(_rmsnorm(q, w["q_norm"], cfg.norm_eps), cfg.rope_theta, positions)
    k = _rope(_rmsnorm(k, w["k_norm"], cfg.norm_eps), cfg.rope_theta, positions)
    attn = jax.ad_checkpoint.checkpoint_name(
        attention(q, k, v, cfg), ATTN_OUT_NAME
    ).reshape(B, S, cfg.n_heads * cfg.head_dim)
    return attn @ w["wo"]


def _layer_body(cfg: Lfm2Config, kind: Tuple[str, str], attention: Any):
    """The scanned body of a run of ``kind``: ``(h, (w, bias, replay)) ->
    (h, stats)``; ``bias`` [E] and ``replay`` [T,k] are None rows for a run
    of dense layers, ``replay`` in a free run too; ``stats`` is ``moe_ffn``'s for
    an expert layer, None for a dense one."""
    mixer, ffn = kind

    def layer(h, xs):
        w, bias, replay = xs
        u = _rmsnorm(h, w["norm"], cfg.norm_eps)
        if mixer == "conv":
            h = h + _conv_mixer(u, w)
        else:
            with jax.named_scope("attn/mixer"):
                h = h + _attn_mixer(u, w, cfg, attention)
        x = _rmsnorm(h, w["ffn_norm"], cfg.norm_eps)
        if ffn == "dense":
            return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"], None
        out, stats = moe_ffn(x, w["router"], w["w_gate"], w["w_up"], w["w_down"],
                             cfg, routing=replay, bias=bias)
        stats.pop("prob_sum")  # no auxiliary loss reads it
        return h + out, stats

    return layer


def _bodies(cfg: Lfm2Config, seq: int, attention_fn: Optional[Any]):
    return lambda kind: _layer_body(cfg, kind, attention_fn or _attention)


def _counters(stats: Dict[str, jax.Array], tokens: jax.Array, cfg: Lfm2Config
              ) -> Dict[str, jax.Array]:
    """The expert layers' free routing with its margins (``routing``
    [L,T,k], ``p_kth``, ``p_next`` [L,T]) and ``moe.expert_scalars``' two for
    this family: ``load_max_over_mean`` and ``bias_moved_share``."""
    return expert_scalars(stats, tokens.size * cfg.top_k, mean_floor=None)


LFM2 = Decoder(_bodies, _counters, routed=lambda kind: kind[1] == "moe")
lfm2_hidden, lfm2_forward = LFM2.hidden, LFM2.forward
lfm2_loss_and_stats, lfm2_loss = LFM2.loss_and_stats, LFM2.loss


def lfm2_param_specs(cfg: Lfm2Config, mesh: Optional[Any] = None) -> Dict[str, Any]:
    """PartitionSpecs for the pytree. Attention and the dense feed-forward as
    the dense decoder's (fsdp and tp), the experts as ``moe_param_specs``'
    (the dropless block keeps every expert on one device: ``ep`` > 1 is
    refused there), a convolution mixer's matrices over fsdp alone, the small
    leaves and ``expert_bias`` replicated."""
    from jax.sharding import PartitionSpec as P

    if mesh is not None:
        _refuse_dropless_ep(cfg, [a for a, n in mesh.shape.items() if n > 1])
    mixer = {
        "conv": {"in_proj": P(None, "fsdp", None), "conv_w": P(None, None, None),
                 "out_proj": P(None, "fsdp", None)},
        "attn": {"wq": P(None, "fsdp", "tp"), "wk": P(None, "fsdp", "tp"),
                 "wv": P(None, "fsdp", "tp"), "wo": P(None, "tp", "fsdp"),
                 "q_norm": P(None, None), "k_norm": P(None, None)}}
    ffn = {f: ffn_specs(ffn_leaves(cfg, f)) for f in ("dense", "moe")}
    specs = spec_tree(cfg, lambda kind: {"norm": P(None, None), **mixer[kind[0]],
                                         "ffn_norm": P(None, None), **ffn[kind[1]]})
    if cfg.n_moe_layers:
        specs["expert_bias"] = P(None, None)
    return specs


register(Lfm2Config, LFM2_CONFIGS, lambda: ModelFns(
    lfm2_init, logged(lfm2_loss_and_stats,
                      moe=("aux_loss", "load_max_over_mean", "bias_moved_share")),
    lfm2_param_specs, None, LFM2_FROZEN))
