"""Linear-attention / gated-attention decoder with a share of its routed
experts (Upstage's Solar Open 2 family, ``model_type`` ``solar_open2``) as a
twelfth kind of the one trainer's model: three layers in four mix the
sequence with KDA (``models/kda.py``, ``ops/kda.py``) in Kimi Linear's own
form, one in four (``gqa_layers``) with grouped-query softmax attention that
has NO positions and an output gate; every layer ends in ``num_experts``
sigmoid-routed experts chosen ``top_k`` a token under a frozen selection
bias, beside one shared expert.

Every layer: ``h = x + mixer(rmsnorm(x))``, then ``h + ffn(rmsnorm(h))``.

The ``kda`` mixer is ``models/kda.py``'s told what this family adds to it:
NO lower bound under the decay, ``g = -exp(A_log) softplus(.)`` in (-inf, 0)
(``decay_floor`` None: the kernel's general body); the decay's and the
gate's projections through pairs of rank ``kda_rank`` (``w_fa``/``w_fb``,
``w_ga``/``w_gb``: ``kda_use_full_proj`` false); the gate one value a
CHANNEL with a bias (``b_g``); ``beta = 2 sigmoid(.)`` in (0, 2)
(``kda_allow_neg_eigval``). Its two counters ride the stats:
``kda_decay_past_bound_share`` and ``kda_beta_over_one_share``.

The ``gqa`` mixer (``n_heads`` query heads over ``n_kv_heads`` of
``head_dim``; the order of the sequence comes from the KDA layers)::

    q, k, v = u @ wq, u @ wk, u @ wv            # no rotary turn, no q/k norm
    a       = causal_attention(q, k, v)         # 1 / sqrt(head_dim); the dispatcher's
    out     = (a * sigmoid(u @ w_g)) @ wo       # use_gqa_gate: one value a channel

The expert feed-forward is ``models/moe.py``'s dropless block as
``models/ling.py`` has it (sigmoid scores, the selection bias, the shared
expert, ``held_experts``: a SHARE of the experts) with one group and a
scaling of 1. ``expert_bias`` ([layers, num_experts] float32, a top-level
leaf) is state and not a parameter, as Ling's and for its reasons.

The vocabulary may be a slice (``vocab_size`` rows of embedding and of head);
the head is untied.

The parameters are one stack per RUN of like layers (an expert layer alone:
every layer here) and ``models/decoder.py`` scans the runs: this module is
the configuration, ``init``, the gated mixer, the layer body, the
PartitionSpecs and the counters, and declares them (``SOLAR``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.models.decoder import Decoder, init_tree, runs_of, spec_tree
from torchft_tpu.models.kda import kda_mixer
from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import _attention, _rmsnorm
from torchft_tpu.models.moe import (BIAS_INIT_SCALE, MoEConfig, _refuse_dropless_ep,
                                    expert_scalars, ffn_init, ffn_leaves, ffn_specs, moe_ffn)
from torchft_tpu.models.remat import ATTN_OUT_NAME

__all__ = [
    "SolarConfig",
    "SOLAR_CONFIGS",
    "SOLAR_FROZEN",
    "solar_init",
    "solar_hidden",
    "solar_forward",
    "solar_loss",
    "solar_loss_and_stats",
    "solar_param_specs",
]

# the top-level leaves that are state and not parameters
SOLAR_FROZEN = ("expert_bias",)
BETA_MAX = 2.0  # kda_allow_neg_eigval
KDA_COUNTERS = ("decay_past_bound_share", "beta_over_one_share")  # ``kda_mixer``'s
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SolarConfig(MoEConfig):
    # ``ffn_hidden`` (the published ``intermediate_size``) is read by no
    # layer: every layer ends in experts (``first_k_dense_replace`` 0)
    head_dim: int = 128  # the GQA layers' own: not dim // n_heads
    gqa_layers: Tuple[int, ...] = ()  # these layers (from 0) mix with GQA, the others with KDA
    kda_head_dim: int = 128  # d_k = d_v of a KDA head; n_heads of them
    kda_conv: int = 4  # taps of the short convolutions
    kda_rank: int = 128  # of the decay's and the gate's projection pairs
    # positions a block of the KDA mixer's norm and gate (``kda_mixer``'s
    # ``out_block``); 0: whole
    kda_out_block: int = 0
    norm_eps: float = 1e-5
    moe_intermediate_size: int = 1280  # one expert's width, the shared one's too
    num_experts: int = 320
    top_k: int = 8
    routed_scaling: float = 1.0
    capacity_factor: Optional[float] = None  # dropless
    aux_loss_weight: float = 0.0
    router_score: str = "sigmoid"
    gate_eps: float = 1e-20
    loss_chunk: int = 0  # as ``Lfm2Config.loss_chunk``

    def __post_init__(self) -> None:
        super().__post_init__()
        self._check_dropless_block()
        if any(not 0 <= i < self.n_layers for i in self.gqa_layers):
            raise ValueError(f"gqa_layers={self.gqa_layers}: layers of {self.n_layers}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(f"n_kv_heads={self.n_kv_heads}: whole groups of the "
                             f"{self.n_heads} query heads")

    def kinds(self) -> List[str]:
        """The mixer of every layer: "gqa" | "kda"."""
        return ["gqa" if i in self.gqa_layers else "kda" for i in range(self.n_layers)]

    def runs(self) -> List[Tuple[str, str, int]]:
        """Runs of like layers in order: every layer ends in experts, so each
        is a run of its own (``decoder.runs_of``)."""
        return runs_of(self.kinds(), name=lambda kind: f"{kind}_moe", merges=lambda kind: False)

    def num_params(self) -> int:
        """Every leaf this chip holds, ``expert_bias`` among them."""
        def size(leaves, shape_at):
            return sum(math.prod(leaf[shape_at]) for leaf in leaves.values())

        ffn = size(ffn_leaves(self, "moe", shared=True), 1) + self.num_experts
        return (sum(size(_mixer_leaves(self, kind), 0) + ffn + 2 * self.dim
                    for kind in self.kinds()) + 2 * self.vocab_size * self.dim + self.dim)


SOLAR_CONFIGS: Dict[str, SolarConfig] = {
    # one whole period, GQA then three KDA layers, each with a share of 16
    # experts; bf16 like the published one, so the float32 routers, decays
    # and bias sit among bf16 leaves in a trainer's bucket plan. The share
    # has room for every pair: a toy batch swings far from the even share.
    "solar_debug": SolarConfig(
        vocab_size=256, dim=64, n_layers=4, n_heads=4, n_kv_heads=2, ffn_hidden=128,
        max_seq_len=128, head_dim=16, gqa_layers=(0,), kda_head_dim=16, kda_rank=8,
        moe_intermediate_size=32, num_experts=16, top_k=4, held_experts=(4, 4),
        share_room=4.0,
    ),
    # upstage/Solar-Open2-250B, one chip's share of the first four published
    # layers (one whole period) in a deployment of 32 chips a layer: 10 of
    # the 320 experts, an eighth of the vocabulary, every head
    "solar_open2_250b_share": SolarConfig(
        vocab_size=24576, dim=4096, n_layers=4, n_heads=64, n_kv_heads=8, ffn_hidden=10240,
        max_seq_len=1048576, rope_theta=10000.0, gqa_layers=(0,), held_experts=(0, 10),
        share_room=3.0, loss_chunk=2048, kda_out_block=2048,
    ),
}


def _mixer_leaves(cfg: SolarConfig, kind: str
                  ) -> Dict[str, Tuple[Tuple[int, ...], Optional[int], Any]]:
    """A mixer's leaves: leaf -> (its shape without the layers' axis, its
    fan-in (None: not a normal matrix, :func:`solar_init` says what), its
    PartitionSpec with the layers' axis). One table for init, specs and the
    count."""
    from jax.sharding import PartitionSpec as P

    d, H = cfg.dim, cfg.n_heads
    col, row, down = P(None, "fsdp", "tp"), P(None, "tp", "fsdp"), P(None, "fsdp", None)
    up, rep2, rep3 = P(None, None, "tp"), P(None, None), P(None, None, None)
    if kind == "gqa":
        q, kv = H * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        return {"wq": ((d, q), d, col), "wk": ((d, kv), d, col), "wv": ((d, kv), d, col),
                "w_g": ((d, q), d, col), "wo": ((q, d), q, row)}
    kd, r, taps = H * cfg.kda_head_dim, cfg.kda_rank, (cfg.kda_conv, H * cfg.kda_head_dim)
    return {"wq": ((d, kd), d, col), "wk": ((d, kd), d, col), "wv": ((d, kd), d, col),
            "w_fa": ((d, r), d, down), "w_fb": ((r, kd), r, up),
            "conv_q": (taps, cfg.kda_conv, rep3), "conv_k": (taps, cfg.kda_conv, rep3),
            "conv_v": (taps, cfg.kda_conv, rep3), "A_log": ((H,), None, rep2),
            "dt_bias": ((kd,), None, rep2), "w_beta": ((d, H), d, down),
            "o_norm": ((cfg.kda_head_dim,), None, rep2), "w_ga": ((d, r), d, down),
            "w_gb": ((r, kd), r, up), "b_g": ((kd,), None, rep2), "wo": ((kd, d), kd, row)}


def solar_init(key: jax.Array, cfg: SolarConfig) -> Dict[str, Any]:
    """Parameter pytree: ``embed``, ``lm_head``, ``final_norm``, ``layers``
    (one stack a layer, :meth:`SolarConfig.runs`; the expert leaves ``[1,
    held, ...]``, the router ``[1, dim, num_experts]`` float32) and
    ``expert_bias`` [layers, num_experts] float32 (state: ``SOLAR_FROZEN``).
    Every matrix normal over the root of its fan-in; Kimi Linear's decay:
    ``A_log = log U(1, 16)`` a head and ``dt_bias`` the inverse softplus of a
    step log-uniform in [0.001, 0.1] a channel, both float32 (they sit in an
    exponent); ``b_g`` zeros, ``o_norm`` ones."""
    k_emb, k_head, k_bias, k_layers = jax.random.split(key, 4)
    d = cfg.dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, _F32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    def leaf(key, name, shape, fan_in):
        if fan_in is not None:
            return dense(key, shape, fan_in)
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, _F32, 1.0, 16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(key, shape, _F32, math.log(1e-3), math.log(0.1)))
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus(dt_bias) = dt
        return (jnp.zeros if name == "b_g" else jnp.ones)(shape, cfg.dtype)

    def run(key, kind, L):
        ks = jax.random.split(key, 24)
        mixer = {name: leaf(k, name, (L, *shape), fan_in)
                 for k, (name, (shape, fan_in, _)) in zip(ks, _mixer_leaves(cfg, kind).items())}
        return {"norm": jnp.ones((L, d), cfg.dtype), **mixer,
                "ffn_norm": jnp.ones((L, d), cfg.dtype),
                **ffn_init(ffn_leaves(cfg, "moe", shared=True), ks[16:], L, cfg.dtype)}

    return {**init_tree(k_emb, k_layers, cfg, run),
            "lm_head": dense(k_head, (d, cfg.vocab_size), d),
            "expert_bias": BIAS_INIT_SCALE * jax.random.normal(
                k_bias, (cfg.n_layers, cfg.num_experts), _F32)}


def _gqa_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: SolarConfig,
               attention: Any) -> jax.Array:
    """Causal grouped-query attention without positions (``use_rope``
    false), times an element-wise sigmoid gate from the layer's input
    (``use_gqa_gate``, arXiv:2505.06708), through the dispatcher every kind
    uses."""
    (B, S, _), hd = u.shape, cfg.head_dim
    with jax.named_scope("gqa/in_proj"):
        q = (u @ w["wq"]).reshape(B, S, cfg.n_heads, hd)
        k = (u @ w["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
        v = (u @ w["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    with jax.named_scope("gqa/attend"):
        a = jax.ad_checkpoint.checkpoint_name(
            attention(q, k, v, cfg), ATTN_OUT_NAME).reshape(B, S, cfg.n_heads * hd)
    with jax.named_scope("gqa/gate"):
        a = a * jax.nn.sigmoid(u @ w["w_g"])  # in the activations' dtype, as the KDA gate
    with jax.named_scope("gqa/out"):
        return a @ w["wo"]


def _layer_body(cfg: SolarConfig, kind: str, attention: Any):
    """The scanned body of a layer of ``kind``, as ``models/ling.py``'s:
    ``(h, (w, bias, replay)) -> (h, stats)``; a KDA layer's stats hold the
    mixer's two counters beside the expert block's."""

    def layer(h, xs):
        w, bias, replay = xs
        u = _rmsnorm(h, w["norm"], cfg.norm_eps)
        if kind == "gqa":
            mixed, counted = _gqa_mixer(u, w, cfg, attention), {}
        else:
            mixed, counted = kda_mixer(u, w, cfg, decay_floor=None, beta_max=BETA_MAX,
                                       counted=True, out_block=cfg.kda_out_block)
        h = h + mixed
        out, stats = moe_ffn(
            _rmsnorm(h, w["ffn_norm"], cfg.norm_eps), w["router"], w["w_gate"], w["w_up"],
            w["w_down"], cfg, routing=replay, bias=bias,
            shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))
        stats.pop("prob_sum")  # no auxiliary loss reads it
        return h + out, {**stats, **counted}

    return layer


def _bodies(cfg: SolarConfig, seq: int, attention_fn: Optional[Any]):
    return lambda kind: _layer_body(cfg, kind, attention_fn or _attention)


def _counters(stats: Dict[str, jax.Array], tokens: jax.Array, cfg: SolarConfig
              ) -> Dict[str, jax.Array]:
    """The layers' free routing with its margins (``routing`` [L,T,k],
    ``p_kth``, ``p_next`` [L,T]), ``moe.expert_scalars``' for a share under a
    bias in one group (``load_max_over_mean``, ``bias_moved_share``,
    ``held_pair_share``, ``overflow_pairs``, ``visited_row_share``,
    ``moved_row_share``) and the KDA layers' two, each the mean over them:
    ``decay_past_bound_share`` and ``beta_over_one_share`` (``models/kda.py``;
    a trainer logs them as ``kda_...``)."""
    stats = expert_scalars(stats, tokens.size * cfg.top_k)
    for k in KDA_COUNTERS:
        stats[k] = jnp.mean(stats[k])
    return stats


SOLAR = Decoder(_bodies, _counters, routed=lambda kind: True)
solar_hidden, solar_forward = SOLAR.hidden, SOLAR.forward
solar_loss_and_stats, solar_loss = SOLAR.loss_and_stats, SOLAR.loss


def solar_param_specs(cfg: SolarConfig, mesh: Optional[Any] = None) -> Dict[str, Any]:
    """PartitionSpecs for the pytree: the mixers' and the shared expert's
    matrices over fsdp and tp as the dense decoder's, the experts as
    ``moe_param_specs``' (the dropless block keeps its experts on one device:
    ``ep`` > 1 is refused, a share is one chip's), the small leaves, the
    decay's and ``expert_bias`` replicated."""
    from jax.sharding import PartitionSpec as P

    if mesh is not None:
        _refuse_dropless_ep(cfg, [a for a, n in mesh.shape.items() if n > 1])
    rep2 = P(None, None)
    ffn = ffn_specs(ffn_leaves(cfg, "moe", shared=True))
    return {**spec_tree(cfg, lambda kind: {
        "norm": rep2, **{name: leaf[2] for name, leaf in _mixer_leaves(cfg, kind).items()},
        "ffn_norm": rep2, **ffn}), "lm_head": P("fsdp", "tp"), "expert_bias": rep2}


register(SolarConfig, SOLAR_CONFIGS, lambda: ModelFns(
    solar_init, logged(solar_loss_and_stats, moe=(
        "aux_loss", "load_max_over_mean", "bias_moved_share", "held_pair_share", "overflow_pairs",
        "visited_row_share", "moved_row_share"), kda=KDA_COUNTERS),
    solar_param_specs, None, SOLAR_FROZEN))
