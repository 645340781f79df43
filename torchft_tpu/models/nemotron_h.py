"""Mamba-2 / attention / expert decoder whose every layer is ONE residual
branch (NVIDIA's Nemotron-H family, ``model_type`` ``nemotron_h``) as a
seventh run-kind of the one trainer's model: ``hybrid_override_pattern``
spells the stack out a character a layer, ``M`` a Mamba-2 mixer, ``E`` an
expert block, ``*`` grouped-query attention, and a layer is
``h + branch(rmsnorm(h))`` and nothing else: never a mixer followed by a
feed-forward. ``-`` (a dense feed-forward alone) is refused: no published
pattern this module was written against has one.

``M``, Mamba-2 with ``H`` heads of ``P`` channels (``d_inner = H * P``, NOT
``expand * dim``), ``G`` groups, state ``N``::

    z, xBC, dt = split(u @ in_proj)                  # d_inner, d_inner + 2GN, H
    xBC        = silu(causal_depthwise_conv(xBC) + conv_b)
    x, B, C    = split(xBC)                          # d_inner, G*N, G*N
    dt         = softplus(dt + dt_bias)              # [H], float32
    y          = ssd(x, dt, -exp(A_log), B, C) + D * x     # ops/ssd.py
    out        = (rmsnorm_groups(y * silu(z)) * w) @ out_proj

the gate BEFORE the norm, the mean of squares over each of the ``G`` groups
of ``d_inner / G`` channels, one learned weight of ``d_inner``. ``dt``'s
columns of ``in_proj`` are multiplied once more with a float32 result: the
step size sits in an exponent, and the bf16 rounding of a pre-activation
near -5 would be 2% of it (``models/jamba.py`` keeps its ``dt`` so).

``*``: GQA without any positions (the Mamba layers carry the order), the
dispatcher every kind uses. ``E``: ``models/moe.py``'s dropless block told
what this family's is: sigmoid scores, the selection on scores + a frozen
correction bias, gates the unbiased scores over their sum times
``routed_scaling``, experts UNGATED (``down(relu(up(x))^2)``,
``expert_act="relu2"``) beside one shared expert of that form
``shared_intermediate_size`` wide, and, where ``held_experts`` says so, this
chip's SHARE of the experts. ``expert_bias`` ([E layers, num_experts]
float32) is state and not a parameter, as ``models/ling.py``'s.

The vocabulary may be a slice; the head is untied. The parameters are one
stack per RUN of like layers (``00_mamba``, ``01_moe``, ...: an expert layer
alone) and ``models/decoder.py`` scans the runs: this module is the
configuration, ``init``, the three bodies, the PartitionSpecs and the
counters, and declares them (``NEMOTRON_H``). Initialisation is Mamba-2's:
``dt_bias = softplus^-1(dt0)``, ``dt0`` log-uniform in [``time_step_min``,
``time_step_max``] and at least ``time_step_floor``; ``A_log =
log(uniform(1, 16))`` a head; ``D = 1``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.models.decoder import Decoder, _causal_conv, init_tree, runs_of, spec_tree
from torchft_tpu.models.kinds import ModelFns, register
from torchft_tpu.models.llama import _attention, _rmsnorm
from torchft_tpu.models.moe import (BIAS_INIT_SCALE, MoEConfig, _refuse_dropless_ep,
                                    expert_scalars, ffn_init, ffn_leaves, ffn_specs, moe_ffn)
from torchft_tpu.models.remat import ATTN_OUT_NAME
from torchft_tpu.ops.ssd import CHUNK, ssd

__all__ = [
    "NemotronHConfig",
    "NEMOTRON_H_CONFIGS",
    "NEMOTRON_H_FROZEN",
    "nemotron_h_init",
    "nemotron_h_hidden",
    "nemotron_h_forward",
    "nemotron_h_loss",
    "nemotron_h_loss_and_stats",
    "nemotron_h_param_specs",
]

# the top-level leaves that are state and not parameters
NEMOTRON_H_FROZEN = ("expert_bias",)
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}
_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(MoEConfig):
    # ``ffn_hidden`` is the dense ``-`` layer's width and unread (the pattern
    # may have none); ``rope_theta`` is unread: no layer turns anything
    pattern: str = ""  # ``hybrid_override_pattern``: M | E | * a layer
    head_dim: int = 128  # the configuration's own: not dim // n_heads
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    mamba_n_groups: int = 8  # groups of heads that share B and C (``n_groups``)
    conv_kernel: int = 4
    use_conv_bias: bool = True
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    moe_intermediate_size: int = 1856  # one routed expert's width
    shared_intermediate_size: Optional[int] = 3712
    expert_act: str = "relu2"
    num_experts: int = 128
    top_k: int = 6
    routed_scaling: float = 2.5
    capacity_factor: Optional[float] = None  # dropless
    aux_loss_weight: float = 0.0
    router_score: str = "sigmoid"
    gate_eps: float = 1e-20
    norm_eps: float = 1e-5
    loss_chunk: int = 0  # as ``Lfm2Config.loss_chunk``

    def __post_init__(self) -> None:
        super().__post_init__()
        if len(self.pattern) != self.n_layers:
            raise ValueError(f"pattern names {len(self.pattern)} layers, "
                             f"n_layers is {self.n_layers}")
        other = sorted(set(self.pattern) - set(KINDS))
        if other:
            raise ValueError(f"pattern {other}: models/nemotron_h.py builds M (Mamba-2), "
                             "E (experts) and * (attention); '-' is a dense "
                             "feed-forward alone, which it has not")
        if not self.use_conv_bias:
            raise ValueError("use_conv_bias=False: the convolution has a bias here")
        self._check_dropless_block()
        if self.mamba_num_heads % self.mamba_n_groups:
            raise ValueError(f"mamba_n_groups={self.mamba_n_groups} of "
                             f"{self.mamba_num_heads} heads")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the convolution mixes: x, B and C."""
        return self.d_inner + 2 * self.mamba_n_groups * self.ssm_state_size

    @property
    def n_moe_layers(self) -> int:
        return self.pattern.count("E")

    def kinds(self) -> List[str]:
        """"mamba" | "moe" | "attn" of every layer."""
        return [KINDS[c] for c in self.pattern]

    def runs(self) -> List[Tuple[str, str, int]]:
        """Runs of like layers in order (names that sort in layer order); an
        expert layer runs alone, as :meth:`Lfm2Config.runs`."""
        return runs_of(self.kinds(), merges=lambda kind: kind != "moe")

    def num_params(self) -> int:
        """Every leaf this chip holds, ``expert_bias`` (a buffer: state, no
        parameter) among them."""
        d, di, H = self.dim, self.d_inner, self.mamba_num_heads
        q, kv = self.n_heads * self.head_dim, self.n_kv_heads * self.head_dim
        W = self.moe_intermediate_size
        S = self.shared_intermediate_size or W
        m = self.expert_matrices
        one = {"mamba": d * (di + self.conv_dim + H) + (self.conv_kernel + 1) * self.conv_dim
                        + 3 * H + di + di * d,
               "attn": 2 * d * q + 2 * d * kv,
               "moe": d * self.num_experts + self.num_experts
                      + m * d * (self.n_held * W + S)}
        return (sum(one[k] + d for k in self.kinds()) + 2 * self.vocab_size * d + d)


NEMOTRON_H_CONFIGS: Dict[str, NemotronHConfig] = {
    # every kind of layer, an expert layer first after a mixer and two in a
    # row never; two heads a group, a share of 16 experts; bf16 like the
    # published one, so the float32 routers, A_log, D, dt_bias and the bias
    # sit among bf16 leaves in a trainer's bucket plan. The share has room
    # for every pair: a toy batch swings far from the even share.
    "nemotron_h_debug": NemotronHConfig(
        vocab_size=256, dim=64, n_layers=6, n_heads=4, n_kv_heads=2, head_dim=16,
        ffn_hidden=64, max_seq_len=256, pattern="MEM*EM",
        mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16, mamba_n_groups=2,
        moe_intermediate_size=32, shared_intermediate_size=64, num_experts=16, top_k=4,
        held_experts=(4, 4), share_room=4.0,
    ),
    # nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, one chip's share of the
    # first thirteen published layers in a deployment of sixteen chips a
    # layer: MEMEM* and one whole unit EMEMEM*, 8 of the 128 experts, an
    # eighth of the vocabulary
    "nemotron_3_nano_30b_a3b_share": NemotronHConfig(
        vocab_size=16384, dim=2688, n_layers=13, n_heads=32, n_kv_heads=2,
        ffn_hidden=1856, max_seq_len=262144, pattern="MEMEM*EMEMEM*",
        held_experts=(0, 8), share_room=4.0, loss_chunk=2048,
    ),
}


def nemotron_h_init(key: jax.Array, cfg: NemotronHConfig) -> Dict[str, Any]:
    """Parameter pytree: ``embed``, ``lm_head``, ``final_norm``, ``layers``
    (one stack per run of like layers, :meth:`NemotronHConfig.runs`; the
    expert leaves ``[1, held, ...]``, the router ``[1, dim, num_experts]``)
    and, where there are expert layers, ``expert_bias`` [expert layers,
    num_experts] float32 (state: ``NEMOTRON_H_FROZEN``)."""
    k_emb, k_head, k_bias, k_layers = jax.random.split(key, 4)
    d, di, H = cfg.dim, cfg.d_inner, cfg.mamba_num_heads
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, _F32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    def run(key, kind, L):
        ks = jax.random.split(key, 8)
        norm = {"norm": jnp.ones((L, d), cfg.dtype)}
        if kind == "mamba":
            dt0 = jnp.maximum(jnp.exp(jax.random.uniform(
                ks[2], (L, H), _F32, jnp.log(cfg.time_step_min), jnp.log(cfg.time_step_max))),
                cfg.time_step_floor)
            return {**norm,
                    "in_proj": dense(ks[0], (L, d, di + cfg.conv_dim + H), d),
                    "conv_w": dense(ks[1], (L, cfg.conv_kernel, cfg.conv_dim), cfg.conv_kernel),
                    "conv_b": jnp.zeros((L, cfg.conv_dim), cfg.dtype),
                    # the decay's own leaves in float32: they sit in an exponent;
                    # softplus^-1(dt0) = dt0 + log(1 - exp(-dt0))
                    "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
                    "A_log": jnp.log(jax.random.uniform(ks[3], (L, H), _F32, 1.0, 16.0)),
                    "D": jnp.ones((L, H), _F32),
                    "gate_norm": jnp.ones((L, di), cfg.dtype),
                    "out_proj": dense(ks[4], (L, di, d), di)}
        if kind == "attn":
            return {**norm, "wq": dense(ks[0], (L, d, q), d), "wk": dense(ks[1], (L, d, kv), d),
                    "wv": dense(ks[2], (L, d, kv), d), "wo": dense(ks[3], (L, q, d), q)}
        return {**norm, **ffn_init(ffn_leaves(cfg, "moe", shared=True), ks, L, cfg.dtype)}

    params = {**init_tree(k_emb, k_layers, cfg, run),
              "lm_head": dense(k_head, (d, cfg.vocab_size), d)}
    if cfg.n_moe_layers:
        params["expert_bias"] = BIAS_INIT_SCALE * jax.random.normal(
            k_bias, (cfg.n_moe_layers, cfg.num_experts), _F32)
    return params


def _gated_norm(y: jax.Array, z: jax.Array, w: jax.Array, cfg: NemotronHConfig) -> jax.Array:
    """``rmsnorm_groups(y * silu(z)) * w``: the gate first, then the mean of
    squares over each of the ``mamba_n_groups`` groups of channels, float32."""
    B, S, di = y.shape
    g = (y.astype(_F32) * jax.nn.silu(z.astype(_F32))).reshape(B, S, cfg.mamba_n_groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
    return (g.reshape(B, S, di) * w.astype(_F32)).astype(y.dtype)


def _mamba_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: NemotronHConfig
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    (B, S, _), H, P = u.shape, cfg.mamba_num_heads, cfg.mamba_head_dim
    G, N, di = cfg.mamba_n_groups, cfg.ssm_state_size, cfg.d_inner
    with jax.named_scope("ssd/in_proj"):
        zx = u @ w["in_proj"]
        z, xbc = zx[..., :di], zx[..., di:di + cfg.conv_dim]
        # the step size's pre-activation leaves its product in float32
        dt = jnp.matmul(u, w["in_proj"][:, di + cfg.conv_dim:], preferred_element_type=_F32)
    with jax.named_scope("ssd/conv"):
        xbc = _causal_conv(xbc, w["conv_w"], w["conv_b"])
    with jax.named_scope("ssd/scan"):
        x = xbc[..., :di].reshape(B, S, H, P)
        bm = xbc[..., di:di + G * N].reshape(B, S, G, N)
        cm = xbc[..., di + G * N:].reshape(B, S, G, N)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        a = -jnp.exp(w["A_log"])
        y = ssd(x, dt, a, bm, cm)
        y = (y.astype(_F32) + w["D"][:, None] * x.astype(_F32)).astype(u.dtype)
    with jax.named_scope("ssd/norm"):
        y = _gated_norm(y.reshape(B, S, di), z, w["gate_norm"], cfg)
    with jax.named_scope("ssd/out_proj"):
        out = y @ w["out_proj"]
    # a chunk's summed log-decay, the most negative over heads and chunks:
    # how near exp() inside the kernel came to underflow (float32: -87)
    log_decay = jnp.pad(dt * a, ((0, 0), (0, -S % CHUNK), (0, 0)))
    stats = {"dt_mean": jnp.mean(dt),
             "chunk_log_decay_min": jnp.min(jnp.sum(
                 log_decay.reshape(B, -1, CHUNK, H), axis=2))}
    return out, jax.lax.stop_gradient(stats)


def _bodies(cfg: NemotronHConfig, seq: int, attention_fn: Optional[Any]):
    """``(h, (w, bias, replay)) -> (h, stats)`` of each kind: a Mamba layer's
    stats are its mixer's, an expert layer's ``moe_ffn``'s, an attention
    layer has none."""
    attention = attention_fn or _attention

    def mamba_layer(h, xs):
        w = xs[0]
        out, stats = _mamba_mixer(_rmsnorm(h, w["norm"], cfg.norm_eps), w, cfg)
        return h + out, stats

    def attention_layer(h, xs):
        w = xs[0]
        B, S = h.shape[0], h.shape[1]
        with jax.named_scope("attn/mixer"):
            x = _rmsnorm(h, w["norm"], cfg.norm_eps)
            # no rotary or other positions: the Mamba layers carry the order
            q = (x @ w["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
            k = (x @ w["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
            v = (x @ w["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
            attn = jax.ad_checkpoint.checkpoint_name(
                attention(q, k, v, cfg), ATTN_OUT_NAME
            ).reshape(B, S, cfg.n_heads * cfg.head_dim)
            return h + attn @ w["wo"], None

    def expert_layer(h, xs):
        w, bias, replay = xs
        out, stats = moe_ffn(
            _rmsnorm(h, w["norm"], cfg.norm_eps), w["router"], None, w["w_up"], w["w_down"],
            cfg, routing=replay, bias=bias, shared=(None, w["shared_up"], w["shared_down"]))
        stats.pop("prob_sum")  # no auxiliary loss reads it
        return h + out, stats

    return {"mamba": mamba_layer, "attn": attention_layer, "moe": expert_layer}.__getitem__


def _counters(stats: Dict[str, jax.Array], tokens: jax.Array, cfg: NemotronHConfig
              ) -> Dict[str, jax.Array]:
    """The expert layers' free routing with its margins (``routing``
    [L,T,k], ``p_kth``, ``p_next`` [L,T]) and ``moe.expert_scalars``' six for
    this family (``load_max_over_mean``, ``bias_moved_share``,
    ``held_pair_share``, ``overflow_pairs``, ``visited_row_share``,
    ``moved_row_share``); what
    ran (``ssd_layers``, ``attn_layers``, ``moe_layers``); and of the Mamba
    layers ``ssd_dt_mean`` (the mean step size: its inverse over -a is the
    state's memory in positions) and ``ssd_chunk_log_decay_min`` (the most
    negative summed log-decay over one chunk)."""
    mamba = {k: stats.pop(k) for k in ("dt_mean", "chunk_log_decay_min") if k in stats}
    out = expert_scalars(stats, tokens.size * cfg.top_k)
    for name, kind in (("ssd", "mamba"), ("attn", "attn"), ("moe", "moe")):
        out[f"{name}_layers"] = jnp.asarray(cfg.kinds().count(kind), _F32)
    if mamba:
        out["ssd_dt_mean"] = jnp.mean(mamba["dt_mean"])
        out["ssd_chunk_log_decay_min"] = jnp.min(mamba["chunk_log_decay_min"])
    return out


NEMOTRON_H = Decoder(_bodies, _counters, routed=lambda kind: kind == "moe")
nemotron_h_hidden, nemotron_h_forward = NEMOTRON_H.hidden, NEMOTRON_H.forward
nemotron_h_loss_and_stats, nemotron_h_loss = NEMOTRON_H.loss_and_stats, NEMOTRON_H.loss

_LOGGED_MOE = ("aux_loss", "load_max_over_mean", "bias_moved_share", "held_pair_share",
               "overflow_pairs", "visited_row_share", "moved_row_share")
_LOGGED_OWN = ("ssd_layers", "attn_layers", "moe_layers", "ssd_dt_mean",
               "ssd_chunk_log_decay_min")


def _logged_loss(*args: Any, **kw: Any) -> Tuple[jax.Array, Dict[str, Dict[str, jax.Array]]]:
    """The loss with what a trainer logs, under the names it logs it by
    (``kinds.logged`` prefixes a group's name; the counters of what ran have
    theirs already)."""
    value, stats = nemotron_h_loss_and_stats(*args, **kw)
    return value, {"moe_stats": {f"moe_{k}": stats[k] for k in _LOGGED_MOE if k in stats},
                   "ssd_stats": {k: stats[k] for k in _LOGGED_OWN if k in stats}}


def nemotron_h_param_specs(cfg: NemotronHConfig, mesh: Optional[Any] = None) -> Dict[str, Any]:
    """PartitionSpecs for the pytree: a Mamba mixer's matrices over fsdp
    alone, its channels whole on every device (the kernel owns every head of
    a group), attention as the dense decoder's, the experts as
    ``moe_param_specs``' (the dropless block keeps its experts on one device:
    ``ep`` > 1 is refused, a share is one chip's), the small leaves, the
    decay's and ``expert_bias`` replicated."""
    from jax.sharding import PartitionSpec as P

    if mesh is not None:
        _refuse_dropless_ep(cfg, [a for a, n in mesh.shape.items() if n > 1])
    col, row, rep2, rep3 = (P(None, "fsdp", "tp"), P(None, "tp", "fsdp"),
                            P(None, None), P(None, None, None))
    run = {"mamba": {"in_proj": P(None, "fsdp", None), "conv_w": rep3, "conv_b": rep2,
                     "dt_bias": rep2, "A_log": rep2, "D": rep2, "gate_norm": rep2,
                     "out_proj": P(None, "fsdp", None)},
           "attn": {"wq": col, "wk": col, "wv": col, "wo": row},
           "moe": ffn_specs(ffn_leaves(cfg, "moe", shared=True))}
    specs = {**spec_tree(cfg, lambda kind: {"norm": rep2, **run[kind]}),
             "lm_head": P("fsdp", "tp")}
    if cfg.n_moe_layers:
        specs["expert_bias"] = rep2
    return specs


register(NemotronHConfig, NEMOTRON_H_CONFIGS, lambda: ModelFns(
    nemotron_h_init, _logged_loss, nemotron_h_param_specs, None, NEMOTRON_H_FROZEN))
