"""Window / full-attention decoder with softmax-routed experts (JetBrains'
Mellum 2 family, ``model_type`` ``mellum``; the key set is Qwen3-MoE's) as a
sixth kind of the one trainer's model: every layer is GQA attention round a
mixture of experts, and the layers differ by a STATIC property alone, their
``layer_types``: a ``window`` layer sees each query's last ``window``
positions and turns queries and keys by the plain rotary table, a ``full``
layer sees every earlier position and turns them by YaRN's table. The
leaves of the two kinds have the same shapes.

Every layer, pre-norm: ``h = x + attn(rmsnorm(x))``, then ``h +
experts(rmsnorm(h))``::

    q, k, v = u @ wq, u @ wk, u @ wv              # n_heads / n_kv_heads of head_dim
    q, k    = rmsnorm_head(q), rmsnorm_head(k)    # one learned weight of head_dim each
    q, k    = rotary(q, table), rotary(k, table)  # halves rotated; the layer's kind's table
    attn    = causal_attention(q, k, v, window=window | None) @ wo

``head_dim`` is the configuration's own (32 heads of 128 beside a hidden size
of 2,304: the heads do not multiply out to it). The two rotary tables are
made once a step (:func:`rope_tables`) and handed to every layer of their
kind. YaRN (:func:`yarn_inv_freq`): the rotary pairs that turn more than
``beta_fast`` times over the original context keep their frequency, those
that turn less than ``beta_slow`` times have it divided by ``factor``, the
ones between are blended linearly; cos and sin are multiplied by
``attention_factor``, so a full layer's scores carry its square.

The expert feed-forward is ``models/moe.py``'s dropless block as it stands:
softmax over all ``num_experts``, ``top_k`` a token, the gates renormalised
over the chosen, no bias, no shared expert, no auxiliary loss; where
``held_experts`` says so this chip holds a SHARE of the experts (``moe_ffn``).
The vocabulary may be a slice too; the head is untied. The family's
multi-token-prediction head has no key in the published configuration and
is not built.

The parameters are one stack per RUN of like layers (``00_window`` [3, ...],
``01_full`` [1, ...], ...) and ``models/decoder.py`` scans the runs: this
module is the configuration, the two rotary tables, ``init``, the layer
body, the PartitionSpecs and the counters, and declares them (``MELLUM``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.models.decoder import Decoder, init_tree, runs_of
from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import _attention, _rmsnorm
from torchft_tpu.models.moe import MoEConfig, expert_scalars, moe_ffn, moe_param_specs
from torchft_tpu.models.remat import ATTN_OUT_NAME
from torchft_tpu.ops.attention import window_block_share

__all__ = [
    "MellumConfig",
    "MELLUM_CONFIGS",
    "mellum_init",
    "mellum_hidden",
    "mellum_forward",
    "mellum_loss",
    "mellum_loss_and_stats",
    "mellum_param_specs",
    "yarn_inv_freq",
    "rope_tables",
]

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class MellumConfig(MoEConfig):
    # ``ffn_hidden`` is one expert's width (``moe_intermediate_size``), as
    # ``MoEConfig``'s; ``rope_theta`` is both tables' base
    layer_types: Tuple[str, ...] = ()  # "window" | "full"
    window: int = 1024  # keys a window layer's query sees, its own among them
    head_dim: int = 128  # the configuration's own: not dim // n_heads
    # YaRN, the full layers' table
    yarn_factor: float = 16.0
    yarn_original_max: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782  # 0.1 ln(factor) + 1
    num_experts: int = 64
    top_k: int = 8
    capacity_factor: Optional[float] = None  # dropless
    aux_loss_weight: float = 0.0
    loss_chunk: int = 0  # as ``Lfm2Config.loss_chunk``

    def __post_init__(self) -> None:
        super().__post_init__()
        self._check_layer_types(("window", "full"))
        if self.window < 1 or self.head_dim % 2:
            raise ValueError(f"window={self.window}, head_dim={self.head_dim}")

    def runs(self) -> List[Tuple[str, str, int]]:
        """The runs of like layers in order: (the name of the run's stack,
        its kind, layers); neighbours of a kind merge."""
        return runs_of(self.layer_types)

    def num_params(self) -> int:
        """Every leaf this chip holds."""
        d, hd = self.dim, self.head_dim
        q, kv = self.n_heads * hd, self.n_kv_heads * hd
        per_layer = (2 * d * q + 2 * d * kv + 2 * hd + 2 * d + d * self.num_experts
                     + 3 * self.n_held * d * self.ffn_hidden)
        return self.n_layers * per_layer + 2 * self.vocab_size * d + d


MELLUM_CONFIGS: Dict[str, MellumConfig] = {
    # both kinds of layer twice over, heads that do not multiply out to the
    # hidden size, a window shorter than the tests' sequences, a share of 16
    # experts; bf16 like the published one, so the float32 routers sit among
    # bf16 leaves in a trainer's bucket plan. The share has room for every
    # pair: a toy batch swings far from the even share.
    "mellum_debug": MellumConfig(
        vocab_size=256, dim=48, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16,
        ffn_hidden=32, max_seq_len=128, rope_theta=5e5, norm_eps=1e-6,
        layer_types=("window", "full", "window", "full"), window=8,
        num_experts=16, top_k=4, held_experts=(4, 4),
        share_room=4.0,
    ),
    # JetBrains/Mellum2-12B-A2.5B-Instruct, one chip's share of the first
    # eight published layers in a deployment of four chips a layer: two
    # periods window window window full, 16 of the 64 experts, a quarter of
    # the vocabulary
    "mellum2_12b_a2_5b_share": MellumConfig(
        vocab_size=24576, dim=2304, n_layers=8, n_heads=32, n_kv_heads=4,
        ffn_hidden=896, max_seq_len=131072, rope_theta=5e5, norm_eps=1e-6,
        layer_types=("window", "window", "window", "full") * 2,
        held_experts=(0, 16), share_room=2.0, loss_chunk=2048,
    ),
}


def _plain_inv_freq(cfg: Any, dim: Optional[int] = None) -> jax.Array:
    """The plain rotary frequencies ``theta^(-2i / hd)`` [hd / 2], ``hd`` the
    rotary width: ``dim``, or the configuration's ``head_dim``."""
    hd = dim or cfg.head_dim
    return 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=_F32) / hd))


def yarn_inv_freq(cfg: Any, dim: Optional[int] = None) -> jax.Array:
    """YaRN's rotary frequencies [hd / 2], float32 (the full layers' here;
    ``models/deepseek.py``'s over its ``dim`` = 64 rotary dimensions, from
    the same ``yarn_*`` fields): pair ``i`` of the plain table ``theta^(-2i
    / hd)`` kept below ``low``, divided by ``yarn_factor`` above ``high``,
    blended between, where ``low`` and ``high`` are the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over ``yarn_original_max``
    positions."""
    hd = dim or cfg.head_dim

    def pair_of(turns: float) -> float:
        return (hd * math.log(cfg.yarn_original_max / (turns * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(pair_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(pair_of(cfg.yarn_beta_slow)), hd - 1)
    plain = _plain_inv_freq(cfg, hd)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=_F32) - low) / max(high - low, 1e-3), 0, 1)
    return (1 - ramp) * plain + ramp * plain / cfg.yarn_factor


def rope_tables(cfg: MellumConfig, seq: int) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """(cos, sin) [seq, head_dim / 2] float32 of each kind of layer: the
    window layers' plain, the full layers' YaRN's times its attention
    factor. Made once a step."""
    at = jnp.arange(seq, dtype=_F32)[:, None]
    plain, yarn = at * _plain_inv_freq(cfg), at * yarn_inv_freq(cfg)
    scale = jnp.asarray(cfg.yarn_attention_factor, _F32)
    return {"window": (jnp.cos(plain), jnp.sin(plain)),
            "full": (jnp.cos(yarn) * scale, jnp.sin(yarn) * scale)}


def _rotate(x: jax.Array, table: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """x [B, S, H, hd] turned by (cos, sin) [S, hd / 2], halves rotated."""
    cos, sin = (t[None, :, None, :] for t in table)
    x1, x2 = jnp.split(x.astype(_F32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def mellum_init(key: jax.Array, cfg: MellumConfig) -> Dict[str, Any]:
    """Parameter pytree: ``embed``, ``lm_head``, ``final_norm`` and
    ``layers``, one stack per run of like layers (:meth:`MellumConfig.runs`;
    the expert leaves ``[L, held, ...]``, the router ``[L, dim,
    num_experts]`` float32)."""
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    d, hd, E, held, W = cfg.dim, cfg.head_dim, cfg.num_experts, cfg.n_held, cfg.ffn_hidden
    qd, kvd = cfg.n_heads * hd, cfg.n_kv_heads * hd

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, _F32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    def run(key, kind, L):
        ks = jax.random.split(key, 8)
        return {
            "attn_norm": jnp.ones((L, d), cfg.dtype),
            "wq": dense(ks[0], (L, d, qd), d), "wk": dense(ks[1], (L, d, kvd), d),
            "wv": dense(ks[2], (L, d, kvd), d), "wo": dense(ks[3], (L, qd, d), qd),
            "q_norm": jnp.ones((L, hd), cfg.dtype), "k_norm": jnp.ones((L, hd), cfg.dtype),
            "ffn_norm": jnp.ones((L, d), cfg.dtype),
            # router in f32: its probabilities drive routing decisions
            "router": jax.random.normal(ks[4], (L, d, E), _F32) / jnp.sqrt(d),
            "w_gate": dense(ks[5], (L, held, d, W), d),
            "w_up": dense(ks[6], (L, held, d, W), d),
            "w_down": dense(ks[7], (L, held, W, d), W)}

    return {**init_tree(k_emb, k_layers, cfg, run),
            "lm_head": dense(k_head, (d, cfg.vocab_size), d)}


def _mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: MellumConfig, kind: str,
           table: Tuple[jax.Array, jax.Array], attention: Any) -> jax.Array:
    """One layer's attention from its normalised input to ``wo``'s output."""
    (B, S, _), hd = u.shape, cfg.head_dim
    q = (u @ w["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (u @ w["wk"]).reshape(B, S, cfg.n_kv_heads, hd)
    v = (u @ w["wv"]).reshape(B, S, cfg.n_kv_heads, hd)
    q = _rotate(_rmsnorm(q, w["q_norm"], cfg.norm_eps), table)
    k = _rotate(_rmsnorm(k, w["k_norm"], cfg.norm_eps), table)
    # a full layer is called as every other kind's attention is
    attn = (attention(q, k, v, cfg, window=cfg.window) if kind == "window"
            else attention(q, k, v, cfg))
    attn = jax.ad_checkpoint.checkpoint_name(attn, ATTN_OUT_NAME)
    return attn.reshape(B, S, cfg.n_heads * hd) @ w["wo"]


def _layer_body(cfg: MellumConfig, kind: str, table: Tuple[jax.Array, jax.Array],
                attention: Any):
    """The scanned body of a run of ``kind``: ``(h, (w, None, replay)) ->
    (h, moe_ffn's stats)`` (no selection bias in this family)."""

    def layer(h, xs):
        w, _, replay = xs
        u = _rmsnorm(h, w["attn_norm"], cfg.norm_eps)
        with jax.named_scope(f"attn_{kind}/mixer"):
            h = h + _mixer(u, w, cfg, kind, table, attention)
        x = _rmsnorm(h, w["ffn_norm"], cfg.norm_eps)
        out, stats = moe_ffn(x, w["router"], w["w_gate"], w["w_up"], w["w_down"], cfg,
                             routing=replay)
        stats.pop("prob_sum")  # no auxiliary loss reads it
        return h + out, stats

    return layer


def _bodies(cfg: MellumConfig, seq: int, attention_fn: Optional[Any]):
    attention, tables = attention_fn or _attention, rope_tables(cfg, seq)
    return lambda kind: _layer_body(cfg, kind, tables[kind], attention)


def _counters(stats: Dict[str, jax.Array], tokens: jax.Array, cfg: MellumConfig
              ) -> Dict[str, jax.Array]:
    """The layers' free routing with its margins (``routing`` [L,T,k],
    ``p_kth``, ``p_next`` [L,T]), ``moe.expert_scalars``' five for this
    family (``load_max_over_mean`` and, under a share, ``held_pair_share``,
    ``overflow_pairs``, ``visited_row_share`` and ``moved_row_share``), and
    what the attention kernels were built for: ``window_layers``,
    ``full_layers`` and ``window_block_share`` (``ops.attention``'s: 1.0
    means a window layer's kernel skips nothing)."""
    stats = expert_scalars(stats, tokens.size * cfg.top_k)
    windows = sum(t == "window" for t in cfg.layer_types)
    stats["window_layers"] = jnp.asarray(windows, _F32)
    stats["full_layers"] = jnp.asarray(cfg.n_layers - windows, _F32)
    stats["window_block_share"] = jnp.asarray(window_block_share(tokens.shape[1], cfg.window), _F32)
    return stats


MELLUM = Decoder(_bodies, _counters, routed=lambda kind: True)
mellum_hidden, mellum_forward = MELLUM.hidden, MELLUM.forward
mellum_loss_and_stats, mellum_loss = MELLUM.loss_and_stats, MELLUM.loss


def mellum_param_specs(cfg: MellumConfig, mesh: Optional[Any] = None) -> Dict[str, Any]:
    """PartitionSpecs for the pytree: every run's leaves as
    ``moe_param_specs``' (the dropless block keeps its experts on one device:
    ``ep`` > 1 is refused, a share is one chip's), the head norms
    replicated."""
    from jax.sharding import PartitionSpec as P

    specs = moe_param_specs(cfg, mesh)
    run = {**specs.pop("layers"), "q_norm": P(None, None), "k_norm": P(None, None)}
    return {**specs, "layers": {name: dict(run) for name, _, _ in cfg.runs()}}


register(MellumConfig, MELLUM_CONFIGS, lambda: ModelFns(
    mellum_init, logged(
        mellum_loss_and_stats,
        moe=("aux_loss", "load_max_over_mean", "held_pair_share", "overflow_pairs",
             "visited_row_share", "moved_row_share"),
        attn=("window_layers", "full_layers", "window_block_share")), mellum_param_specs, None))
