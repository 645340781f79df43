"""Mamba / attention hybrid decoder (AI21's Jamba family, ``model_type``
``jamba``) as a third kind of the one trainer's model: most layers mix the
sequence with a Mamba-1 state-space block, one in every
``attn_layer_period`` with grouped-query attention WITHOUT positions, and
every layer ends in the dense decoder's SwiGLU feed-forward.

Every layer: ``h = x + mixer(rmsnorm(x))``, then ``h + swiglu(rmsnorm(h))``.
The Mamba mixer (``d_inner = mamba_expand * dim``)::

    x, z     = split(u @ in_proj)                      # no bias
    x        = silu(causal_depthwise_conv(x) + conv_b) # kernel mamba_d_conv
    dt,B,C   = split(x @ x_proj)                       # dt_rank, d_state, d_state
    dt,B,C   = rmsnorm(dt), rmsnorm(B), rmsnorm(C)     # Jamba's own, learned
    dt       = softplus(dt @ dt_proj + dt_bias)
    y        = selective_scan(x, dt, -exp(A_log), B, C, D, z)   # ops/selective_scan.py
    out      = y @ out_proj

The state, the ``exp`` and the sums of the scan are float32 whatever
``cfg.dtype`` is; ``A_log`` and ``D`` are float32 leaves among bf16 ones (as
OLMoE's router is). The head is tied by default: ``logits = h @ embed.T``,
one leaf read twice, its gradient the sum of both uses, and ``num_params``
counts it once: this config object owns the tied case (``models/llama.py``
has an untied head only).

The layers are unlike, so the parameters are one stack per RUN of like
layers (``layers["00_mamba"]`` [7, ...], ``layers["01_attn"]`` [1, ...],
``layers["02_mamba"]`` [6, ...] for one period) and ``models/decoder.py``
scans the runs: this module is the configuration, ``init``, the two layer
bodies, the PartitionSpecs and the two counters, and declares them (``JAMBA``).
Initialisation is Mamba's (``A_log = log(1..d_state)``, ``D = 1``,
``dt_bias = softplus^-1(dt0)``, ``dt0`` log-uniform in [1e-3, 1e-1]): with
steps near 1e-3 the state remembers about a thousand positions.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.models.decoder import Decoder, _causal_conv, init_tree, runs_of, spec_tree
from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import LlamaConfig, _attention, _rmsnorm
from torchft_tpu.models.remat import ATTN_OUT_NAME
from torchft_tpu.ops.selective_scan import selective_scan

__all__ = [
    "JambaConfig",
    "JAMBA_CONFIGS",
    "jamba_init",
    "jamba_hidden",
    "jamba_forward",
    "jamba_loss",
    "jamba_loss_and_stats",
    "jamba_param_specs",
]


@dataclasses.dataclass(frozen=True)
class JambaConfig(LlamaConfig):
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_experts: int = 1
    tie_word_embeddings: bool = True
    # the loss over sequence chunks of this length where it divides the
    # sequence (``llama_loss``'s ``loss_chunk``; 0: never): 8,192 positions x
    # 65,536 rows of float32 logits are 2 GiB, and twice that with the copy
    # the tied head's layout costs
    loss_chunk: int = 0

    def __post_init__(self) -> None:
        if self.num_experts != 1:
            raise ValueError(
                f"num_experts={self.num_experts}: models/jamba.py is the dense "
                "hybrid (every feed-forward a plain SwiGLU); the routed sibling "
                "is another model")
        if self.mamba_proj_bias:
            raise ValueError("mamba_proj_bias=True: in_proj and out_proj have "
                             "no bias here")

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.dim

    @property
    def layers_block_type(self) -> List[str]:
        """``JambaConfig.layers_block_type`` of transformers: layer ``i`` is
        attention where ``i % period == offset``, else Mamba."""
        return ["attention" if i % self.attn_layer_period == self.attn_layer_offset
                else "mamba" for i in range(self.n_layers)]

    def runs(self) -> List[Tuple[str, str, int]]:
        """Runs of like layers in order: (name of the run's stack under
        ``params["layers"]``, kind, layers); neighbours of a kind merge."""
        return runs_of(self.layers_block_type,
                       name=lambda kind: "attn" if kind == "attention" else kind)

    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_hidden, self.vocab_size
        di, n, r, k = self.d_inner, self.mamba_d_state, self.mamba_dt_rank, self.mamba_d_conv
        kv = self.n_kv_heads * self.head_dim
        ffn = 3 * d * f + 2 * d  # SwiGLU and the layer's two norms
        mamba = (d * 2 * di + di * k + (di if self.mamba_conv_bias else 0)
                 + di * (r + 2 * n) + r * di + di + di * n + di + (r + 2 * n)
                 + di * d)
        attn = 2 * d * d + 2 * d * kv
        kinds = self.layers_block_type
        head = 0 if self.tie_word_embeddings else v * d
        return (kinds.count("mamba") * (mamba + ffn)
                + kinds.count("attention") * (attn + ffn) + v * d + head + d)


JAMBA_CONFIGS: Dict[str, JambaConfig] = {
    # both kinds twice: mamba, mamba, attention, mamba, mamba, mamba, attention,
    # mamba; bf16 like the published one, so float32 A_log and D sit among
    # bf16 leaves in a trainer's bucket plan
    "jamba_debug": JambaConfig(
        vocab_size=256, dim=64, n_layers=8, n_heads=4, n_kv_heads=1,
        ffn_hidden=128, max_seq_len=128, norm_eps=1e-6,
        attn_layer_period=4, attn_layer_offset=2, mamba_dt_rank=8,
    ),
    # ai21labs/AI21-Jamba2-3B as published
    "jamba2_3b": JambaConfig(
        vocab_size=65536, dim=2560, n_layers=28, n_heads=20, n_kv_heads=1,
        ffn_hidden=8192, max_seq_len=262144, norm_eps=1e-6, loss_chunk=2048,
    ),
}


def jamba_init(key: jax.Array, cfg: JambaConfig) -> Dict[str, Any]:
    """Parameter pytree: ``embed``, ``final_norm``, ``layers`` (one stack
    per run of like layers, :meth:`JambaConfig.runs`), and ``lm_head`` only
    where the head is not tied."""
    k_emb, k_out, k_layers = jax.random.split(key, 3)
    d, f, hd = cfg.dim, cfg.ffn_hidden, cfg.head_dim
    di, n, r = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    kvd = cfg.n_kv_heads * hd

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(fan_in)).astype(cfg.dtype)

    def ffn(keys, L):
        return {"ffn_norm": jnp.ones((L, d), cfg.dtype),
                "w_gate": dense(keys[0], (L, d, f), d),
                "w_up": dense(keys[1], (L, d, f), d),
                "w_down": dense(keys[2], (L, f, d), f)}

    def mamba(key, L):
        ks = jax.random.split(key, 9)
        dt0 = jnp.exp(jax.random.uniform(ks[4], (L, di), jnp.float32,
                                         jnp.log(1e-3), jnp.log(1e-1)))
        w = {
            "norm": jnp.ones((L, d), cfg.dtype),
            "in_proj": dense(ks[0], (L, d, 2 * di), d),
            "conv_w": dense(ks[1], (L, cfg.mamba_d_conv, di), cfg.mamba_d_conv),
            "x_proj": dense(ks[2], (L, di, r + 2 * n), di),
            "dt_norm": jnp.ones((L, r), cfg.dtype),
            "b_norm": jnp.ones((L, n), cfg.dtype),
            "c_norm": jnp.ones((L, n), cfg.dtype),
            "dt_proj": dense(ks[3], (L, r, di), r),
            # softplus^-1(dt0) = dt0 + log(1 - exp(-dt0))
            "dt_bias": (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(cfg.dtype),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (L, di, n)),
            "D": jnp.ones((L, di), jnp.float32),
            "out_proj": dense(ks[5], (L, di, d), di),
            **ffn(ks[6:9], L),
        }
        if cfg.mamba_conv_bias:
            w["conv_b"] = jnp.zeros((L, di), cfg.dtype)
        return w

    def attention(key, L):
        ks = jax.random.split(key, 7)
        return {
            "norm": jnp.ones((L, d), cfg.dtype),
            "wq": dense(ks[0], (L, d, cfg.n_heads * hd), d),
            "wk": dense(ks[1], (L, d, kvd), d),
            "wv": dense(ks[2], (L, d, kvd), d),
            "wo": dense(ks[3], (L, cfg.n_heads * hd, d), cfg.n_heads * hd),
            **ffn(ks[4:7], L),
        }

    make = {"mamba": mamba, "attention": attention}
    params = init_tree(k_emb, k_layers, cfg, lambda key, kind, L: make[kind](key, L))
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(k_out, (d, cfg.vocab_size), d)
    return params


def _feed_forward(h: jax.Array, w: Dict[str, jax.Array], cfg: JambaConfig) -> jax.Array:
    x = _rmsnorm(h, w["ffn_norm"], cfg.norm_eps)
    return h + (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _mamba_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: JambaConfig
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
    with jax.named_scope("ssm/in_proj"):
        x, z = jnp.split(u @ w["in_proj"], 2, axis=-1)
    with jax.named_scope("ssm/conv"):
        x = _causal_conv(x, w["conv_w"], w.get("conv_b"))
    with jax.named_scope("ssm/scan"):
        # dt, B and C leave their product and Jamba's norms in float32: the
        # kernel reads B and C as float32 whatever the weights' dtype
        dt, B, C = jnp.split(
            jnp.matmul(x, w["x_proj"], preferred_element_type=jnp.float32),
            [r, r + n], axis=-1)
        dt = _rmsnorm(dt, w["dt_norm"], cfg.norm_eps).astype(x.dtype)
        B = _rmsnorm(B, w["b_norm"], cfg.norm_eps)
        C = _rmsnorm(C, w["c_norm"], cfg.norm_eps)
        # the step size sits in an exponent: its pre-activation (near -7 at
        # Mamba's initialisation) leaves the product in float32, where a
        # bf16 rounding of it would be 2-3% of dt
        dt = jax.nn.softplus(
            jnp.matmul(dt, w["dt_proj"], preferred_element_type=jnp.float32)
            + w["dt_bias"].astype(jnp.float32))
        y = selective_scan(x, dt, -jnp.exp(w["A_log"]), B, C, w["D"], z)
    with jax.named_scope("ssm/gate_out"):
        out = y @ w["out_proj"]
    stats = {"dt_max": jnp.max(dt),
             "y_absmax": jnp.max(jnp.abs(y)).astype(jnp.float32)}
    return out, jax.lax.stop_gradient(stats)


def _bodies(cfg: JambaConfig, seq: int, attention_fn: Optional[Any]):
    """A Mamba layer's stats are its mixer's, an attention layer has none."""
    attention = attention_fn or _attention

    def mamba_layer(h, xs):
        w = xs[0]
        out, stats = _mamba_mixer(_rmsnorm(h, w["norm"], cfg.norm_eps), w, cfg)
        return _feed_forward(h + out, w, cfg), stats

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
            h = h + attn @ w["wo"]
        return _feed_forward(h, w, cfg), None

    return {"mamba": mamba_layer, "attention": attention_layer}.__getitem__


def _counters(stats: Dict[str, jax.Array], tokens: jax.Array, cfg: JambaConfig
              ) -> Dict[str, jax.Array]:
    """The two scalars a training loop logs, over the Mamba layers:
    ``dt_max`` (the largest step size) and ``y_absmax`` (the largest
    magnitude of the scan's gated output); Jamba's inner norms exist because
    these spike."""
    return {"dt_max": jnp.max(stats["dt_max"]), "y_absmax": jnp.max(stats["y_absmax"])}


# ``jamba_hidden`` -> (hidden states, the Mamba layers' ``dt_max`` and
# ``y_absmax``, each [n_mamba]); the head is tied unless the tree has ``lm_head``
JAMBA = Decoder(_bodies, _counters)
jamba_hidden, jamba_forward = JAMBA.hidden, JAMBA.forward
jamba_loss_and_stats, jamba_loss = JAMBA.loss_and_stats, JAMBA.loss


def jamba_param_specs(cfg: JambaConfig) -> Dict[str, Any]:
    """PartitionSpecs for the hybrid's pytree. The feed-forward and the
    attention layers as the dense decoder's (fsdp and tp); a Mamba mixer's
    matrices over fsdp alone, its channels whole on every device (the scan
    kernel owns all of ``d_inner``), its small leaves replicated."""
    from jax.sharding import PartitionSpec as P

    ffn = {"ffn_norm": P(None, None), "w_gate": P(None, "fsdp", "tp"),
           "w_up": P(None, "fsdp", "tp"), "w_down": P(None, "tp", "fsdp")}
    mamba = {
        "norm": P(None, None), "in_proj": P(None, "fsdp", None),
        "conv_w": P(None, None, None), "x_proj": P(None, "fsdp", None),
        "dt_norm": P(None, None), "b_norm": P(None, None), "c_norm": P(None, None),
        "dt_proj": P(None, None, "fsdp"), "dt_bias": P(None, None),
        "A_log": P(None, None, None), "D": P(None, None),
        "out_proj": P(None, "fsdp", None), **ffn}
    if cfg.mamba_conv_bias:
        mamba["conv_b"] = P(None, None)
    attn = {"norm": P(None, None), "wq": P(None, "fsdp", "tp"),
            "wk": P(None, "fsdp", "tp"), "wv": P(None, "fsdp", "tp"),
            "wo": P(None, "tp", "fsdp"), **ffn}
    specs = spec_tree(cfg, {"mamba": mamba, "attention": attn}.__getitem__)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P("fsdp", "tp")
    return specs


register(JambaConfig, JAMBA_CONFIGS, lambda: ModelFns(
    jamba_init, logged(jamba_loss_and_stats, ssm=("dt_max", "y_absmax")), jamba_param_specs, None))
