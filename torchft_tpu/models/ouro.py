"""A looped decoder (ByteDance's Ouro, LoopLM): ONE stack of sandwich-norm
layers run ``total_ut_steps`` times over the same weights, an exit after
every pass and an exit gate that weighs them.

With ``RMS(x; g) = x / sqrt(mean(x^2) + eps) * g``, no bias anywhere:

- layer (four norm weights): ``h = h + RMS(Attn(RMS(h; g1)); g2)``, then
  ``h = h + RMS(SwiGLU(RMS(h; g3)); g4)``: the dense kind's layer body
  (``make_llama_layer_body``), which normalises a branch once more where the
  stack has the two post-norm leaves;
- loop: ``h_0 = E[tokens]``; for ``t = 1..T``: ``u_t = Layers(h_{t-1})``, the
  same ``L`` layers at every ``t``, and ``h_t = RMS(u_t; g_f)``: what exit
  ``t`` reads and what pass ``t + 1`` starts from;
- exits: ``logits_t = h_t W_head``; ``lambda_t = sigmoid(h_t . w_e + b_e)``
  (float32 leaves ``exit_gate.w`` [dim], ``exit_gate.b`` []); ``p_t =
  lambda_t prod_{j<t} (1 - lambda_j)`` for ``t < T`` and the last exit takes
  what is left, ``p_T = prod_{j<T} (1 - lambda_j)``;
- ``forward`` (no early exit) is ``logits_T``; the training loss is the mean
  over tokens of ``sum_t p_t ce_t - beta H(p)``, ``H(p) = -sum_t p_t log
  p_t``, all of it in float32.

The passes are a ``lax.scan`` over the scanned stack: one compiled layer body
whatever ``T`` and ``L``. Under ``remat="full"`` the backward pass keeps one
input per layer APPLICATION, ``T x L`` of them. A layer's gradient is the sum
over the passes, so a staged gradient (models/staged.py) has it whole only in
the backward of pass 1: :func:`ouro_stages` says how many times the stack
runs and what stands between two passes. ``jax.named_scope``: ``loop/pass``
round the stack's passes, ``loop/exit`` round the between-pass norm, the
heads, the gate and the loss.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.decoder import loss_chunk_for
from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import (LlamaConfig, _rmsnorm, llama_init,
                                      make_llama_layer_body, sum_over_chunks, token_ce)
from torchft_tpu.models.remat import remat_wrap
from torchft_tpu.models.staged import Stages

__all__ = ["OuroConfig", "OURO_CONFIGS", "ouro_init", "ouro_exits", "ouro_exit_logits",
           "ouro_forward", "ouro_loss_and_stats", "ouro_loss", "ouro_stages",
           "ouro_param_specs", "exit_loss", "exit_log_probs"]

_F32 = jnp.float32
# the cross-entropies a trainer logs by name: those of the first exits
LOGGED_EXITS = 8


@dataclasses.dataclass(frozen=True)
class OuroConfig(LlamaConfig):
    total_ut_steps: int = 4  # T: passes over the one stack
    exit_beta: float = 0.05  # the weight of the exit distribution's entropy
    loss_chunk: int = 0  # positions whose T exits' logits are alive at once

    def num_params(self) -> int:
        d, f, v = self.dim, self.ffn_hidden, self.vocab_size
        kv = self.n_kv_heads * self.head_dim
        per_layer = 2 * d * d + 2 * d * kv + 3 * d * f + 4 * d
        return self.n_layers * per_layer + 2 * v * d + d + (d + 1)


OURO_CONFIGS: Dict[str, OuroConfig] = {
    "ouro_debug": OuroConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4, ffn_hidden=128,
        max_seq_len=128, rope_theta=1e6, norm_eps=1e-6, dtype=jnp.float32),
}


def even_exit_bias(loops: int) -> float:
    """``b`` at which a gate that sees nothing (``lambda = sigmoid(b)`` at
    every exit) leaves after ``(loops + 1) / 2`` passes on average: the
    survival ``q = 1 - lambda`` with ``1 + q + ... + q^(loops-1)`` that."""
    lo, hi = 0.0, 1.0
    for _ in range(60):
        q = (lo + hi) / 2
        lo, hi = (q, hi) if sum(q ** t for t in range(loops)) < (loops + 1) / 2 else (lo, q)
    return math.log((1 - lo) / lo) if loops > 1 else 0.0


def ouro_init(key: jax.Array, cfg: OuroConfig) -> Dict[str, Any]:
    """The dense tree (``llama_init``) with the two post-norms a layer, and
    the exit gate: ``w`` normal, a tenth over the root of ``dim`` (on
    normalised states the logits spread by a tenth: an untrained gate tells
    tokens apart, and barely), ``b`` so that it spreads its exits evenly
    round the middle one (:func:`even_exit_bias`)."""
    k_dense, k_gate = jax.random.split(key)
    params = llama_init(k_dense, cfg)
    for name in ("attn_post_norm", "ffn_post_norm"):  # a buffer each: a step donates them
        params["layers"][name] = jnp.ones((cfg.n_layers, cfg.dim), cfg.dtype)
    params["exit_gate"] = {
        "w": jax.random.normal(k_gate, (cfg.dim,), _F32) * (0.1 / math.sqrt(cfg.dim)),
        "b": jnp.asarray(even_exit_bias(cfg.total_ut_steps), _F32)}
    return params


def ouro_param_specs(cfg: OuroConfig) -> Dict[str, Any]:
    from jax.sharding import PartitionSpec as P

    from torchft_tpu.parallel.mesh import llama_param_specs  # it imports models

    specs = llama_param_specs(cfg)
    specs["layers"].update(attn_post_norm=P(None, None), ffn_post_norm=P(None, None))
    specs["exit_gate"] = {"w": P(None), "b": P()}
    return specs


def _between(final_norm: jax.Array, u: jax.Array, cfg: OuroConfig) -> jax.Array:
    """What a pass hands on: to its exit and to the pass that follows."""
    with jax.named_scope("loop/exit"):
        return _rmsnorm(u, final_norm, cfg.norm_eps)


def ouro_exits(
    params: Dict[str, Any], tokens: jax.Array, cfg: OuroConfig,
    attention_fn: Optional[Any] = None, remat: Any = "full",
) -> jax.Array:
    """tokens int32 [B, S] -> every exit's hidden state [T, B, S, dim]."""
    body = remat_wrap(make_llama_layer_body(cfg, attention_fn), remat)

    def one_pass(h, _):
        with jax.named_scope("loop/pass"):
            u, _ = jax.lax.scan(body, h, params["layers"])
        h = _between(params["final_norm"], u, cfg)
        return h, h

    _, hs = jax.lax.scan(one_pass, params["embed"][tokens], None,
                         length=cfg.total_ut_steps)
    return hs


def ouro_exit_logits(params: Dict[str, Any], tokens: jax.Array, cfg: OuroConfig,
                     **kw: Any) -> jax.Array:
    """Every exit's logits, f32 [T, B, S, vocab]."""
    return (ouro_exits(params, tokens, cfg, **kw) @ params["lm_head"]).astype(_F32)


def ouro_forward(params: Dict[str, Any], tokens: jax.Array, cfg: OuroConfig,
                 **kw: Any) -> jax.Array:
    """The last exit's logits, f32 [B, S, vocab]: inference that never
    leaves early (``early_exit_threshold`` 1)."""
    return (ouro_exits(params, tokens, cfg, **kw)[-1] @ params["lm_head"]).astype(_F32)


def exit_log_probs(z: jax.Array) -> jax.Array:
    """``log p_t`` [T, ...] from the gate's logits ``z`` [T, ...] f32."""
    stop, go = jax.nn.log_sigmoid(z), jax.nn.log_sigmoid(-z)
    passed = jnp.cumsum(go, axis=0) - go  # sum over j < t of log(1 - lambda_j)
    return jnp.concatenate([(stop + passed)[:-1], passed[-1:]])


def exit_loss(
    hs: jax.Array, lm_head: jax.Array, gate: Dict[str, jax.Array], targets: jax.Array,
    beta: float, loss_chunk: int = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The expected-exit loss of the exits' states ``hs`` [T, B, S, dim] and
    its counters, means over tokens: ``exit_step_mean`` (``sum_t t p_t``),
    ``exit_entropy``, ``p_last``, ``ce_1`` .. ``ce_T``. ``loss_chunk > 0``:
    the T exits' logits of that many positions at a time, rematerialised
    (``head_loss``'s chunking: [T, B, chunk, vocab] float32 alive, not
    [T, B, S, vocab])."""
    T, B, S, _ = hs.shape
    steps = jnp.arange(1, T + 1, dtype=_F32)

    def sums(hc, tc):  # hc [T, B, c, dim], tc [B, c] -> [4 + T] sums over tokens
        with jax.named_scope("loop/exit"):
            ce = token_ce(hc, lm_head, jnp.broadcast_to(tc, hc.shape[:-1]))
            logp = exit_log_probs(hc.astype(_F32) @ gate["w"] + gate["b"])
            p = jnp.exp(logp)
            entropy = -jnp.sum(p * logp, axis=0)
            token = jnp.sum(p * ce, axis=0) - beta * entropy
            return jnp.stack([jnp.sum(token), jnp.sum(jnp.tensordot(steps, p, 1)),
                              jnp.sum(entropy), jnp.sum(p[-1]), *jnp.sum(ce, axis=(1, 2))])

    if loss_chunk <= 0:
        total = sums(hs, targets)
    else:
        if S % loss_chunk != 0:
            raise ValueError(f"loss_chunk {loss_chunk} must divide seq len {S}")
        n = S // loss_chunk
        total = sum_over_chunks(sums, jnp.zeros((4 + T,), _F32), (
            jnp.moveaxis(hs.reshape(T, B, n, loss_chunk, -1), 2, 0),
            jnp.swapaxes(targets.reshape(B, n, loss_chunk), 0, 1)))
    mean = total / (B * S)
    return mean[0], {"exit_step_mean": mean[1], "exit_entropy": mean[2], "p_last": mean[3],
                     **{f"ce_{t + 1}": mean[4 + t] for t in range(T)}}


def ouro_loss_and_stats(
    params: Dict[str, Any], tokens: jax.Array, targets: jax.Array, cfg: OuroConfig,
    attention_fn: Optional[Any] = None, remat: Any = "full", loss_chunk: int = 0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    hs = ouro_exits(params, tokens, cfg, attention_fn=attention_fn, remat=remat)
    return exit_loss(hs, params["lm_head"], params["exit_gate"], targets,
                     cfg.exit_beta, loss_chunk_for(cfg, tokens.shape[1], loss_chunk))


def ouro_loss(*args: Any, **kw: Any) -> jax.Array:
    """:func:`ouro_loss_and_stats`' loss alone (``llama_loss``'s shape)."""
    return ouro_loss_and_stats(*args, **kw)[0]


def ouro_stages(cfg: OuroConfig, attention_fn: Optional[Any] = None) -> Stages:
    """:func:`ouro_loss_and_stats` as the stages a staged gradient composes
    (models/staged.py): the stack runs ``total_ut_steps`` times, the final
    norm stands between two passes, the head reads every exit."""

    def head(head_params, hs, emitted, targets):
        return exit_loss(hs, head_params["lm_head"], head_params["exit_gate"], targets,
                         cfg.exit_beta, loss_chunk_for(cfg, targets.shape[1]))

    return Stages(lambda embed, tokens: embed[tokens],
                  make_llama_layer_body(cfg, attention_fn), head,
                  loops=cfg.total_ut_steps,
                  between=lambda late, u: _between(late["final_norm"], u, cfg),
                  between_reads=("final_norm",))


_LOGGED = ("exit_step_mean", "exit_entropy", "p_last",
           *(f"ce_{t}" for t in range(1, LOGGED_EXITS + 1)))


def _stages(*args: Any, **kw: Any) -> Stages:
    s = ouro_stages(*args, **kw)
    return s._replace(head=logged(s.head, loop=_LOGGED))


register(OuroConfig, OURO_CONFIGS, lambda: ModelFns(
    ouro_init, logged(ouro_loss_and_stats, loop=_LOGGED), ouro_param_specs, _stages))
