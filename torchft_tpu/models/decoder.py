"""The one decoder over RUNS of like layers: what every kind whose layers
are unlike (``jamba``, ``lfm2``, ``ling``, ``mellum``, ``nemotron_h``, ``deepseek``)
repeated, once.

Such a kind keeps one stack of parameters per run of like layers
(``params["layers"]["00_mamba"]`` [7, ...], ``["01_attn"]`` [1, ...]; the
names sort in layer order: the heal's, the checksum's and the bucket plan's)
and scans each run under one remat policy: one compiled body per kind of
layer whatever the depth. (One stack per kind of layer, cut into runs inside
the step, would copy every weight every step: a slice of a stacked leaf is a
new buffer to XLA; PERF.md section 6, PR 33.)

Here: the fold of layer kinds into runs (:func:`runs_of`), the tree round
the runs (:func:`init_tree`, :func:`spec_tree`), the scan of the runs with
the logits head and the loss head (:class:`Decoder`), and the causal
convolution four kinds mix with. A kind brings its configuration class with
``runs()``, ``init``, PartitionSpecs, one layer's scanned body and its
counters. A staged gradient over runs (ROADMAP R8) has this scan to cut.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Hashable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.llama import _rmsnorm, head_loss
from torchft_tpu.models.remat import remat_wrap
from torchft_tpu.ops.short_conv import short_conv

__all__ = ["Decoder", "runs_of", "init_tree", "spec_tree", "loss_chunk_for"]

_F32 = jnp.float32


def runs_of(
    kinds: Sequence[Hashable],
    name: Callable[[Any], str] = str,
    merges: Callable[[Any], bool] = lambda kind: True,
) -> List[Tuple[str, Any, int]]:
    """The kind of every layer -> the runs of like layers in order: (the
    name of the run's stack: its place and ``name(kind)``, its kind, layers).
    Neighbours of one kind run together where ``merges(kind)``: an expert
    layer runs alone (scanned over a stack, each layer's expert matrices are
    copied out of it and their gradients written back slice by slice: 3.4
    GiB more at LFM2's published widths, PERF.md section 6, PR 35)."""
    out: List[Tuple[str, Any, int]] = []
    for kind in kinds:
        if out and out[-1][1] == kind and merges(kind):
            out[-1] = (out[-1][0], kind, out[-1][2] + 1)
        else:
            out.append((f"{len(out):02d}_{name(kind)}", kind, 1))
    return out


def init_tree(k_embed: jax.Array, k_layers: jax.Array, cfg: Any,
              run: Callable[[jax.Array, Any, int], Dict[str, jax.Array]]) -> Dict[str, Any]:
    """``embed`` (normal over the root of ``dim``), ``final_norm`` and
    ``layers``: ``run(key, kind, L)`` for each of ``cfg.runs()``, one key a
    run. The kind adds its other top-level leaves."""
    d, runs = cfg.dim, cfg.runs()
    embed = jax.random.normal(k_embed, (cfg.vocab_size, d), _F32) / jnp.sqrt(d)
    return {"embed": embed.astype(cfg.dtype),
            "layers": {name: run(k, kind, L) for (name, kind, L), k
                       in zip(runs, jax.random.split(k_layers, len(runs)))},
            "final_norm": jnp.ones((d,), cfg.dtype)}


def spec_tree(cfg: Any, run: Callable[[Any], Dict[str, Any]]) -> Dict[str, Any]:
    """:func:`init_tree`'s PartitionSpecs, ``run(kind)`` those of a run's stack."""
    from jax.sharding import PartitionSpec as P

    return {"embed": P("fsdp", "tp"), "final_norm": P(None),
            "layers": {name: dict(run(kind)) for name, kind, _ in cfg.runs()}}


def _causal_conv(x: jax.Array, w: jax.Array, b: Optional[jax.Array],
                 activation: Optional[Any] = jax.nn.silu) -> jax.Array:
    """``activation`` (Mamba's, Mamba-2's and KDA's silu; None: LFM2's, none)
    of the depthwise causal convolution, summed in float32. x [B,T,di],
    w [k,di] (``w[k-1]`` weighs the current position), b [di] or None.

    One program for every kind (``ops/short_conv.py``): where the shape
    tiles (``di`` whole lanes of 128, ``T`` whole tiles of positions: every
    cell's) a Pallas kernel pair that reads the narrow rows once and keeps
    the float32 sums, the bias and the activation in VMEM, forward and
    backward, so that no float32 copy of [B, T, di] reaches HBM (at 32k
    three of [T, 4096] were 1.5 GB of one of Ling's layers' backward pass,
    PR 40); otherwise (the debug configurations' widths) shifted
    multiply-adds over the padded sequence in ``jax.numpy``."""
    return short_conv(x, w, b, activation)


def loss_chunk_for(cfg: Any, seq: int, loss_chunk: int = 0) -> int:
    """``loss_chunk``, or (0) the configuration's where that divides a
    longer sequence."""
    if not loss_chunk and cfg.loss_chunk and seq > cfg.loss_chunk and seq % cfg.loss_chunk == 0:
        return cfg.loss_chunk
    return loss_chunk


def _head(params: Dict[str, Any]) -> jax.Array:
    """[dim, vocab]: ``lm_head``, or the embedding transposed where the kind
    ties them (no such leaf: one leaf read twice, its gradient the sum)."""
    return params["lm_head"] if "lm_head" in params else params["embed"].T


@dataclasses.dataclass(frozen=True)
class Decoder:
    """A kind of decoder over runs, declared by what is its own.

    ``bodies(cfg, seq, attention_fn)`` -> ``body_of(kind)`` -> the scanned
    body of a run: ``(h, (w, bias, replay)) -> (h, stats)``, ``w`` one layer
    of the run's stack, ``stats`` a flat dict of arrays or None. The kind's own
    function, run at every call before the embedding is read: what it reads
    from its module's globals (the default attention, a table made once a
    step) it reads then. Where ``routed(kind)``, ``bias`` is the run's rows
    of ``params["expert_bias"]`` (if the tree has it) and ``replay`` its rows
    of ``routing`` (if given); else None. ``counters(stats, tokens, cfg)``
    -> what ``loss_and_stats`` hands out beside the loss, from the runs'
    stats stacked over the layers that have any (beside ``aux_loss``, where
    the expert layers emit the sequence-wise balance term)."""

    bodies: Callable[..., Callable[[Any], Callable[..., Any]]]
    counters: Callable[..., Dict[str, jax.Array]]
    routed: Callable[[Any], bool] = lambda kind: False

    def hidden(self, params: Dict[str, Any], tokens: jax.Array, cfg: Any,
               attention_fn: Optional[Any] = None, remat: Any = "full",
               routing: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """tokens int32 [B, S] -> (final-norm hidden states [B, S, dim] (the
        last layer's output where the tree has no ``final_norm``), the
        layers' stats, each stacked over the layers that emit any).
        ``routing`` [routed layers, B*S, k]: the experts to use (replay)."""
        body_of = self.bodies(cfg, tokens.shape[1], attention_fn)
        h = params["embed"][tokens]
        stats, at = [], 0  # ``at``: routed layers before this run
        for name, kind, L in cfg.runs():
            rows = slice(at, at + L) if self.routed(kind) else None
            xs = (params["layers"][name],
                  params["expert_bias"][rows]
                  if rows is not None and "expert_bias" in params else None,
                  None if routing is None or rows is None else routing[rows])
            h, out = jax.lax.scan(remat_wrap(body_of(kind), remat), h, xs)
            if out is not None:
                stats.append(out)
            if rows is not None:
                at += L
        if routing is not None and routing.shape[0] != at:
            raise ValueError(f"routing names {routing.shape[0]} layers, {at} choose experts")
        # each stat over the layers that emit it: runs of unlike kinds may
        # emit unlike stats (a mixer's beside an expert block's)
        stats = {k: jnp.concatenate([out[k] for out in stats if k in out])
                 for k in sorted({k for out in stats for k in out})}
        # a tree without ``final_norm`` (a stage that has no head) gets the
        # last layer's output as it is
        if "final_norm" in params:
            h = _rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return h, stats

    def forward(self, params: Dict[str, Any], tokens: jax.Array, cfg: Any,
                attention_fn: Optional[Any] = None, remat: Any = "full",
                routing: Optional[jax.Array] = None) -> jax.Array:
        """tokens int32 [B, S] -> logits f32 [B, S, the vocabulary held]."""
        h, _ = self.hidden(params, tokens, cfg, attention_fn, remat, routing)
        return (h @ _head(params)).astype(_F32)

    def loss_and_stats(self, params: Dict[str, Any], tokens: jax.Array,
                       targets: jax.Array, cfg: Any,
                       attention_fn: Optional[Any] = None, remat: Any = "full",
                       loss_chunk: int = 0, routing: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Mean next-token cross-entropy (``llama_loss``'s; ``loss_chunk``
        as there, and 0 takes ``cfg.loss_chunk`` where that divides a longer
        sequence: 8,192 x 65,536 float32 logits are 2 GiB), plus
        ``cfg.aux_loss_weight`` x the expert layers' sequence-wise balance
        terms summed where they emit one (``cfg.seq_aux``; the sum rides the
        counters as ``aux_loss``), and ``counters``."""
        h, stats = self.hidden(params, tokens, cfg, attention_fn, remat, routing)
        loss = head_loss(h, _head(params), targets, loss_chunk_for(cfg, tokens.shape[1], loss_chunk))
        if "seq_aux" in stats:
            stats["aux_loss"] = jnp.sum(stats.pop("seq_aux"))
            loss = loss + cfg.aux_loss_weight * stats["aux_loss"]
        return loss, self.counters(stats, tokens, cfg)

    def loss(self, *args: Any, **kw: Any) -> jax.Array:
        """:meth:`loss_and_stats`' loss alone (``llama_loss``'s shape)."""
        return self.loss_and_stats(*args, **kw)[0]
