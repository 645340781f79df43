"""Mixture-of-Experts Llama variant with expert parallelism.

Not present in the reference (its model families are a CIFAR CNN, MultiMLP
and torchtitan Llama; EP is absent per SURVEY.md §2.4) but first-class here:
the sparse-FFN transformer is the standard way to scale params without
scaling per-token FLOPs, and TPU meshes make expert parallelism a natural
axis.

Two dispatch paths, chosen by ``MoEConfig.capacity_factor``, both with
static shapes (nothing compiles twice when the load shifts):
- **A number: the capacity path. It DROPS.** GShard-style: every expert
  processes exactly ``capacity`` token slots per step, and a token that finds
  its expert's queue full loses that expert's term (it falls through on the
  residual). The presets ``debug`` and ``bench_moe`` and the ``ep`` axis use
  it.
- **``None``: the dropless path. It drops NOTHING, at any load.** The
  ``T * top_k`` (token, choice) pairs are sorted by expert and the three
  projections run as grouped matrix multiplications over those rows with the
  per-expert counts as group sizes (OLMoE's block, ``olmoe_1b_7b``). All the
  experts live on one device: ``ep`` > 1 is refused for it.

TPU-first design:
- **Expert parallelism as a mesh axis.** Expert weights carry ``ep`` in
  their PartitionSpec (leading E dim); when the dispatched activations
  [E, C, d] are sharded over ``ep``, XLA inserts the all-to-alls — no manual
  collective code.
- **Router in f32** (scores and cumsum position math need it), payload
  matmuls in bf16.
- **One router, the configuration's scoring.** A token's scores over the
  experts are ``MoEConfig.router_score`` of the router's logits: ``softmax``
  (Mixtral, OLMoE) or ``sigmoid``, each expert by itself. The DECISION is the
  top-k of ``scores + bias`` where the caller hands :func:`moe_ffn` a
  selection bias ([E]; state of the model and no parameter: it takes no
  gradient) and of the scores alone where not; the GATES are the scores at
  the chosen experts, never the biased ones, renormalised over the chosen
  where ``norm_topk_prob`` (over their sum + ``gate_eps``). The margins
  handed back (``p_kth``, ``p_next``) are of what decided: ``scores + bias``.
- **Routing replay.** ``routing=`` (per layer ``[T, top_k]`` expert indices)
  makes the block use the given experts with this model's own scores
  at them as gates, so the router's gradient flows as in a free run; the
  routing the model would have chosen freely comes back beside it.
- **The expert's form is the configuration's** (``MoEConfig.expert_act``):
  SwiGLU, ``down(silu(gate(x)) * up(x))``, or the ungated
  ``down(relu(up(x))^2)`` with no ``w_gate`` leaf anywhere (dropless path and
  share; two grouped products forward where SwiGLU has three). A shared
  expert has the same form and its own width (``shared_intermediate_size``).
- Attention/norms/RoPE reuse the dense Llama blocks, including the Pallas
  flash-attention path.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import LlamaConfig, _attention, _rmsnorm, _rope
from torchft_tpu.models.remat import ATTN_OUT_NAME, remat_wrap
from torchft_tpu.models.staged import Stages
from torchft_tpu.ops.take_rows import take_rows

__all__ = [
    "MoEConfig",
    "MOE_CONFIGS",
    "moe_init",
    "moe_forward",
    "moe_loss",
    "moe_loss_and_stats",
    "moe_param_specs",
    "moe_stages",
    "moe_ffn",
    "load_balancing_loss",
    "sequence_balance_loss",
    "expert_scalars",
    "ffn_leaves",
    "ffn_init",
    "ffn_specs",
]


ROUTER_PRECISION = jax.lax.Precision.HIGHEST
# the standard deviation a kind gives its frozen selection bias at the start
# (``expert_bias``: LFM2's, Ling's, Nemotron-H's, Solar's), so that a check
# sees it read
BIAS_INIT_SCALE = 0.01


@dataclasses.dataclass(frozen=True)
class MoEConfig(LlamaConfig):
    num_experts: int = 8
    top_k: int = 2
    # slots per expert as a multiple of the even share; None: dropless
    capacity_factor: Optional[float] = 1.25
    aux_loss_weight: float = 0.01
    norm_topk_prob: bool = True  # gates renormalised over the chosen experts
    qk_norm: bool = False  # RMSNorm over the whole q and k projections
    router_score: str = "softmax"  # or "sigmoid": each expert scored by itself
    gate_eps: float = 1e-9  # beside the chosen gates' sum where renormalised
    # group-limited choice: the experts lie in ``n_group`` equal groups, a
    # token keeps the ``topk_group`` best and chooses inside them; 1 group: a
    # plain top-k. ``topk_method`` (the published key) is how a group is
    # scored: "noaux_tc" by the sum of its best two, "group_limited_greedy"
    # by its best one
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    # the auxiliary loss of the kinds over the dropless block (the published
    # key): ``aux_loss_weight`` x :func:`sequence_balance_loss` of every
    # expert layer, each sequence balanced by itself, which
    # ``decoder.Decoder.loss_and_stats`` adds for every such kind
    seq_aux: bool = False
    routed_scaling: float = 1.0  # a factor on every gate
    # (first, count): this chip holds experts first .. first + count - 1 of
    # the router's ``num_experts`` and computes their part alone (the
    # dropless path; the expert leaves are ``[count, ...]``); None: all
    held_experts: Optional[Tuple[int, int]] = None
    # rows the share's buffer has, over the even share T * top_k * count /
    # num_experts; pairs beyond it are counted (``overflow``) and computed
    # by nobody. A room costs memory and the two scatter-adds' row traffic
    # (XLA's, over every row of the buffer) and NO products and no gather:
    # the grouped matmuls visit the held pairs only (``_share_sizes``) and
    # the gathers go as far as the pairs do (``_over_tiles``)
    share_room: float = 1.5
    # an expert's form: "swiglu", ``down(silu(gate(x)) * up(x))``, three
    # matrices; or "relu2", ungated, ``down(relu(up(x))^2)``, two matrices and
    # no ``w_gate`` leaf anywhere (the dropless path and the share)
    expert_act: str = "swiglu"
    # the shared expert's width where the family has one; None: an expert's
    shared_intermediate_size: Optional[int] = None

    def __post_init__(self) -> None:
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score={self.router_score!r}: models/moe.py "
                             "scores by 'softmax' or 'sigmoid'")
        if self.expert_act not in ("swiglu", "relu2") or (
                self.expert_act == "relu2" and self.capacity_factor is not None):
            raise ValueError(f"expert_act={self.expert_act!r}: 'swiglu', or 'relu2' "
                             "on the dropless path (capacity_factor=None)")
        if self.num_experts % self.n_group or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"n_group={self.n_group}, topk_group={self.topk_group} "
                             f"of {self.num_experts} experts")
        if self.topk_method not in ("noaux_tc", "group_limited_greedy"):
            raise ValueError(f"topk_method={self.topk_method!r}: a group is scored by the "
                             "sum of its best two ('noaux_tc') or by its best "
                             "('group_limited_greedy')")
        if self.seq_aux and type(self) is MoEConfig:
            raise ValueError("seq_aux: the sequence-wise term is the kinds' over "
                             "models/decoder.py; this kind's auxiliary loss is "
                             "load_balancing_loss over all layers at once")
        if self.held_experts is not None:
            first, count = self.held_experts
            if self.capacity_factor is not None or first < 0 or count < 1 \
                    or first + count > self.num_experts:
                raise ValueError(f"held_experts={self.held_experts}: a share of the "
                                 f"{self.num_experts} experts, on the dropless path")

    def _check_layer_types(self, known: Tuple[str, ...], dense_layers: int = 0) -> None:
        """What a kind with ``layer_types`` over the dropless block refuses,
        the key named (its ``__post_init__`` calls this; ``dense_layers``:
        its ``num_dense_layers`` where it has leading dense layers)."""
        if len(self.layer_types) != self.n_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers, "
                             f"n_layers is {self.n_layers}")
        other = sorted(set(self.layer_types) - set(known))
        if other:
            raise ValueError(f"layer_types {other}: {type(self).__name__} mixes with {known}")
        self._check_dropless_block(dense_layers)

    def _check_dropless_block(self, dense_layers: int = 0) -> None:
        """What every kind over the dropless block refuses, whatever its
        mixers: a capacity, an auxiliary loss other than the sequence-wise
        one (``seq_aux``), more leading dense layers than layers."""
        if self.capacity_factor is not None or (self.aux_loss_weight and not self.seq_aux):
            raise ValueError("capacity_factor / aux_loss_weight: the family's "
                             "expert block drops nothing and has no auxiliary loss "
                             "but the sequence-wise one (seq_aux)")
        if not 0 <= dense_layers <= self.n_layers:
            raise ValueError(f"num_dense_layers={dense_layers} of {self.n_layers} layers")

    @property
    def n_held(self) -> int:
        """Experts this chip holds (all of them unless ``held_experts``)."""
        return self.num_experts if self.held_experts is None else self.held_experts[1]

    def share_rows(self, tokens: int) -> int:
        """Rows of the share's buffer for a batch of ``tokens`` (static)."""
        pairs = tokens * self.top_k
        rows = math.ceil(self.share_room * pairs * self.n_held / self.num_experts)
        tile = 512 if rows >= 512 else 8
        return min(-(-rows // tile) * tile, -(-pairs // 8) * 8)

    def capacity(self, tokens: int) -> int:
        """Slots per expert for a batch of ``tokens`` (static given shapes)."""
        c = int(self.capacity_factor * tokens * self.top_k / self.num_experts)
        return max(c, self.top_k)

    @property
    def expert_matrices(self) -> int:
        """Matrices an expert has: SwiGLU's three, the ungated form's two."""
        return 2 if self.expert_act == "relu2" else 3

    def num_params(self) -> int:
        d, h, v, L = self.dim, self.ffn_hidden, self.vocab_size, self.n_layers
        kv = self.n_kv_heads * self.head_dim
        per_layer = (2 * d * d + 2 * d * kv + 2 * d + d * self.num_experts
                     + self.expert_matrices * self.num_experts * d * h
                     + (d + kv if self.qk_norm else 0))
        return L * per_layer + 2 * v * d + d


MOE_CONFIGS: Dict[str, MoEConfig] = {
    "debug": MoEConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
        ffn_hidden=128, max_seq_len=128, dtype=jnp.float32,
        num_experts=4, top_k=2,
    ),
    # ~8x330M sparse params, dense-420M compute class
    "bench_moe": MoEConfig(
        vocab_size=32000, dim=1024, n_layers=24, n_heads=16, n_kv_heads=8,
        ffn_hidden=2816, max_seq_len=2048, num_experts=8, top_k=2,
    ),
    # allenai/OLMoE-1B-7B-0125-Instruct as published: 64 experts of width
    # 1024, 8 a token, gates not renormalised, QK-norm, no token dropped
    "olmoe_1b_7b": MoEConfig(
        vocab_size=50304, dim=2048, n_layers=16, n_heads=16, n_kv_heads=16,
        ffn_hidden=1024, max_seq_len=4096, rope_theta=10000.0,
        num_experts=64, top_k=8, capacity_factor=None, norm_topk_prob=False,
        qk_norm=True,
    ),
}


def moe_init(key: jax.Array, cfg: MoEConfig) -> Dict[str, Any]:
    """Parameter pytree: llama layout with the FFN replaced by router +
    stacked experts ([L, E, ...] so lax.scan still sees one layer body)."""
    k_emb, k_out, k_layers = jax.random.split(key, 3)
    d, hd = cfg.dim, cfg.head_dim
    kvd = cfg.n_kv_heads * hd
    L, E, H = cfg.n_layers, cfg.num_experts, cfg.ffn_hidden

    def dense_init(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)).astype(
            cfg.dtype
        )

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn_norm": jnp.ones((L, d), cfg.dtype),
        "wq": dense_init(ks[0], (L, d, cfg.n_heads * hd), d),
        "wk": dense_init(ks[1], (L, d, kvd), d),
        "wv": dense_init(ks[2], (L, d, kvd), d),
        "wo": dense_init(ks[3], (L, cfg.n_heads * hd, d), cfg.n_heads * hd),
        "ffn_norm": jnp.ones((L, d), cfg.dtype),
        # router in f32: small, and its probabilities drive routing decisions
        "router": (jax.random.normal(ks[4], (L, d, E), jnp.float32) / jnp.sqrt(d)),
        "w_gate": dense_init(ks[5], (L, E, d, H), d),
        "w_up": dense_init(ks[6], (L, E, d, H), d),
        "w_down": dense_init(ks[7], (L, E, H, d), H),
    }
    if cfg.expert_act == "relu2":
        del layers["w_gate"]
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, cfg.n_heads * hd), cfg.dtype)
        layers["k_norm"] = jnp.ones((L, kvd), cfg.dtype)
    return {
        "embed": dense_init(k_emb, (cfg.vocab_size, d), d),
        "layers": layers,
        "final_norm": jnp.ones((d,), cfg.dtype),
        "lm_head": dense_init(k_out, (d, cfg.vocab_size), d),
    }


def _scores_at(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """scores [T, E], idx [T, k] -> scores[t, idx[t, j]] [T, k], as the
    maximum over the experts of the one score a mask lets through: exact,
    its gradient too, and one fused pass where ``take_along_axis`` is XLA's
    gather of T*k scalars at 10 ns each (0.66 ms a call at OLMoE's shape;
    PERF.md section 6, PR 39). A maximum and no sum: XLA merges a sum over
    the experts with the renormalisation's sum over the k choices, and the
    merged sum adds in another order."""
    hot = idx[:, :, None] == jnp.arange(scores.shape[1], dtype=idx.dtype)
    return jnp.max(jnp.where(hot, scores[:, None, :], -jnp.inf), axis=-1)


def _counts(idx: jax.Array, num_experts: int) -> jax.Array:
    """idx [T, k] -> [E] int32: the (token, choice) pairs each expert has
    (a masked sum again: the scatter-add of T*k ones was 0.57 ms a call)."""
    hot = idx.reshape(-1, 1) == jnp.arange(num_experts, dtype=idx.dtype)
    return jnp.sum(hot, axis=0, dtype=jnp.int32)


def _within_groups(decide: jax.Array, cfg: MoEConfig):
    """The group limit. decide [T, E] -> (``decide`` with the experts of
    every group but the token's ``topk_group`` best at -inf, the score of
    its last kept group, that of its best dropped one (equal where no group
    is dropped)). A group's score is ``cfg.topk_method``'s: the sum of its
    best two, or its best one."""
    T, E = decide.shape
    groups = cfg.n_group
    grouped = decide.reshape(T, groups, E // groups)
    if cfg.topk_method == "group_limited_greedy":
        score = jnp.max(grouped, axis=-1)
    else:
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
    top_g, top_i = jax.lax.top_k(score, min(cfg.topk_group + 1, groups))
    kept = jnp.any(top_i[:, :cfg.topk_group, None]
                   == jnp.arange(groups, dtype=top_i.dtype), axis=1)  # [T, groups]
    kept = jnp.repeat(kept, E // groups, axis=1)
    return (jnp.where(kept, decide, -jnp.inf), top_g[:, cfg.topk_group - 1],
            top_g[:, -1])


def _choose(
    scores: jax.Array, cfg: MoEConfig, routing: Optional[jax.Array],
    bias: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """The experts each token uses and their gates. scores: [T, E] f32, the
    configuration's scoring of the router's logits; bias: [E] f32 or None.

    Returns (gates [T,k] f32, idx [T,k] int32, free). ``idx`` is ``routing``
    where one is given (replay) and the top-k of ``scores + bias`` otherwise;
    the gates are this model's own ``scores`` (without the bias) at ``idx``
    either way, so the router's gradient flows the same. ``free``: what the
    model would have chosen by itself (``routing`` [T,k]) and what decided
    it, ``scores + bias`` of its k-th and (k+1)-th choice (``p_kth``,
    ``p_next``: how near a tie the decision was; equal where there is no
    (k+1)-th expert; under a group limit ``p_next`` is raised to ``p_kth``
    times the best dropped group's score over the last kept one's where that
    is nearer: the nearer tie of the two, relative to its larger side).
    """
    k = cfg.top_k
    decide = scores if bias is None else scores + bias
    if cfg.n_group > 1:
        decide, g_kth, g_next = _within_groups(decide, cfg)
    top_p, top_i = jax.lax.top_k(decide, min(k + 1, cfg.num_experts))
    free = {"routing": top_i[:, :k], "p_kth": top_p[:, k - 1],
            "p_next": top_p[:, -1]}
    if cfg.n_group > 1:
        # the nearer tie of the two decisions taken: a token whose groups
        # change chooses other experts however clear its k-th expert was.
        # ``p_next`` is moved up to where the experts' tie is as near as the
        # groups' (continuous in the scores: no flag to flip at a tie of ties)
        free["p_next"] = jnp.maximum(free["p_next"], free["p_kth"] * (g_next / g_kth))
    idx = free["routing"] if routing is None else routing.astype(jnp.int32)
    gates = _scores_at(scores, idx)
    if cfg.norm_topk_prob:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + cfg.gate_eps)
    if cfg.routed_scaling != 1.0:
        gates = gates * cfg.routed_scaling
    return gates, idx, free


def _queue_positions(
    idx: jax.Array, num_experts: int, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Capacity path: each (token, choice)'s place in its expert's queue.

    Returns (pos [T,k] int32, within [T,k] bool). Slot 0 has queue priority
    over slot 1, earlier tokens over later — all dense cumsums/one-hots over
    [T, E], static shapes, no sorting. The [T, E, C] routing tensors are
    never materialized (at training shapes they would dwarf the
    activations); dispatch is scatter/gather in :func:`moe_ffn`.
    """
    pos_cols = []
    counts = jnp.zeros((num_experts,), jnp.int32)
    for j in range(idx.shape[1]):  # static, small
        mask = jax.nn.one_hot(idx[:, j], num_experts, dtype=jnp.int32)  # [T, E]
        pos = jnp.cumsum(mask, axis=0) - 1 + counts[None, :]
        counts = counts + jnp.sum(mask, axis=0)
        pos_cols.append(jnp.sum(pos * mask, axis=-1))  # [T]
    pos = jnp.stack(pos_cols, axis=1)
    return pos, pos < capacity


def _top_k_dispatch(
    probs: jax.Array, top_k: int, capacity: int
) -> Tuple[jax.Array, jax.Array]:
    """Dense [T, E, C] combine/dispatch tensors of GShard top-k routing with
    per-expert capacity, gates renormalised — test/reference form only; the
    model uses the scatter/gather path (:func:`_capacity_ffn`)."""
    T, E = probs.shape
    gates, idx = jax.lax.top_k(probs, top_k)  # [T, k]
    gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
    pos, within = _queue_positions(idx, E, capacity)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    for j in range(top_k):
        combine = combine + (
            gates[:, j, None, None]
            * within[:, j].astype(jnp.float32)[:, None, None]
            * jax.nn.one_hot(idx[:, j], E)[:, :, None]
            * jax.nn.one_hot(pos[:, j], capacity)[:, None, :]
        )
    dispatch = (combine > 0).astype(jnp.float32)
    return combine, dispatch


def _capacity_ffn(flat, gates, idx, w_gate, w_up, w_down, capacity):
    """The capacity path: scatter-add into the [E*C, d] expert slot buffer,
    batched [E, C, d] x [E, d, h] expert matmuls on the MXU (the ``ep``
    sharding of the E dim is where XLA inserts the all-to-alls), gather
    back — O(T*d) routing memory. A choice past its expert's capacity is
    dropped."""
    (T, d), E, C = flat.shape, w_gate.shape[0], capacity
    pos, within = _queue_positions(idx, E, C)
    # slot id in the flattened [E*C] expert queue; out-of-capacity tokens are
    # parked on slot 0 with zero weight (mode="drop" would also work, but an
    # explicit zero weight keeps the gradient story obvious)
    slots = idx * C + jnp.minimum(pos, C - 1)  # [T, k]
    keep = within.astype(flat.dtype)  # [T, k]

    buf = jnp.zeros((E * C, d), flat.dtype)
    for j in range(idx.shape[1]):
        buf = buf.at[slots[:, j]].add(flat * keep[:, j, None])
    expert_in = buf.reshape(E, C, d)

    h = jax.nn.silu(jnp.einsum("ecd,edh->ech", expert_in, w_gate)) * jnp.einsum(
        "ecd,edh->ech", expert_in, w_up
    )
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_down).reshape(E * C, d)

    out = jnp.zeros((T, d), flat.dtype)
    for j in range(idx.shape[1]):
        w = (gates[:, j].astype(flat.dtype) * keep[:, j])[:, None]
        out = out + expert_out[slots[:, j]] * w
    return out


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, take, back, fan):
    """``x[take]`` whose backward pass is a gather too: ``back`` lists, for
    each row of ``x`` in turn, the ``fan`` rows of the result that came from
    it (for a permutation, its inverse), so the cotangent is gathered and
    summed in groups of ``fan`` where XLA would scatter-add. The rows move
    through ``ops/take_rows.py``: on a TPU ``x`` is put into VMEM first."""
    return take_rows(x, take)


def _take_rows_fwd(x, take, back, fan):
    return take_rows(x, take), back


def _take_rows_bwd(fan, back, g):
    g = g[back]
    if fan > 1:
        g = g.reshape(-1, fan, g.shape[-1]).sum(axis=1)
    return g, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _permuted(v, back):
    """``v[at]`` for v [M] and the permutation ``at`` whose inverse is
    ``back``, as a sort of ``v`` by ``back`` (0.1 ms for 65,536 scalars where
    XLA's gather of them takes 0.52)."""
    return jax.lax.sort_key_val(back, v)[1]


@jax.custom_vjp
def _combine(rows, weights, inverse, order):
    """rows [T*k, d] in expert order, weights [T, k] -> [T, d]: each token's
    k rows brought back (``rows[inverse]``), weighted and summed over the k
    choices. ``order`` is ``inverse``'s inverse, for the backward pass: the
    rows' cotangent is the token's cotangent times the row's weight, so it
    is gathered from the ``[T, d]`` cotangent at ``order // k`` where
    autodiff would build the ``[T*k, d]`` products in token order and permute
    them, and the weights' cotangent is taken against ``rows`` as they lie
    and its T*k scalars permuted, where autodiff would gather the rows once
    more. XLA's gather moves rows out of a source that small (it fits the
    chip's fast memory) five times as fast as it permutes ``[T*k, d]``
    (PERF.md section 6, PR 39). The same products and sums either way."""
    (T, k), d = weights.shape, rows.shape[-1]
    return jnp.sum(rows[inverse].reshape(T, k, d) * weights[..., None], axis=1)


def _combine_fwd(rows, weights, inverse, order):
    return _combine(rows, weights, inverse, order), (rows, weights, inverse, order)


def _combine_bwd(saved, g):
    rows, weights, inverse, order = saved
    at_rows = g[order // weights.shape[1]]  # [T*k, d]: a row's token's cotangent
    # the product's own pullback, so that both cotangents are the primitives
    # autodiff would have emitted (the weights': a reduce_sum in rows' dtype)
    _, pullback = jax.vjp(lambda r, w: r * w[:, None], rows,
                          _permuted(weights.reshape(-1), inverse))  # [order]
    d_rows, d_weights = pullback(at_rows)
    return d_rows, _permuted(d_weights, order).reshape(weights.shape), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _grouped_matmul(rows, weights, sizes):
    """rows [M, a] sorted by group, weights [E, a, b], sizes [E] -> [M, b]:
    each row times its group's matrix, float32 accumulation inside the
    kernel. ``megablox.gmm`` (a Pallas kernel with its own VJP: one more
    grouped product for the rows' cotangent, a transposed one for the
    weights'); tiles of 512 rows x 1024 x 1024 measured 2.5x
    ``lax.ragged_dot`` at OLMoE's shape on a v5e, and the kernel's default
    128-cubed tiles 14x slower than these (PERF.md section 6, PR 28). Off the
    TPU the kernel runs interpreted. Imported here: a dense model's process
    never pays for loading Pallas' TPU kernels."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    (m, a), b = rows.shape, weights.shape[2]
    tiling = (math.gcd(m, 512), min(a, 1024), min(b, 1024))
    return gmm(rows, weights, sizes, rows.dtype, tiling,
               interpret=jax.default_backend() != "tpu")


def _hidden(rows, w_gate, w_up, product, act):
    """An expert's hidden activations in the configuration's form (``act``:
    ``MoEConfig.expert_act``): SwiGLU's ``silu(gate) * up``, or, ungated
    (``w_gate`` is None), ``relu(up)^2``. ``product(rows, matrix)``: the
    grouped product of the routed experts, a plain one for the shared."""
    if act == "relu2":
        return jnp.square(jax.nn.relu(product(rows, w_up)))
    return jax.nn.silu(product(rows, w_gate)) * product(rows, w_up)


def _dropless_ffn(flat, gates, idx, sizes, w_gate, w_up, w_down, act="swiglu"):
    """The dropless path: the T*k (token, choice) pairs sorted by expert
    (stable: an expert sees its tokens in order), one grouped matrix
    multiplication per projection (three for SwiGLU, two for the ungated
    form) over the T*k rows with the per-expert
    counts as group sizes, unsorted, weighted by the gates and summed over
    the k choices. Every shape is static (T*k rows whatever the load); no
    pair is dropped, also when every token picks the same expert.
    ``sizes`` [E] int32: the pairs each expert was given."""
    T, k = flat.shape[0], idx.shape[1]
    with jax.named_scope("moe/route"):
        expert_of = idx.reshape(T * k)
        order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32), unique_indices=True)
    with jax.named_scope("moe/dispatch"):
        rows = _take_rows(flat, order // k, inverse, k)  # [T*k, d]
    with jax.named_scope("moe/experts"):
        h = _hidden(rows, w_gate, w_up, lambda r, w: _grouped_matmul(r, w, sizes), act)
        rows = _grouped_matmul(h, w_down, sizes)
    with jax.named_scope("moe/combine"):
        return _combine(rows, gates.astype(flat.dtype), inverse, order)


def _share_sizes(counts: jax.Array, rows_n: int) -> jax.Array:
    """counts [held] -> the group sizes of a share's grouped products: the
    held pairs' own counts, clipped to the buffer's ``rows_n`` rows (they sum
    to ``min(pairs, rows_n)``). The rows the pairs leave free belong to NO
    group: ``megablox`` takes its grid from the sizes, so the row tiles past
    the last pair are not visited (a tile the last pair shares is, at most one
    more an expert where a group's border cuts a tile) and the products' time
    follows the load, not the room."""
    ends = jnp.minimum(jnp.cumsum(counts), rows_n)
    return jnp.diff(ends, prepend=0).astype(jnp.int32)


# rows a step of a share's gathers (:func:`_over_tiles`): four tiles of the
# grouped products' 512
MOVE_TILE = 2048


def _move_tile(rows_n: int) -> int:
    """The row tile of a share's gathers for a buffer of ``rows_n`` rows: the
    largest part of ``MOVE_TILE`` that divides them."""
    return math.gcd(rows_n, MOVE_TILE)


def _moved_tiles(n, rows_n: int):
    """Row tiles the first ``min(n, rows_n)`` rows of a buffer reach."""
    tile = _move_tile(rows_n)
    return (jnp.minimum(n, rows_n) + tile - 1) // tile


def _over_tiles(n, rows_n: int, body, init):
    """``carry = body(cut, put, valid, carry)`` for each row tile that the
    first ``n`` of a buffer's ``rows_n`` rows reach, in order, and for no
    other: a loop whose trip count is a count the router already has, so a
    gather's time follows the pairs and not the room. ``cut(x)`` is the tile
    of a per-row array, ``put(x, rows)`` is ``x`` with ``rows`` in the tile's
    place; ``valid`` [tile, 1]: the rows that hold a pair (in the last tile
    the pairs reach, the rows before ``n``; every row once ``n`` passes the
    buffer). Nobody differentiates through it: the moves bring pullbacks of
    their own."""
    tile = _move_tile(rows_n)

    def step(i, carry):
        at = i * tile
        return body(lambda x: jax.lax.dynamic_slice_in_dim(x, at, tile),
                    lambda x, rows: jax.lax.dynamic_update_slice_in_dim(x, rows, at, 0),
                    (at + jnp.arange(tile) < n)[:, None], carry)

    return jax.lax.fori_loop(0, _moved_tiles(n, rows_n), step, init)


def _held(take, n):
    """[rows_n, 1]: the rows of a share's buffer that hold a pair, the first ``n``."""
    return (jnp.arange(take.shape[0]) < n)[:, None]


def _token_order(take, n, tokens: int):
    """take [rows_n]: the token of each row of a share's buffer, of which the
    first ``n`` hold a pair -> (``key_s``, ``perm``), both [rows_n] int32: the
    rows' tokens ascending (``tokens`` for a row that holds no pair: they sort
    last) and the rows in that order. The sort is stable: a token's rows keep
    the buffer's order."""
    key = jnp.where(_held(take, n)[:, 0], take, tokens)
    return tuple(jax.lax.sort_key_val(key, jnp.arange(take.shape[0], dtype=jnp.int32)))


# the widest rows XLA's sorted scatter-add was read on its fast side at
# (Nemotron's; 60-115 ns a row from 2,304 up to here): wider rows go in blocks
ADD_COLUMNS = 2688


def _add_block(d: int) -> int:
    """The columns a scatter-add of rows of ``d`` takes at once: the widest
    multiple of 128 lanes under ``ADD_COLUMNS`` that divides ``d``; ``d``
    itself where it is no multiple of 128 (a debug width)."""
    if d % 128:
        return d
    return max(c for c in range(128, min(d, ADD_COLUMNS) + 1, 128) if d % c == 0)


def _add_in_token_order(rows, by_token, tokens: int):
    """rows [rows_n, d] -> [tokens, d]: each row added at its token by XLA's
    scatter-add, the rows brought into token order first (``by_token``:
    :func:`_token_order`'s pair), in column blocks of ``ADD_COLUMNS`` at
    most (the widest multiple of 128 lanes under it that divides ``d``).
    Sorted, a token's rows lie side by side and XLA adds the run in float32
    before it rounds; XLA sorts and gathers like this by itself, so at the
    widths up to ``ADD_COLUMNS`` (one block) this is its own program
    written out, sums and all. The blocks are for what it does NOT do by
    itself: at a width of 5,120 its pass over the ``[tokens, d]`` operand
    alone takes 28.5 ms whatever the rows, and the same rows in two blocks
    of 2,560 take 4.0 (PERF.md section 6, PR 60). A row that holds no pair
    has the key ``tokens`` and is dropped; the callers select it away all
    the same, because it is gathered."""
    key_s, perm = by_token
    rows, d, dc = rows[perm], rows.shape[1], _add_block(rows.shape[1])
    blocks = [jnp.zeros((tokens, dc), rows.dtype).at[key_s].add(
        rows[:, at:at + dc], indices_are_sorted=True, mode="drop") for at in range(0, d, dc)]
    return blocks[0] if len(blocks) == 1 else jnp.concatenate(blocks, axis=1)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _share_take(flat, take, by_token, n, tokens):
    """flat [tokens, d], take [rows_n] -> [rows_n, d]: ``flat[take]`` in the
    first ``n`` rows and zeros after them, gathered tile by tile as far as
    the ``n`` rows go (:func:`_over_tiles`). Its pullback adds the
    cotangent's rows into ``[tokens, d]`` in token order
    (:func:`_add_in_token_order`), over every row of the buffer. A row past
    ``n`` is SELECTED away both ways. (``tokens`` is ``flat.shape[0]``, given
    apart because a pullback's residuals carry no shape.)"""
    def body(cut, put, valid, buf):
        return put(buf, jnp.where(valid, flat[cut(take)], 0))

    return _over_tiles(n, take.shape[0], body,
                       jnp.zeros((take.shape[0], flat.shape[1]), flat.dtype))


def _share_take_fwd(flat, take, by_token, n, tokens):
    return _share_take(flat, take, by_token, n, tokens), (take, by_token, n)


def _share_take_bwd(tokens, saved, g):
    take, by_token, n = saved
    return (_add_in_token_order(jnp.where(_held(take, n), g, 0), by_token, tokens),
            None, None, None)


_share_take.defvjp(_share_take_fwd, _share_take_bwd)


def _weighed(valid, rows, weights):
    """Rows of the buffer as the combine adds them: selected, then weighed."""
    return jnp.where(valid, rows, 0) * weights


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _share_add(rows, weights, take, by_token, n, tokens):
    """rows [rows_n, d], weights [rows_n, 1] -> [tokens, d]: the first ``n``
    rows, each times its weight, added at ``take`` in token order
    (:func:`_add_in_token_order`: a token's rows in the buffer's order, the
    run's sum rounded once on the chip). Its pullback gathers the cotangent
    at ``take`` tile by tile as far as the ``n`` rows go (:func:`_over_tiles`)
    and takes each tile through the product's own pullback (the primitives
    autodiff emits for the whole buffer); the cotangents of the rows and
    weights that no tile reaches are zeros."""
    return _add_in_token_order(_weighed(_held(take, n), rows, weights), by_token, tokens)


def _share_add_fwd(rows, weights, take, by_token, n, tokens):
    return (_share_add(rows, weights, take, by_token, n, tokens),
            (rows, weights, take, n))


def _share_add_bwd(tokens, saved, g):
    rows, weights, take, n = saved

    def body(cut, put, valid, carry):
        _, pullback = jax.vjp(partial(_weighed, valid), cut(rows), cut(weights))
        return tuple(map(put, carry, pullback(g[cut(take)])))

    return (*_over_tiles(n, rows.shape[0], body,
                         (jnp.zeros_like(rows), jnp.zeros_like(weights))),
            None, None, None)


_share_add.defvjp(_share_add_fwd, _share_add_bwd)


def _share_ffn(flat, gates, idx, cfg, w_gate, w_up, w_down):
    """The dropless path of a chip that holds ``cfg.held_experts`` alone:
    of the T*k (token, choice) pairs those whose expert is held, sorted by
    expert, in a buffer of ``cfg.share_rows(T)`` rows (the even share with
    room, never T*k); the pairs of absent experts are computed by nobody,
    and so are held pairs beyond the buffer (``overflow``). -> (the held
    experts' part of the block's output [T, d], stats: ``counts`` [held],
    ``held_pairs``, ``overflow``, ``visited``: the part of the buffer's rows
    the grouped products visit, ``min(held_pairs, rows) / rows``, ``moved``:
    the part the gathers touch, the same in whole tiles of
    :func:`_move_tile` rows).

    The rows leave by a gather that runs tile by tile as far as the held
    pairs go, and so does the gather of the combine's pullback
    (:func:`_share_take`, :func:`_share_add`); the grouped products visit
    the held pairs only (:func:`_share_sizes`). The two adds into ``[T, d]``
    (the combine, the dispatch's pullback) go in TOKEN order
    (:func:`_token_order`, once a layer; :func:`_add_in_token_order`): the
    held pairs sorted by token, the rows gathered into that order and
    scatter-added sorted, in column blocks where the rows are wider than
    XLA's scatter-add takes at speed. They follow the room still: every row
    of the buffer is gathered and added. The custom pullbacks of the
    whole-layer path gather T*k rows; a share's buffer is a sixteenth of
    that at the published cut.

    What a grouped product leaves past the pairs is whatever its output
    buffer held (NaN under the interpreter, any bits on a chip), so no
    product with such a row may reach a sum: inside the last tile the pairs
    reach the rows are SELECTED by ``valid`` before they are weighed, and a
    cotangent leaves the buffer's tail through a select's pullback (a
    select), never through a product with a mask (NaN x 0 is NaN)."""
    (T, d), k = flat.shape, idx.shape[1]
    first, held = cfg.held_experts
    rows_n = cfg.share_rows(T)
    with jax.named_scope("moe/route"):
        local = idx.reshape(T * k) - first
        local = jnp.where((local >= 0) & (local < held), local, held)  # absent: last
        counts = _counts(local[:, None], held)
        pairs = jnp.sum(counts)
        order = jnp.argsort(local, stable=True).astype(jnp.int32)[:rows_n]
        valid = _held(order, pairs)
        sizes = _share_sizes(counts, rows_n)
        weights = jnp.where(valid, gates.reshape(T * k, 1)[order], 0.0)
        by_token = _token_order(order // k, pairs, T)
    with jax.named_scope("moe/dispatch"):
        rows = _share_take(flat, order // k, by_token, pairs, T)
    with jax.named_scope("moe/experts"):
        h = _hidden(rows, w_gate, w_up, lambda r, w: _grouped_matmul(r, w, sizes),
                    cfg.expert_act)
        rows = _grouped_matmul(h, w_down, sizes)
    with jax.named_scope("moe/combine"):
        out = _share_add(rows, weights.astype(flat.dtype), order // k, by_token, pairs, T)
    return out, {"counts": counts, "held_pairs": pairs,
                 "overflow": jnp.maximum(pairs - rows_n, 0),
                 "visited": jnp.minimum(pairs, rows_n).astype(jnp.float32) / rows_n,
                 "moved": (_moved_tiles(pairs, rows_n) * _move_tile(rows_n)).astype(jnp.float32)
                 / rows_n}


def _groups_hit(idx: jax.Array, cfg: MoEConfig) -> jax.Array:
    """idx [T, k] -> the mean number of groups a token's k experts lie in."""
    group = idx // (cfg.num_experts // cfg.n_group)
    hit = jnp.any(group[:, :, None] == jnp.arange(cfg.n_group, dtype=idx.dtype), axis=1)
    return jnp.mean(jnp.sum(hit, axis=-1).astype(jnp.float32))


def moe_ffn(
    x: jax.Array,
    router: jax.Array,
    w_gate: Optional[jax.Array],
    w_up: jax.Array,
    w_down: jax.Array,
    cfg: MoEConfig,
    routing: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    shared: Optional[Tuple[Optional[jax.Array], jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The sparse feed-forward block, its experts in the configuration's
    form (``cfg.expert_act``: SwiGLU, or ungated relu^2, for which ``w_gate``
    and the shared expert's gate are None). x: [B, S, d] -> ([B, S, d], stats).

    ``routing`` ([T, k] expert indices, T = B*S): replay these choices
    instead of the router's own (:func:`_choose`). ``bias`` ([E] f32): added
    to the scores for the decision alone. ``stats``: ``counts``
    [E] (pairs each expert was given under the routing in effect, dropped
    ones included), ``prob_sum`` [E] (the router's scores summed
    over tokens: the differentiable half of the auxiliary loss), the
    free routing with its margins (``routing``, ``p_kth``, ``p_next``: of
    ``scores + bias``, what decided) and, under a bias, ``bias_moved``: the
    share of tokens whose k experts would be others without it; where
    ``cfg.seq_aux``, ``seq_aux``: the layer's :func:`sequence_balance_loss`
    under the routing in effect (differentiable: the caller's loss adds it).

    What the configuration may add, each absent unless it says so:
    ``cfg.n_group`` > 1 limits a token's choice to its ``topk_group`` best
    groups (:func:`_within_groups`; the margin is then the nearer tie of
    the two decisions, and ``groups_hit`` the mean number of groups a token
    uses); ``cfg.routed_scaling`` multiplies the gates;
    ``cfg.held_experts`` makes this a chip's SHARE of the layer: the router
    and the decision over all ``num_experts``, the expert leaves
    ``[held, ...]``, the output the held experts' part alone
    (:func:`_share_ffn`; ``counts`` are then the held experts', beside
    ``held_pairs``, ``overflow``, ``visited`` and ``moved``); ``shared`` (gate, up,
    down) is one expert of the same form, as wide as its matrices are, that
    every token passes, added to the output without a router's gate.
    """
    B, S, d = x.shape
    T = B * S
    flat = x.reshape(T, d)

    with jax.named_scope("moe/route"):
        # float32 in earnest: a TPU multiplies f32 matrices in one bf16 pass
        # unless told otherwise, and a router's decisions sit on near-ties
        logits = jnp.matmul(flat.astype(jnp.float32), router,
                            precision=ROUTER_PRECISION)  # [T, E]
        probs = (jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))
        gates, idx, free = _choose(probs, cfg, routing, bias)
        if bias is not None:
            unbiased = (jax.lax.top_k(probs, cfg.top_k)[1] if cfg.n_group == 1
                        else _choose(probs, cfg, None)[2]["routing"])
            kept = jnp.any(free["routing"][:, :, None] == unbiased[:, None, :], axis=-1)
            free["bias_moved"] = 1.0 - jnp.mean(jnp.all(kept, axis=-1).astype(jnp.float32))
        if cfg.n_group > 1:
            free["groups_hit"] = _groups_hit(idx, cfg)
        if cfg.held_experts is None:
            sizes = _counts(idx, cfg.num_experts)
    if cfg.seq_aux:
        with jax.named_scope("moe/aux"):
            free["seq_aux"] = sequence_balance_loss(
                probs.reshape(B, S, -1), idx.reshape(B, S, -1))
    payload = flat.astype(w_up.dtype)  # the router saw x as it came
    if cfg.held_experts is not None:
        out, share = _share_ffn(payload, gates, idx, cfg, w_gate, w_up, w_down)
        sizes = share.pop("counts")
        free.update(share)
    elif cfg.capacity_factor is None:
        out = _dropless_ffn(payload, gates, idx, sizes, w_gate, w_up, w_down,
                            cfg.expert_act)
    else:
        out = _capacity_ffn(payload, gates, idx, w_gate, w_up, w_down, cfg.capacity(T))
    if shared is not None:
        with jax.named_scope("moe/shared"):
            s_gate, s_up, s_down = shared
            out = out + _hidden(payload, s_gate, s_up, jnp.matmul, cfg.expert_act) @ s_down
    stats = {"counts": sizes.astype(jnp.float32), "prob_sum": jnp.sum(probs, axis=0),
             **free}
    return out.astype(x.dtype).reshape(B, S, d), stats


def load_balancing_loss(counts: jax.Array, prob_sum: jax.Array, tokens: int) -> jax.Array:
    """``load_balancing_loss_func`` of the published Mixtral / OLMoE
    modelling code: the layers' router outputs are concatenated, every one
    of a token's k choices counts, and the value is E * sum_e f_e * P_e with
    f_e the share of (layer, token) pairs that chose expert e among their k
    (sums to k) and P_e the mean probability of e. counts, prob_sum: [L, E]
    per layer; ``tokens`` per layer. Even load gives k."""
    n = counts.shape[0] * tokens
    return _balance(jnp.sum(counts, axis=0) / n, jnp.sum(prob_sum, axis=0) / n)


def _balance(f: jax.Array, p: jax.Array) -> jax.Array:
    """E * sum_e f_e * P_e over the last axis, ``f`` (the load) a constant:
    what the two auxiliary losses share."""
    return f.shape[-1] * jnp.sum(jax.lax.stop_gradient(f) * p, axis=-1)


def sequence_balance_loss(probs: jax.Array, idx: jax.Array) -> jax.Array:
    """DeepSeek-V2's ``seq_aux`` term of one expert layer: probs [B, S, E]
    (the router's scores over ALL its outputs), idx [B, S, k] (the experts in
    effect) -> the mean over sequences of ``sum_e f_e P_e``, ``f_e`` = (the
    sequence's tokens that chose e among their k) x E / (k S), no gradient;
    ``P_e`` = e's mean score over the sequence. Even load gives 1. A chip
    that holds a share of the experts has the whole router, so the term is
    the deployment's own."""
    (B, S, E), k = probs.shape, idx.shape[-1]
    hot = idx.reshape(B, S * k, 1) == jnp.arange(E, dtype=idx.dtype)
    f = jnp.sum(hot, axis=1, dtype=jnp.float32) / (S * k)
    return jnp.mean(_balance(f, jnp.mean(probs, axis=1)))


def expert_scalars(stats: Dict[str, jax.Array], pairs: int,
                   mean_floor: Optional[float] = 1e-9) -> Dict[str, jax.Array]:
    """:func:`moe_ffn`'s stats stacked over the expert layers -> the same
    dict, the per-layer counters a configuration gives replaced by the
    scalars a loop logs: ``load_max_over_mean`` (the busiest expert's pairs
    over the mean, the maximum over layers: 1 is even; over the HELD experts
    under a share), ``bias_moved_share`` (the (layer, token) pairs whose k
    experts under ``scores + bias`` are not the k under the scores alone: 0
    says the bias does not reach the selection, near 1 that it drowns the
    scores), ``groups_hit_mean`` (the groups a token's k experts lie in: at
    most ``topk_group``), ``held_pair_share`` (the pairs that reached a held
    expert over ``pairs`` = T * k: evenly, held / num_experts),
    ``overflow_pairs`` (held pairs that found the share's buffer full, over
    all layers: computed by nobody, so anything but 0 is a wrong step),
    ``visited_row_share`` (the part of the share's buffer its grouped
    products visit, ``min(held pairs, rows) / rows``, the mean over layers:
    evenly about 1 / ``share_room``; 1.0 is every row multiplied, which is
    what overflow costs) and ``moved_row_share`` (the part its gathers move,
    the dispatch's and the combine's pullback's: ``visited_row_share`` in
    whole tiles of :func:`_move_tile` rows, so at most a tile above it; 1.0
    is every row gathered).

    ``mean_floor`` guards the mean of a share's counts, which may all be
    zero; None divides by the mean as it is: LFM2's program (every expert
    held: the mean is T * k / E). Another guard is another compiled step,
    and LFM2's is not moved to it here."""
    if not stats:  # no expert layer
        return stats
    counts = stats.pop("counts")
    busiest, mean = jnp.max(counts, axis=1), jnp.mean(counts, axis=1)
    stats["load_max_over_mean"] = jnp.max(
        busiest / (mean if mean_floor is None else jnp.maximum(mean, mean_floor)))
    if "bias_moved" in stats:
        stats["bias_moved_share"] = jnp.mean(stats.pop("bias_moved"))
    if "groups_hit" in stats:
        stats["groups_hit_mean"] = jnp.mean(stats.pop("groups_hit"))
    if "held_pairs" in stats:
        stats["held_pair_share"] = jnp.mean(
            stats.pop("held_pairs").astype(jnp.float32)) / pairs
        stats["overflow_pairs"] = jnp.sum(stats.pop("overflow"))
        stats["visited_row_share"] = jnp.mean(stats.pop("visited"))
        stats["moved_row_share"] = jnp.mean(stats.pop("moved"))
    return stats


def ffn_leaves(cfg: MoEConfig, kind: str, shared: bool = False
               ) -> Dict[str, Tuple[int, Tuple[int, ...], int, Any]]:
    """The feed-forward leaves of one layer that ends in a SwiGLU (``kind``
    "dense", ``cfg.ffn_hidden`` wide) or in routed experts ("moe",
    ``cfg.moe_intermediate_size`` wide, ``cfg.n_held`` of them here, in the
    configuration's form: no ``w_gate`` where ``cfg.expert_act`` is the
    ungated "relu2"; beside one ``shared`` expert of that form where the
    family has one, ``cfg.shared_intermediate_size`` wide where that is
    given): leaf -> (which of the
    layer's feed-forward keys draws it, its shape and its fan-in without the
    layers' axis, its PartitionSpec with it). One table for :func:`ffn_init`
    and :func:`ffn_specs`."""
    from jax.sharding import PartitionSpec as P

    d, f = cfg.dim, cfg.ffn_hidden
    col, row = P(None, "fsdp", "tp"), P(None, "tp", "fsdp")
    if kind == "dense":
        return {"w_gate": (0, (d, f), d, col), "w_up": (1, (d, f), d, col),
                "w_down": (2, (f, d), f, row)}
    held, W = cfg.n_held, cfg.moe_intermediate_size
    leaves = {"router": (3, (d, cfg.num_experts), d, P(None, "fsdp", None)),
              "w_gate": (0, (held, d, W), d, P(None, "ep", "fsdp", "tp")),
              "w_up": (1, (held, d, W), d, P(None, "ep", "fsdp", "tp")),
              "w_down": (2, (held, W, d), W, P(None, "ep", "tp", "fsdp"))}
    if shared:
        S = cfg.shared_intermediate_size or W
        leaves.update(shared_gate=(4, (d, S), d, col), shared_up=(5, (d, S), d, col),
                      shared_down=(6, (S, d), S, row))
    if cfg.expert_act == "relu2":
        leaves = {name: leaf for name, leaf in leaves.items() if "gate" not in name}
    return leaves


def ffn_init(leaves: Dict[str, Any], keys: jax.Array, L: int, dtype: Any
             ) -> Dict[str, jax.Array]:
    """:func:`ffn_leaves`' leaves stacked over ``L`` layers, normal over the
    root of the fan-in; the router stays float32: its scores drive routing
    decisions."""
    return {name: (jax.random.normal(keys[at], (L, *shape), jnp.float32) / jnp.sqrt(fan_in)
                   ).astype(jnp.float32 if name == "router" else dtype)
            for name, (at, shape, fan_in, _) in leaves.items()}


def ffn_specs(leaves: Dict[str, Any]) -> Dict[str, Any]:
    """:func:`ffn_leaves`' PartitionSpecs."""
    return {name: leaf[3] for name, leaf in leaves.items()}


def _moe_layer(cfg, attention, positions, h, xs):
    """The ONE scanned MoE layer body (:func:`moe_forward`'s scan and the
    staged gradient's segment programs, :func:`moe_stages`): ``xs`` is
    ``(layer_params, replay)``; -> ``(h, moe_ffn's stats)``."""
    layer_params, replay = xs
    B, S = h.shape[0], h.shape[1]
    x = _rmsnorm(h, layer_params["attn_norm"], cfg.norm_eps)
    q, k = x @ layer_params["wq"], x @ layer_params["wk"]
    if cfg.qk_norm:  # over the whole projection, before the heads split
        q = _rmsnorm(q, layer_params["q_norm"], cfg.norm_eps)
        k = _rmsnorm(k, layer_params["k_norm"], cfg.norm_eps)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (x @ layer_params["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = _rope(q, cfg.rope_theta, positions)
    k = _rope(k, cfg.rope_theta, positions)
    attn = jax.ad_checkpoint.checkpoint_name(
        attention(q, k, v, cfg), ATTN_OUT_NAME
    ).reshape(B, S, cfg.n_heads * cfg.head_dim)
    h = h + attn @ layer_params["wo"]
    x = _rmsnorm(h, layer_params["ffn_norm"], cfg.norm_eps)
    moe_out, stats = moe_ffn(
        x,
        layer_params["router"],
        layer_params.get("w_gate"),
        layer_params["w_up"],
        layer_params["w_down"],
        cfg,
        routing=replay,
    )
    return h + moe_out, stats


def moe_forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: MoEConfig,
    attention_fn: Optional[Any] = None,
    remat: Any = True,
    routing: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """tokens int32 [B, S] -> (logits f32 [B, S, V], aux loss, stats).

    ``remat`` takes the shared modes ("none"/"dots"/"attn"/"full" or bool aliases;
    torchft_tpu.models.remat). Default full remat: MoE layers hold per-expert
    activations, so the conservative mode is the safe default.

    ``routing`` [L, B*S, k]: the experts to use in each layer (replay).
    ``stats``, per layer: the routing the model would have chosen freely
    (``routing`` [L,T,k], ``p_kth``, ``p_next`` [L,T]) and the ``counts``
    [L,E] each expert was given (XLA drops what a caller does not use)."""
    attention = attention_fn or _attention
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = params["embed"][tokens]
    _refuse_dropless_ep(cfg, _sharded_axes(params["layers"]["w_up"]))
    layer = partial(_moe_layer, cfg, attention, positions)
    body = remat_wrap(layer, remat)
    h, stats = jax.lax.scan(body, h, (params["layers"], routing))
    h = _rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = (h @ params["lm_head"]).astype(jnp.float32)
    aux = load_balancing_loss(stats["counts"], stats.pop("prob_sum"), B * S)
    return logits, aux, stats


def _sharded_axes(x: Any) -> Tuple[str, ...]:
    """Mesh axis names of size > 1 a concrete array is sharded over (none
    for a tracer or an array without a named sharding)."""
    sharding = getattr(x, "sharding", None)
    spec = getattr(sharding, "spec", None) or ()
    return tuple(a for part in spec if part
                 for a in (part if isinstance(part, tuple) else (part,))
                 if sharding.mesh.shape[a] > 1)


def _refuse_dropless_ep(cfg: MoEConfig, axes: Any) -> None:
    if cfg.capacity_factor is None and "ep" in axes:
        raise ValueError(
            "the dropless path (capacity_factor=None) keeps every expert on "
            "one device: its grouped matrix multiplication is not sharded "
            "over ep and nothing here imitates that. Give the mesh ep=1, or "
            "the config a capacity_factor.")


def moe_loss_and_stats(
    params: Dict[str, Any],
    tokens: jax.Array,
    targets: jax.Array,
    cfg: MoEConfig,
    attention_fn: Optional[Any] = None,
    remat: Any = True,
    routing: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Cross-entropy (logsumexp form) + ``aux_loss_weight`` x the
    load-balancing loss (:func:`load_balancing_loss`), and stats — for
    ``value_and_grad(..., has_aux=True)``: :func:`moe_forward`'s free routing
    and the two scalars a training loop logs: ``aux_loss`` and
    ``load_max_over_mean`` (the busiest expert's pairs over the mean, the
    maximum over layers: 1 is even, ``num_experts`` is everything on one
    expert)."""
    logits, aux, stats = moe_forward(
        params, tokens, cfg, attention_fn=attention_fn, remat=remat,
        routing=routing,
    )
    loss, scalars = _loss_and_scalars(
        logits, targets, aux, stats.pop("counts"), cfg)
    return loss, {**stats, **scalars}


def _loss_and_scalars(logits, targets, aux, counts, cfg):
    """The loss of :func:`moe_loss_and_stats` from logits, the auxiliary loss
    and the per-layer ``counts`` [L,E], and the two scalars it logs."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = jnp.mean(lse - tgt) + cfg.aux_loss_weight * aux
    return loss, {
        "aux_loss": aux,
        "load_max_over_mean": jnp.max(
            jnp.max(counts, axis=1) / jnp.mean(counts, axis=1))}


def moe_stages(cfg: MoEConfig, attention_fn: Optional[Any] = None) -> Stages:
    """:func:`moe_loss_and_stats` in the three stages a staged gradient
    composes (models/staged.py). A layer emits its ``counts`` and
    ``prob_sum``: the head turns them into the auxiliary loss, whose
    cotangent reaches each layer's router through ``prob_sum``."""
    attention = attention_fn or _attention

    def layer(h, layer_params):
        positions = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])
        h, stats = _moe_layer(cfg, attention, positions, h, (layer_params, None))
        return h, {"counts": stats["counts"], "prob_sum": stats["prob_sum"]}

    def head(head_params, h, emitted, targets):
        h = _rmsnorm(h, head_params["final_norm"], cfg.norm_eps)
        logits = (h @ head_params["lm_head"]).astype(jnp.float32)
        aux = load_balancing_loss(
            emitted["counts"], emitted["prob_sum"], targets.size)
        loss, scalars = _loss_and_scalars(
            logits, targets, aux, emitted["counts"], cfg)
        return loss, scalars

    return Stages(lambda embed, tokens: embed[tokens], layer, head)


def moe_loss(*args: Any, **kw: Any) -> jax.Array:
    """:func:`moe_loss_and_stats`' loss alone (``llama_loss``'s shape)."""
    return moe_loss_and_stats(*args, **kw)[0]


def moe_param_specs(cfg: MoEConfig, mesh: Optional[Any] = None) -> Dict[str, Any]:
    """PartitionSpecs for the MoE pytree: experts over ``ep``, within-expert
    dims over fsdp/tp (Megatron column/row), dense blocks as in the HSDP
    Llama specs. ``mesh``, where the caller has one: a dropless config on a
    mesh with ep > 1 is refused here, before anything is placed (inside a
    jitted step :func:`moe_forward` sees tracers and cannot tell)."""
    from jax.sharding import PartitionSpec as P

    if mesh is not None:
        _refuse_dropless_ep(cfg, [a for a, n in mesh.shape.items() if n > 1])

    layers = {
        "attn_norm": P(None, None),
        "wq": P(None, "fsdp", "tp"),
        "wk": P(None, "fsdp", "tp"),
        "wv": P(None, "fsdp", "tp"),
        "wo": P(None, "tp", "fsdp"),
        "ffn_norm": P(None, None),
        "router": P(None, "fsdp", None),
        "w_gate": P(None, "ep", "fsdp", "tp"),
        "w_up": P(None, "ep", "fsdp", "tp"),
        "w_down": P(None, "ep", "tp", "fsdp"),
    }
    if cfg.qk_norm:
        layers["q_norm"] = layers["k_norm"] = P(None, None)
    if cfg.expert_act == "relu2":
        del layers["w_gate"]
    return {
        "embed": P("fsdp", "tp"),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P("fsdp", "tp"),
    }


def _model_fns() -> ModelFns:
    scalars = ("load_max_over_mean", "aux_loss")

    def stages(*args: Any, **kw: Any) -> Stages:
        s = moe_stages(*args, **kw)
        return s._replace(head=logged(s.head, moe=scalars))

    return ModelFns(moe_init, logged(moe_loss_and_stats, moe=scalars), moe_param_specs, stages)


register(MoEConfig, {"moe_debug" if name == "debug" else name: cfg  # the dense one is ``debug``
                     for name, cfg in MOE_CONFIGS.items()}, _model_fns)
