"""Latent-attention decoder with group-limited softmax experts, as ONE CHIP'S
SHARE of its heads and of its experts (DeepSeek-V2, ``model_type``
``deepseek_v2``) as an eleventh kind of the one trainer's model: every layer
mixes the sequence with MLA (``models/mla.py``: queries through a normalised
latent of ``q_lora_rank``, keys and values from one of ``kv_lora_rank``, 64
rotary dimensions under YaRN, no output gate); the first
``num_dense_layers`` end in a SwiGLU, the others in ``num_experts``
softmax-routed experts chosen ``top_k`` a token inside ``topk_group`` of
``n_group`` groups (a group scored by its best expert:
``group_limited_greedy``), the gates not renormalised and times
``routed_scaling``, beside the shared experts run as one SwiGLU of
``shared_intermediate_size``.

Every layer: ``h = x + mla(rmsnorm(x))``, then ``h + ffn(rmsnorm(h))``.

**The heads' share.** ``n_heads`` is the PUBLISHED count; ``held_heads``
(first, count) says which of them this chip holds, as ``held_experts`` does
for the experts. The mixer's leaves are the held heads' (``w_uq`` ``[..,
count x 192]``, ``w_kvb`` ``[.., count x 256]``, ``wo`` ``[count x 128,
..]``), both latents' projections and norms are whole, and the mixer hands
on the held heads' part of the sum over heads. The chips that share a layer
see the same tokens and the sum of their parts is the layer's output; on one
chip that exchange does not run and nothing stands in for it, so what the
other heads (and experts) would add is computed by nobody, here and in the
reference alike (``chipbench/reference_deepseek.py``). A mesh that shards
inside the layer (``tp``, ``ep``) is refused by :func:`deepseek_param_specs`:
the sum across chips that hold other heads is not built.

**YaRN.** The rotary frequencies of the 64 rotary dimensions are
``mellum.yarn_inv_freq``'s blend (``yarn_factor`` over
``yarn_original_max``); cos and sin carry ``mscale(factor, yarn_mscale) /
mscale(factor, yarn_mscale_all_dim)`` (1 as published) and the softmax scale
``mscale(factor, yarn_mscale_all_dim)`` squared, ``mscale(s, m) = 0.1 m ln s
+ 1`` (:attr:`DeepseekConfig.softmax_factor`: 1.58963 as published).

**The balance loss.** The loss is the cross-entropy over the vocabulary held
plus ``aux_loss_weight`` x the sum over the expert layers of
``moe.sequence_balance_loss`` (``seq_aux``): computed over all
``num_experts`` outputs of the whole router, which this chip has. Under a
share it is the one gradient a router gets that does not say "route to me".
The paper's device-level and communication balance losses are in no public
modelling code and are not built.

The dense layers' SwiGLU runs whole, under the scope ``ffn/block``. Blocks of
positions (``llama.swiglu``'s ``block``, which ``models/brumby.py`` needs) are
not asked for here: at hidden 5,120 and 12,288 the three [S, 12,288]
temporaries of 16,384 positions are 1.2 GB, and XLA counts the same 3.95 GiB
of temporaries for the published cut's step with blocks of 2,048 and without
(the peak stands in an expert layer), so blocks would only add their second
forward pass.

The parameters are one stack per RUN of like layers (the dense layers
together, an expert layer alone) and ``models/decoder.py`` scans the runs.

**DeepSeek sparse attention's warm-up stage** (``dsa_stage="warmup"``:
DeepSeek-V3.2-Exp, ``model_type`` ``deepseek_v32``). The same layers with a
learned scorer beside every mixer (``models/dsa.py``: ``index_n_heads``
heads of ``index_head_dim`` fed by the queries' latent and the layer's
input) under the family's later routing (``router_score="sigmoid"`` under
the frozen selection bias ``expert_bias``, a group scored by its best two,
the gates renormalised and scaled: every one a field of ``MoEConfig`` that
``moe_ffn`` already reads). In this stage the model keeps DENSE attention
and the indexers are the only parameters: the tree is ``embed``, ``layers``,
``expert_bias`` (all frozen: :func:`frozen_keys`) and ``indexer`` (one stack
a run, as ``layers``); it has no final norm and no head, for the loss is the
sum over the layers of ``L_layer``, the KL divergence of the indexer's
softmax from the head-sum of the layer's own attention probabilities, and
nothing over the vocabulary. ``remat`` is not read: nothing frozen has a
backward pass, and a layer's indexer gradient is taken inside its forward
pass (``dsa.layer_kl``). The stage that follows in the source's recipe
(``dsa_stage="sparse"``: each query attends to the ``index_topk`` keys its
indexer scores highest and every parameter trains) is NOT built and is
refused by name: the attention kernels have no mask that is data.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models import dsa
from torchft_tpu.models.decoder import Decoder, init_tree, runs_of, spec_tree
from torchft_tpu.models.kinds import ModelFns, logged, register
from torchft_tpu.models.llama import _attention, _rmsnorm, swiglu
from torchft_tpu.models.mellum import _rotate, yarn_inv_freq
from torchft_tpu.models.mla import mla_mixer
from torchft_tpu.models.moe import (BIAS_INIT_SCALE, MoEConfig, _refuse_dropless_ep,
                                    expert_scalars, ffn_init, ffn_leaves, ffn_specs, moe_ffn)

__all__ = [
    "DeepseekConfig",
    "DEEPSEEK_CONFIGS",
    "deepseek_init",
    "deepseek_hidden",
    "deepseek_forward",
    "deepseek_loss",
    "deepseek_loss_and_stats",
    "deepseek_param_specs",
    "dsa_loss_and_stats",
    "frozen_keys",
    "rope_table",
]

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class DeepseekConfig(MoEConfig):
    # ``n_heads`` is the published count of heads, ``ffn_hidden`` the dense
    # layers' SwiGLU width (``intermediate_size``); ``n_kv_heads`` is not
    # read: MLA expands keys and values for every head
    num_dense_layers: int = 1
    # (first, count): this chip holds heads first .. first + count - 1 of the
    # layer's ``n_heads`` and computes their part of the sum alone; None: all
    held_heads: Optional[Tuple[int, int]] = None
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # YaRN, over the rotary dimensions (``rope_scaling``)
    yarn_factor: float = 40.0
    yarn_original_max: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 0.707
    yarn_mscale_all_dim: float = 0.707
    moe_intermediate_size: int = 1536  # one routed expert's width
    shared_intermediate_size: Optional[int] = 3072  # the two shared experts as one
    num_experts: int = 160
    top_k: int = 6
    n_group: int = 8
    topk_group: int = 3
    topk_method: str = "group_limited_greedy"
    norm_topk_prob: bool = False
    routed_scaling: float = 16.0
    capacity_factor: Optional[float] = None  # dropless
    aux_loss_weight: float = 0.001  # ``aux_loss_alpha``
    seq_aux: bool = True
    loss_chunk: int = 0  # as ``Lfm2Config.loss_chunk``
    # the learned scorer beside every mixer (``models/dsa.py``) and the stage
    # of its training: None: no indexer (DeepSeek-V2); "warmup": the model
    # frozen, dense attention, the indexers trained by the KL loss a layer
    dsa_stage: Optional[str] = None
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048  # read by the counter ``dsa_topk_mass`` alone
    index_norm_eps: float = 1e-6  # the LayerNorm on the indexer's key

    def __post_init__(self) -> None:
        super().__post_init__()
        self._check_dropless_block(self.num_dense_layers)
        if self.dsa_stage == "sparse":
            raise ValueError(
                "dsa_stage='sparse': the stage after the warm-up (attention over the "
                "index_topk keys the indexer selects, the KL over the selected set, every "
                "parameter trained) is not built: ops/attention.py has no gather of keys a "
                "query and no mask that is data. dsa_stage='warmup' trains the indexers "
                "under dense attention; None is the model without them")
        if self.dsa_stage not in (None, "warmup"):
            raise ValueError(f"dsa_stage={self.dsa_stage!r}: None or 'warmup'")
        if self.dsa_stage and (self.held_heads is not None or self.aux_loss_weight
                               or self.qk_rope_head_dim > self.index_head_dim):
            raise ValueError(
                "dsa_stage='warmup': the target is the sum over ALL the layer's heads "
                "(held_heads=None), the loss has no balance term (aux_loss_weight=0) and "
                "the indexer turns its first qk_rope_head_dim <= index_head_dim values")
        if self.held_heads is not None:
            first, count = self.held_heads
            if first < 0 or count < 1 or first + count > self.n_heads:
                raise ValueError(f"held_heads={self.held_heads}: a share of the "
                                 f"{self.n_heads} heads")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim={self.qk_rope_head_dim}: rotary pairs")

    @property
    def n_held_heads(self) -> int:
        """Heads this chip holds (all of them unless ``held_heads``)."""
        return self.n_heads if self.held_heads is None else self.held_heads[1]

    def _mscale(self, m: float) -> float:
        return 0.1 * m * math.log(self.yarn_factor) + 1.0 if self.yarn_factor > 1 else 1.0

    @property
    def softmax_factor(self) -> float:
        """On ``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``: YaRN's
        ``mscale(factor, mscale_all_dim)`` squared."""
        return self._mscale(self.yarn_mscale_all_dim) ** 2

    @property
    def rotary_factor(self) -> float:
        """On cos and sin: ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``."""
        return self._mscale(self.yarn_mscale) / self._mscale(self.yarn_mscale_all_dim)

    def kinds(self) -> List[str]:
        """The feed-forward of every layer: "dense" | "moe"."""
        return ["dense" if i < self.num_dense_layers else "moe" for i in range(self.n_layers)]

    def runs(self) -> List[Tuple[str, str, int]]:
        """Runs of like layers in order, as :meth:`LingConfig.runs`."""
        return runs_of(self.kinds(), merges=lambda kind: kind == "dense")

    def num_params(self) -> int:
        """Every leaf this chip holds."""
        def size(leaves):
            return sum(math.prod(shape) for shape, *_ in leaves.values())

        per = {kind: size(_mixer_leaves(self)) + 2 * self.dim
               + sum(math.prod(leaf[1]) for leaf in ffn_leaves(self, kind, shared=True).values())
               for kind in ("dense", "moe")}
        layers = sum(per[kind] for kind in self.kinds())
        if self.dsa_stage:  # the indexers; the embedding alone: no norm, no head
            return layers + self.num_trainable() + self.vocab_size * self.dim
        return layers + 2 * self.vocab_size * self.dim + self.dim

    def num_trainable(self) -> int:
        """The leaves an optimizer sees: all of them, or a stage's indexers."""
        if not self.dsa_stage:
            return self.num_params()
        return self.n_layers * sum(math.prod(shape) for shape, *_
                                   in dsa.indexer_leaves(self).values())


DEEPSEEK_CONFIGS: Dict[str, DeepseekConfig] = {
    # a dense layer, then two expert layers; a share of 2 of 8 heads and of 4
    # of 16 experts in 4 groups; YaRN's ramp inside the 4 rotary pairs and
    # the tests' sequences beyond ``yarn_original_max``; bf16 like the
    # published one, so the float32 routers sit among bf16 leaves in a
    # trainer's bucket plan. The share has room for every pair: a toy batch
    # swings far from the even share.
    "deepseek_debug": DeepseekConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=8, ffn_hidden=128, max_seq_len=128,
        held_heads=(2, 2), q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, yarn_original_max=32,
        moe_intermediate_size=32, shared_intermediate_size=64, num_experts=16, top_k=4,
        n_group=4, topk_group=2, held_experts=(4, 4), share_room=4.0,
    ),
    # deepseek-ai/DeepSeek-V2, one chip's share of the first five published
    # layers in a deployment of sixteen chips a layer: the leading dense
    # layer and four expert layers, 8 of the 128 heads, 10 of the 160 experts
    # (half of the first group), an eighth of the vocabulary
    "deepseek_v2_share": DeepseekConfig(
        vocab_size=12800, dim=5120, n_layers=5, n_heads=128, n_kv_heads=128,
        ffn_hidden=12288, max_seq_len=163840, held_heads=(0, 8), held_experts=(0, 10),
        share_room=2.5, loss_chunk=2048,
    ),
    # the warm-up stage at a few hundred KB of leaves: a dense layer, then two
    # expert layers; every one of 8 heads, an indexer of 4 heads of 16 (its
    # first 8 values rotary), a share of 4 of 16 sigmoid-routed experts in 4
    # groups under the selection bias
    "dsv32_debug": DeepseekConfig(
        vocab_size=256, dim=64, n_layers=3, n_heads=8, ffn_hidden=128, max_seq_len=128,
        q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, yarn_original_max=32, yarn_mscale=1.0, yarn_mscale_all_dim=1.0,
        moe_intermediate_size=32, shared_intermediate_size=32, num_experts=16, top_k=4,
        n_group=4, topk_group=2, topk_method="noaux_tc", router_score="sigmoid",
        norm_topk_prob=True, gate_eps=1e-20, routed_scaling=2.5, aux_loss_weight=0.0,
        seq_aux=False, held_experts=(4, 4), share_room=4.0,
        dsa_stage="warmup", index_n_heads=4, index_head_dim=16, index_topk=16,
    ),
    # deepseek-ai/DeepSeek-V3.2-Exp's indexer warm-up, one chip's share of the
    # first five kinds of layer in a deployment of sixteen chips an expert
    # group: the leading dense layer and four expert layers, all 128 heads
    # and the 64-head indexer of every layer, 16 of the 256 experts (half of
    # the first group), an eighth of the vocabulary's embedding rows
    "deepseek_v32_share": DeepseekConfig(
        vocab_size=16160, dim=7168, n_layers=5, n_heads=128, n_kv_heads=128,
        ffn_hidden=18432, max_seq_len=163840, yarn_mscale=1.0, yarn_mscale_all_dim=1.0,
        moe_intermediate_size=2048, shared_intermediate_size=2048, num_experts=256, top_k=8,
        n_group=8, topk_group=4, topk_method="noaux_tc", router_score="sigmoid",
        norm_topk_prob=True, gate_eps=1e-20, routed_scaling=2.5, aux_loss_weight=0.0,
        seq_aux=False, held_experts=(0, 16), share_room=6.0, dsa_stage="warmup",
    ),
}


def _mixer_leaves(cfg: DeepseekConfig) -> Dict[str, Tuple[Tuple[int, ...], Optional[int], Any]]:
    """The mixer's leaves for the heads held: leaf -> (its shape without the
    layers' axis, its fan-in (None: a norm's weight, ones), its
    PartitionSpec with it). One table for init, specs and the count."""
    from jax.sharding import PartitionSpec as P

    d, H, rq, r = cfg.dim, cfg.n_held_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    down, rep = P(None, "fsdp", None), P(None, None)
    return {"w_dq": ((d, rq), d, down), "q_norm": ((rq,), None, rep),
            "w_uq": ((rq, H * (dn + dr)), rq, P(None, None, "tp")),
            "w_kva": ((d, r + dr), d, down), "kv_norm": ((r,), None, rep),
            "w_kvb": ((r, H * (dn + dv)), r, P(None, None, "tp")),
            "wo": ((H * dv, d), H * dv, P(None, "tp", "fsdp"))}


def deepseek_init(key: jax.Array, cfg: DeepseekConfig) -> Dict[str, Any]:
    """Parameter pytree: ``embed``, ``lm_head``, ``final_norm`` and
    ``layers``, one stack per run of like layers (:meth:`DeepseekConfig.runs`;
    the mixer's leaves the held heads', the expert leaves ``[1, held, ...]``,
    the router ``[1, dim, num_experts]`` float32). Every matrix normal over
    the root of its fan-in, the norms' weights ones."""
    k_emb, k_head, k_layers = jax.random.split(key, 3)
    d = cfg.dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, _F32) / jnp.sqrt(fan_in)).astype(cfg.dtype)

    def run(key, kind, L):
        ks = jax.random.split(key, 16)
        mixer = {name: (jnp.ones((L, *shape), cfg.dtype) if fan_in is None
                        else dense(k, (L, *shape), fan_in))
                 for k, (name, (shape, fan_in, _)) in zip(ks, _mixer_leaves(cfg).items())}
        return {"norm": jnp.ones((L, d), cfg.dtype), **mixer,
                "ffn_norm": jnp.ones((L, d), cfg.dtype),
                **ffn_init(ffn_leaves(cfg, kind, shared=True), ks[8:], L, cfg.dtype)}

    params = init_tree(k_emb, k_layers, cfg, run)
    if not cfg.dsa_stage:
        return {**params, "lm_head": dense(k_head, (d, cfg.vocab_size), d)}
    # the stage's tree: no final norm and no head; the indexers one stack a
    # run beside ``layers``; the selection bias (state, LING_FROZEN's kind)
    del params["final_norm"]
    k_ix, k_bias = jax.random.split(jax.random.fold_in(key, 1))
    runs = cfg.runs()
    params["indexer"] = {name: dsa.indexer_init(k, cfg, L) for (name, _, L), k
                         in zip(runs, jax.random.split(k_ix, len(runs)))}
    params["expert_bias"] = BIAS_INIT_SCALE * jax.random.normal(
        k_bias, (cfg.n_layers - cfg.num_dense_layers, cfg.num_experts), _F32)
    return params


def frozen_keys(cfg: DeepseekConfig) -> Tuple[str, ...]:
    """``ModelFns.frozen`` of this configuration: nothing (DeepSeek-V2 has no
    state), or in the warm-up stage every top-level key but ``indexer``."""
    return ("embed", "layers", "expert_bias") if cfg.dsa_stage else ()


def rope_table(cfg: DeepseekConfig, seq: int) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin) [seq, qk_rope_head_dim / 2] float32: YaRN's frequencies
    over the rotary dimensions, times :attr:`DeepseekConfig.rotary_factor`
    where that is not 1. Made once a step. The indexer of a layer turns by
    the same table."""
    ang = jnp.arange(seq, dtype=_F32)[:, None] * yarn_inv_freq(cfg, cfg.qk_rope_head_dim)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if cfg.rotary_factor != 1.0:
        scale = jnp.asarray(cfg.rotary_factor, _F32)
        cos, sin = cos * scale, sin * scale
    return cos, sin


_IX = "ix_"  # before an indexer leaf's name where a layer's leaves hold it


def _layer_body(cfg: DeepseekConfig, kind: str, table: Tuple[jax.Array, jax.Array],
                attention: Any):
    """The scanned body of a run of ``kind``: ``(h, (w, bias, replay)) ->
    (h, moe_ffn's stats | None)`` (``bias``: the layer's row of the selection
    bias where the tree has one). In the warm-up stage ``w`` also holds the
    layer's indexer (:data:`_IX` before each leaf's name) and the stats its
    ``dsa_kl`` (``L_layer``) and ``dsa_topk_mass``."""

    def layer(h, xs):
        w, bias, replay = xs
        u = _rmsnorm(h, w["norm"], cfg.norm_eps)
        mixed = mla_mixer(u, w, cfg, attention, lambda m: _rotate(m, table),
                          cfg.n_held_heads, cfg.softmax_factor, hand_out=bool(cfg.dsa_stage),
                          exact_scale=bool(cfg.dsa_stage))
        stage = None
        if cfg.dsa_stage:
            mixed, got = mixed
            ix = {k[len(_IX):]: v for k, v in w.items() if k.startswith(_IX)}
            # constants of the stage: every leaf they come from is frozen
            const = jax.lax.stop_gradient((u, got["cq"], got["q"], got["k"]))
            kl = dsa.layer_kl(ix, *const, table, cfg, got["scale"])
            mass = dsa.topk_mass(jax.lax.stop_gradient(ix), *const, table, cfg, got["scale"])
            # the stage's branch ends here: nothing after it reads it, so a
            # scheduler may put it off and keep every layer's queries and
            # keys (2 GB a layer at 16,384 x 128) until the last layer is
            # through; the mixer's output waits for it instead
            mixed, kl, mass = jax.lax.optimization_barrier((mixed, kl, mass))
            stage = {"dsa_kl": kl, "dsa_topk_mass": mass}
        h = h + mixed
        if kind == "dense":
            with jax.named_scope("ffn/block"):
                return h + swiglu(_rmsnorm(h, w["ffn_norm"], cfg.norm_eps), w), stage
        x = _rmsnorm(h, w["ffn_norm"], cfg.norm_eps)
        out, stats = moe_ffn(
            x, w["router"], w["w_gate"], w["w_up"], w["w_down"], cfg, routing=replay,
            bias=bias, shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))
        stats.pop("prob_sum")  # the sequence-wise term has its own mean
        return h + out, {**stats, **(stage or {})}

    return layer


def _bodies(cfg: DeepseekConfig, seq: int, attention_fn: Optional[Any]):
    attention, table = attention_fn or _attention, rope_table(cfg, seq)
    return lambda kind: _layer_body(cfg, kind, table, attention)


def _counters(stats: Dict[str, jax.Array], tokens: jax.Array, cfg: DeepseekConfig
              ) -> Dict[str, jax.Array]:
    """The expert layers' free routing with its margins (``routing``
    [L,T,k], ``p_kth``, ``p_next`` [L,T]), ``moe.expert_scalars``' six for
    this family (``load_max_over_mean``, ``groups_hit_mean`` and, under a
    share, ``held_pair_share``, ``overflow_pairs``, ``visited_row_share``,
    ``moved_row_share``) and ``aux_loss``: the expert layers'
    ``sequence_balance_loss`` summed (1 a layer at an even load), which the
    loss adds ``aux_loss_weight`` times."""
    return expert_scalars(stats, tokens.size * cfg.top_k)


DEEPSEEK = Decoder(_bodies, _counters, routed=lambda kind: kind == "moe")
deepseek_forward = DEEPSEEK.forward


def deepseek_hidden(params: Dict[str, Any], tokens: jax.Array, cfg: DeepseekConfig,
                    attention_fn: Optional[Any] = None, remat: Any = "full",
                    routing: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``Decoder.hidden``; in the warm-up stage each run's indexer stack
    rides with the run's layers (so that the one scan hands a layer its own)
    and nothing is rematerialised."""
    if cfg.dsa_stage:
        params = {**params, "layers": {
            name: {**w, **{_IX + k: v for k, v in params["indexer"][name].items()}}
            for name, w in params["layers"].items()}}
        remat = "none"
    return DEEPSEEK.hidden(params, tokens, cfg, attention_fn, remat, routing)


def dsa_loss_and_stats(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array,
                       cfg: DeepseekConfig, attention_fn: Optional[Any] = None,
                       remat: Any = "full", loss_chunk: int = 0,
                       routing: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The warm-up stage's loss: the sum over the layers of ``L_layer``
    (``targets``, ``remat`` and ``loss_chunk`` are not read: nothing is
    predicted and nothing rematerialised), and beside :func:`_counters`'
    ``hidden`` (the last layer's output [B,S,dim]: what a check compares
    where other kinds have logits), ``kl_layers`` [layers], ``kl_first``,
    ``kl_last``, ``topk_mass`` (``dsa.topk_mass``, the mean over layers) and
    ``param_share`` (the frozen leaves' share of the parameters held: a
    constant of the run)."""
    h, stats = deepseek_hidden(params, tokens, cfg, attention_fn, remat, routing)
    kl, mass = stats.pop("dsa_kl"), stats.pop("dsa_topk_mass")
    return jnp.sum(kl), {
        **_counters(stats, tokens, cfg), "hidden": h, "kl_layers": kl, "kl_first": kl[0],
        "kl_last": kl[-1], "topk_mass": jnp.mean(mass),
        "param_share": jnp.asarray(1.0 - cfg.num_trainable() / cfg.num_params(), _F32)}


def deepseek_loss_and_stats(params: Dict[str, Any], tokens: jax.Array, targets: jax.Array,
                            cfg: DeepseekConfig, **kw: Any
                            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The configuration's loss: next-token cross-entropy with the balance
    term (``Decoder.loss_and_stats``), or a stage's own
    (:func:`dsa_loss_and_stats`)."""
    if cfg.dsa_stage:
        return dsa_loss_and_stats(params, tokens, targets, cfg, **kw)
    return DEEPSEEK.loss_and_stats(params, tokens, targets, cfg, **kw)


def deepseek_loss(*args: Any, **kw: Any) -> jax.Array:
    """:func:`deepseek_loss_and_stats`' loss alone."""
    return deepseek_loss_and_stats(*args, **kw)[0]


def deepseek_param_specs(cfg: DeepseekConfig, mesh: Optional[Any] = None) -> Dict[str, Any]:
    """PartitionSpecs for the pytree: the mixer's and the feed-forwards'
    matrices over fsdp and tp as the dense decoder's, the experts as
    ``moe_param_specs``', the norms replicated. A share is one chip's of a
    layer: on a mesh that shards inside the layer (``tp`` or ``ep`` > 1) a
    share of the heads is refused as a share of the experts is, for the sum
    across the chips that hold the other heads is not built."""
    from jax.sharding import PartitionSpec as P

    if mesh is not None:
        axes = [a for a, n in mesh.shape.items() if n > 1]
        _refuse_dropless_ep(cfg, axes)
        if cfg.held_heads is not None and "tp" in axes:
            raise ValueError(
                f"held_heads={cfg.held_heads}: this chip's share of the layer's "
                f"{cfg.n_heads} heads; the sum across the chips that hold the others "
                "is not built, so the heads are not sharded over tp. Give the mesh "
                "tp=1, or the config every head.")
    rep2 = P(None, None)
    mixer = {name: spec for name, (_, _, spec) in _mixer_leaves(cfg).items()}
    ffn = {f: ffn_specs(ffn_leaves(cfg, f, shared=True)) for f in ("dense", "moe")}
    specs = spec_tree(cfg, lambda kind: {"norm": rep2, **mixer, "ffn_norm": rep2,
                                         **ffn[kind]})
    if not cfg.dsa_stage:
        return {**specs, "lm_head": P("fsdp", "tp")}
    del specs["final_norm"]
    ix = {name: spec for name, (_, _, _, spec) in dsa.indexer_leaves(cfg).items()}
    return {**specs, "indexer": {name: dict(ix) for name, _, _ in cfg.runs()},
            "expert_bias": rep2}


# what the trainer logs: the expert block's scalars and, in the warm-up stage,
# ``dsa_kl_first``, ``dsa_kl_last``, ``dsa_topk_mass`` and ``frozen_param_share``
register(DeepseekConfig, DEEPSEEK_CONFIGS, lambda: ModelFns(
    deepseek_init, logged(deepseek_loss_and_stats, moe=(
        "aux_loss", "load_max_over_mean", "bias_moved_share", "held_pair_share", "overflow_pairs",
        "visited_row_share", "moved_row_share", "groups_hit_mean"),
        dsa=("kl_first", "kl_last", "topk_mass"), frozen=("param_share",)),
    deepseek_param_specs, None, frozen_keys))
