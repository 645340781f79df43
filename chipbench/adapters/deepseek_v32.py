"""Adapter ``deepseek_v32``: what is ``models/deepseek.py``'s own under
``dsa_stage="warmup"`` (DeepSeek-V3.2-Exp in the dense warm-up stage of its
continued training: latent attention over ALL 128 heads in every layer, a
lightning indexer of 64 heads beside it, trained by a KL divergence a layer
against the head-sum of the layer's own attention; three leading dense
layers, then 256 sigmoid-routed experts chosen eight a token in four of
eight groups under a frozen selection bias, a group scored by its best two,
the gates renormalised and times 2.5, beside one shared expert; every
parameter FROZEN but the indexers'; no head and no cross-entropy), as ONE
CHIP'S SHARE of a stated deployment: the configuration file's ``deployment``
says which of the router's experts this chip holds, which published layers
and how many embedding rows. chipbench/adapters/llama.py says what an adapter
is, chipbench/adapters/olmoe.py what the routed job kinds ask beyond that,
chipbench/adapters/ling.py why the loss is NaN where a held pair found the
share's buffer full.

What the job kind ``bare_frozen`` asks beyond ``bare_routed``: ``program()``'s
init hands out the TRAINABLE leaves alone (the indexers: all an optimizer may
see) and :func:`held` makes the frozen tree (8.3 GiB at the published
widths) once, from a seed; the loss and ``forward`` take both as one tree.
``forward`` and the loss's ``hidden`` are the last layer's output: the stage
has no logits, and the check compares these in their place.

The FLOPs are the REQUIRED work of THIS step and of no other: every frozen
layer's forward pass once (causal attention counted exactly, 192-wide scores
and 128-wide values: the zeros the kernels are padded with are not work), the
indexer's three projections forward and into their weights' gradient (their
inputs are frozen: no gradient goes on), its score product once forward and
twice backward (into ``qI`` and ``kI``), the target's head sum and the KL's
row sums as element-wise work. NOT counted: the main heads' scores the
alignment kernels compute a second, third and fourth time, and the indexer's
scores the backward kernel computes again.
"""

from chipbench import reference_deepseek_v32 as reference  # noqa: F401  (the plain reference)
from chipbench.adapters.mellum import _grouped_matmul
from chipbench.worker import TRAINER

# of the first and the last layer's indexer: the queries' projection (rotary
# and the ReLU show), the key's (the LayerNorm and its turn show), the
# weights' (their constant factors show) and the LayerNorm's bias (zero
# where an RMSNorm stands in)
GRAD_LEAVES = [f"indexer.{run}.{leaf}" for run in ("00_dense", "04_moe")
               for leaf in ("w_iq", "w_ik", "w_iw", "k_bias")]

_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "moe_intermediate_size",
    "max_position_embeddings", "rope_theta", "rms_norm_eps", "first_k_dense_replace",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
    "topk_method", "norm_topk_prob", "routed_scaling_factor", "rope_scaling", "scoring_func",
    "index_n_heads", "index_head_dim", "index_topk", "deployment"}
_FIXED = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1, "ep_size": 1,
          "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}
_YARN_KEYS = {"type", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
              "mscale", "mscale_all_dim"}
# ``recipe.expert_bias`` of every configuration file of this adapter: the
# program's functions are handed the config OBJECT, which holds no seed
BIAS = {"seed": 67, "scale": 0.01}


def config(cfg: dict):
    """The configuration file (the published keys) as the program's
    DeepseekConfig in the warm-up stage; refuses what ``models/deepseek.py``
    cannot express, the key named."""
    import jax.numpy as jnp

    from torchft_tpu.models.deepseek import DeepseekConfig

    unknown = sorted(set(cfg) - _EXPRESSED - set(_FIXED) - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'deepseek_v32' cannot express key "
                         + ", ".join(map(repr, unknown)))
    other = sorted(k for k, v in _FIXED.items() if cfg.get(k, v) != v)
    if other:
        raise ValueError("adapter 'deepseek_v32': models/deepseek.py computes one value of "
                         + ", ".join(f"{k!r} ({_FIXED[k]!r})" for k in other))
    dep, yarn, recipe = cfg["deployment"], cfg["rope_scaling"], cfg["recipe"]
    first, last = dep["published_layers"]
    if last - first + 1 != cfg["num_hidden_layers"] or first != 0:
        raise ValueError("key 'deployment.published_layers': the cut's kinds of layer from "
                         "the first, num_hidden_layers long")
    if dep["experts_held"][1] != cfg["n_routed_experts"]:
        raise ValueError("keys 'n_routed_experts', 'deployment.experts_held': the key "
                         "counts the experts held here")
    if dep["heads_held"] != "all" or cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("keys 'deployment.heads_held', 'num_key_value_heads': the stage's "
                         "target is the sum over ALL the layer's heads ('all')")
    if set(yarn) != _YARN_KEYS or yarn["type"] != "yarn":
        raise ValueError("key 'rope_scaling': models/deepseek.py turns by YaRN's table "
                         f"({sorted(_YARN_KEYS)})")
    if cfg["scoring_func"] != "sigmoid" or not cfg["norm_topk_prob"]:
        raise ValueError("keys 'scoring_func', 'norm_topk_prob': this family scores by "
                         "'sigmoid' and renormalises the gates before it scales them")
    if recipe["stage"] != "warmup":
        raise ValueError(f"key 'recipe.stage': {recipe['stage']!r}; the program builds the "
                         "dense warm-up stage ('warmup') alone: the sparse stage's attention "
                         "over selected keys is in no kernel of ops/attention.py")
    if recipe.get("expert_bias", BIAS) != BIAS:
        raise ValueError(f"key 'recipe.expert_bias': this adapter's program is given "
                         f"{BIAS}, the reference what the file says")
    return DeepseekConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], held_heads=None,
        ffn_hidden=cfg["intermediate_size"], max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[recipe["param_dtype"]],
        num_dense_layers=cfg["first_k_dense_replace"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], yarn_factor=float(yarn["factor"]),
        yarn_original_max=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]), yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_mscale=float(yarn["mscale"]), yarn_mscale_all_dim=float(yarn["mscale_all_dim"]),
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        num_experts=dep["router_outputs"], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"], topk_method=cfg["topk_method"],
        router_score="sigmoid", norm_topk_prob=True, gate_eps=1e-20,
        routed_scaling=float(cfg["routed_scaling_factor"]), aux_loss_weight=0.0,
        seq_aux=False, held_experts=tuple(dep["experts_held"]), share_room=dep["share_room"],
        dsa_stage="warmup", index_n_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"],
    )


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def held(seed: int, pc):
    """The frozen tree (every top-level key but the indexers') from
    ``seed``, made once: the leaves the program's own init gives that seed,
    the selection bias :data:`BIAS`'s (what the reference is given)."""
    import jax

    from torchft_tpu.models import split_frozen
    from torchft_tpu.models.deepseek import deepseek_init, frozen_keys

    tree = jax.jit(lambda: split_frozen(
        deepseek_init(jax.random.PRNGKey(seed), pc), frozen_keys(pc))[1])()
    return {**tree, "expert_bias": reference.expert_bias(
        **BIAS, layers=pc.n_layers - pc.num_dense_layers, experts=pc.num_experts)}


def program():
    # the kind's module first: a program without it says so by that name
    from torchft_tpu.models.deepseek import (deepseek_hidden, deepseek_init,
                                             deepseek_loss_and_stats, frozen_keys)
    from torchft_tpu.models import split_frozen  # noqa: I001

    def init(key, pc):  # the trainable leaves: all an optimizer may see
        return split_frozen(deepseek_init(key, pc), frozen_keys(pc))[0]

    def forward(params, tokens, pc, **kw):  # trainable and held leaves, one tree
        import jax.numpy as jnp

        return deepseek_hidden(params, tokens, pc, **kw)[0].astype(jnp.float32)

    def loss(params, tokens, targets, pc, with_stats=False, **kw):
        import jax.numpy as jnp

        value, stats = deepseek_loss_and_stats(params, tokens, targets, pc, **kw)
        if "overflow_pairs" in stats:  # a dropped pair: no step to report
            value = jnp.where(stats["overflow_pairs"] > 0, jnp.nan, value)
        # under the names the job kind reads: each layer's term of the loss
        return (value, {**stats, "layer_losses": stats["kl_layers"]}) if with_stats else value

    return init, loss, forward


def router_alone(params, pc, router_in):
    """The program's expert block (its public ``moe_ffn``, each expert
    layer's own weights and its row of the bias; ``params`` holds the frozen
    tree) given ``router_in`` [L, T, D] float32 as the layers' input: per
    expert layer the ``routing`` [L,T,k] and ``p_kth``, ``p_next`` [L,T]."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.moe import moe_ffn

    out = []
    for name, kind, _ in pc.runs():
        if kind != "moe":
            continue
        w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
        _, stats = moe_ffn(router_in[len(out)][None], w["router"], w["w_gate"],
                           w["w_up"], w["w_down"], pc, bias=params["expert_bias"][len(out)])
        out.append({k: stats[k] for k in ("routing", "p_kth", "p_next")})
    return {k: jnp.stack([o[k] for o in out]) for k in out[0]}


def layers_with(cfg: dict, kernel: str) -> int:
    return {"attention": cfg["num_hidden_layers"], "dsa_kl": cfg["num_hidden_layers"],
            "grouped_matmul": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf this chip holds (the selection bias, state, apart)."""
    return config(cfg).num_params()


def num_trainable(cfg: dict) -> int:
    """The indexers' leaves: what the step's optimizer and gradient see."""
    return config(cfg).num_trainable()


def _widths(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["index_n_heads"],
            cfg["index_head_dim"])


def frozen_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of the frozen layers' ONE forward pass on this chip,
    per token: both latents' projections and their expansions to all the
    heads, the heads' causal products counted exactly, the output
    projection, the dense feed-forward, the router over all its outputs, the
    shared expert and of a token's ``num_experts_per_tok`` experts the share
    held here. No head: the stage has none."""
    d, H, dn, dr, dv, _, _ = _widths(cfg)
    rq, r, W = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    dep = cfg["deployment"]
    mla = (2 * d * rq + 2 * rq * H * (dn + dr) + 2 * d * (r + dr) + 2 * r * H * (dn + dv)
           + 2 * H * dv * d + 2 * H * (dn + dr + dv) * (seq + 1) / 2)
    held_share = cfg["num_experts_per_tok"] * dep["experts_held"][1] / dep["router_outputs"]
    dense_layers = cfg["first_k_dense_replace"]
    ffn = (dense_layers * 3 * 2 * d * cfg["intermediate_size"]
           + (cfg["num_hidden_layers"] - dense_layers)
           * (2 * d * dep["router_outputs"]
              + (cfg["n_shared_experts"] + held_share) * 3 * 2 * d * W))
    return cfg["num_hidden_layers"] * mla + ffn


def indexer_flops_per_token(cfg: dict, seq: int) -> float:
    """The stage's own work a token, forward AND backward, all layers: the
    indexer's three projections forward and into their weights' gradient
    (x 2: their inputs are frozen), its score product forward and into
    ``qI``'s and ``kI``'s gradients (x 3), and as element-wise work one add a
    (main head, pair) for the target's head sum and six operations a pair for
    the KL's row sums and ``dI``."""
    d, H, _, _, _, HI, dI = _widths(cfg)
    pairs = (seq + 1) / 2
    proj = 2 * cfg["q_lora_rank"] * HI * dI + 2 * d * dI + 2 * d * HI
    return cfg["num_hidden_layers"] * (2 * proj + 3 * 2 * HI * dI * pairs + (H + 6) * pairs)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """The required work of the warm-up stage's step (the module's text)."""
    return frozen_flops_per_token(cfg, seq) + indexer_flops_per_token(cfg, seq)


def _attention(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """One forward call of a flash-style causal attention kernel, one
    layer's 128 heads, whole batch: 192-wide scores, 128-wide values, the
    exact causal count; q and k at 192, v and o at 128, bf16, every head its
    own keys. The frozen stage has no backward call."""
    if passes != "fwd":
        raise KeyError(passes)
    _, H, dn, dr, dv, _, _ = _widths(cfg)
    pairs = batch * H * seq * (seq + 1) / 2
    return {"flops": 2 * pairs * (dn + dr + dv),
            "bytes": 2.0 * batch * seq * H * (2 * (dn + dr) + 2 * dv)}


def _dsa_kl(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """What the alignment of one layer REQUIRES of a kernel that was handed
    the target: "fwd": the indexer's score product (2 HI dI a pair) with one
    add a (main head, pair) and six operations a pair element-wise; "bwd":
    the two products into ``qI``'s and ``kI``'s gradients. Bytes: the main
    heads' queries and keys once (the target has to come from somewhere: 192
    wide, bf16), the indexer's queries, keys and weights, and backwards their
    gradients as well. The main heads' scores (computed three times over)
    and the indexer's scores computed again backwards are NOT counted, so
    the recomputation lowers this share and cannot raise it."""
    if passes not in ("fwd", "bwd"):
        raise KeyError(passes)
    _, H, dn, dr, _, HI, dI = _widths(cfg)
    pairs = batch * seq * (seq + 1) / 2
    main = 2.0 * batch * seq * H * 2 * (dn + dr)
    scorer = 2.0 * batch * seq * (HI * dI + dI) + 4.0 * batch * seq * HI
    if passes == "fwd":
        return {"flops": pairs * (2 * HI * dI + H + 6), "bytes": main + scorer}
    return {"flops": pairs * 2 * 2 * HI * dI, "bytes": main + 2 * scorer}


KERNEL_COSTS = {"attention": _attention, "grouped_matmul": _grouped_matmul,
                "dsa_kl": _dsa_kl}
