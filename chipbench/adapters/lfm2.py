"""Adapter ``lfm2``: what is ``models/lfm2.py``'s own (LiquidAI's LFM2
mixture of experts: gated short-convolution mixers round grouped-query
attention layers with per-head QK norms, leading dense layers, then 32
experts chosen four a token by a sigmoid router under a selection bias, a
tied head), for configuration files that name it under ``adapter``.
chipbench/adapters/llama.py says what an adapter is, chipbench/adapters/olmoe.py
what the job kind ``bare_routed`` asks beyond that.

``expert_bias`` is state of the model and no parameter. ``jobs/bare_routed.py``
hands everything ``program()``'s init returns to ``optax.adamw`` with weight
decay, so this init returns the TRAINABLE leaves alone, and loss and forward
put the bias beside them themselves: ``recipe.expert_bias`` of the
configuration file (a seed and a scale), the very array the reference is
given (``reference_lfm2.expert_bias``). In the timed step, too, nothing can
move it.
"""

from chipbench import flops
from chipbench import reference_lfm2 as reference  # noqa: F401  (the plain reference)
from chipbench.adapters.olmoe import grouped_matmul_cost
from chipbench.worker import TRAINER

# the embedding (read twice: the tied head); the first expert layer's float32
# router (its gradient comes through the gates) and its per-head query norm
# (zero where the norm is skipped); a convolution's taps (a lost tap is a
# zero row); an expert matrix of the last layer, element by element and as
# its norms expert by expert (reference_lfm2._expert_norms: where gates taken
# from the biased scores show)
GRAD_LEAVES = ["embed", "layers.01_attn_moe.router", "layers.01_attn_moe.q_norm",
               "layers.02_conv_moe.conv_w", "layers.04_conv_moe.w_down",
               "layers.04_conv_moe.w_down@expert_norms"]

# keys this adapter reads or tests; any other is a property of the model
# this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "moe_intermediate_size",
    "max_position_embeddings", "rope_theta", "norm_eps", "layer_types",
    "num_dense_layers", "conv_L_cache", "conv_bias", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
    "use_expert_bias"}
# ``recipe.expert_bias`` of every configuration file of this adapter: the
# program's functions are handed the config OBJECT, which holds no seed
BIAS = {"seed": 35, "scale": 0.01}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}


def config(cfg: dict):
    """The configuration file (Hugging Face keys) as the program's
    Lfm2Config; refuses what ``models/lfm2.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.lfm2 import Lfm2Config

    unknown = sorted(set(cfg) - _EXPRESSED - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'lfm2' cannot express key "
                         + ", ".join(map(repr, unknown)))
    if cfg.get("conv_bias", False):
        raise ValueError("key 'conv_bias': models/lfm2.py's short convolution "
                         "has no bias")
    if cfg["routed_scaling_factor"] != 1 or not cfg["use_expert_bias"]:
        raise ValueError("keys 'routed_scaling_factor', 'use_expert_bias': "
                         "models/lfm2.py's gates are unscaled and its selection biased")
    if cfg["recipe"].get("expert_bias", BIAS) != BIAS:
        raise ValueError(f"key 'recipe.expert_bias': this adapter's program is "
                         f"given {BIAS}, the reference what the file says")
    return Lfm2Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], ffn_hidden=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"], conv_L_cache=cfg["conv_L_cache"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        loss_chunk=cfg["recipe"].get("loss_chunk", 0),
    )


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def _with_bias(params, pc):
    if not pc.n_moe_layers:
        return params
    return {**params, "expert_bias": reference.expert_bias(
        **BIAS, layers=pc.n_moe_layers, experts=pc.num_experts)}


def program():
    # the kind's module first: a program without it says so by that name
    from torchft_tpu.models.lfm2 import (LFM2_FROZEN, lfm2_forward, lfm2_init,
                                         lfm2_loss_and_stats)
    from torchft_tpu.models import split_frozen  # noqa: I001

    def init(key, pc):  # the trainable leaves: all an optimizer may see
        return split_frozen(lfm2_init(key, pc), LFM2_FROZEN)[0]

    def forward(params, tokens, pc, **kw):
        return lfm2_forward(_with_bias(params, pc), tokens, pc, **kw)

    def loss(params, tokens, targets, pc, with_stats=False, **kw):
        value, stats = lfm2_loss_and_stats(
            _with_bias(params, pc), tokens, targets, pc, **kw)
        return (value, stats) if with_stats else value

    return init, loss, forward


def router_alone(params, pc, router_in):
    """The program's expert block (its public ``moe_ffn``, each expert
    layer's own weights and its row of the bias) given ``router_in`` [L, T,
    D] float32 as the layers' input: per expert layer the ``routing``
    [L,T,k] and ``p_kth``, ``p_next`` [L,T] (of ``scores + bias``). The
    block's output is not used, so XLA drops the experts."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.moe import moe_ffn

    bias = _with_bias({}, pc).get("expert_bias")
    out = []
    for name, kind, _ in pc.runs():
        if kind[1] != "moe":
            continue
        w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
        _, stats = moe_ffn(router_in[len(out)][None], w["router"], w["w_gate"],
                           w["w_up"], w["w_down"], pc,
                           bias=None if bias is None else bias[len(out)])
        out.append({k: stats[k] for k in ("routing", "p_kth", "p_next")})
    return {k: jnp.stack([o[k] for o in out]) for k in out[0]}


def layers_with(cfg: dict, kernel: str) -> int:
    kinds = reference.kinds(cfg)
    return {"attention": sum(m == "attn" for m, _ in kinds),
            "grouped_matmul": sum(f == "moe" for _, f in kinds)}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf, the ``expert_bias`` buffer among them (32 a layer); the
    tied embedding once."""
    d, v, e = cfg["hidden_size"], cfg["vocab_size"], cfg["num_experts"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    mixer = {"conv": d * 3 * d + cfg["conv_L_cache"] * d + d * d,
             "attn": 2 * d * d + 2 * d * kv + 2 * hd}  # and q_norm, k_norm
    ffn = {"dense": 3 * d * cfg["intermediate_size"],
           "moe": 3 * e * d * cfg["moe_intermediate_size"] + d * e + e}
    return sum(mixer[m] + ffn[f] + 2 * d for m, f in reference.kinds(cfg)) + v * d + d


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass, per token: every projection,
    the convolution's taps and its two gates, the attention layers' causal
    products counted exactly, the dense feed-forward, the router and the
    ``num_experts_per_tok`` experts a token uses (not the 32 that exist),
    and the tied head."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    mixer = {"conv": 2 * d * 3 * d + 2 * cfg["conv_L_cache"] * d + 2 * d + 2 * d * d,
             "attn": 2 * d * d + 2 * 2 * d * kv + 2 * d * d + 2 * 2 * d * (seq + 1) / 2}
    ffn = {"dense": 3 * 2 * d * cfg["intermediate_size"],
           "moe": (2 * d * cfg["num_experts"]
                   + cfg["num_experts_per_tok"] * 3 * 2 * d * cfg["moe_intermediate_size"])}
    return sum(mixer[m] + ffn[f] for m, f in reference.kinds(cfg)) + 2 * d * v


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _attention(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    # the dense decoder's count with the head size derived (64 here)
    return flops.attention_kernel_cost(
        {**cfg, "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"]},
        batch, seq, passes)


def _grouped_matmul(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    # the OLMoE adapter's count with an expert's own width (rows x 2048 x 1792)
    return grouped_matmul_cost(
        {**cfg, "intermediate_size": cfg["moe_intermediate_size"]}, batch, seq, passes)


KERNEL_COSTS = {"attention": _attention, "grouped_matmul": _grouped_matmul}
