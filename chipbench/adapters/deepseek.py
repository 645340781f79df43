"""Adapter ``deepseek``: what is ``models/deepseek.py``'s own (DeepSeek-V2:
latent attention in every layer, the queries through a latent of their own,
64 rotary dimensions under YaRN with its factor on the softmax scale; a
leading dense layer, then 160 softmax-routed experts chosen six a token in
three of eight groups, a group scored by its best expert, the gates not
renormalised and times 16, beside two shared experts; the sequence-wise
balance loss; an untied head), as ONE CHIP'S SHARE of a stated deployment:
the configuration file's ``deployment`` says which of the layer's HEADS and
which of the router's experts this chip holds, which published layers and
how many vocabulary rows. chipbench/adapters/llama.py says what an adapter
is, chipbench/adapters/olmoe.py what the job kind ``bare_routed`` asks beyond
that, chipbench/adapters/ling.py why the loss is NaN where a held pair found
the share's buffer full and why ``forward`` hands out the hidden states with
the head still to come.

The attention's cost counts the MODEL's products over the heads held here:
192-wide scores, 128-wide values, the exact causal count; the zeros the
kernel's queries and keys are padded with (192 -> 256) are not required
work, so the padding reads as lost share of the roofline.
"""

from chipbench import reference_deepseek as reference  # noqa: F401  (the plain reference)
# the hidden states with the head still to come; one grouped product over the
# even share's rows of a softmax-routed share: Mellum's, key for key
from chipbench.adapters.mellum import _grouped_matmul, _Logits
from chipbench.worker import TRAINER

# the embedding and the head over the slice; of the dense layer both latent
# norms (zero where one is left out), the queries' expansion (rotary, YaRN's
# table and the softmax factor all move it) and the SwiGLU's last matrix; the
# first expert layer's float32 router (its gradient comes through the gates:
# their scale and renormalisation show) and its keys' and values' expansion;
# the last layer's shared experts and an expert matrix, element by element
# and as its norms expert by expert
GRAD_LEAVES = ["embed", "lm_head", "layers.00_dense.q_norm", "layers.00_dense.kv_norm",
               "layers.00_dense.w_uq", "layers.00_dense.w_down", "layers.01_moe.router",
               "layers.01_moe.w_kvb", "layers.04_moe.shared_down", "layers.04_moe.w_down",
               "layers.04_moe.w_down@expert_norms"]

# keys this adapter reads; the others it knows are held to the one value
# ``models/deepseek.py`` computes (``_FIXED``); any other is a property of
# the model this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "moe_intermediate_size",
    "max_position_embeddings", "rope_theta", "rms_norm_eps", "first_k_dense_replace",
    "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group",
    "topk_method", "norm_topk_prob", "routed_scaling_factor", "rope_scaling", "seq_aux",
    "aux_loss_alpha", "deployment"}
_FIXED = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
          "scoring_func": "softmax", "tie_word_embeddings": False}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}
_YARN_KEYS = {"type", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
              "mscale", "mscale_all_dim"}


def config(cfg: dict):
    """The configuration file (the published keys) as the program's
    DeepseekConfig; refuses what ``models/deepseek.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.deepseek import DeepseekConfig

    unknown = sorted(set(cfg) - _EXPRESSED - set(_FIXED) - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'deepseek' cannot express key "
                         + ", ".join(map(repr, unknown)))
    other = sorted(k for k, v in _FIXED.items() if cfg.get(k, v) != v)
    if other:
        raise ValueError("adapter 'deepseek': models/deepseek.py computes one value of "
                         + ", ".join(f"{k!r} ({_FIXED[k]!r})" for k in other))
    dep, published, yarn = cfg["deployment"], cfg["published"], cfg["rope_scaling"]
    first, last = dep["published_layers"]
    if last - first + 1 != cfg["num_hidden_layers"] or first != 0:
        raise ValueError("key 'deployment.published_layers': the cut's layers from the "
                         "first, num_hidden_layers long")
    if dep["experts_held"][1] != cfg["n_routed_experts"]:
        raise ValueError("keys 'n_routed_experts', 'deployment.experts_held': the key "
                         "counts the experts held here")
    if not dep["heads_held"][1] == cfg["num_attention_heads"] == cfg["num_key_value_heads"]:
        raise ValueError("keys 'num_attention_heads', 'num_key_value_heads', "
                         "'deployment.heads_held': the keys count the heads held here")
    if set(yarn) != _YARN_KEYS or yarn["type"] != "yarn":
        raise ValueError("key 'rope_scaling': models/deepseek.py turns by YaRN's table "
                         f"({sorted(_YARN_KEYS)})")
    if not cfg["seq_aux"]:
        raise ValueError("key 'seq_aux': models/deepseek.py has the sequence-wise "
                         "balance loss alone")
    return DeepseekConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=published["num_attention_heads"],
        n_kv_heads=published["num_key_value_heads"], held_heads=tuple(dep["heads_held"]),
        ffn_hidden=cfg["intermediate_size"], max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
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
        norm_topk_prob=cfg["norm_topk_prob"],
        # the source scales the gates where it does not renormalise them
        routed_scaling=1.0 if cfg["norm_topk_prob"] else float(cfg["routed_scaling_factor"]),
        aux_loss_weight=float(cfg["aux_loss_alpha"]), seq_aux=True,
        held_experts=tuple(dep["experts_held"]), share_room=dep["share_room"],
        loss_chunk=cfg["recipe"].get("loss_chunk", 0),
    )


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def program():
    # the kind's module first: a program without it says so by that name
    from torchft_tpu.models.deepseek import (deepseek_hidden, deepseek_init,
                                             deepseek_loss_and_stats)

    def forward(params, tokens, pc, **kw):
        return _Logits(deepseek_hidden(params, tokens, pc, **kw)[0], params["lm_head"])

    def loss(params, tokens, targets, pc, with_stats=False, **kw):
        import jax.numpy as jnp

        value, stats = deepseek_loss_and_stats(params, tokens, targets, pc, **kw)
        if "overflow_pairs" in stats:  # a dropped pair: no step to report
            value = jnp.where(stats["overflow_pairs"] > 0, jnp.nan, value)
        return (value, stats) if with_stats else value

    return deepseek_init, loss, forward


def router_alone(params, pc, router_in):
    """The program's expert block (its public ``moe_ffn``, each expert
    layer's own weights) given ``router_in`` [L, T, D] float32 as the
    layers' input: per expert layer the ``routing`` [L,T,k] and ``p_kth``,
    ``p_next`` [L,T]. The block's output is not used, so XLA drops the
    experts."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.moe import moe_ffn

    out = []
    for name, kind, _ in pc.runs():
        if kind != "moe":
            continue
        w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
        _, stats = moe_ffn(router_in[len(out)][None], w["router"], w["w_gate"],
                           w["w_up"], w["w_down"], pc)
        out.append({k: stats[k] for k in ("routing", "p_kth", "p_next")})
    return {k: jnp.stack([o[k] for o in out]) for k in out[0]}


def layers_with(cfg: dict, kernel: str) -> int:
    return {"attention": cfg["num_hidden_layers"],
            "grouped_matmul": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf this chip holds."""
    return config(cfg).num_params()


def _widths(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"])


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass on THIS chip, per token: both
    latents' projections (whole) and their expansions to the heads held
    here, those heads' causal products counted exactly (192-wide scores,
    128-wide values; the zeros the kernel is padded with are not required
    work), their rows of the output projection, the dense feed-forward, the
    router over all its outputs, the shared experts, the head over the
    slice, and of a token's ``num_experts_per_tok`` experts the share that
    is held here (held / router outputs of them on average: the others are
    other chips' work, as the other heads are)."""
    d, H, dn, dr, dv = _widths(cfg)
    rq, r, W = cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    dep = cfg["deployment"]
    mla = (2 * d * rq + 2 * rq * H * (dn + dr) + 2 * d * (r + dr) + 2 * r * H * (dn + dv)
           + 2 * H * dv * d + 2 * H * (dn + dr + dv) * (seq + 1) / 2)
    held = cfg["num_experts_per_tok"] * dep["experts_held"][1] / dep["router_outputs"]
    dense_layers = cfg["first_k_dense_replace"]
    ffn = (dense_layers * 3 * 2 * d * cfg["intermediate_size"]
           + (cfg["num_hidden_layers"] - dense_layers)
           * (2 * d * dep["router_outputs"] + (cfg["n_shared_experts"] + held) * 3 * 2 * d * W))
    return cfg["num_hidden_layers"] * mla + ffn + 2 * d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _attention(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """FLOPs and HBM bytes one call of a flash-style causal attention
    kernel requires for one layer's held heads, whole batch: scores over 192
    values a head, the weighted sum over 128. "fwd": QK^T and PV; "bwd":
    QK^T again, dP, dV (128 wide), dQ, dK (192 wide). Bytes: q and k at 192,
    v, o and their cotangents at 128, bf16, every head its own keys."""
    _, H, dn, dr, dv = _widths(cfg)
    qk = dn + dr
    pairs = batch * H * seq * (seq + 1) / 2
    rows = 2.0 * batch * seq * H  # bf16
    if passes == "fwd":
        return {"flops": 2 * pairs * (qk + dv), "bytes": rows * (2 * qk + 2 * dv)}
    if passes == "bwd":
        return {"flops": 2 * pairs * (3 * qk + 2 * dv), "bytes": rows * (4 * qk + 4 * dv)}
    raise KeyError(passes)


KERNEL_COSTS = {"attention": _attention, "grouped_matmul": _grouped_matmul}
