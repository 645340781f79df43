"""Adapter ``ling``: what is ``models/ling.py``'s own (inclusionAI's Ling
3.0 hybrid: KDA, a gated delta rule with a per-channel decay, round MLA,
latent attention; a leading dense layer, then sigmoid-routed experts chosen
eight a token in four of eight groups under a frozen selection bias, beside
a shared expert; an untied head), as ONE CHIP'S SHARE of a stated deployment:
the configuration file's ``deployment`` says which of the router's experts
this chip holds, which published layers and how many vocabulary rows.
chipbench/adapters/llama.py says what an adapter is, chipbench/adapters/olmoe.py
what the job kind ``bare_routed`` asks beyond that, chipbench/adapters/lfm2.py
why the init hands out the trainable leaves alone.

Two things here are the benchmark's and not the program's. The loss this
adapter hands the job is NaN where ``overflow_pairs`` is not 0: a held pair
that found the share's buffer full was computed by nobody, the step is then
wrong, and the job kind reads a loss that is not finite as not ``correct``
(it has no other way to be told). And ``forward`` returns the hidden states
with the head still to come (:class:`_Logits`): the check asks for 16
positions' logits, and 32,768 x 19,648 float32 ones would be 2.4 GiB beside
a step that leaves under one free.
"""

from chipbench import reference_ling as reference  # noqa: F401  (the plain reference)
from chipbench.worker import TRAINER

# the embedding and the head over the slice; the first expert layer's float32
# router (its gradient comes through the gates: their scale shows), a
# convolution's taps (a lost tap is a zero row), the decay's A_log (zero
# where the decay is left out) and beta's projection (zero at beta = 1); the
# MLA layer's latent norm (zero where it is left out) and its queries
# (rotary or not); the last layer's shared expert and an expert matrix,
# element by element and as its norms expert by expert
GRAD_LEAVES = ["embed", "lm_head", "layers.01_kda_moe.router",
               "layers.01_kda_moe.conv_k", "layers.01_kda_moe.A_log",
               "layers.01_kda_moe.w_beta", "layers.04_mla_moe.kv_norm",
               "layers.04_mla_moe.wq", "layers.06_kda_moe.shared_down",
               "layers.06_kda_moe.w_down", "layers.06_kda_moe.w_down@expert_norms"]

# keys this adapter reads; the others it knows are held to the one value
# ``models/ling.py`` computes (``_FIXED``); any other is a property of the
# model this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "intermediate_size", "moe_intermediate_size", "max_position_embeddings",
    "rope_theta", "rms_norm_eps", "first_k_dense_replace", "layer_group_size",
    "short_conv_kernel_size", "kda_lower_bound", "head_dim", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts",
    "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
    "routed_scaling_factor", "deployment"}
_FIXED = {
    "gated_attention_proj_granularity_type": "head_wise", "group_norm_size": 1,
    "hidden_act": "silu", "kda_safe_gate": True, "linear_silu": True,
    "moe_router_enable_expert_bias": True, "moe_shared_expert_intermediate_size": 768,
    "mtp_use_kda": False, "no_kda_lora": True, "num_key_value_heads": 32,
    "num_kv_heads_for_linear_attn": 0, "num_nextn_predict_layers": 0,
    "num_shared_experts": 1, "partial_rotary_factor": 0.5, "q_lora_rank": None,
    "qk_head_dim": 192, "rope_interleave": True, "rope_scaling": None,
    "rotary_dim": 64, "scale_router_input": False, "score_function": "sigmoid",
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_method": "noaux_tc", "up_proj_norm": False, "use_bias": False,
    "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
    "use_qk_norm": True, "use_qkv_bias": False, "value_norm": False}
# read by nothing at the cut: the limits of the layers kept are 0 (checked),
# the auxiliary loss and the window keys belong to paths this cut has not
_UNREAD = {"expert_swiglu_limit_list", "share_expert_swiglu_limit_list",
           "mtp_loss_scaling_factor", "seq_aux", "max_window_layers"}
# ``recipe.expert_bias`` of every configuration file of this adapter: the
# program's functions are handed the config OBJECT, which holds no seed
BIAS = {"seed": 40, "scale": 0.01}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}


def _layer_types(cfg: dict) -> "tuple[str, ...]":
    first, last = cfg["deployment"]["published_layers"]
    return tuple("mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"
                 for i in range(first, last + 1))


def config(cfg: dict):
    """The configuration file (the published keys) as the program's
    LingConfig; refuses what ``models/ling.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.ling import LingConfig

    unknown = sorted(set(cfg) - _EXPRESSED - set(_FIXED) - _UNREAD - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'ling' cannot express key "
                         + ", ".join(map(repr, unknown)))
    other = sorted(k for k, v in _FIXED.items() if cfg.get(k, v) != v)
    if other:
        raise ValueError("adapter 'ling': models/ling.py computes one value of "
                         + ", ".join(f"{k!r} ({_FIXED[k]!r})" for k in other))
    dep = cfg["deployment"]
    first, last = dep["published_layers"]
    if last - first + 1 != cfg["num_hidden_layers"]:
        raise ValueError("key 'deployment.published_layers': not num_hidden_layers long")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(cfg[key][first:last + 1]):
            raise ValueError(f"key {key!r}: a clamp inside the cut; models/ling.py has none")
    if cfg["mtp_loss_scaling_factor"]:
        raise ValueError("key 'mtp_loss_scaling_factor': models/ling.py builds no "
                         "multi-token-prediction layer (the published weight is 0)")
    if dep["experts_held"][1] != cfg["num_experts"]:
        raise ValueError("keys 'num_experts', 'deployment.experts_held': the key "
                         "counts the experts held here")
    if cfg["recipe"].get("expert_bias", BIAS) != BIAS:
        raise ValueError(f"key 'recipe.expert_bias': this adapter's program is "
                         f"given {BIAS}, the reference what the file says")
    return LingConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_attention_heads"], ffn_hidden=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
        layer_types=_layer_types(cfg), num_dense_layers=cfg["first_k_dense_replace"],
        kda_head_dim=cfg["head_dim"], kda_conv=cfg["short_conv_kernel_size"],
        kda_lower_bound=float(cfg["kda_lower_bound"]),
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=dep["router_outputs"], top_k=cfg["num_experts_per_tok"],
        n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        held_experts=tuple(dep["experts_held"]), share_room=dep["share_room"],
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


class _Logits:
    """``forward``'s answer: the logits, computed where they are asked for.
    ``x[:, positions]`` is the head over those positions alone; as an array
    (``jnp.asarray(x)``) it is all of them."""

    def __init__(self, hidden, head):
        self.hidden, self.head = hidden, head

    def __getitem__(self, at):
        import jax.numpy as jnp

        return (self.hidden[at] @ self.head).astype(jnp.float32)

    def __jax_array__(self):
        return self[:]


def program():
    # the kind's module first: a program without it says so by that name
    from torchft_tpu.models.ling import (LING_FROZEN, ling_hidden, ling_init,
                                         ling_loss_and_stats)
    from torchft_tpu.models import split_frozen  # noqa: I001

    def init(key, pc):  # the trainable leaves: all an optimizer may see
        return split_frozen(ling_init(key, pc), LING_FROZEN)[0]

    def forward(params, tokens, pc, **kw):
        hidden, _ = ling_hidden(_with_bias(params, pc), tokens, pc, **kw)
        return _Logits(hidden, params["lm_head"])

    def loss(params, tokens, targets, pc, with_stats=False, **kw):
        import jax.numpy as jnp

        value, stats = ling_loss_and_stats(
            _with_bias(params, pc), tokens, targets, pc, **kw)
        if "overflow_pairs" in stats:  # a dropped pair: no step to report
            value = jnp.where(stats["overflow_pairs"] > 0, jnp.nan, value)
        return (value, stats) if with_stats else value

    return init, loss, forward


def router_alone(params, pc, router_in):
    """The program's expert block (its public ``moe_ffn``, each expert
    layer's own weights and its row of the bias) given ``router_in`` [L, T,
    D] float32 as the layers' input: per expert layer the ``routing``
    [L,T,k] and ``p_kth``, ``p_next`` [L,T]. The block's output is not used,
    so XLA drops the experts."""
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
    return {"attention": sum(m == "mla" for m, _ in kinds),
            "kda": sum(m == "kda" for m, _ in kinds),
            "grouped_matmul": sum(f == "moe" for _, f in kinds)}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf this chip holds, the ``expert_bias`` buffer among them."""
    return config(cfg).num_params()


def _sizes(cfg: dict):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, H, H * cfg["head_dim"], cfg["moe_intermediate_size"]


# operations a (position, head, key channel, value channel) of the delta rule
# asks for, one position after another: the decay, S^T k (two), the update
# (two), S^T q (two)
KDA_OPS = 7


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass on THIS chip, per token: every
    projection, the convolutions' taps, the delta rule's own operations, the
    MLA layer's causal products counted exactly (192-wide scores, 128-wide
    values; the zeros the kernel is padded with are not required work), the
    dense feed-forward, the router over all its outputs, the shared expert,
    the head over the slice, and of a token's ``num_experts_per_tok``
    experts the share that is held here (held / router outputs of them on
    average: the others are other chips' work)."""
    d, H, kd, W = _sizes(cfg)
    dn, dr, dv, r = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    dep = cfg["deployment"]
    mixer = {
        "kda": (2 * d * kd * 5 + 2 * 2 * d * H + 3 * 2 * cfg["short_conv_kernel_size"] * kd
                + KDA_OPS * kd * cfg["head_dim"]),
        "mla": (2 * d * H * (dn + dr) + 2 * d * (r + dr) + 2 * r * H * (dn + dv)
                + 2 * d * H + 2 * H * dv * d
                + 2 * H * (dn + dr + dv) * (seq + 1) / 2)}
    held = cfg["num_experts_per_tok"] * dep["experts_held"][1] / dep["router_outputs"]
    ffn = {"dense": 3 * 2 * d * cfg["intermediate_size"],
           "moe": 2 * d * dep["router_outputs"] + (1 + held) * 3 * 2 * d * W}
    return (sum(mixer[m] + ffn[f] for m, f in reference.kinds(cfg))
            + 2 * d * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _attention(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """FLOPs and HBM bytes one call of a flash-style causal attention
    kernel requires for the MLA layer, whole batch: scores over 192 values
    a head, the weighted sum over 128. "fwd": QK^T and PV; "bwd": QK^T
    again, dP, dV (128 wide), dQ, dK (192 wide). Bytes: q and k at 192, v,
    o and their cotangents at 128, bf16, every head its own keys."""
    H = cfg["num_attention_heads"]
    qk, dv = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    pairs = batch * H * seq * (seq + 1) / 2
    rows = 2.0 * batch * seq * H  # bf16
    if passes == "fwd":
        return {"flops": 2 * pairs * (qk + dv), "bytes": rows * (2 * qk + 2 * dv)}
    if passes == "bwd":
        return {"flops": 2 * pairs * (3 * qk + 2 * dv), "bytes": rows * (4 * qk + 4 * dv)}
    raise KeyError(passes)


def kda_cost(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """Operations and HBM bytes one pass of the delta rule requires over the
    batch, one layer: what MUST move, whatever a kernel keeps to itself.
    "fwd": q, k, v in and o out at 2 bytes a (position, channel), g at 4,
    beta at 4 a (position, head); "bwd": those again, do in, and the
    cotangents of q, k, v (2), g (4) and beta out; the states are computed
    again and none MUST be stored. ``KDA_OPS`` operations a (position, head,
    128, 128) forward, three times that backward (the states again, then two
    operations for one). On a v5e the HBM bound is the larger; peaks.json
    holds the MXU's rate and no rate of a chunked form's extra products, so
    a share well under 100% is expected and one over 105% would be a
    miscount here."""
    _, H, kd, _ = _sizes(cfg)
    wide, narrow = batch * seq * kd, batch * seq * H
    fwd = wide * (2 * 4 + 4.0) + 4.0 * narrow
    ops = float(KDA_OPS * wide * cfg["head_dim"])
    if passes == "fwd":
        return {"flops": ops, "bytes": fwd}
    if passes == "bwd":
        return {"flops": 3 * ops, "bytes": fwd + wide * (2 * 4 + 4.0) + 4.0 * narrow}
    raise KeyError(passes)


def _grouped_matmul(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """One grouped product over the rows that reach a held expert (the
    even share: tokens x experts a token x held / router outputs; the
    buffer's room beyond them is rows of zeros, not required work), one
    layer; every held expert's matrix read (or written) once."""
    if passes not in ("fwd", "dlhs", "drhs"):
        raise KeyError(passes)
    d, _, _, W = _sizes(cfg)
    dep = cfg["deployment"]
    e = dep["experts_held"][1]
    m = batch * seq * cfg["num_experts_per_tok"] * e / dep["router_outputs"]
    return {"flops": 2.0 * m * d * W, "bytes": 2.0 * (m * d + m * W + e * d * W)}


KERNEL_COSTS = {"attention": _attention, "kda": kda_cost,
                "grouped_matmul": _grouped_matmul}
