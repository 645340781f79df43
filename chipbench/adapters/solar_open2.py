"""Adapter ``solar_open2``: what is ``models/solar.py``'s own (Upstage's Solar
Open 2 hybrid: KDA in Kimi Linear's unbounded form with ``beta`` to 2 and
rank-128 decay and gate projections, three layers in four, round a gated
grouped-query attention layer WITHOUT positions; every layer ends in
sigmoid-routed experts chosen eight a token of 320 under a frozen selection
bias, beside a shared expert; an untied head), as ONE CHIP'S SHARE of a stated
deployment: the configuration file's ``deployment`` says which of the
router's experts this chip holds, which published layers and how many
vocabulary rows. chipbench/adapters/llama.py says what an adapter is,
chipbench/adapters/olmoe.py what the job kind ``bare_routed`` asks beyond
that, chipbench/adapters/lfm2.py why the init hands out the trainable leaves
alone, chipbench/adapters/ling.py why the loss is NaN where a held pair
overflowed and why ``forward`` hands out the hidden states with the head
still to come (``_Logits``, chipbench/adapters/mellum.py's, as the grouped
product's cost; ``KDA_OPS`` is Ling's adapter's: the recurrent form's 7
operations a (position, head, 128, 128), not the chunked kernel's).
"""

from chipbench import reference_solar_open2 as reference  # noqa: F401  (the plain reference)
from chipbench.adapters.ling import KDA_OPS
from chipbench.adapters.mellum import _grouped_matmul, _Logits
from chipbench.worker import TRAINER

# the embedding and the head over the slice; the GQA layer's queries (a
# rotary turn shows there) and its gate (zero where the gate is left out);
# the first router (its gradient comes through the gates); of the first KDA
# layer the decay's A_log, dt_bias and W_fb (the decay's form, a clip), beta's
# projection (beta's range), the gate's W_gb (its granularity) and a
# convolution's taps (a lost tap is a zero row); the last layer's shared
# expert and an expert matrix, element by element and as its norms expert by
# expert
GRAD_LEAVES = ["embed", "lm_head", "layers.00_gqa_moe.wq", "layers.00_gqa_moe.w_g",
               "layers.00_gqa_moe.router", "layers.01_kda_moe.A_log",
               "layers.01_kda_moe.dt_bias", "layers.01_kda_moe.w_fb",
               "layers.01_kda_moe.w_beta", "layers.01_kda_moe.w_gb",
               "layers.01_kda_moe.conv_k", "layers.03_kda_moe.shared_down",
               "layers.03_kda_moe.w_down", "layers.03_kda_moe.w_down@expert_norms"]

# keys this adapter reads; the others it knows are held to the one value
# ``models/solar.py`` computes (``_FIXED``); any other is a property of the
# model this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "linear_attn_config", "intermediate_size",
    "moe_intermediate_size", "max_position_embeddings", "rope_theta", "rms_norm_eps",
    "gqa_layers", "n_routed_experts", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "deployment"}
_FIXED = {
    "first_k_dense_replace": 0, "use_rope": False, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "n_shared_experts": 1,
    "tie_word_embeddings": False}
# read by nothing: without a rotary turn (use_rope false) its share of a head
# has nothing to turn, and gqa_layers says what gqa_interval summarises
# (checked against it); intermediate_size is the width of dense layers, of
# which there are none (first_k_dense_replace 0): the program is told it and
# no layer reads it
_UNREAD = {"partial_rotary_factor", "gqa_interval"}
# ``recipe.expert_bias`` of every configuration file of this adapter: the
# program's functions are handed the config OBJECT, which holds no seed
BIAS = {"seed": 64, "scale": 0.01}
_DESCRIBES = {
    "name", "source", "adapter", "model_type", "published", "reduced", "assumed", "recipe",
    "cut", "stands_for"}
_LINEAR = {"short_conv_kernel_size", "head_dim", "num_heads", "num_kv_heads"}


def _gqa_layers(cfg: dict) -> "tuple[int, ...]":
    """The kept layers (from 0) that mix with GQA: ``gqa_layers``' entries
    inside ``deployment.published_layers``."""
    first, last = cfg["deployment"]["published_layers"]
    return tuple(i - first for i in cfg["gqa_layers"] if first <= i <= last)


def config(cfg: dict):
    """The configuration file (the published keys) as the program's
    SolarConfig; refuses what ``models/solar.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.solar import SolarConfig

    unknown = sorted(set(cfg) - _EXPRESSED - set(_FIXED) - _UNREAD - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'solar_open2' cannot express key "
                         + ", ".join(map(repr, unknown)))
    other = sorted(k for k, v in _FIXED.items() if cfg.get(k, v) != v)
    if other:
        raise ValueError("adapter 'solar_open2': models/solar.py computes one value of "
                         + ", ".join(f"{k!r} ({_FIXED[k]!r})" for k in other))
    lin, dep = cfg["linear_attn_config"], cfg["deployment"]
    if set(lin) != _LINEAR:
        raise ValueError("key 'linear_attn_config': this adapter reads "
                         f"{sorted(_LINEAR)}, the file has {sorted(lin)}")
    if lin["num_heads"] != cfg["num_attention_heads"] or lin["num_kv_heads"] not in (
            None, lin["num_heads"]):
        raise ValueError("key 'linear_attn_config': models/solar.py has as many KDA heads "
                         "as query heads, for keys and values alike")
    first, last = dep["published_layers"]
    if last - first + 1 != cfg["num_hidden_layers"]:
        raise ValueError("key 'deployment.published_layers': not num_hidden_layers long")
    step = cfg["gqa_interval"] + 1
    if [i for i in cfg["gqa_layers"] if i % step]:
        raise ValueError("keys 'gqa_layers', 'gqa_interval': a GQA layer every "
                         f"{step} layers is what the interval says")
    if dep["experts_held"][1] != cfg["n_routed_experts"]:
        raise ValueError("keys 'n_routed_experts', 'deployment.experts_held': the key "
                         "counts the experts held here")
    if cfg["recipe"].get("expert_bias", BIAS) != BIAS:
        raise ValueError(f"key 'recipe.expert_bias': this adapter's program is "
                         f"given {BIAS}, the reference what the file says")
    return SolarConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"], max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
        gqa_layers=_gqa_layers(cfg), kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"], kda_rank=lin["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=dep["router_outputs"], top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling=float(cfg["routed_scaling_factor"]),
        held_experts=tuple(dep["experts_held"]), share_room=dep["share_room"],
        loss_chunk=cfg["recipe"].get("loss_chunk", 0),
        kda_out_block=cfg["recipe"].get("kda_out_block", 0),
    )


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def _with_bias(params, pc):
    return {**params, "expert_bias": reference.expert_bias(
        **BIAS, layers=pc.n_layers, experts=pc.num_experts)}


def program():
    # the kind's module first: a program without it says so by that name
    from torchft_tpu.models.solar import (SOLAR_FROZEN, solar_hidden, solar_init,
                                          solar_loss_and_stats)
    from torchft_tpu.models import split_frozen  # noqa: I001

    def init(key, pc):  # the trainable leaves: all an optimizer may see
        return split_frozen(solar_init(key, pc), SOLAR_FROZEN)[0]

    def forward(params, tokens, pc, **kw):
        hidden, _ = solar_hidden(_with_bias(params, pc), tokens, pc, **kw)
        return _Logits(hidden, params["lm_head"])

    def loss(params, tokens, targets, pc, with_stats=False, **kw):
        import jax.numpy as jnp

        value, stats = solar_loss_and_stats(
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

    bias = _with_bias({}, pc)["expert_bias"]
    out = []
    for name, _, _ in pc.runs():
        w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
        _, stats = moe_ffn(router_in[len(out)][None], w["router"], w["w_gate"],
                           w["w_up"], w["w_down"], pc,
                           bias=bias[len(out)])
        out.append({k: stats[k] for k in ("routing", "p_kth", "p_next")})
    return {k: jnp.stack([o[k] for o in out]) for k in out[0]}


def layers_with(cfg: dict, kernel: str) -> int:
    mixers = [m for m, _ in reference.kinds(cfg)]
    return {"attention": mixers.count("gqa"), "kda": mixers.count("kda"),
            "grouped_matmul": len(mixers)}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf this chip holds, the ``expert_bias`` buffer among them."""
    return config(cfg).num_params()


def _sizes(cfg: dict):
    lin = cfg["linear_attn_config"]
    return (cfg["hidden_size"], cfg["num_attention_heads"], lin["num_heads"] * lin["head_dim"],
            cfg["moe_intermediate_size"])


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass on THIS chip, per token: every
    projection (the rank-128 pairs as two products each), the convolutions'
    taps, the delta rule's own operations, the GQA layer's causal products
    counted exactly with its gate's projection, the router over all its
    outputs, the shared expert, the head over the slice, and of a token's
    ``num_experts_per_tok`` experts the share that is held here (held /
    router outputs of them on average: the others are other chips' work)."""
    d, H, kd, W = _sizes(cfg)
    lin, dep = cfg["linear_attn_config"], cfg["deployment"]
    hd, kv, r = cfg["head_dim"], cfg["num_key_value_heads"] * cfg["head_dim"], lin["head_dim"]
    mixer = {
        "kda": (2 * d * kd * 4 + 2 * 2 * (d * r + r * kd) + 2 * d * H
                + 3 * 2 * lin["short_conv_kernel_size"] * kd + KDA_OPS * kd * lin["head_dim"]),
        "gqa": (2 * d * H * hd * 3 + 2 * 2 * d * kv + 2 * H * 2 * hd * (seq + 1) / 2)}
    held = cfg["num_experts_per_tok"] * dep["experts_held"][1] / dep["router_outputs"]
    ffn = 2 * d * dep["router_outputs"] + (1 + held) * 3 * 2 * d * W
    return sum(mixer[m] + ffn for m, _ in reference.kinds(cfg)) + 2 * d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _attention(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """FLOPs and HBM bytes one call of a flash-style causal attention
    kernel requires for the GQA layer, whole batch: 64 query heads over 8
    key/value heads of 128. "fwd": QK^T and PV; "bwd": QK^T again, dP, dV,
    dQ, dK. Bytes: q, o and their cotangents a query head, k, v and theirs
    a key/value head, bf16."""
    H, KV, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    pairs = batch * H * seq * (seq + 1) / 2
    rows = 2.0 * batch * seq * hd  # bf16
    if passes == "fwd":
        return {"flops": 2 * pairs * 2 * hd, "bytes": rows * (2 * H + 2 * KV)}
    if passes == "bwd":
        return {"flops": 2 * pairs * 5 * hd, "bytes": rows * (4 * H + 4 * KV)}
    raise KeyError(passes)


def kda_cost(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """Operations and HBM bytes one pass of the delta rule requires over the
    batch, one layer, as chipbench/adapters/ling.py's at this file's 64
    heads: what MUST move, whatever a kernel keeps to itself. "fwd": q, k,
    v in and o out at 2 bytes a (position, channel), g at 4, beta at 4 a
    (position, head); "bwd": those again, do in, and the cotangents of q, k,
    v (2), g (4) and beta out. ``KDA_OPS`` operations a (position, head,
    128, 128) forward, three times that backward. The general body's
    pair-by-pair exponents are the chunked form's extra work, not required
    work: the share reads lower than Ling's for it."""
    _, _, kd, _ = _sizes(cfg)
    lin = cfg["linear_attn_config"]
    wide, narrow = batch * seq * kd, batch * seq * lin["num_heads"]
    fwd = wide * (2 * 4 + 4.0) + 4.0 * narrow
    ops = float(KDA_OPS * wide * lin["head_dim"])
    if passes == "fwd":
        return {"flops": ops, "bytes": fwd}
    if passes == "bwd":
        return {"flops": 3 * ops, "bytes": fwd + wide * (2 * 4 + 4.0) + 4.0 * narrow}
    raise KeyError(passes)


KERNEL_COSTS = {"attention": _attention, "kda": kda_cost,
                "grouped_matmul": _grouped_matmul}
