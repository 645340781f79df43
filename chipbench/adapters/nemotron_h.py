"""Adapter ``nemotron_h``: what is ``models/nemotron_h.py``'s own (NVIDIA's
Nemotron-H hybrid: every layer ONE pre-norm residual branch, a Mamba-2 mixer
under the chunked SSD kernel, GQA without positions, or sigmoid-routed
UNGATED relu^2 experts chosen six a token under a frozen selection bias
beside a shared expert twice as wide; an untied head), as ONE CHIP'S SHARE of
a stated deployment: the configuration file's ``deployment`` says which of
the router's experts this chip holds, which published layers and how many
vocabulary rows. chipbench/adapters/llama.py says what an adapter is,
chipbench/adapters/olmoe.py what the job kind ``bare_routed`` asks beyond
that, chipbench/adapters/lfm2.py why the init hands out the trainable leaves
alone, chipbench/adapters/ling.py why the loss is NaN where
``overflow_pairs`` is not 0 and why ``forward`` hands out hidden states with
the head still to come.
"""

from chipbench import reference_nemotron_h as reference  # noqa: F401  (the plain reference)
# the logits computed where they are asked for, and one grouped product over the
# even share: Ling's, word for word (the same share, the same keys)
from chipbench.adapters.ling import _grouped_matmul, _Logits
from chipbench.worker import TRAINER

# the embedding and the head over the slice; of the first Mamba layer the
# whole in_proj (z, x, B, C and dt's columns: the gate, the convolution and
# the recurrence all reach it), the decay's A_log (zero where the carry
# across chunks is lost) and dt_bias; of the first expert layer the float32
# router (its gradient comes through the gates: their scale shows), an up
# matrix, the down matrices as their norms expert by expert and the shared
# expert's up (zero where it is left out); the attention layer's queries
# (a rotary turn shows there)
GRAD_LEAVES = ["embed", "lm_head", "layers.00_mamba.in_proj", "layers.00_mamba.A_log",
               "layers.00_mamba.dt_bias", "layers.01_moe.router", "layers.01_moe.w_up",
               "layers.01_moe.w_down@expert_norms", "layers.01_moe.shared_up",
               "layers.05_attn.wq"]

# keys this adapter reads; the others it knows are held to the one value
# ``models/nemotron_h.py`` computes (``_FIXED``); any other is a property of
# the model this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size",
    "max_position_embeddings", "rope_theta", "layer_norm_epsilon", "norm_eps",
    "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
    "use_conv_bias", "time_step_min", "time_step_max", "time_step_floor",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size", "n_routed_experts",
    "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
    "routed_scaling_factor", "deployment"}
_FIXED = {
    "attention_bias": False, "chunk_size": 128, "mamba_hidden_act": "silu",
    "mamba_proj_bias": False, "mlp_bias": False, "mlp_hidden_act": "relu2",
    "n_shared_experts": 1, "residual_in_fp32": False, "sliding_window": None,
    "tie_word_embeddings": False, "use_bias": False}
# read by nothing: ``expand`` (the head keys give d_inner), the rotary keys
# (no layer turns anything), an initialisation rule and two switches of the
# published code's own paths
_UNREAD = {"expand", "partial_rotary_factor", "rescale_prenorm_residual",
           "use_mamba_kernels", "num_logits_to_keep"}
# ``recipe.expert_bias`` of every configuration file of this adapter: the
# program's functions are handed the config OBJECT, which holds no seed
BIAS = {"seed": 52, "scale": 0.01}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}


def config(cfg: dict):
    """The configuration file (the published keys) as the program's
    NemotronHConfig; refuses what ``models/nemotron_h.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.nemotron_h import NemotronHConfig

    unknown = sorted(set(cfg) - _EXPRESSED - set(_FIXED) - _UNREAD - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'nemotron_h' cannot express key "
                         + ", ".join(map(repr, unknown)))
    other = sorted(k for k, v in _FIXED.items() if cfg.get(k, v) != v)
    if other:
        raise ValueError("adapter 'nemotron_h': models/nemotron_h.py computes one value of "
                         + ", ".join(f"{k!r} ({_FIXED[k]!r})" for k in other))
    dep = cfg["deployment"]
    first, last = dep["published_layers"]
    if not (last - first + 1 == cfg["num_hidden_layers"] == len(cfg["hybrid_override_pattern"])):
        raise ValueError("keys 'deployment.published_layers', 'hybrid_override_pattern': "
                         "not num_hidden_layers long")
    if cfg["layer_norm_epsilon"] != cfg["norm_eps"]:
        raise ValueError("keys 'layer_norm_epsilon', 'norm_eps': the norms have one epsilon")
    if dep["experts_held"][1] != cfg["n_routed_experts"]:
        raise ValueError("keys 'n_routed_experts', 'deployment.experts_held': the key "
                         "counts the experts held here")
    if cfg["recipe"].get("expert_bias", BIAS) != BIAS:
        raise ValueError(f"key 'recipe.expert_bias': this adapter's program is "
                         f"given {BIAS}, the reference what the file says")
    return NemotronHConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"], max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["layer_norm_epsilon"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
        pattern=cfg["hybrid_override_pattern"],
        mamba_num_heads=cfg["mamba_num_heads"], mamba_head_dim=cfg["mamba_head_dim"],
        ssm_state_size=cfg["ssm_state_size"], mamba_n_groups=cfg["n_groups"],
        conv_kernel=cfg["conv_kernel"], use_conv_bias=cfg["use_conv_bias"],
        time_step_min=cfg["time_step_min"], time_step_max=cfg["time_step_max"],
        time_step_floor=cfg["time_step_floor"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_intermediate_size=cfg["moe_shared_expert_intermediate_size"],
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


def program():
    # the kind's module first: a program without it says so by that name
    from torchft_tpu.models.nemotron_h import (NEMOTRON_H_FROZEN, nemotron_h_hidden,
                                               nemotron_h_init, nemotron_h_loss_and_stats)
    from torchft_tpu.models import split_frozen  # noqa: I001

    def init(key, pc):  # the trainable leaves: all an optimizer may see
        return split_frozen(nemotron_h_init(key, pc), NEMOTRON_H_FROZEN)[0]

    def forward(params, tokens, pc, **kw):
        hidden, _ = nemotron_h_hidden(_with_bias(params, pc), tokens, pc, **kw)
        return _Logits(hidden, params["lm_head"])

    def loss(params, tokens, targets, pc, with_stats=False, **kw):
        import jax.numpy as jnp

        value, stats = nemotron_h_loss_and_stats(
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
        if kind != "moe":
            continue
        w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
        _, stats = moe_ffn(router_in[len(out)][None], w["router"], None, w["w_up"],
                           w["w_down"], pc, bias=None if bias is None else bias[len(out)])
        out.append({k: stats[k] for k in ("routing", "p_kth", "p_next")})
    return {k: jnp.stack([o[k] for o in out]) for k in out[0]}


def layers_with(cfg: dict, kernel: str) -> int:
    kinds = reference.kinds(cfg)
    return {"attention": kinds.count("attn"), "ssd": kinds.count("mamba"),
            "grouped_matmul": kinds.count("moe")}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf this chip holds, the ``expert_bias`` buffer among them."""
    return config(cfg).num_params()


def _sizes(cfg: dict):
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return cfg["hidden_size"], H, P, cfg["n_groups"], cfg["ssm_state_size"]


def _ssd_ops(cfg: dict, chunk: int) -> float:
    """Multiply-adds x 2 of the chunked form a position, one layer: ``C
    B^T`` once a GROUP (chunk x N a position), and a head's three products:
    within the chunk (chunk x P), from the state (N x P) and into it (N x P)."""
    _, H, P, G, N = _sizes(cfg)
    return 2.0 * (G * chunk * N + H * (chunk * P + 2 * N * P))


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass on THIS chip, per token: every
    projection, the convolution's taps, the SSD's products (``C B^T`` once a
    group), attention's causal products counted exactly, the router over all
    its outputs, the shared expert, the head over the slice, and of a
    token's ``num_experts_per_tok`` experts the share that is held here
    (held / router outputs of them on average: the others are other chips'
    work)."""
    d, H, P, G, N = _sizes(cfg)
    di, conv = H * P, H * P + 2 * G * N
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    dep = cfg["deployment"]
    held = cfg["num_experts_per_tok"] * dep["experts_held"][1] / dep["router_outputs"]
    one = {
        "mamba": (2 * d * (di + conv + H) + 2 * cfg["conv_kernel"] * conv
                  + _ssd_ops(cfg, cfg["chunk_size"]) + 2 * di * d),
        "attn": 2 * d * q + 2 * 2 * d * kv + 2 * q * d + 2 * 2 * q * (seq + 1) / 2,
        "moe": (2 * d * dep["router_outputs"]
                + 2 * 2 * d * (held * cfg["moe_intermediate_size"]
                               + cfg["moe_shared_expert_intermediate_size"]))}
    return sum(one[k] for k in reference.kinds(cfg)) + 2 * d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _attention(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """chipbench/flops.py's count of a flash-style causal GQA kernel's
    operations and bytes (32 / 2 heads of 128), whole batch, one layer."""
    from chipbench import flops

    return flops.attention_kernel_cost(cfg, batch, seq, passes)


def ssd_cost(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """Operations and HBM bytes one pass of the SSD requires over the batch,
    one layer: what MUST move, whatever a kernel keeps to itself. "fwd": x
    in and y out at 2 bytes a (position, channel of d_inner), B and C at 2 a
    (position, group, state), dt at 4 a (position, head); "bwd": those
    inputs again, dy in, and the cotangents of x, B, C (2) and dt (4) out;
    the states at chunk boundaries are the implementation's choice and none
    MUST be stored. Operations: :func:`_ssd_ops` forward, three times that
    backward (the chunk again, then two products for one). On a v5e the
    compute bound is the larger (bf16 peak); the kernel multiplies in
    float32 at six MXU passes a product, which is not required work, so a
    share well under 100% is expected and one over 105% would be a miscount
    here."""
    _, H, P, G, N = _sizes(cfg)
    pos = batch * seq
    io = pos * (2.0 * H * P + 2 * 2.0 * G * N + 4.0 * H)  # x, B, C, dt
    ops = pos * _ssd_ops(cfg, cfg["chunk_size"])
    if passes == "fwd":
        return {"flops": ops, "bytes": io + pos * 2.0 * H * P}
    if passes == "bwd":
        return {"flops": 3 * ops, "bytes": 2 * io + pos * 2.0 * H * P}
    raise KeyError(passes)


KERNEL_COSTS = {"attention": _attention, "ssd": ssd_cost,
                "grouped_matmul": _grouped_matmul}
