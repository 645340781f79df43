"""Adapter ``jamba``: what is ``models/jamba.py``'s own (AI21's Jamba without
routed experts: Mamba-1 mixers round one attention layer a period, no
positions, a tied head), for configuration files that name it under
``adapter``. chipbench/adapters/llama.py says what an adapter is.
"""

from chipbench import flops
from chipbench import reference_jamba as reference  # noqa: F401  (the plain reference)
from chipbench.worker import TRAINER

# the embedding (read twice: the tied head), a Mamba leaf the scan's own
# cotangents (dt, B, C) reach, a feed-forward leaf; of the first run of
# layers (the program keeps one stack per run of like layers)
GRAD_LEAVES = ["embed", "layers.00_mamba.x_proj", "layers.00_mamba.w_down"]

# keys this adapter reads or tests; any other is a property of the model
# this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "max_position_embeddings",
    "rms_norm_eps", "hidden_act", "sliding_window", "tie_word_embeddings",
    "attn_layer_offset", "attn_layer_period", "mamba_d_state", "mamba_d_conv",
    "mamba_expand", "mamba_dt_rank", "mamba_conv_bias", "mamba_proj_bias",
    "num_experts", "num_experts_per_tok"}
# keys of the published file that say nothing of the training arithmetic at
# num_experts 1 (the configuration file says so under ``assumed``)
_SILENT = {"use_mamba_kernels", "num_logits_to_keep", "expert_layer_offset",
           "expert_layer_period"}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}


def config(cfg: dict):
    """The configuration file (Hugging Face keys) as the program's
    JambaConfig; refuses what ``models/jamba.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.jamba import JambaConfig

    unknown = sorted(set(cfg) - _EXPRESSED - _SILENT - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'jamba' cannot express key "
                         + ", ".join(map(repr, unknown)))
    for key, want in (("hidden_act", "silu"), ("sliding_window", None),
                      ("num_experts", 1), ("num_experts_per_tok", 1),
                      ("mamba_proj_bias", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"key {key!r}: models/jamba.py has {want!r} only, "
                             f"not {cfg[key]!r}")
    return JambaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], ffn_hidden=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"], norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        mamba_d_state=cfg["mamba_d_state"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_expand=cfg["mamba_expand"], mamba_dt_rank=cfg["mamba_dt_rank"],
        mamba_conv_bias=cfg["mamba_conv_bias"],
        tie_word_embeddings=cfg["tie_word_embeddings"],
        loss_chunk=cfg["recipe"].get("loss_chunk", 0),
    )


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def program():
    from torchft_tpu.models.jamba import jamba_forward, jamba_init, jamba_loss

    return jamba_init, jamba_loss, jamba_forward


def _layers(cfg: dict) -> "tuple[int, int]":
    """(Mamba layers, attention layers)."""
    kinds = reference.kinds(cfg)
    return kinds.count("mamba"), kinds.count("attention")


def layers_with(cfg: dict, kernel: str) -> int:
    mamba, attention = _layers(cfg)
    return {"selective_scan": mamba, "attention": attention}[kernel]


def num_params(cfg: dict) -> int:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    di, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    r, k = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    ffn = 3 * d * f + 2 * d
    mixer = (d * 2 * di + di * k + (di if cfg["mamba_conv_bias"] else 0)
             + di * (r + 2 * n) + r * di + di  # x_proj, dt_proj and its bias
             + di * n + di + (r + 2 * n) + di * d)  # A_log, D, three norms, out_proj
    attention = 2 * d * d + 2 * d * kv
    mamba, attn = _layers(cfg)
    head = 0 if cfg["tie_word_embeddings"] else v * d
    return mamba * (mixer + ffn) + attn * (attention + ffn) + v * d + head + d


# operations a (position, channel, state) of one forward scan: dt*A, exp,
# decay*h, dt*x*B (two), the sum into h, h*C, the sum into y, D*x's share
SCAN_OPS = 9


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass, per token: every projection,
    the convolution, the scan's own operations, the one attention layer's
    causal products counted exactly, the feed-forwards and the tied head."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    di, n = cfg["mamba_expand"] * d, cfg["mamba_d_state"]
    r, k = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    ffn = 3 * 2 * d * f
    mixer = (2 * d * 2 * di + 2 * k * di + 2 * di * (r + 2 * n) + 2 * r * di
             + SCAN_OPS * di * n + 2 * di * d)
    attention = 2 * d * d + 2 * 2 * d * kv + 2 * d * d + 2 * 2 * d * (seq + 1) / 2
    mamba, attn = _layers(cfg)
    return mamba * (mixer + ffn) + attn * (attention + ffn) + 2 * d * v


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _attention(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    # the dense decoder's count with the head size derived
    return flops.attention_kernel_cost(
        {**cfg, "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"]},
        batch, seq, passes)


def selective_scan_cost(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """Operations and HBM bytes one selective scan requires over the batch,
    one layer: what MUST move, whatever a kernel keeps to itself. ``passes``:
    "fwd": x, dt and z in and y out at 2 bytes a (position, channel), B and
    C at 4 a (position, state), A and D; "bwd": those again (the states are
    recomputed, none is stored), dy in, and the cotangents of x, dt and z
    (2 bytes) and of B and C (4), A and D out. The bound is the HBM one on
    every chip of peaks.json, which holds no rate of the vector unit; a
    share well under 100% is expected of a recurrence that does a dozen
    vector operations and an ``exp`` an element, and one over 105% would be
    a miscount here."""
    di, n = cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"]
    wide, narrow, small = batch * seq * di, batch * seq * n, di * n + di
    fwd = 2.0 * 4 * wide + 4.0 * 2 * narrow + 4.0 * small
    if passes == "fwd":
        return {"flops": float(SCAN_OPS * wide * n), "bytes": fwd}
    if passes == "bwd":  # recompute the states, then two operations for one
        return {"flops": float(3 * SCAN_OPS * wide * n),
                "bytes": fwd + 2.0 * 4 * wide + 4.0 * 2 * narrow + 4.0 * small}
    raise KeyError(passes)


KERNEL_COSTS = {"attention": _attention, "selective_scan": selective_scan_cost}
