"""Adapter ``brumby``: what is ``models/brumby.py``'s own (Manifest AI's
Brumby: the dense decoder's layer with per-head norms on queries and keys, a
gate leaf and POWER RETENTION where attention stood: degree-2
symmetric-power linear attention under a learned scalar decay, normalised by
the running sum of its weights), for configuration files that name it under
``adapter``. chipbench/adapters/llama.py says what an adapter is.

The counts are of the RECURRENT form, whatever chunk a kernel uses: a token
reads the state once a query head and updates it once a key/value head, 2 x
8,256 x (128 + 1) operations each (the + 1: the normaliser rides along), so
that the same work is counted under any implementation; a kernel that
expands to 128 x 128 products for the 8,256 distinct ones does twice the
work for the same count.
"""

import os

from chipbench import flops
from chipbench import reference_brumby as reference  # noqa: F401  (the plain reference)
from chipbench.worker import REPO, TRAINER

# a program from before the kind stops here, at once and by that name, before
# a reference's child or a worker is started for it
if not os.path.exists(os.path.join(REPO, "torchft_tpu", "models", "brumby.py")):
    raise ImportError("No module named 'torchft_tpu.models.brumby'")

# the embedding and the head over the slice; of the first layer the queries'
# projection (a rotary turn, the per-head norm and phi's weights show there),
# the gate's matrix and bias (zero where the gate is left out), the queries'
# per-head norm and the feed-forward's last matrix
GRAD_LEAVES = ["embed", "lm_head", "layers.00_retention.wq", "layers.00_retention.wg",
               "layers.00_retention.bg", "layers.00_retention.q_norm",
               "layers.00_retention.w_down"]

# keys this adapter reads; the others it knows are held to the one value
# ``models/brumby.py`` computes (``_FIXED``); any other is a property of the
# model this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "max_position_embeddings",
    "rope_theta", "rms_norm_eps", "deployment"}
_FIXED = {"hidden_act": "silu", "rope_scaling": None, "sliding_window": None,
          "use_sliding_window": False, "tie_word_embeddings": False,
          "attention_bias": False}
# read by nothing: no layer has a window (``use_sliding_window`` false)
_UNREAD = {"max_window_layers"}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}


def config(cfg: dict):
    """The configuration file (the published keys) as the program's
    BrumbyConfig; refuses what ``models/brumby.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.brumby import BrumbyConfig

    unknown = sorted(set(cfg) - _EXPRESSED - set(_FIXED) - _UNREAD - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'brumby' cannot express key "
                         + ", ".join(map(repr, unknown)))
    other = sorted(k for k, v in _FIXED.items() if cfg.get(k, v) != v)
    if other:
        raise ValueError("adapter 'brumby': models/brumby.py computes one value of "
                         + ", ".join(f"{k!r} ({_FIXED[k]!r})" for k in other))
    recipe = cfg["recipe"]
    return BrumbyConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["intermediate_size"], max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[recipe["param_dtype"]],
        ffn_block=recipe.get("ffn_block", 0), loss_chunk=recipe.get("loss_chunk", 0),
        retention_chunk=recipe["retention_chunk"])


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def program():
    # the kind's module first: a program without it says so by that name
    from torchft_tpu.models.brumby import brumby_forward, brumby_init, brumby_loss

    return brumby_init, brumby_loss, brumby_forward


def layers_with(cfg: dict, kernel: str) -> int:
    return {"retention": cfg["num_hidden_layers"]}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf: the dense decoder's, two per-head norms a layer and the
    gate's matrix [hidden_size, key/value heads] and bias."""
    hkv = cfg["num_key_value_heads"]
    return flops.num_params(cfg) + cfg["num_hidden_layers"] * (
        2 * cfg["head_dim"] + (cfg["hidden_size"] + 1) * hkv)


def _phi(cfg: dict) -> int:
    """The state's rows: the distinct products of two of ``head_dim``."""
    return cfg["head_dim"] * (cfg["head_dim"] + 1) // 2


def _retention_ops(cfg: dict) -> float:
    """Multiply-adds x 2 of the recurrent form a position, one layer: a
    read of the state and the normaliser a query head, an update of both a
    key/value head."""
    return 2.0 * _phi(cfg) * (cfg["head_dim"] + 1) * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass, per token: the dense decoder's
    projections, SwiGLU and head (chipbench/flops.py's, its causal attention
    taken out), the gate's projection and the retention's recurrent form:
    constant in ``seq``."""
    d, q = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    attention = cfg["num_hidden_layers"] * 2 * 2 * q * (seq + 1) / 2
    return (flops.forward_flops_per_token(cfg, seq) - attention
            + cfg["num_hidden_layers"] * (2 * d * cfg["num_key_value_heads"]
                                          + _retention_ops(cfg)))


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def retention_cost(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """Operations and HBM bytes one pass of the retention requires over the
    batch, one layer: what MUST be done and moved, whatever a kernel keeps to
    itself. "fwd": q in and y out at 2 bytes a (position, query head,
    channel), k and v at 2 a (position, key/value head, channel), g at 4 a
    (position, key/value head); "bwd": those inputs again, dy in, and the
    cotangents of q, k, v (2) and g (4) out; the states a kernel saves are
    its own choice and none MUST be stored. Operations: :func:`_retention_ops`
    forward, twice that backward. On a v5e the compute bound is the larger
    (bf16 peak); the kernel multiplies float32 operands in three bf16 passes
    of the MXU and computes every chunk again in its backward pass, which is
    not required work, so a share of a seventh is what it reads (14.2%, PR
    56) and one over 105% would be a miscount here."""
    hq, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    pos = batch * seq
    inputs = pos * (2.0 * hq * hd + 2 * 2.0 * hkv * hd + 4.0 * hkv)  # q, k, v, g
    ops = pos * _retention_ops(cfg)
    if passes == "fwd":
        return {"flops": ops, "bytes": inputs + pos * 2.0 * hq * hd}
    if passes == "bwd":
        return {"flops": 2 * ops, "bytes": 2 * inputs + pos * 2.0 * hq * hd}
    raise KeyError(passes)


KERNEL_COSTS = {"retention": retention_cost}
