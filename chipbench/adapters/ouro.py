"""Adapter ``ouro``: what is ``models/ouro.py``'s own (ByteDance's Ouro, a
looped language model: one stack of sandwich-norm layers run
``total_ut_steps`` times over the same weights, an exit after every pass, an
exit gate and an expected-exit loss over the exits), for configuration files
that name it under ``adapter``. chipbench/adapters/llama.py says what an
adapter is.

The counts are of layer APPLICATIONS: a step runs ``total_ut_steps x
num_hidden_layers`` of them and ``total_ut_steps`` heads over the one set of
weights ``num_params`` counts once. The embedding is a lookup and no matmul:
no FLOPs are counted for it.
"""

import os

from chipbench import flops
from chipbench import reference_ouro as reference  # noqa: F401  (the plain reference)
from chipbench.worker import REPO, TRAINER

# a program from before the kind stops here, at once and by that name, before
# a reference's child or a worker is started for it
if not os.path.exists(os.path.join(REPO, "torchft_tpu", "models", "ouro.py")):
    raise ImportError("No module named 'torchft_tpu.models.ouro'")

# the embedding; the head every exit reads; the norm between the passes (its
# gradient sums over every boundary); the gate (only the expected-exit loss
# reaches it); of the shared stack the queries' projection, a post-norm (zero
# where the sandwich is left out) and the feed-forward's last matrix
GRAD_LEAVES = ["embed", "lm_head", "final_norm", "exit_gate.w", "layers.wq",
               "layers.attn_post_norm", "layers.w_down"]

# keys this adapter reads; the others it knows are held to the one value
# ``models/ouro.py`` computes (``_FIXED``); any other is a property of the
# model this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "intermediate_size", "max_position_embeddings",
    "rope_theta", "rms_norm_eps", "layer_types", "total_ut_steps"}
_FIXED = {"hidden_act": "silu", "rope_scaling": None, "sliding_window": None,
          "use_sliding_window": False, "tie_word_embeddings": False,
          "early_exit_threshold": 1}
# read by nothing: no layer has a window (``use_sliding_window`` false)
_UNREAD = {"max_window_layers"}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}


def config(cfg: dict):
    """The configuration file (the published keys) as the program's
    OuroConfig; refuses what ``models/ouro.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.ouro import OuroConfig

    unknown = sorted(set(cfg) - _EXPRESSED - set(_FIXED) - _UNREAD - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'ouro' cannot express key "
                         + ", ".join(map(repr, unknown)))
    other = sorted(k for k, v in _FIXED.items() if cfg.get(k, v) != v)
    if other:
        raise ValueError("adapter 'ouro': models/ouro.py computes one value of "
                         + ", ".join(f"{k!r} ({_FIXED[k]!r})" for k in other)
                         + ": inference that leaves early is no training path")
    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise ValueError("key 'head_dim': OuroConfig derives it as hidden_size / "
                         "num_attention_heads")
    if cfg["layer_types"] != ["full_attention"] * cfg["num_hidden_layers"]:
        raise ValueError("key 'layer_types': models/ouro.py has 'full_attention' "
                         "layers only, num_hidden_layers of them")
    if cfg["total_ut_steps"] < 1:
        raise ValueError("key 'total_ut_steps': at least one pass")
    recipe = cfg["recipe"]
    return OuroConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], ffn_hidden=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[recipe["param_dtype"]],
        total_ut_steps=cfg["total_ut_steps"], exit_beta=recipe["exit_beta"],
        loss_chunk=recipe.get("loss_chunk", 0),
    )


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def program():
    # the kind's module first: a program without it says so by that name
    from torchft_tpu.models.ouro import ouro_forward, ouro_init, ouro_loss

    return ouro_init, ouro_loss, ouro_forward


def layers_with(cfg: dict, kernel: str) -> int:
    """The layer applications of a step that call ``kernel``."""
    return {"attention": cfg["total_ut_steps"] * cfg["num_hidden_layers"]}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf, once: the dense decoder's, two more norms a layer, and
    the gate's ``w`` [hidden_size] and scalar ``b``."""
    d = cfg["hidden_size"]
    return flops.num_params(cfg) + cfg["num_hidden_layers"] * 2 * d + d + 1


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass, per token: the dense decoder's
    layers and head (chipbench/flops.py: projections, causal products
    counted exactly, SwiGLU, the head; nothing for the embedding's lookup)
    ``total_ut_steps`` times, and the gate's dot product at every exit."""
    return cfg["total_ut_steps"] * (flops.forward_flops_per_token(cfg, seq)
                                    + 2 * cfg["hidden_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


KERNEL_COSTS = {"attention": flops.attention_kernel_cost}
