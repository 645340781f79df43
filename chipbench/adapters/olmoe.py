"""Adapter ``olmoe``: what is ``models/moe.py``'s dropless block's own
(OLMoE: top-k of many small experts, gates not renormalised, QK-norm over
the whole projection, no token dropped), for configuration files that name
it under ``adapter``. chipbench/adapters/llama.py says what an adapter is.

Beyond that contract, for the job kind ``bare_routed``: ``program()``'s loss
and forward take ``routing=`` ([L, T, k] expert indices to replay), the loss
also ``with_stats=True`` (-> (loss, stats) with the routing the program
would have chosen freely), the reference's answers carry its routing and
its routers' inputs, and ``router_alone`` puts the program's router before
those inputs.
"""

from chipbench import flops
from chipbench import reference_olmoe as reference  # noqa: F401  (the plain reference)
from chipbench.worker import TRAINER

GRAD_LEAVES = ["layers.router", "layers.wq", "layers.w_down"]

# keys this adapter reads or tests; any other is a property of the model
# this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "intermediate_size", "max_position_embeddings",
    "rope_theta", "rms_norm_eps", "hidden_act", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "router_aux_loss_coef",
    "attention_bias", "clip_qkv", "rope_scaling", "tie_word_embeddings"}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}


def config(cfg: dict):
    """The configuration file (Hugging Face keys) as the program's
    MoEConfig; refuses what ``models/moe.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.moe import MoEConfig

    unknown = sorted(set(cfg) - _EXPRESSED - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'olmoe' cannot express key "
                         + ", ".join(map(repr, unknown)))
    for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                      ("clip_qkv", None), ("rope_scaling", None),
                      ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise ValueError(f"key {key!r}: models/moe.py has {want!r} only, "
                             f"not {cfg[key]!r}")
    return MoEConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_hidden=cfg["intermediate_size"],  # one expert's width
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        capacity_factor=None,  # dropless
        aux_loss_weight=cfg["router_aux_loss_coef"],
        norm_topk_prob=cfg["norm_topk_prob"],
        qk_norm=True,  # modeling_olmoe.py; no key of config.json says so
    )


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


def program():
    from torchft_tpu.models.moe import moe_forward, moe_init, moe_loss_and_stats

    def forward(*args, **kw):  # the logits alone
        return moe_forward(*args, **kw)[0]

    def loss(*args, with_stats=False, **kw):
        value, stats = moe_loss_and_stats(*args, **kw)
        return (value, stats) if with_stats else value

    return moe_init, loss, forward


def router_alone(params, pc, router_in):
    """The program's expert block (its public ``moe_ffn``, each layer's own
    weights) given ``router_in`` [L, T, D] float32 as the layers' input:
    what its router makes of the very numbers the reference's router saw,
    per layer the ``routing`` [L,T,k] and ``p_kth``, ``p_next`` [L,T]. The
    block's output is not used, so XLA drops the experts."""
    import jax

    from torchft_tpu.models.moe import moe_ffn

    def layer(_, xs):
        w, x = xs
        _, stats = moe_ffn(x[None], w["router"], w["w_gate"], w["w_up"],
                           w["w_down"], pc)
        return None, {k: stats[k] for k in ("routing", "p_kth", "p_next")}

    return jax.lax.scan(layer, None, (params["layers"], router_in))[1]


def num_params(cfg: dict) -> int:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = d // cfg["num_attention_heads"]
    q, kv = d, cfg["num_key_value_heads"] * hd
    per_layer = (d * q + 2 * d * kv + q * d + q + kv  # attention, q_norm, k_norm
                 + d * cfg["num_experts"] + 3 * cfg["num_experts"] * d * f + 2 * d)
    return cfg["num_hidden_layers"] * per_layer + 2 * v * d + d


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass, per token: the projections,
    causal attention counted exactly, the router and the
    ``num_experts_per_tok`` experts a token uses (not the 64 that exist)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    proj = 2 * d * d + 2 * 2 * d * kv + 2 * d * d
    attn = 2 * 2 * d * (seq + 1) / 2  # QK^T and PV
    router = 2 * d * cfg["num_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * 2 * d * f
    return cfg["num_hidden_layers"] * (proj + attn + router + experts) + 2 * d * v


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _attention(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    # multi-head: the dense decoder's count with the head size derived
    return flops.attention_kernel_cost(
        {**cfg, "head_dim": cfg["hidden_size"] // cfg["num_attention_heads"]},
        batch, seq, passes)


def grouped_matmul_cost(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """FLOPs and HBM bytes one grouped matrix multiplication requires over
    the batch's ``tokens x num_experts_per_tok`` rows, one layer; every
    expert's matrix is read (or written) once, whatever the load.
    ``passes``: "fwd" one forward product (gate, up and down cost the same:
    rows x 2048 x 1024 either way round); "dlhs" the rows' cotangent (the
    same product against the transposed matrices); "drhs" the matrices'
    cotangent (rows^T x cotangent within each group)."""
    m = batch * seq * cfg["num_experts_per_tok"]
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    if passes not in ("fwd", "dlhs", "drhs"):
        raise KeyError(passes)
    # bf16: rows in, rows out, the experts' matrices
    return {"flops": 2.0 * m * d * f, "bytes": 2.0 * (m * d + m * f + e * d * f)}


KERNEL_COSTS = {"attention": _attention, "grouped_matmul": grouped_matmul_cost}


def layers_with(cfg: dict, kernel: str) -> int:
    return cfg["num_hidden_layers"]
