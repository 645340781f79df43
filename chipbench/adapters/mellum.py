"""Adapter ``mellum``: what is ``models/mellum.py``'s own (JetBrains' Mellum 2:
GQA attention whose layers are causal over a window of 1,024 or over the
whole sequence by ``layer_types``, with a rotary table by layer kind, the
full layers' YaRN's; heads of 128 beside a hidden size of 2,304; a per-head
QK norm; 64 softmax-routed experts, eight a token, the gates renormalised;
an untied head), as ONE CHIP'S SHARE of a stated deployment: the
configuration file's ``deployment`` says which of the router's experts this
chip holds, which published layers and how many vocabulary rows.
chipbench/adapters/llama.py says what an adapter is, chipbench/adapters/olmoe.py
what the job kind ``bare_routed`` asks beyond that, chipbench/adapters/ling.py
why the loss is NaN where a held pair found the share's buffer full and why
``forward`` hands out the hidden states with the head still to come.

The counts are exact under both masks: a window layer's attention counts
``W (W + 1) / 2 + (S - W) W`` (i, j) pairs a head, a full layer's ``S (S +
1) / 2`` (:func:`pairs`); a causal count for a window layer would read the
kernels' share of their roofline 16 x too high at 32,768.
"""

from chipbench import reference_mellum as reference  # noqa: F401  (the plain reference)
from chipbench.worker import TRAINER

# the embedding and the head over the slice; of the first window layer and of
# the first full layer the queries' projection (rotary, the window, YaRN and
# its factor all move it) and a head norm (zero where it is left out); the
# first layer's float32 router (its gradient comes through the gates: their
# scale shows); the last layer's expert matrix, element by element and as
# its norms expert by expert
GRAD_LEAVES = ["embed", "lm_head", "layers.00_window.wq", "layers.00_window.q_norm",
               "layers.00_window.router", "layers.01_full.wq", "layers.01_full.k_norm",
               "layers.03_full.w_down", "layers.03_full.w_down@expert_norms"]

# keys this adapter reads; the others it knows are held to the one value
# ``models/mellum.py`` computes (``_FIXED``); any other is a property of the
# model this code would drop in silence
_EXPRESSED = {
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_intermediate_size",
    "max_position_embeddings", "rms_norm_eps", "layer_types", "sliding_window",
    "rope_parameters", "num_experts", "num_experts_per_tok", "norm_topk_prob",
    "deployment"}
_FIXED = {"attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False,
          "use_sliding_window": True}
# read by nothing: every layer is sparse (``mlp_layer_types``, checked), so no
# dense SwiGLU of ``intermediate_size`` is built; ``layer_types`` says which
# layers have a window, ``max_window_layers`` (0) adds nothing to it
_UNREAD = {"intermediate_size", "max_window_layers", "mlp_layer_types"}
_DESCRIBES = {
    "name", "source", "adapter", "architectures", "model_type", "published",
    "reduced", "assumed", "recipe", "cut", "stands_for"}
_ROPE_KEYS = {"sliding_attention": {"rope_type", "rope_theta"},
              "full_attention": {"rope_type", "rope_theta", "factor",
                                 "original_max_position_embeddings", "beta_fast",
                                 "beta_slow", "attention_factor"}}


def config(cfg: dict):
    """The configuration file (the published keys) as the program's
    MellumConfig; refuses what ``models/mellum.py`` cannot express."""
    import jax.numpy as jnp

    from torchft_tpu.models.mellum import MellumConfig

    unknown = sorted(set(cfg) - _EXPRESSED - set(_FIXED) - _UNREAD - _DESCRIBES)
    if unknown:
        raise ValueError("adapter 'mellum' cannot express key "
                         + ", ".join(map(repr, unknown)))
    other = sorted(k for k, v in _FIXED.items() if cfg.get(k, v) != v)
    if other:
        raise ValueError("adapter 'mellum': models/mellum.py computes one value of "
                         + ", ".join(f"{k!r} ({_FIXED[k]!r})" for k in other))
    dep = cfg["deployment"]
    first, last = dep["published_layers"]
    if not last - first + 1 == cfg["num_hidden_layers"] == len(cfg["layer_types"]):
        raise ValueError("keys 'deployment.published_layers', 'layer_types': not "
                         "num_hidden_layers long")
    if set(cfg["mlp_layer_types"]) != {"sparse"} \
            or len(cfg["mlp_layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("key 'mlp_layer_types': models/mellum.py ends every layer in "
                         "experts ('sparse')")
    if dep["experts_held"][1] != cfg["num_experts"]:
        raise ValueError("keys 'num_experts', 'deployment.experts_held': the key "
                         "counts the experts held here")
    rope = cfg["rope_parameters"]
    if {k: set(v) for k, v in rope.items()} != _ROPE_KEYS:
        raise ValueError("key 'rope_parameters': models/mellum.py has a plain table for "
                         "'sliding_attention' and YaRN's for 'full_attention'")
    plain, yarn = rope["sliding_attention"], rope["full_attention"]
    if (plain["rope_type"], yarn["rope_type"]) != ("default", "yarn") \
            or plain["rope_theta"] != yarn["rope_theta"]:
        raise ValueError("key 'rope_parameters': rope_type 'default' and 'yarn' over "
                         "one rope_theta")
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    if set(cfg["layer_types"]) - set(kinds):
        raise ValueError(f"key 'layer_types': {sorted(set(cfg['layer_types']) - set(kinds))}")
    return MellumConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        ffn_hidden=cfg["moe_intermediate_size"],  # one expert's width
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(plain["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg["recipe"]["param_dtype"]],
        layer_types=tuple(kinds[t] for t in cfg["layer_types"]),
        window=cfg["sliding_window"], yarn_factor=float(yarn["factor"]),
        yarn_original_max=yarn["original_max_position_embeddings"],
        yarn_beta_fast=float(yarn["beta_fast"]), yarn_beta_slow=float(yarn["beta_slow"]),
        yarn_attention_factor=float(yarn["attention_factor"]),
        num_experts=dep["router_outputs"], top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        held_experts=tuple(dep["experts_held"]), share_room=dep["share_room"],
        loss_chunk=cfg["recipe"].get("loss_chunk", 0),
    )


def register(cfg: dict) -> "tuple[str, list[str]]":
    from torchft_tpu.models import CONFIGS

    CONFIGS[cfg["name"]] = config(cfg)
    return TRAINER, ["--config", cfg["name"]]


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
    from torchft_tpu.models.mellum import (mellum_hidden, mellum_init,
                                           mellum_loss_and_stats)

    def forward(params, tokens, pc, **kw):
        return _Logits(mellum_hidden(params, tokens, pc, **kw)[0], params["lm_head"])

    def loss(params, tokens, targets, pc, with_stats=False, **kw):
        import jax.numpy as jnp

        value, stats = mellum_loss_and_stats(params, tokens, targets, pc, **kw)
        if "overflow_pairs" in stats:  # a dropped pair: no step to report
            value = jnp.where(stats["overflow_pairs"] > 0, jnp.nan, value)
        return (value, stats) if with_stats else value

    return mellum_init, loss, forward


def router_alone(params, pc, router_in):
    """The program's expert block (its public ``moe_ffn``, each layer's own
    weights) given ``router_in`` [L, T, D] float32 as the layers' input: per
    layer the ``routing`` [L,T,k] and ``p_kth``, ``p_next`` [L,T]. The
    block's output is not used, so XLA drops the experts."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models.moe import moe_ffn

    def layer(_, xs):
        w, x = xs
        _, stats = moe_ffn(x[None], w["router"], w["w_gate"], w["w_up"], w["w_down"], pc)
        return None, {k: stats[k] for k in ("routing", "p_kth", "p_next")}

    out, at = [], 0
    for name, _, n in pc.runs():
        out.append(jax.lax.scan(layer, None, (params["layers"][name],
                                              router_in[at:at + n]))[1])
        at += n
    return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *out)


def _layers(cfg: dict, kind: str) -> int:
    return sum(t == kind for t in cfg["layer_types"])


def layers_with(cfg: dict, kernel: str) -> int:
    return {"attention": cfg["num_hidden_layers"],
            "attention_window": _layers(cfg, "sliding_attention"),
            "attention_full": _layers(cfg, "full_attention"),
            "grouped_matmul": cfg["num_hidden_layers"]}[kernel]


def num_params(cfg: dict) -> int:
    """Every leaf this chip holds."""
    return config(cfg).num_params()


def pairs(cfg: dict, layer_type: str, seq: int) -> float:
    """The (query, key) pairs one head's mask allows in a layer of
    ``layer_type`` at ``seq`` positions: every ``j <= i``, under a window
    those with ``i - j < sliding_window``."""
    w = min(cfg["sliding_window"], seq) if layer_type == "sliding_attention" else seq
    return w * (w + 1) / 2 + (seq - w) * w


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass on THIS chip, per token: the
    projections (heads x head_dim wide, not the hidden size), each layer's
    scores and weighted sums over exactly the pairs its mask allows, the
    router over all its outputs, the head over the slice, and of a token's
    ``num_experts_per_tok`` experts the share that is held here (held /
    router outputs of them on average: the others are other chips' work)."""
    d, hd, H = cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"]
    q, kv = H * hd, cfg["num_key_value_heads"] * hd
    dep = cfg["deployment"]
    held = cfg["num_experts_per_tok"] * dep["experts_held"][1] / dep["router_outputs"]
    common = (2 * d * q + 2 * 2 * d * kv + 2 * q * d + 2 * d * dep["router_outputs"]
              + held * 3 * 2 * d * cfg["moe_intermediate_size"])
    attn = sum(2 * 2 * q * pairs(cfg, t, seq) / seq for t in cfg["layer_types"])
    return cfg["num_hidden_layers"] * common + attn + 2 * d * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def _attention_of(layer_types):
    def cost(cfg: dict, batch: int, seq: int, passes: str) -> dict:
        """FLOPs and HBM bytes one call of a flash-style attention kernel
        requires, whole batch, one layer, the mean over the layers of
        ``layer_types`` that the cut keeps, each under its own mask.
        "fwd": QK^T and PV; "bwd": QK^T again, dP, dQ, dK, dV. Bytes: q, o
        and their cotangents at the query heads, k and v at the key/value
        heads, bf16, each read or written once whatever the mask."""
        kept = [t for t in cfg["layer_types"] if t in layer_types]
        H, K, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
        mean_pairs = sum(pairs(cfg, t, seq) for t in kept) / len(kept)
        qo, kv = 2.0 * batch * seq * H * hd, 2.0 * batch * seq * K * hd  # bf16
        matmuls, rows = {"fwd": (2, 2 * qo + 2 * kv), "bwd": (5, 4 * qo + 4 * kv)}[passes]
        return {"flops": matmuls * 2 * batch * H * mean_pairs * hd, "bytes": rows}

    return cost


def _grouped_matmul(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """One grouped product over the rows that reach a held expert (the
    even share: tokens x experts a token x held / router outputs; the
    buffer's room beyond them is rows of zeros, not required work), one
    layer; every held expert's matrix read (or written) once."""
    if passes not in ("fwd", "dlhs", "drhs"):
        raise KeyError(passes)
    d, W = cfg["hidden_size"], cfg["moe_intermediate_size"]
    dep = cfg["deployment"]
    e = dep["experts_held"][1]
    m = batch * seq * cfg["num_experts_per_tok"] * e / dep["router_outputs"]
    return {"flops": 2.0 * m * d * W, "bytes": 2.0 * (m * d + m * W + e * d * W)}


KERNEL_COSTS = {
    "attention": _attention_of(("sliding_attention", "full_attention")),
    "attention_window": _attention_of(("sliding_attention",)),
    "attention_full": _attention_of(("full_attention",)),
    "grouped_matmul": _grouped_matmul}
