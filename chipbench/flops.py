"""Operations and bytes the algorithm requires, from the shapes alone, and
the table of peaks. The yardstick: kept here so that no PR that claims a gain
can move it. ``cfg`` is a configuration file (Hugging Face key names)."""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """{"bf16_flops", "hbm_bytes_s"} of one chip; an unknown kind is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise ValueError(f"no peaks known for device_kind {device_kind!r}; "
                         f"known: {sorted(table)} (chipbench/peaks.json)")
    return table[device_kind]


def num_params(cfg: dict) -> int:
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f + 2 * d
    head = 0 if cfg.get("tie_word_embeddings") else v * d
    return cfg["num_hidden_layers"] * per_layer + v * d + head + d


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Multiply-adds x 2 of one forward pass, per token, causal attention
    counted exactly (a token attends to (seq + 1) / 2 keys on average)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    proj = 2 * d * q + 2 * 2 * d * kv + 2 * q * d
    attn = 2 * 2 * q * (seq + 1) / 2  # QK^T and PV
    ffn = 3 * 2 * d * f
    return cfg["num_hidden_layers"] * (proj + attn + ffn) + 2 * d * v


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation under
    remat is not required work and is not counted."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def mfu(cfg: dict, seq: int, tokens_per_s_per_chip: float, device_kind: str) -> float:
    return (train_flops_per_token(cfg, seq) * tokens_per_s_per_chip
            / peaks(device_kind)["bf16_flops"])


def attention_kernel_cost(cfg: dict, batch: int, seq: int, passes: str) -> dict:
    """FLOPs and HBM bytes one call of a flash-style causal attention kernel
    requires, whole batch, one layer. ``passes``: "fwd" (QK^T, PV: 2
    matmuls), "bwd" (recompute QK^T, dP, dQ, dK, dV: 5 matmuls; the
    recompute is part of the algorithm, there is no stored score matrix)."""
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    pairs = batch * hq * seq * (seq + 1) / 2
    matmuls = {"fwd": 2, "bwd": 5}[passes]
    qo, kv = batch * seq * hq * hd * 2, batch * seq * hkv * hd * 2  # bf16
    # fwd reads q,k,v writes o; bwd reads q,k,v,o,do writes dq,dk,dv
    nbytes = {"fwd": 2 * qo + 2 * kv, "bwd": 4 * qo + 4 * kv}[passes]
    return {"flops": matmuls * 2 * pairs * hd, "bytes": float(nbytes)}


def roofline_floor_s(cost: dict, device_kind: str) -> "tuple[float, str]":
    """Least time the chip could take, and which peak bounds it."""
    p = peaks(device_kind)
    tc, tb = cost["flops"] / p["bf16_flops"], cost["bytes"] / p["hbm_bytes_s"]
    return (tc, "compute") if tc >= tb else (tb, "memory")
