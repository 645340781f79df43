"""What the files that test the fifth kind of model (``models/ling.py``)
share: ``tests/test_ling_kernels.py`` (the KDA kernels and the router),
``tests/test_ling.py`` (the share, the pinned programs), ``test_ling_kind.py``,
``tests/test_ling_faults.py`` and ``tests/test_ling_faults_moe.py`` (one check,
two lists of faults) and ``tests/test_ling_manager.py``."""

import dataclasses
import functools
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import reference_ling as reference  # noqa: E402,F401
from torchft_tpu.models import CONFIGS  # noqa: E402
from torchft_tpu.models import ling  # noqa: E402


@functools.cache
def load_faults():
    """``benchmarks/ling_check_faults.py`` as a module."""
    spec = importlib.util.spec_from_file_location(
        "ling_check_faults", f"{ROOT}/benchmarks/ling_check_faults.py")
    faults = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(faults)
    return faults


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _mixer_inputs(cfg, name, seed=0):
    params = ling.ling_init(jax.random.PRNGKey(seed), cfg)
    w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
    return jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 96, cfg.dim)), w


FAULT_SEEN_IN = {
    "no_decay": ("kda", 0.05), "beta_one": ("kda", 0.05), "lost_tap": ("kda", 0.05),
    "bf16_state": ("kda", 1e-4), "bf16_kda": ("kda", 1e-4),
    "no_rope": ("mla", 0.02), "no_latent_norm": ("mla", 0.05),
    "no_group_limit": ("moe", 0.05), "no_shared": ("moe", 0.05), "scaling_one": ("moe", 0.05),
    "fp8_experts": ("moe", 0.01),
}



def check_fault(name):
    """The program's mixer or block in float32 is the reference's to
    rounding; with the fault of ``benchmarks/ling_check_faults.py`` in, it
    is off by at least the share stated. (Whether the cell's CHECK refuses
    the fault is tests/chipbench/test_reference_ling.py's, for four of them,
    and the chip's for all.)"""
    cfg = dataclasses.replace(CONFIGS["ling_debug"], dtype=jnp.float32, share_room=8.0)
    part, least = FAULT_SEEN_IN[name]
    file = {"head_dim": 16, "kda_lower_bound": -5.0, "rms_norm_eps": cfg.norm_eps,
            "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "kv_lora_rank": 32, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
            "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
            "deployment": {"experts_held": [4, 4]}}
    if part == "kda":
        u, w = _mixer_inputs(cfg, "01_kda_moe")
        run = lambda: ling._kda_mixer(u, w, cfg)  # noqa: E731
        want = reference._kda(u, w, file, jnp.matmul)
    elif part == "mla":
        u, w = _mixer_inputs(cfg, "02_mla_moe")
        run = lambda: ling._mla_mixer(u, w, cfg, ling._attention)  # noqa: E731
        want = reference._mla(u, w, file, jnp.matmul)
    else:
        u, w = _mixer_inputs(cfg, "03_kda_moe")
        bias = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (16,))
        run = lambda: ling.moe_ffn(  # noqa: E731
            u, w["router"], w["w_gate"], w["w_up"], w["w_down"], cfg, bias=bias,
            shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))[0]
        want = reference._routed(u[0], w, bias, file, jnp.matmul, jnp.matmul)[0][None]
    jax.clear_caches()
    assert rel(run(), want) < 2e-5
    jax.clear_caches()
    with load_faults().fault(name, cfg):
        off = rel(run(), want)
    jax.clear_caches()
    assert off > least, (name, off)
