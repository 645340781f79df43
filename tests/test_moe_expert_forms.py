"""``models/moe.py``'s two expert forms (``MoEConfig.expert_act``: SwiGLU, or
the ungated ``down(relu(up(x))^2)`` with no ``w_gate`` leaf anywhere) and the
shared expert's own width: ``num_params`` against the leaves' sizes for every
registered preset over ``MoEConfig``, and the dropless block ungated against
the sum written out."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import CONFIGS, model_fns, moe


def _leaves(cfg):
    tree = jax.eval_shape(lambda k: model_fns(cfg).init(k, cfg), jax.random.PRNGKey(0))
    if getattr(cfg, "dsa_stage", None):  # that stage counts parameters: its bias, state, apart
        tree = {k: v for k, v in tree.items() if k != "expert_bias"}
    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("name", sorted(n for n, c in CONFIGS.items()
                                        if isinstance(c, moe.MoEConfig)))
def test_num_params_is_the_leaves_sizes_for_every_registered_preset(name):
    """Every preset of every kind over ``MoEConfig``, in both expert forms
    and with the shared expert at its own width."""
    cfg = CONFIGS[name]
    assert cfg.num_params() == _leaves(cfg)
    if type(cfg) is moe.MoEConfig and cfg.capacity_factor is None:
        ungated = dataclasses.replace(cfg, expert_act="relu2")
        assert ungated.num_params() == _leaves(ungated) < cfg.num_params()
        assert ungated.expert_matrices == 2 and cfg.expert_matrices == 3


def test_the_dropless_block_runs_both_expert_forms():
    """OLMoE's block ungated: ``down(relu(up(x))^2)`` per chosen expert,
    against the sum written out; no ``w_gate`` anywhere."""
    cfg = dataclasses.replace(moe.MOE_CONFIGS["debug"], capacity_factor=None,
                              expert_act="relu2")
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (1, 24, cfg.dim))
    router = jax.random.normal(ks[1], (cfg.dim, 4))
    up = jax.random.normal(ks[2], (4, cfg.dim, 32)) / 8
    down = jax.random.normal(ks[3], (4, 32, cfg.dim)) / 6
    got, stats = moe.moe_ffn(x, router, None, up, down, cfg)
    probs = jax.nn.softmax(x[0] @ router, axis=-1)
    gates, idx = jax.lax.top_k(probs, 2)
    gates = gates / (gates.sum(-1, keepdims=True) + cfg.gate_eps)
    every = jnp.einsum("eth,ehd->etd", jnp.square(jax.nn.relu(
        jnp.einsum("td,edh->eth", x[0], up))), down)
    want = sum(gates[:, j, None] * every[idx[:, j], jnp.arange(24)] for j in range(2))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=2e-4, atol=2e-5)
    with pytest.raises(ValueError, match="expert_act"):
        dataclasses.replace(moe.MOE_CONFIGS["debug"], expert_act="relu2")  # the capacity path
    assert "w_gate" not in moe.moe_init(jax.random.PRNGKey(0), cfg)["layers"]
    assert "w_gate" not in moe.moe_param_specs(cfg)["layers"]
