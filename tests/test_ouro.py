"""models/ouro.py: the looped decoder. What ties the loop to the model (the
same layers unrolled ``T x L`` deep with untied copies: equal outputs, the
looped gradient the sum over the copies), the exit distribution and the
expected-exit loss written out token by token, the chunked loss, the tree,
its count and its PartitionSpecs, the stats a trainer logs, and the scopes a
device trace is cut by."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import CONFIGS, model_fns
from torchft_tpu.models.llama import _rmsnorm, make_llama_layer_body
from torchft_tpu.models.ouro import (OuroConfig, even_exit_bias, exit_log_probs, exit_loss,
                                     ouro_exit_logits, ouro_exits, ouro_forward, ouro_init,
                                     ouro_loss, ouro_loss_and_stats, ouro_param_specs)

CFG = dataclasses.replace(CONFIGS["ouro_debug"], n_layers=3)
T, L = CFG.total_ut_steps, CFG.n_layers


@pytest.fixture(scope="module")
def setup():
    params = ouro_init(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, CFG.vocab_size)
    return params, tokens, jnp.roll(tokens, -1, axis=1)


def _off(got, want) -> float:
    """Largest |got - want| over the largest |want|."""
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _unrolled_exits(copies, params, tokens):
    """The same layers ``T x L`` deep, every application with weights of its
    own (``copies``: the stack's leaves [T * L, ...]), no scan."""
    layer = make_llama_layer_body(CFG)
    h, out = params["embed"][tokens], []
    for t in range(T):
        for i in range(L):
            h, _ = layer(h, jax.tree_util.tree_map(lambda x: x[t * L + i], copies))
        h = _rmsnorm(h, params["final_norm"], CFG.norm_eps)
        out.append(h)
    return jnp.stack(out)


def test_the_loop_is_the_unrolled_model_with_tied_copies(setup):
    """Outputs equal, and the looped stack's gradient is the sum of the
    untied copies' gradients over the passes; every other leaf's equal."""
    params, tokens, targets = setup
    copies = jax.tree_util.tree_map(lambda x: jnp.tile(x, (T,) + (1,) * (x.ndim - 1)),
                                    params["layers"])

    def unrolled_loss(copies, rest):
        hs = _unrolled_exits(copies, rest, tokens)
        return exit_loss(hs, rest["lm_head"], rest["exit_gate"], targets, CFG.exit_beta)[0]

    rest = {k: v for k, v in params.items() if k != "layers"}
    np.testing.assert_allclose(np.asarray(ouro_exits(params, tokens, CFG)),
                               np.asarray(_unrolled_exits(copies, rest, tokens)),
                               rtol=1e-4, atol=1e-5)
    want, (g_copies, g_rest) = jax.value_and_grad(unrolled_loss, argnums=(0, 1))(copies, rest)
    got, grads = jax.value_and_grad(ouro_loss)(params, tokens, targets, CFG)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for name, g in grads["layers"].items():
        summed = g_copies[name].reshape(T, L, *g.shape[1:]).sum(axis=0)
        assert _off(g, summed) < 1e-4, name
        assert _off(g, g_copies[name].reshape(T, L, *g.shape[1:])[-1]) > 1e-2, name  # one pass
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(
            {k: v for k, v in grads.items() if k != "layers"}),
            jax.tree_util.tree_leaves(g_rest)):
        assert _off(g, w) < 1e-4, jax.tree_util.keystr(path)


def test_the_exit_distribution_and_the_loss_token_by_token(setup):
    """``p_t = lambda_t prod_{j<t} (1 - lambda_j)``, the last exit what is
    left; the loss ``mean(sum_t p_t ce_t - beta H(p))`` from logits written
    out here; the stats its parts."""
    params, tokens, targets = setup
    z = jnp.asarray([[0.3, -2.0], [1.2, 0.0], [-0.7, 5.0], [9.0, -9.0]])
    lam = np.asarray(jax.nn.sigmoid(z))
    p = np.exp(np.asarray(exit_log_probs(z)))
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[1], lam[1] * (1 - lam[0]), rtol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]), rtol=1e-6)
    np.testing.assert_allclose(p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-5)
    np.testing.assert_allclose(p.sum(axis=0), 1.0, rtol=1e-6)

    logits = ouro_exit_logits(params, tokens, CFG)
    assert logits.shape == (T, 2, 32, CFG.vocab_size) and logits.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(ouro_forward(params, tokens, CFG)),
                                  np.asarray(logits[-1]))
    hs = ouro_exits(params, tokens, CFG)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits), jnp.broadcast_to(
        targets, (T, 2, 32))[..., None], axis=-1)[..., 0]
    p = jnp.exp(exit_log_probs(hs @ params["exit_gate"]["w"] + params["exit_gate"]["b"]))
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    want = jnp.mean(jnp.sum(p * ce, axis=0) - CFG.exit_beta * entropy)
    loss, stats = ouro_loss_and_stats(params, tokens, targets, CFG)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert sorted(stats) == ["ce_1", "ce_2", "ce_3", "ce_4", "exit_entropy",
                             "exit_step_mean", "p_last"]
    assert float(stats["exit_entropy"]) == pytest.approx(float(jnp.mean(entropy)), rel=1e-5)
    assert float(stats["p_last"]) == pytest.approx(float(jnp.mean(p[-1])), rel=1e-5)
    assert float(stats["exit_step_mean"]) == pytest.approx(
        float(jnp.mean(jnp.sum(p * jnp.arange(1, T + 1)[:, None, None], axis=0))), rel=1e-5)
    for t in range(T):
        assert float(stats[f"ce_{t + 1}"]) == pytest.approx(float(jnp.mean(ce[t])), rel=1e-5)
    # an untrained gate spreads its exits round the middle one
    assert 2.3 < float(stats["exit_step_mean"]) < 2.7
    without = ouro_loss(params, tokens, targets, dataclasses.replace(CFG, exit_beta=0.0))
    assert float(without) - float(loss) == pytest.approx(
        CFG.exit_beta * float(jnp.mean(entropy)), rel=1e-3)


@pytest.mark.parametrize("remat", ["none", "dots", "full"])
def test_the_loss_in_chunks_and_under_every_remat_is_the_loss(setup, remat):
    """``loss_chunk`` (the argument, or the configuration's where it divides
    a longer sequence) and the remat mode change the program, not a value or
    a gradient."""
    params, tokens, targets = setup
    want, g_want = jax.value_and_grad(ouro_loss)(params, tokens, targets, CFG, remat="none")
    by_cfg = dataclasses.replace(CFG, loss_chunk=8)
    for cfg, kw in ((CFG, {"loss_chunk": 16}), (by_cfg, {})):
        got, grads = jax.jit(jax.value_and_grad(
            lambda p: ouro_loss(p, tokens, targets, cfg, remat=remat, **kw)))(params)
        assert float(got) == pytest.approx(float(want), rel=1e-6)
        worst = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)),
            grads, g_want)
        assert max(jax.tree_util.tree_leaves(worst)) < 1e-4, worst
    with pytest.raises(ValueError, match="loss_chunk"):
        ouro_loss(params, tokens, targets, CFG, loss_chunk=5)


def test_the_tree_its_count_and_its_specs():
    params = jax.eval_shape(lambda: ouro_init(jax.random.PRNGKey(0), CFG))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params)) \
        == CFG.num_params()
    published = OuroConfig(vocab_size=49152, dim=2048, n_layers=16, n_heads=16,
                           n_kv_heads=16, ffn_hidden=5632)
    assert published.num_params() == 1_023_545_345
    assert dataclasses.replace(published, n_layers=48).num_params() \
        == 48 * 51_388_416 + 2 * 100_663_296 + 2048 + 2049
    assert sorted(params) == ["embed", "exit_gate", "final_norm", "layers", "lm_head"]
    assert {k: v.shape for k, v in params["exit_gate"].items()} == {"w": (64,), "b": ()}
    assert all(v.dtype == jnp.float32 for v in params["exit_gate"].values())
    assert {"attn_post_norm", "ffn_post_norm"} <= set(params["layers"])
    specs = ouro_param_specs(CFG)
    assert jax.tree_util.tree_structure(specs, is_leaf=lambda x: not isinstance(x, dict)) \
        == jax.tree_util.tree_structure(params)
    # a leaf of its own each: a donated step may not be handed one buffer twice
    real = ouro_init(jax.random.PRNGKey(0), CFG)
    assert real["layers"]["attn_post_norm"] is not real["layers"]["ffn_post_norm"]
    m = model_fns(CFG)
    assert m.stages(CFG, None).loops == T and m.frozen == ()


def test_an_untrained_gate_leaves_in_the_middle():
    for loops in (2, 4, 8):
        q = 1 / (1 + np.exp(even_exit_bias(loops)))  # 1 - lambda
        assert sum(q ** t for t in range(loops)) == pytest.approx((loops + 1) / 2, rel=1e-9)
    assert even_exit_bias(1) == 0.0


def test_the_trace_can_be_cut_by_pass_and_by_exit(setup):
    """The compiled step names its operations by the program's scopes:
    forward, recomputation and backward of the stack under ``loop/pass``, the
    norm between passes, the heads, the gate and the loss under
    ``loop/exit``; the optimizer's update and the embedding under neither."""
    import re

    params, tokens, targets = setup
    cfg = dataclasses.replace(CFG, loss_chunk=16)
    text = jax.jit(jax.value_and_grad(
        lambda p: ouro_loss(p, tokens, targets, cfg))).lower(params).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    passes = [n for n in names if "loop/pass" in n]
    exits = [n for n in names if "loop/exit" in n]
    assert passes and exits and not set(passes) & set(exits)
    assert any("transpose" in n for n in passes) and any("transpose" in n for n in exits)
    assert any("dot_general" in n for n in passes) and any("logsumexp" in n or "reduce" in n
                                                          for n in exits)
