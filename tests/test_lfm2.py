"""The short-convolution / attention decoder with routed experts
(torchft_tpu/models/lfm2.py) as a fourth kind of the one trainer's model:
its layer kinds and parameter counts at LFM2-8B-A1B's published keys, the
model against the plain reference ``chipbench/reference_lfm2.py`` (logits,
loss, every gradient leaf), the convolution against ``numpy.convolve``,
causality, head size 64 through the dispatcher, and the leaf that is state
and no parameter."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import reference_lfm2 as reference  # noqa: E402
from torchft_tpu.models import CONFIGS, model_fns, split_frozen  # noqa: E402
from torchft_tpu.models.lfm2 import (LFM2_CONFIGS, LFM2_FROZEN, Lfm2Config,  # noqa: E402
                                     _conv_mixer, lfm2_forward, lfm2_init, lfm2_loss,
                                     lfm2_loss_and_stats, lfm2_param_specs)
from torchft_tpu.ops import attention as attention_ops  # noqa: E402

PRESET = LFM2_CONFIGS["lfm2_debug"]
DEBUG = dataclasses.replace(PRESET, dtype=jnp.float32)  # the preset's shape, in float32
PUBLISHED = LFM2_CONFIGS["lfm2_8b_a1b"]
ONE_PERIOD = dataclasses.replace(
    PUBLISHED, n_layers=5, num_dense_layers=1,
    layer_types=("conv", "full_attention", "conv", "conv", "conv"))


def _hf(cfg: Lfm2Config) -> dict:
    """The config object as the Hugging Face keys the reference reads."""
    return {"hidden_size": cfg.dim, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "conv_L_cache": cfg.conv_L_cache,
            "layer_types": list(cfg.layer_types), "num_hidden_layers": cfg.n_layers,
            "num_dense_layers": cfg.num_dense_layers, "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": 1, "use_expert_bias": True,
            "vocab_size": cfg.vocab_size}


@pytest.fixture(scope="module")
def tiny():
    params = lfm2_init(jax.random.PRNGKey(0), DEBUG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, DEBUG.vocab_size)
    return params, tokens


def test_layer_kinds_and_runs_at_the_published_keys():
    types = PUBLISHED.layer_types
    assert len(types) == 24 and types.count("full_attention") == 6
    assert [i for i, t in enumerate(types) if t == "full_attention"] == [2, 6, 10, 14, 18, 21]
    kinds = PUBLISHED.kinds()
    assert kinds[:3] == [("conv", "dense"), ("conv", "dense"), ("attn", "moe")]
    assert sum(f == "moe" for _, f in kinds) == PUBLISHED.n_moe_layers == 22
    # the two dense layers run together; every expert layer runs alone
    runs = PUBLISHED.runs()
    assert runs[0] == ("00_conv_dense", ("conv", "dense"), 2) and len(runs) == 23
    assert all(n == 1 for _, (_, f), n in runs if f == "moe")
    assert [name for name, _, _ in ONE_PERIOD.runs()] == [
        "00_conv_dense", "01_attn_moe", "02_conv_moe", "03_conv_moe", "04_conv_moe"]
    assert sorted(n for n, _, _ in DEBUG.runs()) == [n for n, _, _ in DEBUG.runs()]
    assert (PUBLISHED.dim, PUBLISHED.n_heads, PUBLISHED.n_kv_heads, PUBLISHED.head_dim,
            PUBLISHED.ffn_hidden, PUBLISHED.moe_intermediate_size, PUBLISHED.num_experts,
            PUBLISHED.top_k, PUBLISHED.vocab_size) == (2048, 32, 8, 64, 7168, 1792, 32, 4,
                                                       65536)
    assert (PUBLISHED.router_score, PUBLISHED.gate_eps, PUBLISHED.norm_topk_prob,
            PUBLISHED.capacity_factor) == ("sigmoid", 1e-6, True, None)


@pytest.mark.parametrize("cfg,count", [
    (ONE_PERIOD, 1_665_448_192), (PUBLISHED, 8_339_930_560),
    (dataclasses.replace(PUBLISHED, n_layers=3, num_dense_layers=1,
                         layer_types=("conv", "full_attention", "conv")), 927_099_072)],
    ids=["one_period", "published", "depth_3"])
def test_num_params_at_the_published_widths(cfg, count):
    assert cfg.num_params() == count
    shapes = jax.eval_shape(lambda: lfm2_init(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == count
    assert shapes["expert_bias"].shape == (cfg.n_moe_layers, 32)
    assert shapes["expert_bias"].dtype == jnp.float32


def test_presets_stand_in_the_registry_and_model_fns_knows_the_kind(tiny):
    assert CONFIGS["lfm2_debug"] is PRESET and CONFIGS["lfm2_8b_a1b"] is PUBLISHED
    assert PRESET.dtype == PUBLISHED.dtype == jnp.bfloat16
    init, loss, specs, stages, frozen = model_fns(DEBUG)
    assert init is lfm2_init and specs is lfm2_param_specs
    assert stages is None and frozen == LFM2_FROZEN == ("expert_bias",)
    params, tokens = tiny
    value, stats = loss(params, tokens, tokens, DEBUG)
    assert sorted(stats["moe_stats"]) == ["moe_bias_moved_share", "moe_load_max_over_mean"]
    assert np.isfinite(float(value))
    assert 0.0 < float(stats["moe_stats"]["moe_bias_moved_share"]) < 1.0
    assert float(stats["moe_stats"]["moe_load_max_over_mean"]) >= 1.0
    assert jax.tree_util.tree_structure(specs(DEBUG)) == jax.tree_util.tree_structure(params)
    trainable, held = split_frozen(params, frozen)
    assert sorted(held) == ["expert_bias"] and "expert_bias" not in trainable
    assert {**trainable, **held}.keys() == params.keys()


@pytest.mark.parametrize("change,match", [
    (dict(layer_types=("conv",)), "layer_types"),
    (dict(layer_types=("conv", "window") * 3), "window"),
    (dict(capacity_factor=1.25), "capacity_factor"),
    (dict(aux_loss_weight=0.01), "aux_loss_weight"),
    (dict(num_dense_layers=9), "num_dense_layers"),
    (dict(router_score="tanh"), "router_score")])
def test_what_the_kind_cannot_express_is_refused_with_the_key_named(change, match):
    with pytest.raises(ValueError, match=match):
        dataclasses.replace(DEBUG, **change)


@pytest.fixture(scope="module")
def against_reference(tiny):
    params, tokens = tiny
    cfg = _hf(DEBUG)
    paths = ["embed", "final_norm"] + [
        f"layers.{name}.{key}" for name, stack in params["layers"].items() for key in stack]
    sample = {"grad_leaves": paths, "grad_elements": 10**9}
    positions = np.arange(tokens.shape[1])
    ref = reference.answers(params, tokens, cfg, positions, sample)
    (val, stats), grads = jax.value_and_grad(
        lambda p: lfm2_loss_and_stats(p, tokens, tokens, DEBUG), has_aux=True)(params)
    return ref, val, stats, grads, paths, sample


def test_logits_and_loss_agree_with_the_plain_reference(tiny, against_reference):
    """Float32 on the CPU: two layouts of the same equations differ by the
    order of float32 sums through six layers: 1e-5 class."""
    params, tokens = tiny
    ref, val, stats, _, _, _ = against_reference
    logits = lfm2_forward(params, tokens, DEBUG)
    assert float(jnp.abs(logits - ref["logits"]).max()) < 5e-5
    assert abs(float(val) - ref["loss"]) < 1e-5
    # the same experts for every token, freely routed; the margins of s + b
    np.testing.assert_array_equal(np.asarray(stats["routing"]), ref["routing"])
    np.testing.assert_allclose(np.asarray(stats["p_kth"]), ref["p_kth"], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(stats["p_next"]), ref["p_next"], rtol=1e-5)
    whole, routing = reference.forward(params, tokens, _hf(DEBUG))
    assert float(jnp.abs(whole - ref["logits"]).max()) < 5e-5  # blocks == all at once
    np.testing.assert_array_equal(np.asarray(routing["routing"]), ref["routing"])


def test_every_gradient_leaf_agrees_with_the_plain_reference(against_reference):
    ref, _, _, grads, paths, sample = against_reference
    got = reference.grad_answers(split_frozen(grads, LFM2_FROZEN)[0], sample)
    assert abs(float(got["grad_norm"]) / float(ref["grad_norm"]) - 1) < 1e-5
    for path in paths:
        a, b = np.asarray(got["grad." + path]), ref["grad." + path]
        assert a.shape == b.shape, path
        assert np.linalg.norm(a - b) <= 2e-5 * max(np.linalg.norm(b), 1e-3), path
    # the bias is read and takes no gradient
    assert float(jnp.abs(grads["expert_bias"]).max()) == 0.0


def test_the_convolution_is_numpy_convolve_a_channel():
    """``c[t] = k0 u[t-2] + k1 u[t-1] + k2 u[t]`` on ``u = B * X``, gated by
    C: channel by channel ``numpy.convolve`` of u with the taps reversed."""
    d, T = 8, 20
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    u = jax.random.normal(ks[0], (1, T, d), jnp.float32)
    w = {"in_proj": jax.random.normal(ks[1], (d, 3 * d), jnp.float32),
         "conv_w": jax.random.normal(ks[2], (3, d), jnp.float32),
         "out_proj": jnp.eye(d, dtype=jnp.float32)}
    got = np.asarray(_conv_mixer(u, w))[0]
    b, c, x = np.split(np.asarray(u[0] @ w["in_proj"]), 3, axis=-1)
    taps = np.asarray(w["conv_w"])
    for ch in range(d):
        want = c[:, ch] * np.convolve((b * x)[:, ch], taps[::-1, ch])[:T]
        np.testing.assert_allclose(got[:, ch], want, rtol=1e-5, atol=1e-5)


def test_a_tokens_output_is_unchanged_by_later_tokens(tiny):
    params, tokens = tiny
    first = lfm2_forward(params, tokens, DEBUG)
    later = tokens.at[:, 30:].set((tokens[:, 30:] + 7) % DEBUG.vocab_size)
    second = lfm2_forward(params, later, DEBUG)
    np.testing.assert_allclose(np.asarray(first[:, :30]), np.asarray(second[:, :30]),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(first[:, 30:] - second[:, 30:]).max()) > 1e-2


def test_head_size_64_goes_through_the_dispatcher(monkeypatch):
    """The published heads are 64 wide: off the TPU the dispatcher resolves
    the XLA path for them; on it 64 is among the sizes the kernels tile."""
    cfg = dataclasses.replace(DEBUG, dim=256, n_heads=4, n_kv_heads=2, ffn_hidden=128)
    assert cfg.head_dim == 64
    params = lfm2_init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 128), 0, cfg.vocab_size)
    attention_ops.LAST_DISPATCH = None
    assert np.isfinite(float(lfm2_loss(params, tokens, tokens, cfg)))
    assert attention_ops.LAST_DISPATCH == "xla"
    q = jnp.zeros((1, 100, 4, 64))  # what a TPU would be asked: 64 tiles, 100 does not
    monkeypatch.setattr(attention_ops, "_on_tpu", lambda: True)
    with pytest.raises(ValueError, match="seq_len=100 head_dim=64"):
        attention_ops.causal_attention(q, q[:, :, :2], q[:, :, :2], cfg)
    with pytest.raises(ValueError, match="head_dim=32"):
        attention_ops.causal_attention(q[:, :, :, :32], q[:, :, :2, :32], q[:, :, :2, :32], cfg)


def test_remat_loss_chunk_replay_and_attention_fn_work_as_for_the_other_kinds(tiny):
    params, tokens = tiny
    whole = lfm2_loss(params, tokens, tokens, DEBUG, remat="none")
    for kw in (dict(remat="full"), dict(remat="dots"), dict(loss_chunk=16)):
        assert abs(float(lfm2_loss(params, tokens, tokens, DEBUG, **kw)) - float(whole)) < 1e-5
    chunked = dataclasses.replace(DEBUG, loss_chunk=16)
    assert abs(float(lfm2_loss(params, tokens, tokens, chunked)) - float(whole)) < 1e-5
    calls = []

    def attention_fn(q, k, v, cfg):
        calls.append(q.shape)
        return attention_ops.xla_attention(q, k, v, cfg)

    lfm2_loss(params, tokens, tokens, DEBUG, attention_fn=attention_fn)
    assert calls and all(s == (2, 48, 4, 16) for s in calls)
    _, free = lfm2_loss_and_stats(params, tokens, tokens, DEBUG)
    assert free["routing"].shape == (DEBUG.n_moe_layers, 2 * 48, DEBUG.top_k)
    same, again = lfm2_loss_and_stats(params, tokens, tokens, DEBUG, routing=free["routing"])
    assert abs(float(same) - float(whole)) < 1e-6
    other, _ = lfm2_loss_and_stats(params, tokens, tokens, DEBUG,
                                   routing=(free["routing"] + 1) % DEBUG.num_experts)
    assert abs(float(other) - float(whole)) > 1e-4


def test_a_model_of_dense_layers_alone_has_no_bias_leaf_and_no_routing():
    cfg = dataclasses.replace(DEBUG, num_dense_layers=DEBUG.n_layers)
    params = lfm2_init(jax.random.PRNGKey(0), cfg)
    assert "expert_bias" not in params and "expert_bias" not in lfm2_param_specs(cfg)
    assert cfg.n_moe_layers == 0 and cfg.num_params() == sum(
        x.size for x in jax.tree_util.tree_leaves(params))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, cfg.vocab_size)
    value, stats = lfm2_loss_and_stats(params, tokens, tokens, cfg)
    assert np.isfinite(float(value)) and stats == {}
    assert split_frozen(params, LFM2_FROZEN)[1] == {}


def test_the_combines_own_backward_pass_moves_no_bit_of_any_gradient(tiny, monkeypatch):
    """PR 39: the loss and every gradient leaf of the whole model (biased
    sigmoid router, remat full) with ``models/moe._combine`` as it is and
    as autodiff was given it before."""
    from torchft_tpu.models import moe

    def as_it_was(rows, weights, inverse, order):
        (T, k), d = weights.shape, rows.shape[-1]
        picked = moe._take_rows(rows, inverse, order, 1).reshape(T, k, d)
        return jnp.sum(picked * weights[..., None], axis=1)

    params, tokens = tiny
    trained, _ = split_frozen(params, LFM2_FROZEN)

    def run():
        def loss(trained):
            return lfm2_loss({**params, **trained}, tokens, tokens, DEBUG, remat="full")
        return jax.jit(jax.value_and_grad(loss))(trained)

    value, grads = run()
    monkeypatch.setattr(moe, "_combine", as_it_was)
    value_was, grads_was = run()
    assert float(value) == float(value_was)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(grads_was)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))
