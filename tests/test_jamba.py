"""The Mamba / attention hybrid (torchft_tpu/models/jamba.py) as a third
kind of the one trainer's model: its layer kinds and parameter counts at
Jamba2-3B's published keys, the tied head, what it refuses, and that remat,
``loss_chunk`` and ``attention_fn=`` work as for the other kinds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu import bucketing
from torchft_tpu.models import CONFIGS, model_fns
from torchft_tpu.models.jamba import (JAMBA_CONFIGS, JambaConfig, jamba_forward,
                                      jamba_init, jamba_loss, jamba_loss_and_stats,
                                      jamba_param_specs)
from torchft_tpu.ops.attention import xla_attention

PRESET = JAMBA_CONFIGS["jamba_debug"]
DEBUG = dataclasses.replace(PRESET, dtype=jnp.float32)  # the preset's shape, in float32
PUBLISHED = JAMBA_CONFIGS["jamba2_3b"]


@pytest.fixture(scope="module")
def tiny():
    params = jamba_init(jax.random.PRNGKey(0), DEBUG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, DEBUG.vocab_size)
    return params, tokens


def test_layer_kinds_are_transformers_layers_block_type():
    # JambaConfig.layers_block_type for (offset 7, period 14, 28 layers)
    want = ["attention" if i % 14 == 7 else "mamba" for i in range(28)]
    assert PUBLISHED.layers_block_type == want
    assert [i for i, k in enumerate(want) if k == "attention"] == [7, 21]
    one_period = dataclasses.replace(PUBLISHED, n_layers=14)
    assert one_period.runs() == [("00_mamba", "mamba", 7), ("01_attn", "attention", 1),
                                 ("02_mamba", "mamba", 6)]
    assert [n for _, _, n in PUBLISHED.runs()] == [7, 1, 13, 1, 6]
    assert DEBUG.layers_block_type.count("attention") == 2  # both kinds twice
    assert DEBUG.layers_block_type.count("mamba") >= 2


@pytest.mark.parametrize("depth,count", [(14, 1_598_556_096), (28, 3_029_337_472),
                                         (8, 973_587_264)])
def test_num_params_at_the_published_widths(depth, count):
    cfg = dataclasses.replace(PUBLISHED, n_layers=depth)
    assert cfg.num_params() == count
    shapes = jax.eval_shape(lambda: jamba_init(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes)) == count


def test_presets_stand_in_the_trainers_registry_and_model_fns_knows_the_kind(tiny):
    assert CONFIGS["jamba_debug"] is PRESET and CONFIGS["jamba2_3b"] is PUBLISHED
    assert PRESET.dtype == PUBLISHED.dtype == jnp.bfloat16
    assert (PUBLISHED.dim, PUBLISHED.n_heads, PUBLISHED.n_kv_heads, PUBLISHED.head_dim,
            PUBLISHED.ffn_hidden, PUBLISHED.vocab_size, PUBLISHED.d_inner,
            PUBLISHED.mamba_dt_rank) == (2560, 20, 1, 128, 8192, 65536, 5120, 160)
    init, loss, specs, stages, frozen = model_fns(DEBUG)
    assert init is jamba_init and specs is jamba_param_specs
    assert stages is None  # the hybrid's gradient is one program
    assert frozen == ()  # every leaf a parameter
    params, tokens = tiny
    value, stats = loss(params, tokens, tokens, DEBUG)
    assert sorted(stats["ssm_stats"]) == ["ssm_dt_max", "ssm_y_absmax"]
    assert np.isfinite(float(value)) and 0 < float(stats["ssm_stats"]["ssm_dt_max"])
    assert jax.tree_util.tree_structure(specs(DEBUG)) == jax.tree_util.tree_structure(params)
    untied = dataclasses.replace(DEBUG, tie_word_embeddings=False)
    assert "lm_head" in jamba_param_specs(untied)
    assert untied.num_params() == DEBUG.num_params() + DEBUG.vocab_size * DEBUG.dim


@pytest.mark.parametrize("key,value", [("num_experts", 16), ("mamba_proj_bias", True)])
def test_what_the_hybrid_cannot_express_is_refused_with_the_key_named(key, value):
    with pytest.raises(ValueError, match=key):
        dataclasses.replace(PUBLISHED, **{key: value})


def test_the_tied_heads_gradient_is_the_sum_of_both_uses(tiny):
    params, tokens = tiny
    assert "lm_head" not in params
    untied = dataclasses.replace(DEBUG, tie_word_embeddings=False)
    split = {**params, "lm_head": params["embed"].T}
    grad = jax.jit(jax.grad(jamba_loss), static_argnums=3)
    g_tied = grad(params, tokens, tokens, DEBUG)
    g_split = grad(split, tokens, tokens, untied)
    both = g_split["embed"] + g_split["lm_head"].T
    assert float(jnp.max(jnp.abs(g_split["lm_head"]))) > 0
    np.testing.assert_allclose(g_tied["embed"], both, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(jamba_loss(params, tokens, tokens, DEBUG),
                               jamba_loss(split, tokens, tokens, untied), rtol=1e-6)


@pytest.mark.parametrize("remat", ["none", "dots", "attn", "full"])
def test_remat_modes_give_one_gradient(tiny, remat):
    params, tokens = tiny
    grad = jax.jit(jax.grad(jamba_loss), static_argnums=3, static_argnames="remat")
    want = grad(params, tokens, tokens, DEBUG, remat="none")
    got = grad(params, tokens, tokens, DEBUG, remat=remat)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-6)


def test_loss_chunk_is_the_same_loss_and_the_configs_own_applies_where_it_divides(tiny):
    params, tokens = tiny
    whole = jamba_loss_and_stats(params, tokens, tokens, DEBUG)[0]
    chunked = jamba_loss(params, tokens, tokens, DEBUG, loss_chunk=16)
    np.testing.assert_allclose(chunked, whole, rtol=1e-6)
    own = dataclasses.replace(DEBUG, loss_chunk=16)
    np.testing.assert_allclose(jamba_loss(params, tokens, tokens, own), whole, rtol=1e-6)
    odd = dataclasses.replace(DEBUG, loss_chunk=36)  # does not divide 48: not used
    np.testing.assert_allclose(jamba_loss(params, tokens, tokens, odd), whole, rtol=1e-6)
    grad = jax.jit(jax.grad(jamba_loss), static_argnums=3)
    g1 = grad(params, tokens, tokens, DEBUG)["embed"]
    g2 = grad(params, tokens, tokens, own)["embed"]
    np.testing.assert_allclose(g2, g1, rtol=1e-4, atol=1e-7)


def test_attention_fn_is_called_once_a_period_without_positions(tiny):
    params, tokens = tiny
    seen = []

    def spy(q, k, v, cfg):
        seen.append((q.shape, k.shape))
        return xla_attention(q, k, v, cfg)

    got = jamba_forward(params, tokens, DEBUG, attention_fn=spy, remat="none")
    assert seen == [((2, 48, 4, 16), (2, 48, 1, 16))]  # traced once: one body a kind
    np.testing.assert_allclose(got, jamba_forward(params, tokens, DEBUG), rtol=1e-6)
    # no positions: with the Mamba mixers silenced, a permutation of the
    # earlier tokens leaves the last position's logits as they were
    one = dataclasses.replace(DEBUG, n_layers=4)  # mamba, mamba, attention, mamba
    loud = jamba_init(jax.random.PRNGKey(2), one)
    quiet = jax.tree_util.tree_map(lambda x: x, loud)
    for name, run in quiet["layers"].items():
        if name.endswith("mamba"):
            run["out_proj"] = jnp.zeros_like(run["out_proj"])
    swapped = tokens.at[:, :-1].set(tokens[:, :-1][:, ::-1])
    last = jax.jit(lambda p, t: jamba_forward(p, t, one)[:, -1])
    np.testing.assert_allclose(last(quiet, tokens), last(quiet, swapped),
                               rtol=1e-4, atol=1e-5)
    assert float(jnp.max(jnp.abs(last(loud, swapped) - last(loud, tokens)))) > 1e-3


def test_float32_ssm_leaves_get_a_bucket_of_their_own_beside_bf16_ones():
    shapes = jax.eval_shape(lambda: jamba_init(jax.random.PRNGKey(0), PRESET))
    by_dtype = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(shapes):
        by_dtype.setdefault(str(leaf.dtype), set()).add(path[-1].key)
    assert by_dtype["float32"] == {"A_log", "D"}
    leaves = [np.zeros(x.shape, x.dtype) for x in jax.tree_util.tree_leaves(shapes)]
    plan = bucketing.build_plan(leaves, 1 << 30)
    assert sorted(str(d) for d in plan.dtypes) == ["bfloat16", "float32"]


def test_mambas_initialisation_remembers_a_thousand_positions():
    params = jamba_init(jax.random.PRNGKey(3), DEBUG)
    run = params["layers"]["00_mamba"]
    np.testing.assert_allclose(jnp.exp(run["A_log"][0, 0]), np.arange(1, 17), rtol=1e-6)
    assert float(jnp.min(run["D"])) == float(jnp.max(run["D"])) == 1.0
    dt0 = jax.nn.softplus(run["dt_bias"])
    assert 0.9e-3 < float(jnp.min(dt0)) and float(jnp.max(dt0)) < 1.1e-1
