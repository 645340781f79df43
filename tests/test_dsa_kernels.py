"""``ops/dsa.py``: the alignment kernels (interpreted off the TPU) against
the dense formula with every ``[T, T]`` matrix in memory, value and the
three gradients, at a length that is no multiple of the block, at 128 main
heads and 64 indexer heads, two sequences at once, with a row whose ``I``
spans 60 nats and a row whose target is one-hot; each of the four faults the
chip script puts into the kernels seen by that comparison; and the three
kernels compiled for a described v5e at the published widths (Mosaic refuses
there what it would refuse on the chip)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import dsa

_F32 = jnp.float32


@pytest.fixture(autouse=True)
def small_block(monkeypatch):
    monkeypatch.setattr(dsa, "BLOCK", 128)


def inputs(T, H=16, D=32, HI=8, dI=16, B=1, seed=0, dtype=_F32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (B, T, H, D), dtype),
            jax.random.normal(ks[1], (B, T, H, D), dtype),
            jax.random.normal(ks[2], (B, T, HI, dI), dtype),
            jax.random.normal(ks[3], (B, T, dI), dtype),
            0.1 * jax.random.normal(ks[4], (B, T, HI), _F32))


def both(fn, scale, q, k, qI, kI, w):
    return jax.jit(lambda qI, kI, w: jax.value_and_grad(
        lambda qI, kI, w: jnp.mean(fn(q, k, scale, qI, kI, w)), argnums=(0, 1, 2))(
            qI, kI, w))(qI, kI, w)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


CASES = {"not_whole_blocks": dict(T=200), "one_block": dict(T=96), "whole_blocks": dict(T=256),
         "two_sequences": dict(T=136, B=2),
         "the_models_head_counts": dict(T=136, H=128, D=16, HI=64, dI=16)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_value_and_gradients_are_the_dense_formulas(case):
    args = inputs(**CASES[case])
    scale = args[0].shape[-1] ** -0.5
    rows = jax.jit(lambda *a: dsa.index_kl(a[0], a[1], scale, *a[2:]))(*args)
    want = dsa.index_kl_reference(args[0], args[1], scale, *args[2:])
    assert rows.shape == want.shape == args[0].shape[:2] and rows.dtype == _F32
    np.testing.assert_allclose(rows, want, atol=5e-6)
    assert float(rows[0, 0]) == pytest.approx(0.0, abs=1e-6)  # one key: both are 1
    (a, ga), (b, gb) = both(dsa.index_kl, scale, *args), both(
        dsa.index_kl_reference, scale, *args)
    assert abs(float(a) - float(b)) < 2e-6
    assert all(rel(x, y) < 5e-6 for x, y in zip(ga, gb))
    assert [g.dtype for g in ga] == [x.dtype for x in args[2:]]


def test_a_row_that_spans_60_nats_and_a_one_hot_target():
    """Row 150's indexer scores span over 60 nats (its softmax is one key's
    to rounding, the others underflow) and row 170's target is one key's
    for every head (``p log p`` at p = 0 in all the others): both are
    finite, the dense formula's, and so are the gradients."""
    q, k, qI, kI, w = inputs(200)
    w = w.at[0, 150].multiply(400.0)
    k = k.at[0, 40].set(8.0)
    q = q.at[0, 170].set(8.0)
    scale = 1.0
    I = jnp.einsum("j,js->s", w[0, 150], jax.nn.relu(qI[0, 150] @ kI[0, :151].T))
    assert float(I.max() - I.min()) > 60
    (a, ga), (b, gb) = both(dsa.index_kl, scale, q, k, qI, kI, w), both(
        dsa.index_kl_reference, scale, q, k, qI, kI, w)
    rows = dsa.index_kl(q, k, scale, qI, kI, w)
    want = dsa.index_kl_reference(q, k, scale, qI, kI, w)
    assert np.isfinite(rows).all() and float(want[0, 150]) > 5
    np.testing.assert_allclose(rows, want, rtol=2e-5, atol=2e-5)
    assert all(np.isfinite(g).all() for g in ga)
    assert abs(float(a) - float(b)) < 1e-5 and all(rel(x, y) < 2e-5 for x, y in zip(ga, gb))


def test_the_main_heads_get_no_cotangent():
    q, k, qI, kI, w = inputs(96)
    gq, gk = jax.grad(lambda q, k: jnp.mean(dsa.index_kl(q, k, 0.2, qI, kI, w)),
                      argnums=(0, 1))(q, k)
    assert not np.asarray(gq).any() and not np.asarray(gk).any()


def test_bf16_operands_give_the_dense_formula_to_their_rounding():
    args = inputs(200, dtype=jnp.bfloat16)
    (a, ga), (b, gb) = both(dsa.index_kl, 0.2, *args), both(dsa.index_kl_reference, 0.2, *args)
    assert abs(float(a) - float(b)) < 1e-4
    # dI x w is rounded to bf16 before its two products, as an attention
    # kernel rounds dS: a few 1e-3 of the two gradients it feeds
    assert rel(ga[0], gb[0]) < 1e-2 and rel(ga[1], gb[1]) < 1e-2 and rel(ga[2], gb[2]) < 1e-4


@pytest.mark.parametrize("knob, value", [("RELU", False), ("TARGET_HEADS", 1),
                                         ("P_DTYPE", jnp.bfloat16), ("I_DTYPE", jnp.bfloat16)])
def test_each_fault_the_chip_script_sets_shows(monkeypatch, knob, value):
    args = inputs(200)
    b, gb = both(dsa.index_kl_reference, 0.2, *args)
    monkeypatch.setattr(dsa, knob, value)
    a, ga = both(dsa.index_kl, 0.2, *args)
    assert abs(float(a) - float(b)) > 1e-5 and rel(ga[0], gb[0]) > 1e-4


def test_shapes_that_are_refused():
    q, k, qI, kI, w = inputs(64)
    with pytest.raises(ValueError, match="main heads"):
        dsa.index_kl(q[:, :, :12], k[:, :, :12], 1.0, qI, kI, w)
    with pytest.raises(ValueError, match="index_kl"):
        dsa.index_kl(q, k, 1.0, qI, kI[:, :32], w)


# ---- the kernels compiled for the chip they run on (no chip needed)

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_three_kernels_compile_for_a_v5e_at_the_published_widths(one_chip, monkeypatch):
    """One sequence of 16,384, 128 main heads padded to 256, 64 indexer
    heads of 128, bf16, the module's own BLOCK: forward and backward."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(dsa, "BLOCK", 512)
    monkeypatch.setattr(dsa, "_interpret", lambda: False)
    T, H, D, HI, dI = 16384, 128, 256, 64, 128
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = (sd((1, T, H, D)), sd((1, T, H, D)), sd((1, T, HI, dI)), sd((1, T, dI)),
            sd((1, T, HI), _F32))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(lambda q, k, qI, kI, w: jax.value_and_grad(
            lambda qI, kI, w: jnp.mean(dsa.index_kl(q, k, 1 / 16.0, qI, kI, w)),
            argnums=(0, 1, 2))(qI, kI, w)).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    for name in ("dsa_kl_fwd_lse", "dsa_kl_fwd", "dsa_kl_bwd"):
        assert f'"{name}"' in text or name in text, name
