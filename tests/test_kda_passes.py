"""``ops/kda_passes.py``: the KDA mixer's two kernel pairs, interpreted,
against the module's ``jax.numpy`` forms at small shapes that tile: values
and every gradient, both forms of the decay, both granularities of the gate;
a shape that does not tile takes the ``jax.numpy`` form; saturated gates stay
finite. Small shapes only: an interpreted kernel at a cell's shape takes
minutes (ROADMAP D11)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import kda_passes as kp

_F32, _BF16 = jnp.float32, jnp.bfloat16
EPS = 1e-5


def _qkg_args(shape, dtype, scale=1.0, seed=0):
    B, S, H, dk = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    rows = lambda key, dt=dtype, by=1.0: (by * jax.random.normal(key, (B, S, H * dk))).astype(dt)
    args = (rows(ks[0], by=scale), rows(ks[1], by=scale), rows(ks[2], _F32, 2.0 * scale),
            jax.random.normal(ks[3], (H * dk,)) - 1.0,
            jnp.log(jax.random.uniform(ks[4], (H,), _F32, 1.0, 4.0)))
    return args, (rows(ks[5]), rows(ks[6]), rows(ks[7], _F32))


def _gate_args(shape, dtype, per_head, scale=1.0, seed=1):
    B, S, H, dk = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = lambda key, by=1.0: (by * jax.random.normal(key, (B, S, H * dk))).astype(dtype)
    logits = 3.0 * scale * jax.random.normal(ks[1], (B, S, H)) if per_head \
        else rows(ks[1], 3.0 * scale)
    return (rows(ks[0], scale), logits,
            (1.0 + 0.1 * jax.random.normal(ks[2], (dk,))).astype(dtype)), rows(ks[3])


def _both(fn, args, cts):
    out, pull = jax.vjp(fn, *args)
    return jax.tree.leaves((out, pull(cts)))


def _close(got, want, names, tol):
    for name, g, w in zip(names, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name


# two tiles of positions, two blocks of channels and two images: every
# accumulator is summed over all three grid axes
@pytest.fixture()
def small_tiles(monkeypatch):
    monkeypatch.setattr(kp, "_ROWS", 16)
    monkeypatch.setattr(kp, "_LANES", 256)
    monkeypatch.setattr(kp, "_ELEMS", 16 * 256)


@pytest.mark.parametrize("decay_floor", [-5.0, None], ids=["bounded", "unbounded"])
@pytest.mark.parametrize("dtype,tol", [(_F32, 1e-5), (_BF16, 1e-2)], ids=["f32", "bf16"])
def test_the_qkg_pair_is_the_jax_numpy_form(small_tiles, dtype, tol, decay_floor):
    shape = (2, 32, 4, 128)
    args, cts = _qkg_args(shape, dtype)
    assert kp.tiles(args[0], 128) and kp._blocks(32, 512, 128, False) == (16, 256)
    got = _both(lambda *a: kp.kda_qkg(*a, decay_floor), args, cts)
    want = _both(lambda *a: kp.qkg_reference(*a, decay_floor), args, cts)
    _close(got, want, "q k g dq dk df d_dt_bias d_A_log".split(), tol)
    g = np.asarray(got[2])
    assert (g <= 0).all() and (decay_floor is None or (g >= decay_floor).all())
    # the decay is float32 from the product to the delta rule, whatever the rows are
    assert got[2].dtype == got[5].dtype == _F32 and got[0].dtype == dtype


@pytest.mark.parametrize("per_head", [True, False], ids=["a_head", "a_channel"])
@pytest.mark.parametrize("dtype,tol", [(_F32, 1e-5), (_BF16, 3e-2)], ids=["f32", "bf16"])
def test_the_gate_pair_is_the_jax_numpy_form(small_tiles, dtype, tol, per_head):
    """In bf16 the kernel rounds once where the ``jax.numpy`` form rounds
    after the norm, the weight and the gate: within three roundings of it."""
    shape = (2, 32, 4, 128)
    args, ct = _gate_args(shape, dtype, per_head)
    # a gate a head takes every head in one block
    assert kp._blocks(32, 512, 128, per_head) == ((16, 512) if per_head else (16, 256))
    got = _both(lambda *a: kp.kda_gate(*a, EPS), args, ct)
    want = _both(lambda *a: kp.gate_reference(*a, EPS), args, ct)
    _close(got, want, "out do dlogits d_o_norm".split(), tol)
    assert got[2].dtype == (_F32 if per_head else dtype)


def test_the_bf16_gate_is_nearer_the_float32_one_than_the_jax_numpy_form(small_tiles):
    """Fewer roundings, never a lower precision."""
    args, _ = _gate_args((1, 32, 2, 128), _BF16, True)
    exact = kp.gate_reference(*(a.astype(_F32) for a in args), EPS)
    err = lambda out: float(jnp.abs(out.astype(_F32) - exact).mean())  # noqa: E731
    assert err(kp.kda_gate(*args, EPS)) < err(kp.gate_reference(*args, EPS))


@pytest.mark.parametrize("shape", [(1, 24, 2, 128), (1, 32, 4, 64), (1, 32, 8, 16)],
                         ids=["rows", "half_lanes", "debug_width"])
def test_a_shape_that_does_not_tile_takes_the_jax_numpy_form(small_tiles, shape):
    args, cts = _qkg_args(shape, _BF16)
    assert not kp.tiles(args[0], shape[3])
    text = str(jax.make_jaxpr(lambda *a: kp.kda_qkg(*a, None))(*args))
    gate, ct = _gate_args(shape, _BF16, True)
    text += str(jax.make_jaxpr(lambda *a: kp.kda_gate(*a, EPS))(*gate))
    assert "pallas_call" not in text and "custom_vjp" not in text
    for g, w in zip(_both(lambda *a: kp.kda_qkg(*a, None), args, cts),
                    _both(lambda *a: kp.qkg_reference(*a, None), args, cts)):
        assert (np.asarray(g, np.float32) == np.asarray(w, np.float32)).all()


@pytest.mark.parametrize("decay_floor,per_head", [(-5.0, True), (None, False)],
                         ids=["bounded_a_head", "unbounded_a_channel"])
def test_saturated_gates_stay_finite(small_tiles, decay_floor, per_head):
    """As ``tests/test_solar.py`` holds the mixer to at 1e4 times its
    input: every value and gradient finite, the decay inside its range."""
    shape = (1, 32, 2, 128)
    args, cts = _qkg_args(shape, _BF16, scale=1e4)
    got = _both(lambda *a: kp.kda_qkg(*a, decay_floor), args, cts)
    gate, ct = _gate_args(shape, _BF16, per_head, scale=1e4)
    got += _both(lambda *a: kp.kda_gate(*a, EPS), gate, ct)
    assert all(bool(jnp.isfinite(m.astype(_F32)).all()) for m in got)
    g = np.asarray(got[2])
    assert (g <= 0).all() and (decay_floor is None or (g >= decay_floor).all())
    zero = tuple(jnp.zeros_like(a) for a in args[:3]) + args[3:]
    assert all(bool(jnp.isfinite(m.astype(_F32)).all())
               for m in _both(lambda *a: kp.kda_qkg(*a, decay_floor), zero, cts))


def test_the_blocks_of_the_two_cells():
    """Whole heads under ``_LANES`` channels, every channel for a gate a
    head, and a tile of at most ``_ELEMS`` elements."""
    assert kp._blocks(32768, 4096, 128, False) == (512, 512)
    assert kp._blocks(32768, 4096, 128, True) == (256, 4096)
    assert kp._blocks(16384, 8192, 128, False) == (512, 512)
    assert kp._blocks(768, 384, 128, False) == (256, 384)
