"""``ops/kda.py``'s general body (every ``g <= 0``, ``beta`` to 2; interpreted
here) against the recurrence one position after another in FLOAT64, forward
and all five gradients, where the bounded body's reasoning does not reach: a
log decay of -80 a step on some channels beside -1e-4 on others of one head,
a chunk whose running sum passes -700 (float32's exp underflows at -87, and
float64's at -745), hard resets between positions that hardly decay (the
case one reference point a sub-block cannot hold), ``beta`` = 1.999 on keys
within 1e-3 of one vector with hardly any decay (the chunk inverse's worst
case), 64 heads; the bounded body beside it on what both may take; and both
kernels compiled for a described v5e at the cell's widths. Ling's standing
cases are ``tests/test_ling_kernels.py``'s and run the general body too (the
default)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import kda as K


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _recurrence64(q, k, v, g, beta, w):
    """(o, the gradients of ``sum(o * w)``) of the recurrence in float64."""
    with jax.enable_x64(True):
        args = [jnp.asarray(np.asarray(m, np.float64)) for m in (q, k, v, g, beta)]
        w = jnp.asarray(np.asarray(w, np.float64))

        def f(q, k, v, g, beta):
            def step(S, x):  # S [B,H,dk,dv]
                q_t, k_t, v_t, g_t, b_t = x
                S = jnp.exp(g_t)[..., None] * S
                S = S + (b_t[..., None] * k_t)[..., None] * (
                    v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t))[..., None, :]
                return S, jnp.einsum("bhkv,bhk->bhv", S, q_t)

            S0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), jnp.float64)
            _, o = jax.lax.scan(step, S0, tuple(jnp.swapaxes(m, 0, 1) for m in (q, k, v, g, beta)))
            return jnp.swapaxes(o, 0, 1)

        o = f(*args)
        grads = jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=range(5))(*args)
        return np.asarray(o), [np.asarray(x) for x in grads]


def _inputs(B, T, H, d, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    return [unit(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5,
            unit(jax.random.normal(ks[1], (B, T, H, d))), jax.random.normal(ks[2], (B, T, H, d)),
            -5 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (B, T, H, d))),
            2 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))]


def _case(name):
    """(q, k, v, g, beta) of a case, and the limits its output, its
    gradients and the decay's gradient are held to (shares of the float64
    answer's norm; readings in the test's docstring)."""
    B, T, H, d = (1, 130, 64, 16) if name == "heads_64" else (1, 200, 2, 32)
    a = _inputs(B, T, H, d, seed=len(name))
    limits = (5e-6, 2e-5, 2e-5)
    if name == "fast_beside_slow_channels":
        a[3] = jnp.broadcast_to(jnp.where(jnp.arange(d) % 3 == 0, -80.0, -1e-4), (B, T, H, d))
    elif name == "chunk_sum_past_700":
        a[3] = jnp.full((B, T, H, d), -12.0)
        limits = (5e-6, 2e-5, None)  # the decay's gradient is e^-12 of the others': see below
    elif name == "resets_between_slow_steps":
        a[3] = jnp.broadcast_to(jnp.where((jnp.arange(T) % 7 == 3)[:, None, None], -80.0, -1e-3),
                                (B, T, H, d))
        limits = (1e-4, 3e-4, 3e-4)
    elif name == "beta_near_2_on_one_key":
        near = a[1][:, :1] + 1e-3 * jax.random.normal(jax.random.PRNGKey(3), a[1].shape) / d ** 0.5
        a[1] = near / jnp.linalg.norm(near, axis=-1, keepdims=True)
        a[3], a[4] = jnp.full((B, T, H, d), -1e-5), jnp.full((B, T, H), 1.999)
        limits = (2e-4, 3e-4, 3e-4)
    return a, limits


@pytest.mark.parametrize("name", ["beta_to_2", "fast_beside_slow_channels", "chunk_sum_past_700",
                                  "resets_between_slow_steps", "beta_near_2_on_one_key",
                                  "heads_64"])
def test_the_general_body_is_the_float64_recurrence_where_no_bound_holds(name):
    """Nothing is non-finite, and output and gradients are the float64
    recurrence's. Readings (this sandbox's CPU, the kernels interpreted):
    random decays under ``beta`` to 2: 1.7e-6, gradients 1.3e-6 to 4.8e-6;
    -80 beside -1e-4 by channel 2.7e-7 / 6e-7; a chunk's sum at -768 7.7e-8 /
    1.5e-7, with the decay's own gradient, e^-12 of the others' and mostly
    rounding's, held to a 1e-6 of the keys' gradient's norm instead; resets
    of -80 between steps of -1e-3 2.1e-5 / 8.1e-5: the exponents are
    differences of a float32 running sum over the chunk's rows, whose
    absolute error is 6e-8 x the chunk's largest |sum| (here 700): the
    chunked form's own limit, in any body; ``beta`` = 1.999 on keys within
    1e-3 of one vector 4.1e-5 / 4.7e-5 (the inverse's entries stay O(1) and
    so do the doubling's intermediates; the product form of powers holds
    binomials x 2^8 there and read 1.1e-4 at ``beta`` = 1 already)."""
    args, (out_limit, grad_limit, decay_limit) = _case(name)
    w = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    want, want_grads = _recurrence64(*args, w)
    got = K.kda(*args)
    grads = jax.grad(lambda *a: jnp.sum(K.kda(*a) * w), argnums=range(5))(*args)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in (got, *grads)), name
    assert _rel(got, want) < out_limit, (name, _rel(got, want))
    for leaf, a, b in zip("qkvgb", grads, want_grads):
        if leaf == "g" and decay_limit is None:
            assert np.linalg.norm(np.asarray(a) - b) < 1e-6 * np.linalg.norm(want_grads[1]), name
        else:
            assert _rel(a, b) < (decay_limit if leaf == "g" else grad_limit), (
                name, leaf, _rel(a, b))


def test_the_two_bodies_agree_where_both_may_run_and_the_model_chooses():
    """``g`` in [-5, 0] and ``beta`` <= 1: the bounded body (``decay_floor``
    -5 or above and ``beta_max`` 1, which only a model whose form promises
    them says) and the general body give the recurrence's answer; any other
    promise runs the general body."""
    q, k, v, g, beta = _inputs(1, 200, 2, 32, seed=1)
    beta = beta / 2
    want = K.kda_reference(q, k, v, g, beta)
    general = K.kda(q, k, v, g, beta)
    bounded = K.kda(q, k, v, g, beta, decay_floor=-5.0, beta_max=1.0)
    assert _rel(general, want) < 5e-6 and _rel(bounded, want) < 5e-6
    assert not np.array_equal(np.asarray(general), np.asarray(bounded))  # two bodies
    for kw in ({"decay_floor": -8.0, "beta_max": 1.0}, {"decay_floor": -5.0, "beta_max": 2.0},
               {"decay_floor": None, "beta_max": 1.0}):
        np.testing.assert_array_equal(np.asarray(K.kda(q, k, v, g, beta, **kw)),
                                      np.asarray(general))


def test_the_doubling_inverse_holds_no_power_of_its_argument():
    """``beta`` = 1.999 on keys within 1e-3 of one vector for 64 rows: ``I +
    X`` is near ``I + 2 L``; the inverse's entries are at most 2 and the
    doubling's are the float64 inverse's to 1e-5 (read 2.3e-6), where the
    product form of powers is off by a fifth (read 0.195: its powers reach
    256 x C(14, 7) and cancel to O(1)); at ``beta`` = 1 it reads 6.3e-5
    beside the doubling's 3.0e-8."""
    C = K.CHUNK
    for beta, best, other in ((1.999, 1e-5, 0.05), (1.0, 1e-6, 1e-5)):
        x = beta * jnp.tril(1 - 1e-3 * jax.random.uniform(jax.random.PRNGKey(0), (C, C)), -1)
        want = np.linalg.inv(np.eye(C) + np.asarray(x, np.float64))
        assert np.abs(want).max() <= 2.0
        assert _rel(K._products_inverse(x), want) < best
        assert _rel(K._power_inverse(x), want) > other


# -- the kernels at the cell's widths, compiled for the chip that is described


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("passes", ["forward", "backward"])
@pytest.mark.parametrize("body", ["general", "bounded"])
def test_the_kernels_compile_for_a_v5e_at_the_cells_widths(monkeypatch, one_chip, body, passes):
    """64 heads of 128 at 16,384 positions (the general body: this cell's)
    and 32 at 32,768 (the bounded one: Ling's): what Mosaic refuses (a slice
    off the tiling, a relayout it has not, more VMEM than a kernel may use)
    it refuses here, at no chip time; nothing runs. The general body's
    pair-by-pair exponents are [4, 16, 16, 128] float32 a chunk, four chunks
    at once in the backward kernel."""
    monkeypatch.setattr(K, "_interpret", lambda: False)
    H, T = (64, 16384) if body == "general" else (32, 32768)
    kw = {} if body == "general" else {"decay_floor": -5.0, "beta_max": 1.0}
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    args = (shape((1, T, H, 128), jnp.bfloat16),) * 3 + (
        shape((1, T, H, 128), jnp.float32), shape((1, T, H), jnp.float32))
    f = (lambda *a: K.kda(*a, **kw)) if passes == "forward" else jax.grad(
        lambda *a: jnp.sum(K.kda(*a, **kw).astype(jnp.float32)), argnums=range(5))
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "kda_fwd" in text and ("kda_bwd" in text) == (passes == "backward")
