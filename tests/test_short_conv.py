"""``ops/short_conv.py``: the mixers' short causal convolution as a kernel
pair (interpreted here) against the ``jax.numpy`` form differentiated by XLA
(``short_conv_reference``): value, ``dx``, ``dw`` and ``db``."""

import jax
import jax.numpy as jnp
import pytest

from torchft_tpu.ops import short_conv as sc

TILE, LANES = 32, 128  # what the tests cut the module's tile to: shapes stay small


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(sc, "TILE", TILE)
    monkeypatch.setattr(sc, "_LANES", LANES)


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _args(B, T, di, k, bias, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (B, T, di)).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (k, di))).astype(dtype)
    b = jax.random.normal(ks[2], (di,)).astype(dtype) if bias else None
    dy = jax.random.normal(ks[3], (B, T, di)).astype(dtype)
    return x, w, b, dy


def _both(conv, act, x, w, b, dy):
    """-> (y, (dx, dw, db or None)) of ``conv`` in one compiled call."""
    def run(x, w, b, dy):
        y, pull = jax.vjp(lambda x, w, b: conv(x, w, b, act), x, w, b)
        return y, pull(dy)
    return jax.jit(run)(x, w, b, dy)


def _calls_the_kernel(x, w, b, act=jax.nn.silu):
    return "pallas_call" in str(jax.make_jaxpr(lambda *a: sc.short_conv(*a, act))(x, w, b))


# taps, bias, SiLU, batch, dtype: every value of each beside every value of
# each other at least once; two or three sequence tiles, two channel tiles
CASES = {
    "mamba_bf16": (4, True, True, 2, jnp.bfloat16, 64),
    "mamba_f32": (4, True, True, 1, jnp.float32, 96),
    "kda_bf16": (4, False, True, 1, jnp.bfloat16, 96),
    "kda_f32": (4, False, True, 2, jnp.float32, 64),
    "lfm2_bf16": (3, False, False, 1, jnp.bfloat16, 64),
    "lfm2_f32": (3, False, False, 2, jnp.float32, 96),
    "three_taps_bias_silu_bf16": (3, True, True, 2, jnp.bfloat16, 64),
    "three_taps_silu_f32": (3, False, True, 1, jnp.float32, 64),
    "four_taps_bias_plain_f32": (4, True, False, 1, jnp.float32, 64),
    "four_taps_plain_bf16": (4, False, False, 2, jnp.bfloat16, 64),
    "three_taps_bias_plain_bf16": (3, True, False, 1, jnp.bfloat16, 96),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_the_jax_numpy_form_forward_and_backward(case):
    k, bias, silu, B, dtype, T = CASES[case]
    x, w, b, dy = _args(B, T, 2 * LANES, k, bias, dtype, seed=len(case))
    act = jax.nn.silu if silu else None
    assert _calls_the_kernel(x, w, b, act)
    (y, grads), (want_y, want) = _both(sc.short_conv, act, x, w, b, dy), \
        _both(sc.short_conv_reference, act, x, w, b, dy)
    assert y.dtype == dtype and all(g.dtype == dtype for g in grads if g is not None)
    # float32: the same sums in the same order, but for the activation's
    # last digits; bf16: at most one rounding of the narrow result apart
    tol = 2e-6 if dtype == jnp.float32 else 8e-3
    assert _rel(y, want_y) < tol
    for name, got, ref in zip(("dx", "dw", "db"), grads, want):
        assert (got is None) == (ref is None) == (name == "db" and not bias)
        if got is not None:
            assert _rel(got, ref) < (2e-5 if dtype == jnp.float32 else 8e-3), (case, name)


@pytest.mark.parametrize("at", ["last_row_of_a_tile", "first_row_of_a_tile"])
def test_an_impulse_at_a_tiles_edge_reaches_across_it_both_ways(at):
    """Forward, ``x``'s impulse is read by the ``k - 1`` rows after it (the
    halo before a tile); backward, ``dy``'s impulse reaches ``dx`` of the
    ``k - 1`` rows before it through ``dpre`` (the halo after a tile), and
    ``dw`` sees every pair of rows across the edge."""
    t, k, di = (TILE - 1 if at == "last_row_of_a_tile" else TILE), 4, 2 * LANES
    _, w, b, _ = _args(1, 3 * TILE, di, k, True, jnp.float32)
    x = jnp.zeros((1, 3 * TILE, di)).at[0, t].set(1.0)
    y, (dx, dw, db) = _both(sc.short_conv, None, x, w, None, x)
    for j in range(k):  # y_{t+s} = w[k-1-s] and dx_{t-s} = w[k-1-s]
        assert jnp.array_equal(y[0, t + j], w[k - 1 - j]) and jnp.array_equal(dx[0, t - j], w[k - 1 - j])
    # and no other row holds anything
    assert int(jnp.count_nonzero(y)) == int(jnp.count_nonzero(dx)) == int(jnp.count_nonzero(w))
    assert jnp.array_equal(dw, jnp.zeros_like(w).at[k - 1].set(1.0))  # x_t * dy_t alone
    # and with a bias and the SiLU round it, on rows that are not zeros
    x, _, _, dy = _args(1, 3 * TILE, di, k, True, jnp.float32, seed=3)
    only = jnp.zeros_like(dy).at[0, t].set(dy[0, t])
    got, want = _both(sc.short_conv, jax.nn.silu, x, w, b, only), \
        _both(sc.short_conv_reference, jax.nn.silu, x, w, b, only)
    dx_ref = want[1][0]
    assert int(jnp.count_nonzero(dx_ref)) == int(jnp.count_nonzero(dx_ref[0, t - k + 1:t + 1])) == k * di
    for g, r in zip(got[1], want[1]):
        assert _rel(g, r) < 2e-5


def test_position_0_sees_zeros_in_every_sequence_and_channel_tile():
    """Ones everywhere: row ``t < k - 1`` sums the last ``t + 1`` taps only,
    in the second sequence of the batch and the second channel tile as in
    the first (the carried rows are the tile's before, never the sequence's
    before or the channels' beside)."""
    k, di = 4, 2 * LANES
    w = _args(2, 2 * TILE, di, k, False, jnp.float32)[1]
    x = jnp.ones((2, 2 * TILE, di))
    assert _calls_the_kernel(x, w, None, None)
    y = sc.short_conv(x, w, None, None)
    for t in range(k):
        want = sum(w[j] for j in range(k - 1 - t, k))
        assert _rel(y[0, t], want) < 1e-6 and jnp.array_equal(y[1, t], y[0, t])
    assert jnp.array_equal(y[:, k - 1:], jnp.broadcast_to(y[:, k - 1:k], y[:, k - 1:].shape))


def test_under_a_checkpoint_inside_a_scan_over_a_stack_of_layers():
    """As ``models/decoder.py`` calls it: one body scanned over the layers'
    stacked taps and biases under ``jax.checkpoint``, so the forward kernel
    runs again inside the backward pass and the residuals are ``x``, ``w``
    and ``b``."""
    L, k, di = 3, 4, 2 * LANES
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    h0 = jax.random.normal(ks[0], (2, 2 * TILE, di)).astype(jnp.bfloat16)
    stack = {"w": (0.5 * jax.random.normal(ks[1], (L, k, di))).astype(jnp.bfloat16),
             "b": jax.random.normal(ks[2], (L, di)).astype(jnp.bfloat16)}

    def step(conv):
        def body(h, layer):  # the rows after the convolution are read again, as a mixer's are
            y = conv(h, layer["w"], layer["b"], jax.nn.silu)
            return h + y * y, None

        def run(h, stack):
            return jnp.mean(jax.lax.scan(jax.checkpoint(body), h, stack)[0].astype(jnp.float32) ** 2)
        return jax.value_and_grad(run, argnums=(0, 1))

    (got, (dh, ds)), (want, (rh, rs)) = (jax.jit(step(conv))(h0, stack)
                                         for conv in (sc.short_conv, sc.short_conv_reference))
    assert abs(float(got) - float(want)) < 2e-3 * abs(float(want))
    assert _rel(dh, rh) < 2e-2 and _rel(ds["w"], rs["w"]) < 2e-2 and _rel(ds["b"], rs["b"]) < 2e-2
    text = str(jax.make_jaxpr(step(sc.short_conv))(h0, stack))
    assert text.count("name=short_conv_fwd") == 2 and text.count("name=short_conv_bwd") == 1


@pytest.mark.parametrize("shape", [(1, TILE + 16, 2 * LANES), (2, 2 * TILE, LANES + 64),
                                   (1, 24, 48)], ids=["length", "width", "debug"])
def test_a_shape_that_does_not_tile_takes_the_jax_numpy_form(shape):
    """No kernel in the program, and the reference's numbers to the bit:
    what the debug configurations' model tests run."""
    x, w, b, dy = _args(*shape, 4, True, jnp.bfloat16)
    assert not _calls_the_kernel(x, w, b)
    got, want = _both(sc.short_conv, jax.nn.silu, x, w, b, dy), \
        _both(sc.short_conv_reference, jax.nn.silu, x, w, b, dy)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert jnp.array_equal(g, r)


def test_the_modules_own_tile_at_two_tiles_each_way(monkeypatch):
    """The constants the cells run with (the other tests cut them down)."""
    monkeypatch.undo()
    assert (sc.TILE, sc._LANES) != (TILE, LANES)
    assert sc._lanes(6144) == sc._lanes(5120) == sc._lanes(4096) == sc._lanes(2048) == sc._LANES
    assert sc._lanes(384) == 384 and sc._lanes(640) == 128
    x, w, b, dy = _args(1, 2 * sc.TILE, 2 * sc._LANES, 4, True, jnp.bfloat16)
    assert _calls_the_kernel(x, w, b)
    got, want = _both(sc.short_conv, jax.nn.silu, x, w, b, dy), \
        _both(sc.short_conv_reference, jax.nn.silu, x, w, b, dy)
    for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _rel(g, r) < 8e-3
