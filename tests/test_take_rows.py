"""How the dropless expert block moves rows (PR 39): the combine's own
backward pass (``models/moe._combine``) against the expression autodiff was
given before it, bitwise, alone, under ``jax.checkpoint`` and inside
``lax.scan`` as ``_moe_layer`` uses it; the dispatch's ``_take_rows``
against autodiff's scatter-add; and step 0's row-gather kernel
(``benchmarks/take_rows_check.py``, interpreted) against ``x[take]``,
bitwise, so that the script's table can be taken again; the staging of
``ops/take_rows.py`` (interpreted) and what decides whether it is taken."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import moe
from torchft_tpu.ops import take_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


@pytest.fixture(scope="module")
def check():
    spec = importlib.util.spec_from_file_location(
        "take_rows_check", os.path.join(ROOT, "benchmarks", "take_rows_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _case(check, tokens, k, d, dtype, seed=0):
    """rows [T*k, d] in expert order, weights [T, k], the cotangent [T, d]
    and the routing's (order, inverse)."""
    order, inverse = check.takes(seed, tokens, 4, k)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    rows = jax.random.normal(keys[0], (tokens * k, d)).astype(dtype)
    weights = jax.nn.softmax(jax.random.normal(keys[1], (tokens, k))).astype(dtype)
    g = jax.random.normal(keys[2], (tokens, d)).astype(dtype)
    return rows, weights, g, order, inverse


def _out_and_cotangents(combine, rows, weights, g, order, inverse, wrap=lambda f: f):
    @jax.jit
    def f(rows, weights, g):
        out, pullback = jax.vjp(
            wrap(lambda r, w: combine(r, w, inverse, order)), rows, weights)
        return (out, *pullback(g))
    return f(rows, weights, g)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("k", [1, 4, 8])
def test_the_combines_backward_pass_is_bitwise_autodiffs(check, k, dtype):
    case = _case(check, 24, k, 64, DTYPES[dtype])
    want = _out_and_cotangents(check.was_combine, *case)
    got = _out_and_cotangents(moe._combine, *case)
    for name, a, b in zip(("out", "d_rows", "d_weights"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=name)


def _in_a_scan(f):
    """``f`` as the body of a two-layer ``lax.scan`` under ``jax.checkpoint``
    (the second layer sees the first one's rows scaled)."""
    def scanned(rows, weights):
        def body(carry, scale):
            return carry, jax.checkpoint(f)(rows * scale, weights)
        return jnp.sum(jax.lax.scan(body, 0, jnp.array([1, 2], rows.dtype))[1], axis=0)
    return scanned


@pytest.mark.parametrize("wrap", ["checkpoint", "scan"])
def test_under_checkpoint_and_inside_a_scan_it_is_bitwise_too(check, wrap):
    wrap = {"checkpoint": jax.checkpoint, "scan": _in_a_scan}[wrap]
    case = _case(check, 16, 4, 64, jnp.bfloat16)
    want = _out_and_cotangents(check.was_combine, *case, wrap=wrap)
    got = _out_and_cotangents(moe._combine, *case, wrap=wrap)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


def test_the_backward_pass_permutes_no_row(check):
    """The one gather in the backward pass reads the [T, d] cotangent; the
    T*k weights and their cotangents are permuted by a sort each."""
    rows, weights, g, order, inverse = _case(check, 16, 4, 64, jnp.float32)
    eqns = list(_eqns(jax.make_jaxpr(lambda r, w, g: moe._combine_bwd(
        (r, w, inverse, order), g))(rows, weights, g).jaxpr))
    gathered = [e.invars[0].aval.shape for e in eqns if e.primitive.name == "gather"]
    assert gathered == [(16, 64)], gathered
    assert sum(e.primitive.name == "sort" for e in eqns) == 2


@pytest.mark.parametrize("fan", [1, 4])
def test_take_rows_cotangent_is_autodiffs_scatter_add(check, fan):
    order, inverse = check.takes(3, 16, 4, fan)
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
    g = jax.random.normal(jax.random.PRNGKey(1), (16 * fan, 32))
    take = order // fan
    got = jax.vjp(lambda x: moe._take_rows(x, take, inverse, fan), x)[1](g)[0]
    want = jax.vjp(lambda x: x[take], x)[1](g)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_staging_moves_no_bit_and_is_not_taken_off_the_tpu(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 256)).astype(DTYPES[dtype])
    take = jax.random.randint(jax.random.PRNGKey(1), (70,), 0, 32)
    staged = take_rows.stage(x, take, interpret=True)
    assert staged.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(staged), np.asarray(x))
    assert not take_rows.applies(x)  # the CPU: x[take] alone
    np.testing.assert_array_equal(np.asarray(take_rows.take_rows(x, take)),
                                  np.asarray(x[take]))


@pytest.mark.parametrize("shape,kind,count,want", [
    ((8192, 2048), "TPU v5 lite", 1, True),
    ((8192, 2048), "TPU v5 lite", 4, False),   # a mesh: the operands are not on one device
    ((8192, 2048), "TPU v4", 1, False),        # a chip whose VMEM the module does not know
    ((65536, 2048), "TPU v5 lite", 1, False),  # 268 MB: over a third of VMEM
    ((8192, 2000), "TPU v5 lite", 1, False),   # a row that is no whole number of lanes
])
def test_staging_is_taken_on_what_the_call_can_see(monkeypatch, shape, kind, count, want):
    class Device:
        device_kind = kind

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: count)
    monkeypatch.setattr(jax, "devices", lambda: [Device()])
    assert take_rows.applies(jax.ShapeDtypeStruct(shape, jnp.bfloat16)) is want


KERNEL_CASES = {  # rows in, rows out, fan, block
    "permutation": (64, 64, 1, 32),
    "fan_out": (16, 64, 4, 32),
    "rows_no_multiple_of_the_block": (40, 70, None, 32),
    "one_block": (24, 24, 1, 256),
}


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_step_0s_kernel_is_bitwise_x_take(check, case, dtype):
    n, m, fan, block = KERNEL_CASES[case]
    if fan is None:
        take = jax.random.randint(jax.random.PRNGKey(2), (m,), 0, n)
    else:
        order, inverse = check.takes(2, n, 4, fan)
        take = inverse if fan == 1 else order // fan
    x = jax.random.normal(jax.random.PRNGKey(4), (n, 1024)).astype(DTYPES[dtype])
    got = check.take_rows(x, take, block=block, interpret=True)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x[take]))
