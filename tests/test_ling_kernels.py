"""The fifth kind of the one trainer's model (``models/ling.py``), its
kernels and its router: the KDA kernels against the recurrence one position
after another, and what ``moe_ffn``'s choice gained for it (the group limit,
the scaling). The share, the pinned programs and the kind are
``tests/test_ling.py``'s, the faults ``tests/test_ling_faults.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ling_helpers import reference, rel as _rel
from torchft_tpu.models import moe
from torchft_tpu.models.ling import LING_CONFIGS
from torchft_tpu.ops import kda as K


def _qkvgb(B, T, H, d, seed, g=None, beta=None, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda m: m / jnp.linalg.norm(m, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    g = -5 * jax.nn.sigmoid(2 * jax.random.normal(ks[3], (B, T, H, d))) if g is None else g
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H))) if beta is None else beta
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def _bounds(B, T, H, d):
    """Decays at the bound in runs longer than a sub-block beside none at
    all, and ``beta`` at 0, at 1 and next to both."""
    at = jnp.arange(T)[None, :, None, None] // 24 + jnp.arange(d) // 5
    g = jnp.broadcast_to(jnp.where(at % 3 == 0, -5.0, jnp.where(at % 3 == 1, 0.0, -0.7)),
                         (B, T, H, d))
    beta = jnp.broadcast_to(jnp.asarray([0.0, 1.0, 1e-4, 1 - 1e-4])[jnp.arange(T) % 4][
        None, :, None], (B, T, H))
    return g, beta


def _one_key(args):
    """Every key the same vector, ``beta`` = 1, no decay."""
    q, k, v, g, beta = args
    return q, jnp.broadcast_to(k[:, :1], k.shape), v, jnp.zeros_like(g), jnp.ones_like(beta)


@pytest.mark.parametrize("case", ["across_blocks", "one_short_block", "at_the_bounds",
                                  "all_at_the_bound", "one_key"])
def test_the_kernels_are_the_recurrence_forward_and_backward(case):
    """``kda`` (interpreted here) against ``kda_reference``'s scan over
    positions: the output and all five gradients, across chunk and block
    borders, a sequence shorter than a block, with decays at -5 for whole
    sub-blocks beside ``beta`` at 0 and 1, and with one key for every
    position."""
    B, T, H, d = {"across_blocks": (2, 300, 2, 32), "one_short_block": (1, 50, 1, 16),
                  "at_the_bounds": (1, 150, 2, 16), "all_at_the_bound": (1, 70, 1, 16),
                  "one_key": (1, 128, 1, 16)}[case]
    g, beta = _bounds(B, T, H, d) if case == "at_the_bounds" else (None, None)
    if case == "all_at_the_bound":
        g = jnp.full((B, T, H, d), -5.0)
    args = _qkvgb(B, T, H, d, seed=len(case), g=g, beta=beta)
    if case == "one_key":
        # every key the same vector, beta = 1, no decay: a chunk inverse in
        # one step over 64 rows overflows float32 here (binomials to 1e18);
        # in two steps over sub-blocks of 16 it is the recurrence's
        args = _one_key(args)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, d))
    got, want = K.kda(*args), K.kda_reference(*args)
    assert _rel(got, want) < 5e-6
    grads = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=range(5))(*args)
    for name, a, b in zip("qkvgb", grads(K.kda), grads(K.kda_reference)):
        if float(jnp.linalg.norm(b)):
            # at decays of -5 everywhere the decay's own gradient is e^-5 of
            # the others': what is left of it is rounding's by a larger share
            # and one key for every position is as ill-conditioned as a chunk gets
            loose = name == "g" or case == "one_key"
            assert _rel(a, b) < (1e-3 if loose else 5e-5), (case, name)
        else:  # nothing depends on a decay that is given
            assert float(jnp.linalg.norm(a)) < 1e-6, (case, name)


@pytest.mark.parametrize("case", ["random", "at_the_bounds", "one_key"])
def test_the_inverses_closed_form_pullback_is_autodiff_through_its_products(case, monkeypatch):
    """PR 58: ``_inverse``'s pullback, ``-M^T dM M^T``, against ``jax.vjp``
    through the ten products of ``_products_inverse`` and against the same
    formula in float64, on the ``X`` the state-free half builds from a chunk
    of random inputs, of decays at -5 and 0 beside ``beta`` at 0 and 1, and
    of one key for every position (the ``X`` whose one-step inverse is not
    finite). On and above the diagonal the two differ by right (off the
    strictly lower triangle the products are no inverse) and the mask that
    made ``X`` drops both, so the strictly lower part is compared. Limits,
    as shares of the answer's norm: the closed form within 1e-6 of float64
    (read 4e-8 to 1e-7: two products of 64 terms at ``Precision.HIGHEST``),
    autodiff within 1e-6 of the closed form (read 7e-8 and 8e-8) but 1e-4
    for the one key, where it read 2.3e-5: the chain of ten pullbacks
    multiplies by powers of ``X`` that reach 1e4 over 16 rows and rounds at
    every link, so there the closed form is the better gradient by three
    digits, and the kernels' gradients moved by that much."""
    C, d = K.CHUNK, 16
    g, beta = _bounds(1, C, 1, d) if case == "at_the_bounds" else (None, None)
    args = _qkvgb(1, C, 1, d, seed=len(case), g=g, beta=beta)
    q, k, _, g, beta = (a[0, :, 0] for a in (_one_key(args) if case == "one_key" else args))
    seen = []
    monkeypatch.setattr(K, "_inverse", lambda x: seen.append(x) or K._products_inverse(x))
    K._state_free(q, k, g, beta[:, None])
    monkeypatch.undo()
    (x,) = seen
    lower = np.tril(np.ones((C, C)), -1)
    assert float(jnp.max(jnp.abs(x * (1 - lower)))) == 0 and float(jnp.max(jnp.abs(x))) > 0.1
    dm = jax.random.normal(jax.random.PRNGKey(4), (C, C))
    inv, closed = jax.vjp(K._inverse, x)
    products, auto = jax.vjp(K._products_inverse, x)
    np.testing.assert_array_equal(np.asarray(inv), np.asarray(products))
    m = np.linalg.inv(np.eye(C) + np.asarray(x, np.float64))
    assert _rel(inv, m) < 1e-6
    want = -m.T @ np.asarray(dm, np.float64) @ m.T * lower
    closed, auto = np.asarray(closed(dm)[0]) * lower, np.asarray(auto(dm)[0]) * lower
    assert _rel(closed, want) < 1e-6 and _rel(auto, closed) < (
        1e-4 if case == "one_key" else 1e-6), (
        case, _rel(closed, want), _rel(auto, want))


def test_a_block_takes_each_chunks_inverse_once_forward_and_once_backward():
    """PR 58, counted in the traced kernels: the products of 64 squared by 64
    squared are the inverse's and nobody else's. A forward block holds ten a
    chunk; forward and backward together hold those, the backward kernel's
    own ten a chunk (the state-free half linearised ONCE, its four chunks as
    one batch, shared by the states pass and the walk) and two a chunk for
    the closed form, where autodiff through the products would hold twenty
    a chunk and a second linearisation of a half its products again."""
    args = _qkvgb(1, K.BLOCK, 1, 16, seed=2)

    def inverses(f):  # a batch of chunks counts each
        return sum(int(np.prod(shapes[0][:-2])) for shapes, _ in _square_products(f, args))

    grad = jax.grad(lambda *a: jnp.sum(K.kda(*a)), argnums=range(5))
    assert inverses(K.kda) == 4 * 10 and inverses(grad) == 4 * 10 + 4 * (10 + 2)


def _equations(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _equations(sub)


def _square_products(f, args):
    """(operand shapes, batch dimensions) of every ``dot_general`` of
    ``[CHUNK, CHUNK] x [CHUNK, CHUNK]`` in ``f``'s traced kernels: at 16
    channels the inverse's and nobody else's."""
    for e in _equations(jax.make_jaxpr(f)(*args).jaxpr):
        shapes = [tuple(v.aval.shape) for v in e.invars]
        if e.primitive.name == "dot_general" and [s[-2:] for s in shapes] == [
                (K.CHUNK, K.CHUNK)] * 2:
            yield shapes, e.params["dimension_numbers"][1]


BODIES = {"bounded": ({"decay_floor": -5.0, "beta_max": 1.0}, K._state_free_bounded),
          "general": ({}, K._state_free)}


@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_forward_kernel_takes_a_blocks_inverses_as_one_batch(body):
    """PR 65, read in the traced FORWARD kernel: the state-free half of a
    block's chunks is one batch there too (``jax.vmap``), so each of the
    inverse's ten products of 64 squared by 64 squared is ONE ``dot_general``
    with the block's chunks as its leading batch dimension, four independent
    chains side by side, and none is left that waits on the chunk before."""
    kw, _ = BODIES[body]
    args = _qkvgb(1, K.BLOCK, 1, 16, seed=2)
    found = list(_square_products(lambda *a: K.kda(*a, **kw), args))
    assert len(found) == 10
    a_batch = (K.BLOCK // K.CHUNK, K.CHUNK, K.CHUNK)
    for shapes, batch in found:
        assert shapes == [a_batch] * 2 and batch == ((0,), (0,)), (shapes, batch)


@pytest.mark.parametrize("body", sorted(BODIES))
def test_the_forward_kernel_is_the_two_halves_composed_chunk_by_chunk(body):
    """``kda_fwd``'s output and the states it saves at every block's start
    against the definition of a chunk, ``_through_state(*free(...))``, walked
    one chunk after the other outside any kernel: the batch moved when the
    state-free half runs, not what it computes. Not to the bit on a CPU,
    whose batched product adds in another order than its plain one: read
    2.5e-7 to 2.9e-7 of the norm in ``o`` and 4.2e-7 to 5.3e-7 in the states,
    under decays of at most 0.1 a step (at Ling's -5 a step the running sum
    of a chunk reaches 160 and its rounding, 6e-8 of that in the exponents,
    puts parent and change alike 1e-5 to 3e-5 from float64 in the states)."""
    _, free = BODIES[body]
    B, T, H, d = 1, 2 * K.BLOCK, 2, 16
    g = -0.1 * jax.random.uniform(jax.random.PRNGKey(11), (B, T, H, d))
    beta = None
    if body == "general":  # what only the general body may be given: beta to 2
        beta = 2 * jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(12), (B, T, H)))
    q, k, v, g, beta = _qkvgb(B, T, H, d, seed=7, g=g, beta=beta)
    flat = lambda m: m.reshape(B, T, -1)  # noqa: E731
    o, hs = K._forward(flat(q), flat(k), flat(v), flat(g),
                       jnp.swapaxes(beta, 1, 2).reshape(B, H, -1, 1, K.BLOCK), H, free)
    o = o.reshape(B, T, H, d)

    @jax.jit
    def walk(q, k, v, g, beta):  # one head: [T, d] and beta [T, 1]
        st, outs, states = jnp.zeros((d, d), jnp.float32), [], []
        for lo in range(0, T, K.CHUNK):
            if lo % K.BLOCK == 0:
                states.append(st)
            at = slice(lo, lo + K.CHUNK)
            out, st = K._through_state(*free(q[at], k[at], g[at], beta[at]), v[at], beta[at], st)
            outs.append(out)
        return jnp.concatenate(outs), jnp.stack(states)

    for h in range(H):
        want_o, want_hs = walk(*(m[0, :, h] for m in (q, k, v, g)), beta[0, :, h, None])
        assert float(jnp.linalg.norm(want_hs[1])) > 0.1
        for got, want in ((o[0, :, h], want_o), (hs[0, h], want_hs)):
            assert _rel(got, want) < 1e-6, (body, h, _rel(got, want))


def test_a_cotangent_that_arrives_through_the_state_alone_is_the_recurrences():
    """A loss on the last block only: ``do`` is zero in the two blocks
    before it, so what they give back (``dk``, ``dv``, ``dg``, ``dbeta``;
    ``dq`` is zero there, exactly) came through ``dst_scr``'s carry from
    block to block and through the chunks' state cotangent inside a block,
    and through nothing else."""
    B, T, H, d = 1, 2 * K.BLOCK + 70, 2, 16
    # little decay and small writes: under the usual ones a state of 16
    # channels is gone, decayed or overwritten, within a block
    slow = -0.02 * jax.random.uniform(jax.random.PRNGKey(6), (B, T, H, d))
    faint = 0.05 * jax.random.uniform(jax.random.PRNGKey(7), (B, T, H))
    args = _qkvgb(B, T, H, d, seed=5, g=slow, beta=faint)
    early = slice(0, 2 * K.BLOCK)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, d)).at[:, early].set(0.0)
    grads = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=range(5))(*args)
    got, want = grads(K.kda), grads(K.kda_reference)
    assert float(jnp.max(jnp.abs(got[0][:, early]))) == 0.0
    for name, a, b in zip("kvgb", got[1:], want[1:]):
        for block in (slice(0, K.BLOCK), slice(K.BLOCK, 2 * K.BLOCK)):
            assert float(jnp.linalg.norm(b[:, block])) > 0.03 * float(jnp.linalg.norm(b)), name
            assert _rel(a[:, block], b[:, block]) < (1e-3 if name == "g" else 5e-5), name


def test_the_kernels_take_bf16_and_keep_state_and_decays_in_float32(monkeypatch):
    """bf16 q, k, v: the output is bf16 and within bf16 rounding of the
    float32 recurrence on the same rounded inputs; with a state rounded to
    bf16 after every chunk (the fault the chip check has to refuse) float32
    inputs land a hundred times further off than with the float32 state."""
    args = _qkvgb(1, 300, 2, 32, seed=3, dtype=jnp.bfloat16,
                  g=-0.05 * jnp.ones((1, 300, 2, 32)))
    want = K.kda_reference(*(a.astype(jnp.float32) for a in args))
    got = K.kda(*args)
    assert got.dtype == jnp.bfloat16 and _rel(got, want) < 4e-3
    wide = tuple(a.astype(jnp.float32) for a in args)
    assert _rel(K.kda(*wide), want) < 5e-6
    monkeypatch.setattr(K, "STATE_DTYPE", jnp.bfloat16)
    jax.clear_caches()
    assert _rel(K.kda(*wide), want) > 2e-4
    jax.clear_caches()


def test_a_positions_output_is_unchanged_by_later_positions():
    args = _qkvgb(1, 200, 1, 16, seed=1)
    cut = tuple(a[:, :130] for a in args)
    np.testing.assert_allclose(np.asarray(K.kda(*args))[:, :130], np.asarray(K.kda(*cut)),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------- the router

def _scores(T, E, seed=0):
    return jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(seed), (T, E)))


def test_one_group_is_the_plain_top_k_bit_for_bit():
    cfg = dataclasses.replace(moe.MOE_CONFIGS["debug"], num_experts=16, top_k=4,
                              router_score="sigmoid")
    same = dataclasses.replace(cfg, n_group=1, topk_group=1)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    a, b = moe._choose(_scores(64, 16), cfg, None, bias), moe._choose(
        _scores(64, 16), same, None, bias)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert sorted(a[2]) == ["p_kth", "p_next", "routing"]


def test_the_group_limit_differs_from_a_plain_top_k_on_a_stated_share():
    """32 experts in 4 groups, 4 a token in 2 groups: every token's experts
    lie in 2 groups, the choice is the reference's, and for 30 to 95% of
    random tokens it is not the plain top-4 (whose four lie in three or four
    groups more often than not)."""
    cfg = dataclasses.replace(LING_CONFIGS["ling_debug"], num_experts=32, top_k=4,
                              n_group=4, topk_group=2, held_experts=None)
    s = _scores(512, 32, seed=2)
    _, idx, free = moe._choose(s, cfg, None)
    assert int(jnp.max(jnp.sum(jnp.any(
        (idx // 8)[:, :, None] == jnp.arange(4), axis=1), axis=-1))) <= 2
    ref_idx, _, p_k, p_n = reference.choose(s, jnp.zeros(32), {
        "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
        "moe_router_enable_expert_bias": True, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5})
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(ref_idx, -1))
    np.testing.assert_allclose(free["p_kth"], p_k, rtol=1e-6)
    np.testing.assert_allclose(free["p_next"], p_n, rtol=1e-6)
    plain = jax.lax.top_k(s, 4)[1]
    differ = float(jnp.mean(jnp.any(jnp.sort(idx, -1) != jnp.sort(plain, -1), axis=-1)))
    assert 0.3 < differ < 0.95, differ
    assert float(moe._groups_hit(idx, cfg)) <= 2.0 < float(moe._groups_hit(plain, cfg))


def test_gates_are_the_unbiased_scores_renormalised_and_scaled():
    cfg = dataclasses.replace(LING_CONFIGS["ling_debug"], held_experts=None)
    s = _scores(64, 16, seed=3)
    gates, idx, _ = moe._choose(s, cfg, None, 0.3 * jnp.ones(16))
    at = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(gates, 2.5 * at / at.sum(-1, keepdims=True), rtol=1e-6)
