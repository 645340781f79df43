"""``ops/power_retention.py``: the chunked kernels (interpreted here) against
the recurrence one position after another (``power_retention_reference``,
phi written out 36 wide) and against the masked quadratic form the function
is defined by: three ways, forward and every gradient, at head size 8, 64
positions in chunks of 16 and blocks of 32, four query heads over two
key/value heads."""

import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops import power_retention as R

B, T, HQ, H, D = 2, 64, 4, 2, 8
SIZES = dict(chunk=16, block=32)
# exp(g): spread over the heads as the model's initialisation spreads it; a
# state that forgets within a position or two; one that forgets nothing
DECAYS = {"mixed": (2.0, 1.5), "near_zero": (-6.0, 0.3), "near_one": (9.0, 0.5)}
NAMES = ("y", "dq", "dk", "dv", "dg")


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _args(decay="mixed", T=T, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shift, spread = DECAYS[decay]
    q = jax.random.normal(ks[0], (B, T, HQ, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, H, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, H, D)).astype(dtype)
    g = jax.nn.log_sigmoid(spread * jax.random.normal(ks[3], (B, T, H)) + shift)
    return q, k, v, g


def quadratic(q, k, v, g, normalised=True):
    """The definition: a[t, r] = exp(G_t - G_r) (d^-1/2 q_t . k_r)^2 over r
    <= t, y = a v / (sum a + eps)."""
    T, rep = q.shape[1], q.shape[2] // k.shape[2]
    q, k, v = (m.astype(jnp.float32) for m in (q, k, v))
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    G = jnp.moveaxis(jnp.repeat(jnp.cumsum(g, axis=1), rep, axis=2), 1, 2)  # [B,Hq,T]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bthd,brhd->bhtr", q * q.shape[-1] ** -0.5, k)
        seen = jnp.tril(jnp.ones((T, T), bool))
        a = jnp.where(seen, jnp.exp(jnp.where(seen, G[..., :, None] - G[..., None, :], 0.0)), 0.0) * s * s
        num = jnp.einsum("bhtr,brhd->bthd", a, v)
    den = jnp.moveaxis(jnp.sum(a, axis=-1), 1, 2)[..., None]
    return (num / (den + R.EPS) if normalised else num), den


ORACLES = {"recurrence": R.power_retention_reference,
           "quadratic": lambda *a: quadratic(*a)[0],
           "kernel": functools.partial(R.power_retention, **SIZES)}


@functools.lru_cache(maxsize=None)
def _answers(which: str, decay: str):
    """(y, dq, dk, dv, dg) of one of the three under one cotangent."""
    args = _args(decay)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    f = ORACLES[which]
    y, pull = jax.vjp(jax.jit(f), *args)
    return dict(zip(NAMES, (y, *pull(w))))


@pytest.mark.parametrize("what", NAMES)
@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("oracle", ["recurrence", "quadratic"])
def test_the_kernels_are_the_recurrence_and_the_quadratic_form(oracle, decay, what):
    """The output and the four gradients (the log-decay's among them),
    against both: the carried state over four chunks and two blocks, the
    grouped heads, the normaliser. The recurrence takes (q . k)^2 as a sum of
    36 products of both signs: where that is small beside |q|^2 |k|^2 (a
    query nearly at a right angle to the only key its decay has left it) the
    sum cancels, the normaliser is float32's rounding and the quotient a few
    parts in a thousand off; the quadratic form squares one inner product,
    and so does the kernel inside a chunk, but not in what it reads from the
    state."""
    got, want = _answers("kernel", decay)[what], _answers(oracle, decay)[what]
    assert got.shape == want.shape and bool(jnp.all(jnp.isfinite(got)))
    limit = 5e-3 if oracle == "recurrence" else 2e-3 if decay == "near_zero" else 2e-5
    assert _rel(got, want) < limit, (oracle, decay, what)


def test_a_sequence_that_is_no_whole_block_is_padded_without_a_trace():
    q, k, v, g = _args(T=50, seed=3)
    got, den_min = R.power_retention(q, k, v, g, with_den_min=True, **SIZES)
    want, den = quadratic(q, k, v, g)
    assert got.shape == (B, 50, HQ, D) and _rel(got, want) < 2e-5
    # the padding's own normalisers (zero) are not among those it reports
    assert abs(float(den_min) / float(jnp.min(den)) - 1.0) < 1e-3 and float(den_min) > 0.0


def test_the_carry_across_blocks_is_what_the_second_block_starts_from():
    """The second block alone, from a zero state, is NOT the second half of
    the whole under a long memory."""
    args = _args("near_one")
    whole = _answers("kernel", "near_one")["y"]
    alone = R.power_retention(*(m[:, 32:] for m in args), **SIZES)
    assert _rel(alone, whole[:, 32:]) > 0.1


def test_a_query_head_reads_its_own_groups_state():
    """Every query head on key/value head 0 is another function; and heads
    that do not divide are refused."""
    q, k, v, g = _args()
    first = lambda m: jnp.broadcast_to(m[:, :, :1], m.shape)  # noqa: E731
    got = _answers("kernel", "mixed")["y"]
    wrong = R.power_retention(q, first(k), first(v), first(g), **SIZES)
    assert _rel(wrong[:, :, :2], got[:, :, :2]) < 1e-6  # group 0's heads: the same
    assert _rel(wrong[:, :, 2:], got[:, :, 2:]) > 0.3
    with pytest.raises(ValueError, match="heads"):
        R.power_retention(q[:, :, :3], k, v, g, **SIZES)


def test_a_state_survives_its_decay_over_sixty_positions():
    """One key written, then 63 positions of keys at a right angle to the
    query under exp(g) = 0.99: the last position reads the first value whole
    (the normaliser takes the decay out), and the normaliser it divided by is
    0.99^63 of the first weight."""
    q = jnp.zeros((1, T, 2, D)).at[..., 0].set(1.0)
    k = jnp.zeros((1, T, 1, D)).at[:, 0, :, 0].set(1.0).at[:, 1:, :, 1].set(1.0)
    v = jnp.zeros((1, T, 1, D)).at[:, 0].set(3.0)
    g = jnp.full((1, T, 1), math.log(0.99))
    y, den_min = R.power_retention(q, k, v, g, with_den_min=True, **SIZES)
    assert abs(float(y[0, -1, 0, 0]) - 3.0) < 1e-3
    assert abs(float(den_min) / (0.99 ** 63 / D) - 1.0) < 1e-4  # (d^-1/2 q . k)^2 = 1 / d


@pytest.mark.parametrize("fault", ["cross", "normaliser", "scale"])
def test_what_the_fault_script_switches_is_another_function(monkeypatch, fault):
    """``CROSS`` 1 halves the unlike products in what is read from the
    state (inside a chunk the kernel squares one inner product), ``NORMALISED``
    False is the numerator alone, as the quadratic form computes it, and a
    scale outside the square is the same function to eps: the normaliser
    divides it out, and only ``den_min`` tells."""
    q, k, v, g = _args()
    good = _answers("kernel", "mixed")["y"]
    if fault == "scale":
        y, den_min = R.power_retention(q, k, v, g, scale=D ** -0.25, with_den_min=True, **SIZES)
        _, ours = R.power_retention(q, k, v, g, with_den_min=True, **SIZES)
        assert _rel(y, good) < 1e-3
        assert abs(float(den_min) / float(ours) / D ** 0.5 - 1.0) < 2e-3
        return
    if fault == "cross":
        monkeypatch.setattr(R, "CROSS", 1.0)
        assert _rel(R.power_retention(q, k, v, g, **SIZES), good) > 0.1
        return
    monkeypatch.setattr(R, "NORMALISED", False)
    got = R.power_retention(q, k, v, g, **SIZES)
    assert _rel(got, quadratic(q, k, v, g, normalised=False)[0]) < 2e-5
    assert _rel(got, good) > 0.1


def test_bf16_inputs_keep_a_float32_state(monkeypatch):
    """bf16 q, k and v against the quadratic form on the same rounded
    inputs: what differs is the output's one rounding. With the state and
    the normaliser rounded to bf16 after every chunk the kernel is a hundred
    times further off where the output is not rounded: the check's
    ``bf16_state`` control."""
    args = _args("near_one", dtype=jnp.bfloat16)
    assert _rel(R.power_retention(*args, **SIZES), quadratic(*args)[0]) < 4e-3
    args, want = _args("near_one"), _answers("quadratic", "near_one")["y"]
    kept = _rel(_answers("kernel", "near_one")["y"], want)
    monkeypatch.setattr(R, "STATE_DTYPE", jnp.bfloat16)
    assert _rel(R.power_retention(*args, **SIZES), want) > 100 * kept


# -- what the forward pass saves for the backward pass


def _phi_tiles(x):
    """phi of the columns of ``x`` [d, n] in the kernels' layout, [(d / 2 + 1)
    d, n] (the module's text), by plain indexing."""
    d = x.shape[0]
    r = jnp.arange(d)
    weight = jnp.where((r == d // 2 - 1) | (r == d - 1), 1.0, math.sqrt(2.0))[:, None]
    tiles = [jnp.where(r[:, None] < d // 2, x[a], x[a + d // 2]) * x[(r + a + 1) % d] * weight
             for a in range(d // 2)]
    return jnp.concatenate(tiles + [x * x])


def test_the_saved_read_is_what_every_chunk_reads_from_the_state_it_starts_from():
    """Two blocks of two chunks: the forward rule's new output is ``phi(s q)^T
    S0`` with the normaliser's row, before the decay and before the chunk's
    own pairs, where ``S0`` is the saved state of the block's start for its
    first chunk and that state advanced by plain ``jax.numpy`` for its
    second; the rows under the normaliser's are zero. The plain call, which
    no gradient follows, has no such output."""
    q, k, v, g = _args("near_one")
    C, block = SIZES["chunk"], SIZES["block"]
    rep, rows, scale = HQ // H, D + R.PAD, D ** -0.5
    g6 = jnp.swapaxes(g, 1, 2).reshape(B, H, T // block, block // C, 1, C)
    flat = (q.reshape(B, T, -1), k.reshape(B, T, -1), v.reshape(B, T, -1), g6)
    cfg = (H, scale, C, block, T)
    assert len(R._forward(*flat, cfg, save_reads=False)) == 3
    y, hs, _, reads = jax.jit(lambda *a: R._forward(*a, cfg, save_reads=True))(*flat)
    assert reads.shape == (B, H, T // block, block // C, rows, rep * C) and reads.dtype == jnp.float32
    assert _rel(y.reshape(B, T, HQ, D), _answers("kernel", "near_one")["y"]) == 0.0
    assert float(jnp.max(jnp.abs(reads[..., D + 1:, :]))) == 0.0
    want = np.zeros(reads.shape, np.float32)
    with jax.default_matmul_precision("highest"):
        for b, h, n in np.ndindex(B, H, T // block):
            state = jnp.concatenate(list(hs[b, h, n]), axis=1)  # [rows, tiles * d]
            for c in range(block // C):
                at = slice(n * block + c * C, n * block + (c + 1) * C)
                x = jnp.concatenate([q[b, at, h * rep + i].T for i in range(rep)], axis=1) * scale
                want[b, h, n, c] = state @ _phi_tiles(x)
                G = jnp.cumsum(g[b, at, h])
                vz = jnp.zeros((rows, C)).at[:D].set(v[b, at, h].T).at[D].set(1.0)
                state = jnp.exp(G[-1]) * state + (vz * jnp.exp(G[-1] - G)) @ _phi_tiles(k[b, at, h].T).T
    assert float(jnp.max(jnp.abs(reads[:, :, 0, 0]))) == 0.0  # the first chunk finds a zero state
    assert _rel(reads, want) < 2e-5


# sha256 over the float32 bytes of (dq, dk, dv, dg) through the kernels as they
# stood BEFORE the forward pass saved the reads (PR 61's tree, this machine's
# CPU, interpreted): the backward kernel now loads what it computed then, from
# the same float32 state by the same operations in the same order, so no bit
# of any gradient may move. A digest moves only with the kernels' arithmetic;
# a PR that means to change that rewrites the pins.
PARENT = {1: "afc8e4374da30e97f496586d800a601088f22e342c9b4e667e069a45f5d09e59",
          5: "c5329e3a05e87ea8124b71fa8c72cfe337c995f9d7fb2283082c745ed3550cdd"}


@pytest.mark.parametrize("rep", sorted(PARENT))
def test_the_gradients_are_the_parents_to_the_bit(rep):
    ks = jax.random.split(jax.random.PRNGKey(62 + rep), 5)
    q, w = (jax.random.normal(key, (B, T, H * rep, D)) for key in (ks[0], ks[4]))
    k, v = (jax.random.normal(key, (B, T, H, D)) for key in ks[1:3])
    g = jax.nn.log_sigmoid(1.5 * jax.random.normal(ks[3], (B, T, H)) + 2.0)
    _, pull = jax.vjp(jax.jit(functools.partial(R.power_retention, **SIZES)), q, k, v, g)
    digest = hashlib.sha256()
    for grad in pull(w):
        digest.update(np.asarray(grad, np.float32).tobytes())
    assert digest.hexdigest() == PARENT[rep]


def _equations(jaxpr):
    """Every equation in ``jaxpr`` and under it; a loop's body is one jaxpr
    and counts once."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_the_backward_kernel_multiplies_no_tile_of_the_state_by_the_queries():
    """Five query heads a group, so that the queries' tile [d, rep C] is no
    key's [d, C]. A float32 product is three ``dot_general`` (``_dot``), a
    running sum of log-decays one. The forward kernel reads the state in two
    places (the loop over tiles, the last tile); the backward kernel holds
    the advance (``again``: 1 product a tile), the four products a tile a
    backward pass requires (``back``: u, dt, dw, dx), six of the chunk's own
    pairs and three running sums: 51 where it was 57 with the read, and none
    of [d_v + PAD, d] by [d, rep C]."""
    rep, C = 5, SIZES["chunk"]
    q, k, v, g = (jnp.zeros(s) for s in ((1, T, H * rep, D), (1, T, H, D), (1, T, H, D), (1, T, H)))
    grad = jax.grad(lambda *a: jnp.sum(R.power_retention(*a, **SIZES)), argnums=(0, 1, 2, 3))
    kernels = {e.params["name"]: e.params["jaxpr"]
               for e in _equations(jax.make_jaxpr(grad)(q, k, v, g).jaxpr)
               if e.primitive.name == "pallas_call"}
    assert sorted(kernels) == ["power_retention_bwd", "power_retention_fwd"]

    def products(name):
        return [(tuple(x.aval.shape for x in e.invars), e.params["dimension_numbers"][0])
                for e in _equations(kernels[name]) if e.primitive.name == "dot_general"]

    read = (((D + R.PAD, D), (D, rep * C)), ((1,), (0,)))
    assert products("power_retention_fwd").count(read) == 2 * 3
    backward = products("power_retention_bwd")
    assert read not in backward
    assert len(backward) == 3 * (2 * (1 + 4) + 6) + 3


# -- the kernels at the published widths, compiled for the chip that is described


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
def test_the_kernels_compile_for_a_v5e_at_the_published_widths(monkeypatch, one_chip, passes):
    """40 query heads over 8 of 128 at 16,384 positions: what Mosaic refuses
    (a slice off the tiling, more VMEM than a kernel may use) it refuses
    here, at no chip time; nothing runs."""
    monkeypatch.setattr(R, "_interpret", lambda: False)
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    args = (shape((1, 16384, 40, 128), jnp.bfloat16), shape((1, 16384, 8, 128), jnp.bfloat16),
            shape((1, 16384, 8, 128), jnp.bfloat16), shape((1, 16384, 8), jnp.float32))
    f = R.power_retention if passes == "forward" else jax.grad(
        lambda *a: jnp.sum(R.power_retention(*a).astype(jnp.float32)), argnums=(0, 1, 2, 3))
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "power_retention_fwd" in text
    assert ("power_retention_bwd" in text) == (passes == "backward")
    # the saved reads (0.38 GB: 64 chunks x 8 heads x 144 x 1,280 float32) leave
    # the forward kernel and enter the backward kernel, 2.9 MB a block in VMEM
    # twice over beside what each held; the plain call writes none
    assert ("f32[1,8,16,4,144,1280]" in text) == (passes == "backward")
