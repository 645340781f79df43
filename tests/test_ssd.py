"""``ops/ssd.py``: the chunked Mamba-2 kernels (interpreted here) against the
recurrence one position after another (``ssd_reference``)."""

import jax
import jax.numpy as jnp
import pytest

from torchft_tpu.ops import ssd as S


def _rel(a, b):
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _args(B, T, H, P, G, N, seed=0, dtype=jnp.float32, dt_shift=-2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, T, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) + dt_shift)
    a = -jnp.exp(jax.random.uniform(ks[2], (H,), minval=0.0, maxval=2.77))  # -1 .. -16
    bm = (jax.random.normal(ks[3], (B, T, G, N)) / N ** 0.5).astype(dtype)
    cm = jax.random.normal(ks[4], (B, T, G, N)).astype(dtype)
    return x, dt, a, bm, cm


def _grads(f, args):
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    return jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=range(5))(*args)


CASES = {"several_chunks": (2, 384, 4, 16, 2, 16), "not_whole_chunks": (1, 200, 4, 32, 2, 16),
         "one_head_a_group": (1, 130, 2, 64, 2, 32)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_the_recurrence_forward_and_backward(case):
    """The output and all five gradients: several chunks (the carried
    state), a length the kernel pads, two heads a group and one; one chunk
    alone is ``test_the_carry_across_chunks...``'s second half."""
    args = _args(*CASES[case], seed=len(case))
    assert _rel(S.ssd(*args), S.ssd_reference(*args)) < 5e-6
    for name, got, want in zip(("x", "dt", "a", "B", "C"), _grads(S.ssd, args),
                               _grads(S.ssd_reference, args)):
        # a's gradient is a sum over every position of terms of both signs
        assert _rel(got, want) < (1e-3 if name == "a" else 5e-5), (case, name)


def test_a_sequence_padded_by_the_caller_reads_as_the_unpadded_one():
    """Positions of dt = 0 leave the state as it is, whatever x, B and C
    hold there: the caller's padding (and the kernel's own) changes nothing
    before it."""
    x, dt, a, bm, cm = _args(1, 200, 2, 16, 1, 16)
    pad = lambda m, v: jnp.pad(m, ((0, 0), (0, 56)) + ((0, 0),) * (m.ndim - 2),  # noqa: E731
                               constant_values=v)
    padded = S.ssd(pad(x, 3.0), pad(dt, 0.0), a, pad(bm, 1.0), pad(cm, 1.0))
    assert _rel(padded[:, :200], S.ssd(x, dt, a, bm, cm)) < 1e-6


def test_the_carry_across_chunks_is_what_the_second_chunk_starts_from():
    """The second chunk alone, from a zero state, is NOT the second half of
    the whole: the difference is the first chunk's state read through C and
    the decay, as the reference has it."""
    args = _args(1, 256, 2, 16, 2, 16, dt_shift=-4.0)  # little decay: a long memory
    whole = S.ssd(*args)
    alone = S.ssd(*(m[:, 128:] if m.ndim > 1 else m for m in args))
    assert _rel(alone, whole[:, 128:]) > 0.1
    assert _rel(whole, S.ssd_reference(*args)) < 5e-6


def test_a_head_reads_its_own_groups_b_and_c():
    """Every head on group 0's B and C is another function."""
    x, dt, a, bm, cm = _args(1, 128, 4, 16, 2, 16)
    first = lambda m: jnp.broadcast_to(m[:, :, :1], m.shape)  # noqa: E731
    got, wrong = S.ssd(x, dt, a, bm, cm), S.ssd(x, dt, a, first(bm), first(cm))
    assert _rel(got[:, :, :2], wrong[:, :, :2]) < 1e-6  # group 0's heads: the same
    assert _rel(got[:, :, 2:], wrong[:, :, 2:]) > 0.5
    with pytest.raises(ValueError, match="heads"):
        S.ssd(x[:, :, :3], dt[:, :, :3], a[:3], bm, cm)


def test_a_state_survives_a_thousand_positions():
    """One position written, then 1,023 of dt near 1e-3 under a = -1: the
    read at the end is exp(-sum dt a) of it, a third, and neither 0 nor the
    whole (the factors between chunks multiply up, no chunk's is lost)."""
    T, H, P, N = 1024, 2, 16, 16
    x = jnp.zeros((1, T, H, P)).at[:, 0].set(1.0)
    dt = jnp.full((1, T, H), 1.1e-3).at[:, 0].set(1.0)
    a = -jnp.ones((H,))
    bm = jnp.ones((1, T, 1, N)) / N
    cm = jnp.ones((1, T, 1, N))
    got = S.ssd(x, dt, a, bm, cm)
    want = float(jnp.exp(-1.1e-3 * (T - 1)))  # 0.3246
    assert abs(float(got[0, -1, 0, 0]) - want) < 1e-5
    # the reference multiplies a thousand rounded factors up: it is the one that drifts
    assert _rel(got, S.ssd_reference(x, dt, a, bm, cm)) < 5e-5


def test_bf16_inputs_keep_a_float32_state(monkeypatch):
    """bf16 x, B and C against the reference on the same rounded inputs:
    what differs is the output's one rounding. With the state (and the
    running log-decay) rounded to bf16 after every chunk the kernel is
    several times further off: the check's ``bf16_state`` control."""
    args = _args(1, 512, 2, 16, 1, 16, dtype=jnp.bfloat16, dt_shift=-4.0)
    want = S.ssd_reference(*(m.astype(jnp.float32) for m in args))
    kept = _rel(S.ssd(*args), want)
    assert kept < 4e-3  # one bf16 rounding of y
    monkeypatch.setattr(S, "STATE_DTYPE", jnp.bfloat16)
    assert _rel(S.ssd(*args), want) > 2 * kept
