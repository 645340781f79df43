"""Llama model + HSDP mesh + ring attention tests on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models.llama import CONFIGS, llama_forward, llama_init, llama_loss
from torchft_tpu.parallel.mesh import (
    batch_sharding,
    llama_param_specs,
    make_hsdp_mesh,
    make_train_step,
    shard_params,
)
from torchft_tpu.parallel.ring_attention import make_ring_attention_fn, ring_attention

CFG = CONFIGS["debug"]


@pytest.fixture(scope="module")
def params():
    return llama_init(jax.random.PRNGKey(0), CFG)


class TestLlama:
    def test_forward_shapes(self, params):
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = llama_forward(params, tokens, CFG)
        assert logits.shape == (2, 16, CFG.vocab_size)
        assert logits.dtype == jnp.float32

    def test_loss_finite_and_near_uniform_at_init(self, params):
        key = jax.random.PRNGKey(1)
        tokens = jax.random.randint(key, (2, 16), 0, CFG.vocab_size)
        loss = llama_loss(params, tokens, tokens, CFG)
        assert jnp.isfinite(loss)
        assert abs(float(loss) - np.log(CFG.vocab_size)) < 1.0

    def test_causality(self, params):
        """Changing a future token must not affect earlier logits."""
        t1 = jnp.zeros((1, 8), jnp.int32)
        t2 = t1.at[0, 7].set(5)
        l1 = llama_forward(params, t1, CFG)
        l2 = llama_forward(params, t2, CFG)
        np.testing.assert_allclose(l1[0, :7], l2[0, :7], atol=1e-5)
        assert not np.allclose(l1[0, 7], l2[0, 7])

    def test_grads_flow_everywhere(self, params):
        tokens = jnp.ones((1, 8), jnp.int32)
        grads = jax.grad(llama_loss)(params, tokens, tokens, CFG)
        leaves = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(lambda g: float(jnp.sum(jnp.abs(g))), grads)
        )
        assert all(l > 0 for l in leaves), "some parameter got zero gradient"

    @pytest.mark.parametrize("chunk", [4, 8, 16])
    @pytest.mark.slow  # compile-heavy (>5s on the 1-vCPU CI host)
    def test_chunked_loss_matches_full(self, params, chunk):
        """loss_chunk changes HBM residency, never the math: value and
        gradients must equal the full-logits path."""
        key = jax.random.PRNGKey(2)
        tokens = jax.random.randint(key, (2, 16), 0, CFG.vocab_size)
        full = llama_loss(params, tokens, tokens, CFG)
        chunked = llama_loss(params, tokens, tokens, CFG, loss_chunk=chunk)
        np.testing.assert_allclose(float(full), float(chunked), rtol=1e-6)
        g_full = jax.grad(llama_loss)(params, tokens, tokens, CFG)
        g_chunk = jax.grad(
            lambda p: llama_loss(p, tokens, tokens, CFG, loss_chunk=chunk)
        )(params)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_full),
            jax.tree_util.tree_leaves(g_chunk),
        ):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-3, atol=1e-5,
            )

    @pytest.mark.parametrize("mode", ["dots", "attn", "full"])
    @pytest.mark.slow  # compile-heavy (>5s on the 1-vCPU CI host)
    def test_remat_modes_change_nothing_but_memory(self, params, mode):
        """Every remat mode is a pure recompute schedule: loss and gradients
        must match the no-remat path bit-for-near-bit."""
        key = jax.random.PRNGKey(3)
        tokens = jax.random.randint(key, (2, 16), 0, CFG.vocab_size)
        base, g_base = jax.value_and_grad(llama_loss)(
            params, tokens, tokens, CFG, remat="none"
        )
        got, g_got = jax.value_and_grad(llama_loss)(
            params, tokens, tokens, CFG, remat=mode
        )
        np.testing.assert_allclose(float(base), float(got), rtol=1e-6)
        for a, b in zip(
            jax.tree_util.tree_leaves(g_base), jax.tree_util.tree_leaves(g_got)
        ):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=2e-3, atol=1e-5,
            )

    def test_chunk_must_divide_seq(self, params):
        tokens = jnp.zeros((1, 16), jnp.int32)
        with pytest.raises(ValueError, match="divide"):
            llama_loss(params, tokens, tokens, CFG, loss_chunk=5)

    def test_num_params_formula(self):
        p = llama_init(jax.random.PRNGKey(0), CFG)
        actual = sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(p))
        assert actual == CFG.num_params()

    def test_8b_config_size(self):
        assert 7.9e9 < CONFIGS["llama3_8b"].num_params() < 8.1e9


class TestHSDPMesh:
    def test_sharded_train_step_runs(self, params):
        mesh = make_hsdp_mesh(dp=2, fsdp=2, tp=2, sp=1)
        specs = llama_param_specs(CFG)
        sharded = shard_params(params, mesh, specs)
        tx = optax.adamw(1e-3)
        opt_state = tx.init(sharded)
        step = make_train_step(CFG, tx, mesh, donate=False)
        tokens = jnp.ones((4, 16), jnp.int32)
        new_params, new_opt, loss = step(sharded, opt_state, tokens, tokens)
        assert jnp.isfinite(loss)
        # params actually changed and kept their sharding
        w0 = np.asarray(sharded["lm_head"]).copy()
        w1 = np.asarray(new_params["lm_head"])
        assert not np.allclose(w0, w1)
        assert new_params["lm_head"].sharding.spec == specs["lm_head"]

    def test_sharded_matches_single_device(self, params):
        """HSDP-sharded forward == unsharded forward (XLA SPMD is pure
        parallelization, not approximation)."""
        mesh = make_hsdp_mesh(dp=1, fsdp=2, tp=2, sp=1)
        sharded = shard_params(params, mesh, llama_param_specs(CFG))
        tokens = jnp.ones((2, 16), jnp.int32)
        ref = llama_forward(params, tokens, CFG)
        out = jax.jit(lambda p, t: llama_forward(p, t, CFG))(sharded, tokens)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-4)


def naive_causal_attention(q, k, v):
    """Dense causal softmax reference (GQA: jnp.repeat k/v at the call
    site). One copy for every ring/ulysses comparison in this file."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
    S = q.shape[1]
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


class TestRingAttention:
    def test_matches_dense_attention(self, params):
        """Ring attention over sp=4 must equal the dense causal attention."""
        mesh = make_hsdp_mesh(dp=1, fsdp=1, tp=2, sp=4)
        ring_fn = make_ring_attention_fn(mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, CFG.vocab_size)

        ref = llama_forward(params, tokens, CFG)

        sharded = shard_params(params, mesh, llama_param_specs(CFG))
        out = jax.jit(
            lambda p, t: llama_forward(p, t, CFG, attention_fn=ring_fn)
        )(sharded, tokens)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=3e-4)

    def test_ring_attention_unit(self):
        """Direct shard_map unit check against naive softmax attention."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = make_hsdp_mesh(dp=1, fsdp=1, tp=1, sp=8)
        B, S, H, hd = 2, 64, 4, 8
        key = jax.random.PRNGKey(3)
        q, k, v = (
            jax.random.normal(k_, (B, S, H, hd), jnp.float32)
            for k_ in jax.random.split(key, 3)
        )

        # naive reference
        expected = naive_causal_attention(q, k, v)

        spec = P(None, "sp", None, None)
        with mesh:
            out = shard_map(
                partial(ring_attention, axis_name="sp"),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)

    def test_gqa_ring(self):
        """Ring attention with grouped KV heads (Hq != Hkv)."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = make_hsdp_mesh(dp=1, fsdp=1, tp=1, sp=4)
        B, S, Hq, Hkv, hd = 1, 32, 4, 2, 8
        key = jax.random.PRNGKey(4)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, S, Hq, hd), jnp.float32)
        k = jax.random.normal(kk, (B, S, Hkv, hd), jnp.float32)
        v = jax.random.normal(kv_, (B, S, Hkv, hd), jnp.float32)

        k_rep = jnp.repeat(k, Hq // Hkv, axis=2)
        v_rep = jnp.repeat(v, Hq // Hkv, axis=2)
        expected = naive_causal_attention(q, k_rep, v_rep)

        spec = P(None, "sp", None, None)
        with mesh:
            out = shard_map(
                partial(ring_attention, axis_name="sp"),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)


class TestUlyssesAttention:
    """All-to-all sequence parallelism (parallel/ulysses.py): the second
    long-context strategy next to ring attention."""

    def test_matches_dense_attention(self, params):
        """Ulysses over sp=2 must equal dense causal attention at the model
        level (debug config: 4 q heads / 2 kv heads; tp=1 so sp=2 divides
        both per-device head counts)."""
        from torchft_tpu.parallel.ulysses import make_ulysses_attention_fn

        mesh = make_hsdp_mesh(dp=1, fsdp=1, tp=1, sp=2)
        uly_fn = make_ulysses_attention_fn(mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, CFG.vocab_size)

        ref = llama_forward(params, tokens, CFG)
        sharded = shard_params(params, mesh, llama_param_specs(CFG))
        out = jax.jit(
            lambda p, t: llama_forward(p, t, CFG, attention_fn=uly_fn)
        )(sharded, tokens)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=3e-4)

    def test_unit_matches_naive(self):
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchft_tpu.parallel.ulysses import ulysses_attention

        mesh = make_hsdp_mesh(dp=1, fsdp=1, tp=1, sp=4)
        B, S, H, hd = 2, 64, 4, 8
        key = jax.random.PRNGKey(6)
        q, k, v = (
            jax.random.normal(k_, (B, S, H, hd), jnp.float32)
            for k_ in jax.random.split(key, 3)
        )
        expected = naive_causal_attention(q, k, v)

        spec = P(None, "sp", None, None)
        with mesh:
            out = shard_map(
                partial(ulysses_attention, cfg=CFG, axis_name="sp"),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)

    def test_gqa_ulysses(self):
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchft_tpu.parallel.ulysses import ulysses_attention

        mesh = make_hsdp_mesh(dp=1, fsdp=1, tp=1, sp=2)
        B, S, Hq, Hkv, hd = 1, 32, 4, 2, 8
        key = jax.random.PRNGKey(7)
        kq, kk, kv_ = jax.random.split(key, 3)
        q = jax.random.normal(kq, (B, S, Hq, hd), jnp.float32)
        k = jax.random.normal(kk, (B, S, Hkv, hd), jnp.float32)
        v = jax.random.normal(kv_, (B, S, Hkv, hd), jnp.float32)

        k_rep = jnp.repeat(k, Hq // Hkv, axis=2)
        v_rep = jnp.repeat(v, Hq // Hkv, axis=2)
        expected = naive_causal_attention(q, k_rep, v_rep)

        spec = P(None, "sp", None, None)
        with mesh:
            out = shard_map(
                partial(ulysses_attention, cfg=CFG, axis_name="sp"),
                mesh=mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
                check_vma=False,
            )(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expected), atol=1e-5)

    def test_indivisible_heads_fail_loudly(self):
        """sp=4 cannot divide 2 kv heads: a clear ValueError, not silent
        garbage (the documented ring-attention-instead case)."""
        from functools import partial

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from torchft_tpu.parallel.ulysses import ulysses_attention

        mesh = make_hsdp_mesh(dp=1, fsdp=1, tp=1, sp=4)
        B, S, Hq, Hkv, hd = 1, 32, 4, 2, 8
        q = jnp.ones((B, S, Hq, hd), jnp.float32)
        k = jnp.ones((B, S, Hkv, hd), jnp.float32)
        v = jnp.ones((B, S, Hkv, hd), jnp.float32)
        spec = P(None, "sp", None, None)
        with pytest.raises(ValueError, match="ring attention"):
            with mesh:
                shard_map(
                    partial(ulysses_attention, cfg=CFG, axis_name="sp"),
                    mesh=mesh,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                    check_vma=False,
                )(q, k, v)
