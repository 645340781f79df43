"""Fused-attention op tests (torchft_tpu/ops/attention.py).

The Pallas flash path needs a real TPU; on the CPU test matrix we validate
the XLA fallback's math against a direct per-query reference and confirm the
dispatcher picks the fallback. TPU numerics of flash-vs-XLA are exercised by
bench.py / the driver on real hardware.
"""

import numpy as np
import pytest

import jax
import jax.ad_checkpoint
import jax.numpy as jnp

from torchft_tpu.ops.attention import causal_attention, xla_attention


def naive_causal(q, k, v):
    """Per-query reference: softmax over the causal prefix, GQA-aware."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    groups = Hq // Hkv
    out = np.zeros_like(np.asarray(q, dtype=np.float32))
    q, k, v = (np.asarray(x, dtype=np.float32) for x in (q, k, v))
    for b in range(B):
        for h in range(Hq):
            kh = h // groups
            for s in range(S):
                scores = q[b, s, h] @ k[b, : s + 1, kh].T / np.sqrt(hd)
                w = np.exp(scores - scores.max())
                w /= w.sum()
                out[b, s, h] = w @ v[b, : s + 1, kh]
    return out


class TestXlaAttention:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2)])
    def test_matches_naive(self, hq, hkv):
        B, S, hd = 2, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, hq, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, hkv, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, hkv, hd), jnp.float32)
        out = xla_attention(q, k, v, None)
        np.testing.assert_allclose(
            np.asarray(out), naive_causal(q, k, v), rtol=1e-4, atol=1e-5
        )

    def test_causality(self):
        """Perturbing future tokens must not change earlier outputs."""
        B, S, H, hd = 1, 8, 2, 4
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
        base = np.asarray(xla_attention(q, k, v, None))
        k2 = k.at[:, -1].set(99.0)
        v2 = v.at[:, -1].set(99.0)
        pert = np.asarray(xla_attention(q, k2, v2, None))
        np.testing.assert_allclose(base[:, :-1], pert[:, :-1], rtol=1e-5)
        assert not np.allclose(base[:, -1], pert[:, -1])

    def test_grads_finite(self):
        B, S, H, hd = 1, 8, 2, 4
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
        g = jax.grad(lambda q: jnp.sum(xla_attention(q, k, v, None) ** 2))(q)
        assert np.isfinite(np.asarray(g)).all()


class TestSplashAttention:
    """Splash (GQA-native) kernel numerics via interpret mode — runs the real
    Pallas kernel logic on CPU against the XLA reference, fwd and bwd."""

    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2)])
    def test_matches_xla_forward(self, hq, hkv):
        from torchft_tpu.ops.attention import splash_attention_tpu

        B, S, hd = 2, 256, 128  # min splash tile: S%128==0, hd 128
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (B, S, hq, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, hkv, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, hkv, hd), jnp.float32)
        out = splash_attention_tpu(q, k, v, None, interpret=True)
        ref = xla_attention(q, k, v, None)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3
        )

    def test_backward_matches_xla(self):
        from torchft_tpu.ops.attention import splash_attention_tpu

        B, S, hq, hkv, hd = 1, 128, 4, 2, 128
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (B, S, hq, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, hkv, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, hkv, hd), jnp.float32)

        def loss(fn):
            return jax.grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) ** 2), argnums=(0, 1, 2)
            )(q, k, v)

        g_splash = loss(lambda q, k, v: splash_attention_tpu(
            q, k, v, None, interpret=True))
        g_ref = loss(lambda q, k, v: xla_attention(q, k, v, None))
        for gs, gr in zip(g_splash, g_ref):
            np.testing.assert_allclose(
                np.asarray(gs), np.asarray(gr), rtol=5e-3, atol=5e-3
            )


    def test_kernel_cache_safe_across_traces(self):
        """The cached kernel must not leak tracers: first use inside a
        remat'd scan trace, then reuse in a fresh grad trace (regression —
        mask arrays built inside the first trace escaped via the cache)."""
        from torchft_tpu.models.remat import ATTN_OUT_NAME, remat_wrap
        from torchft_tpu.ops.attention import _splash_kernel, splash_attention_tpu

        _splash_kernel.cache_clear()
        B, S, hq, hkv, hd = 1, 128, 4, 2, 128
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(ks[0], (B, S, hq, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, hkv, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, hkv, hd), jnp.float32)

        def att(q):
            return splash_attention_tpu(q, k, v, None, interpret=True)

        def layer(c, _):
            out = jax.ad_checkpoint.checkpoint_name(att(c), ATTN_OUT_NAME)
            return c + out, None

        body = remat_wrap(layer, "dots")

        def loss(q):
            h, _ = jax.lax.scan(body, q, None, length=2)
            return jnp.sum(h)

        float(loss(q))          # first trace builds + caches the kernel
        g = jax.grad(loss)(q)   # fresh trace reuses it — must not leak
        assert np.isfinite(np.asarray(g)).all()


class TestSplashBlockEnv:
    """Tile-selection plumbing: the env escape hatches must reach the kernel
    builder and reject non-dividing tiles. (Numerics across tile sizes are
    the upstream kernel's contract, exercised on TPU by mfu_sweep --blocks;
    multi-tile interpret mode is minutes-slow on a 1-vCPU host, so these
    tests assert the selected tiles without executing.)"""

    def _selected_blocks(self, monkeypatch, env):
        from torchft_tpu.ops import attention as A

        # isolate from the invoking shell (a TPU session that just ran
        # mfu_sweep cells may have these exported)
        monkeypatch.delenv("TORCHFT_TPU_SPLASH_BLOCK", raising=False)
        monkeypatch.delenv("TORCHFT_TPU_SPLASH_BLOCK_KV", raising=False)
        for k, v in env.items():
            monkeypatch.setenv(k, v)
        seen = {}

        def fake_kernel(n_q_heads, seq_len, block, block_kv, interpret, window=None):
            seen.update(block=block, block_kv=block_kv)
            raise _Stop()

        class _Stop(Exception):
            pass

        monkeypatch.setattr(A, "_splash_kernel", fake_kernel)
        q = jnp.zeros((1, 256, 2, 128), jnp.float32)
        kv = jnp.zeros((1, 256, 1, 128), jnp.float32)
        try:
            A.splash_attention_tpu(q, kv, kv, None, interpret=True)
        except _Stop:
            pass
        return seen

    def test_asymmetric_env_reaches_kernel(self, monkeypatch):
        seen = self._selected_blocks(
            monkeypatch,
            {"TORCHFT_TPU_SPLASH_BLOCK": "128",
             "TORCHFT_TPU_SPLASH_BLOCK_KV": "64"},
        )
        assert seen == {"block": 128, "block_kv": 64}

    def test_block_env_sets_both_dimensions(self, monkeypatch):
        seen = self._selected_blocks(
            monkeypatch, {"TORCHFT_TPU_SPLASH_BLOCK": "128"}
        )
        assert seen == {"block": 128, "block_kv": 128}

    def test_default_prefers_largest_dividing_tile(self, monkeypatch):
        seen = self._selected_blocks(monkeypatch, {})
        # S=256: 1024 and 512 don't divide; 256 is the largest that does
        assert seen == {"block": 256, "block_kv": 256}

    def test_non_dividing_kv_tile_rejected(self, monkeypatch):
        from torchft_tpu.ops import attention as A

        monkeypatch.delenv("TORCHFT_TPU_SPLASH_BLOCK", raising=False)
        monkeypatch.setenv("TORCHFT_TPU_SPLASH_BLOCK_KV", "96")
        q = jnp.zeros((1, 256, 2, 128), jnp.float32)
        kv = jnp.zeros((1, 256, 1, 128), jnp.float32)
        with pytest.raises(ValueError, match="SPLASH_BLOCK_KV"):
            A.splash_attention_tpu(q, kv, kv, None, interpret=True)


@pytest.mark.slow  # compile-heavy (>5s on the 1-vCPU CI host)
class TestSplashInModel:
    def test_llama_fwd_bwd_matches_xla(self):
        """End-to-end: the GQA llama layer stack through the splash kernel
        (interpret) equals the XLA reference, loss and gradients."""
        import dataclasses

        from torchft_tpu.models.llama import CONFIGS, llama_init, llama_loss
        from torchft_tpu.ops.attention import splash_attention_tpu

        cfg = dataclasses.replace(
            CONFIGS["debug"], dim=512, n_heads=4, n_kv_heads=2,
            n_layers=1, dtype=jnp.float32,
        )  # head_dim 128: the splash tile minimum
        params = llama_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (1, 128), 0, cfg.vocab_size
        )
        splash = lambda q, k, v, c: splash_attention_tpu(  # noqa: E731
            q, k, v, c, interpret=True)
        l_splash = float(llama_loss(params, toks, toks, cfg,
                                    attention_fn=splash))
        l_ref = float(llama_loss(params, toks, toks, cfg))
        assert abs(l_splash - l_ref) < 1e-3, (l_splash, l_ref)
        g = jax.grad(
            lambda p: llama_loss(p, toks, toks, cfg, attention_fn=splash)
        )(params)
        leaves = jax.tree_util.tree_leaves(g)
        assert all(np.isfinite(np.asarray(x)).all() for x in leaves)


class TestDispatch:
    def test_cpu_falls_back_to_xla(self):
        if jax.default_backend() != "cpu":
            pytest.skip("fallback dispatch is only observable on cpu")
        B, S, H, hd = 1, 128, 2, 64  # flash-eligible shape, but not on CPU
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, H, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, H, hd), jnp.float32)
        out = causal_attention(q, k, v, None)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(xla_attention(q, k, v, None)), rtol=1e-6
        )

    def _qkv(self, S=128, hd=64):
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        return [jax.random.normal(k, (1, S, 2, hd), jnp.float32) for k in ks]

    def test_unknown_choice_raises(self, monkeypatch):
        """One spelling: the registered enum is auto|splash|flash|reference;
        anything else (the old "xla" included) is an error, not flash."""
        monkeypatch.setenv("TORCHFT_TPU_ATTENTION", "xla")
        with pytest.raises(ValueError, match="TORCHFT_TPU_ATTENTION='xla'"):
            causal_attention(*self._qkv(), None)

    def test_reference_selects_xla_even_on_tpu(self, monkeypatch):
        from torchft_tpu.ops import attention as A

        monkeypatch.setenv("TORCHFT_TPU_ATTENTION", "reference")
        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        monkeypatch.setattr(
            A, "flash_attention_tpu",
            lambda *a: pytest.fail("reference must not select flash"),
        )
        causal_attention(*self._qkv(), None)
        assert A.LAST_DISPATCH == "xla"

    def test_untileable_shape_is_loud_on_tpu(self, monkeypatch):
        """On a TPU a shape the kernels cannot tile raises; it never gives
        way to materialized scores silently."""
        from torchft_tpu.ops import attention as A

        monkeypatch.delenv("TORCHFT_TPU_ATTENTION", raising=False)
        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        with pytest.raises(ValueError, match="does not tile"):
            causal_attention(*self._qkv(S=96), None)
        with pytest.raises(ValueError, match="does not tile"):
            causal_attention(*self._qkv(hd=32), None)
