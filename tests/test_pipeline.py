"""Pipeline-parallel tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from torchft_tpu.models.llama import CONFIGS, llama_init, llama_loss
from torchft_tpu.parallel.mesh import shard_params
from torchft_tpu.parallel.pipeline import (
    make_pp_llama_loss,
    pipeline_apply,
    pp_param_specs,
)

CFG = CONFIGS["debug"]


def make_pp_mesh(pp):
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()[:pp]).reshape(pp)
    return Mesh(devs, ("pp",))


class TestPipelineApply:
    @pytest.mark.parametrize("pp,M", [(2, 2), (2, 4), (4, 4), (4, 8)])
    def test_matches_sequential_scan(self, pp, M):
        """The pipeline must compute exactly what the plain layer scan does."""
        from jax import shard_map

        mesh = make_pp_mesh(pp)
        L, B, D = 4, 8, 16
        key = jax.random.PRNGKey(0)
        ws = jax.random.normal(key, (L, D, D), jnp.float32) / np.sqrt(D)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, D), jnp.float32)

        def layer(h, w):
            return jnp.tanh(h @ w), None

        ref, _ = jax.lax.scan(layer, x, ws)

        def pp_fn(ws_local, x):
            out = pipeline_apply(layer, ws_local, x, num_microbatches=M)
            is_last = (jax.lax.axis_index("pp") == pp - 1).astype(out.dtype)
            return jax.lax.psum(out * is_last, "pp")

        got = shard_map(
            pp_fn, mesh=mesh,
            in_specs=(P("pp", None, None), P(None, None)),
            out_specs=P(None, None),
            check_vma=False,
        )(ws, x)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-6
        )


class TestPPLlama:
    @pytest.mark.parametrize("pp", [2, 4])
    def test_loss_matches_dense(self, pp):
        import dataclasses

        cfg = dataclasses.replace(CFG, n_layers=4)  # pp must divide n_layers
        mesh = make_pp_mesh(pp)
        params = llama_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
        dense = float(llama_loss(params, toks, toks, cfg))
        pp_loss = make_pp_llama_loss(cfg, mesh)
        with mesh:
            got = float(jax.jit(pp_loss)(params, toks, toks))
        assert abs(got - dense) < 1e-4, (got, dense)

    @pytest.mark.slow  # compile-heavy (>5s on the 1-vCPU CI host)
    def test_train_step_with_sharded_layers(self):
        """Full jitted pp train step: layers sharded over pp, loss decreases."""
        import optax

        mesh = make_pp_mesh(2)
        params = llama_init(jax.random.PRNGKey(0), CFG)
        params = shard_params(params, mesh, pp_param_specs(CFG))
        toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, CFG.vocab_size)
        loss_fn = make_pp_llama_loss(CFG, mesh, num_microbatches=2)
        tx = optax.adamw(1e-2)
        opt = tx.init(params)

        @jax.jit
        def step(params, opt, toks):
            l, g = jax.value_and_grad(loss_fn)(params, toks, toks)
            u, opt2 = tx.update(g, opt, params)
            return optax.apply_updates(params, u), opt2, l

        with mesh:
            params, opt, l0 = step(params, opt, toks)
            params, opt, l1 = step(params, opt, toks)
        assert np.isfinite(float(l0)) and float(l1) < float(l0)
