"""MoE model + expert-parallel sharding tests (runs on the virtual 8-device
CPU mesh from conftest)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchft_tpu.models.moe import (
    MOE_CONFIGS,
    MoEConfig,
    _top_k_dispatch,
    moe_ffn,
    moe_init,
    moe_loss,
    moe_param_specs,
)


class TestDispatch:
    def test_combine_weights_sum_to_one_under_capacity(self):
        T, E = 16, 4
        probs = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(0), (T, E)), -1)
        combine, dispatch = _top_k_dispatch(probs, top_k=2, capacity=T)
        # ample capacity: every token's two gates land, normalized to 1
        np.testing.assert_allclose(np.asarray(combine.sum(axis=(1, 2))), 1.0, rtol=1e-5)
        # each (expert, slot) holds at most one token
        assert float(dispatch.sum(axis=0).max()) <= 1.0

    def test_capacity_drops_overflow(self):
        T, E = 8, 2
        # all tokens want expert 0
        probs = jnp.tile(jnp.array([[0.99, 0.01]], jnp.float32), (T, 1))
        combine, dispatch = _top_k_dispatch(probs, top_k=1, capacity=3)
        # only 3 tokens fit; the rest are dropped (zero combine weight)
        kept = np.asarray(combine.sum(axis=(1, 2)) > 0)
        assert kept.sum() == 3
        assert kept[:3].all(), "queue priority must be in token order"

    def test_aux_loss_favors_balance(self):
        T, E = 32, 4
        balanced = jnp.tile(jnp.full((1, E), 1.0 / E, jnp.float32), (T, 1))
        skewed = jax.nn.softmax(
            jnp.tile(jnp.array([[5.0, 0.0, 0.0, 0.0]], jnp.float32), (T, 1)), -1
        )

        def aux(probs):  # the model's own: every choice counted (here: one)
            from torchft_tpu.models.moe import load_balancing_loss

            first = jax.lax.top_k(probs, 1)[1][:, 0]
            counts = jnp.sum(jax.nn.one_hot(first, E), axis=0)
            return load_balancing_loss(counts[None], jnp.sum(probs, axis=0)[None], T)

        assert float(aux(skewed)) > float(aux(balanced))


class TestMoEFFN:
    def test_single_expert_equals_dense_ffn(self):
        """E=1, top_k=1, ample capacity: the MoE layer IS the dense SwiGLU."""
        cfg = MoEConfig(
            vocab_size=64, dim=16, n_layers=1, n_heads=2, n_kv_heads=2,
            ffn_hidden=32, dtype=jnp.float32, num_experts=1, top_k=1,
            capacity_factor=2.0,
        )
        key = jax.random.PRNGKey(1)
        x = jax.random.normal(key, (2, 8, cfg.dim), jnp.float32)
        router = jnp.zeros((cfg.dim, 1), jnp.float32)
        wg = jax.random.normal(key, (1, cfg.dim, cfg.ffn_hidden), jnp.float32)
        wu = jax.random.normal(jax.random.PRNGKey(2), (1, cfg.dim, cfg.ffn_hidden))
        wd = jax.random.normal(jax.random.PRNGKey(3), (1, cfg.ffn_hidden, cfg.dim))
        out, _aux = moe_ffn(x, router, wg, wu, wd, cfg)
        dense = (jax.nn.silu(x @ wg[0]) * (x @ wu[0])) @ wd[0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=2e-4, atol=2e-5)

    @pytest.mark.slow  # compile-heavy (>5s on the 1-vCPU CI host)
    def test_forward_and_grads_finite(self):
        cfg = MOE_CONFIGS["debug"]
        params = moe_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        loss, grads = jax.value_and_grad(moe_loss)(params, toks, toks, cfg)
        assert np.isfinite(float(loss))
        finite = jax.tree_util.tree_map(
            lambda g: bool(np.isfinite(np.asarray(g)).all()), grads
        )
        assert all(jax.tree_util.tree_leaves(finite))
        # router must receive gradient (top_k gating is differentiable
        # through the gate weights)
        assert float(np.abs(np.asarray(grads["layers"]["router"])).max()) > 0


class TestExpertParallel:
    @pytest.mark.slow  # compile-heavy (>5s on the 1-vCPU CI host)
    def test_ep_sharded_train_step(self):
        """Full MoE train step jitted over a mesh with a real ep axis."""
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from torchft_tpu.parallel.mesh import make_hsdp_mesh, shard_params

        cfg = MOE_CONFIGS["debug"]
        mesh = make_hsdp_mesh(dp=1, fsdp=2, ep=2, sp=1, tp=2)
        params = moe_init(jax.random.PRNGKey(0), cfg)
        specs = moe_param_specs(cfg)
        params = shard_params(params, mesh, specs)
        assert "ep" in str(params["layers"]["w_gate"].sharding.spec)

        tx = optax.adamw(1e-3)
        opt = tx.init(params)
        tok_sharding = NamedSharding(mesh, P(("dp", "fsdp"), None))
        toks = jax.device_put(
            np.random.randint(0, cfg.vocab_size, (4, 16)), tok_sharding
        )

        @jax.jit
        def step(params, opt, toks):
            loss, g = jax.value_and_grad(moe_loss)(params, toks, toks, cfg)
            u, opt2 = tx.update(g, opt, params)
            return optax.apply_updates(params, u), opt2, loss

        params, opt, l0 = step(params, opt, toks)
        params, opt, l1 = step(params, opt, toks)
        assert np.isfinite(float(l0)) and float(l1) < float(l0)

    def test_ep_matches_unsharded(self):
        """Expert-parallel execution must be numerically equivalent to
        single-device execution (collectives are transparent)."""
        cfg = MOE_CONFIGS["debug"]
        params = moe_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        base = float(moe_loss(params, toks, toks, cfg))

        from torchft_tpu.parallel.mesh import make_hsdp_mesh, shard_params

        mesh = make_hsdp_mesh(dp=1, fsdp=1, ep=4, sp=1, tp=2)
        sharded = shard_params(params, mesh, moe_param_specs(cfg))
        ep = float(jax.jit(moe_loss, static_argnums=(3,))(sharded, toks, toks, cfg))
        assert abs(base - ep) < 1e-4, (base, ep)


# ---- PR 28: OLMoE's block: dropless routing, gates not renormalised,
# QK-norm, routing replay

import dataclasses  # noqa: E402

from torchft_tpu.models.moe import (  # noqa: E402
    load_balancing_loss,
    moe_forward,
    moe_loss_and_stats,
)

OLMOE_TINY = dataclasses.replace(
    MOE_CONFIGS["debug"], num_experts=8, top_k=4, capacity_factor=None,
    norm_topk_prob=False, qk_norm=True,
)


def _layer_weights(cfg, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    d, h, E = cfg.dim, cfg.ffn_hidden, cfg.num_experts
    return (jax.random.normal(k[0], (2, 16, d), jnp.float32),
            jax.random.normal(k[1], (d, E), jnp.float32) / 4,
            jax.random.normal(k[2], (E, d, h), jnp.float32) / 8,
            jax.random.normal(k[3], (E, d, h), jnp.float32) / 8,
            jax.random.normal(k[4], (E, h, d), jnp.float32) / 8)


def _dense_masked_sum(x, router, wg, wu, wd, cfg):
    """Every expert on every token, weighted by the token's gate for it
    (zero where it was not chosen): the definition, with no dispatch."""
    flat = x.reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(flat @ router, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(idx, cfg.num_experts) * gates[..., None], axis=1)
    every = jnp.einsum("teh,ehd->ted", jax.nn.silu(jnp.einsum("td,edh->teh", flat, wg))
                       * jnp.einsum("td,edh->teh", flat, wu), wd)
    return jnp.sum(every * weight[..., None], axis=1).reshape(x.shape)


class TestDropless:
    @pytest.mark.parametrize("norm", [False, True])
    def test_equals_a_dense_masked_sum_forward_and_every_gradient(self, norm):
        cfg = dataclasses.replace(OLMOE_TINY, norm_topk_prob=norm)
        args = _layer_weights(cfg)
        target = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

        def ours(*a):
            return jnp.sum(moe_ffn(*a, cfg)[0] * target)

        def plain(*a):
            return jnp.sum(_dense_masked_sum(*a, cfg) * target)

        np.testing.assert_allclose(np.asarray(moe_ffn(*args, cfg)[0]),
                                   np.asarray(_dense_masked_sum(*args, cfg)),
                                   rtol=1e-5, atol=1e-5)
        got = jax.grad(ours, argnums=(0, 1, 2, 3, 4))(*args)
        want = jax.grad(plain, argnums=(0, 1, 2, 3, 4))(*args)
        for name, g, w in zip(("x", "router", "w_gate", "w_up", "w_down"), got, want):
            scale = float(jnp.abs(w).max())
            assert float(jnp.abs(g - w).max()) <= 1e-5 * max(scale, 1.0), name

    def test_gates_are_not_renormalised_unless_asked(self):
        """norm_topk_prob False: a token's output is its renormalised output
        times the sum of its chosen probabilities (under 1)."""
        args = _layer_weights(OLMOE_TINY)
        raw, _ = moe_ffn(*args, OLMOE_TINY)
        normed, _ = moe_ffn(*args, dataclasses.replace(OLMOE_TINY, norm_topk_prob=True))
        probs = jax.nn.softmax(args[0].reshape(-1, args[0].shape[-1]) @ args[1], -1)
        mass = jnp.sum(jax.lax.top_k(probs, OLMOE_TINY.top_k)[0], -1).reshape(2, 16, 1)
        assert float(mass.max()) < 0.999
        np.testing.assert_allclose(np.asarray(raw), np.asarray(normed * mass),
                                   rtol=1e-5, atol=1e-6)

    def test_every_token_to_one_expert_drops_nothing(self):
        """All 32 tokens on expert 2: the dropless output is that expert's
        for every token; the capacity path at the same load keeps 5."""
        cfg = dataclasses.replace(OLMOE_TINY, top_k=1, norm_topk_prob=True)
        x, router, wg, wu, wd = _layer_weights(cfg)
        routing = jnp.full((32, 1), 2, jnp.int32)
        out, stats = moe_ffn(x, router, wg, wu, wd, cfg, routing=routing)
        dense = (jax.nn.silu(x @ wg[2]) * (x @ wu[2])) @ wd[2]
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense), rtol=1e-5, atol=1e-5)
        assert stats["counts"].tolist() == [0, 0, 32, 0, 0, 0, 0, 0]
        capped = dataclasses.replace(cfg, capacity_factor=1.25)
        dropped, _ = moe_ffn(x, router, wg, wu, wd, capped, routing=routing)
        kept = np.asarray(jnp.abs(dropped).sum(-1) > 0).reshape(-1)
        assert kept.sum() == capped.capacity(32) == 5

    def test_stats_count_every_choice(self):
        args = _layer_weights(OLMOE_TINY)
        _, stats = moe_ffn(*args, OLMOE_TINY)
        assert float(stats["counts"].sum()) == 32 * OLMOE_TINY.top_k
        assert stats["routing"].shape == (32, OLMOE_TINY.top_k)
        assert bool((stats["p_kth"] >= stats["p_next"]).all())
        np.testing.assert_allclose(float(stats["prob_sum"].sum()), 32.0, rtol=1e-5)

    def test_load_balancing_loss_is_the_published_one(self):
        """load_balancing_loss_func: layers concatenated, every choice
        counted: E * sum_e mean(one_hot)[k, e] * mean(probs)[e]."""
        rng = np.random.RandomState(0)
        L, T, E, k = 3, 40, 8, 4
        probs = jax.nn.softmax(jnp.asarray(rng.randn(L, T, E), jnp.float32), -1)
        idx = jax.lax.top_k(probs, k)[1]
        mask = jax.nn.one_hot(idx.reshape(L * T, k), E)
        want = E * jnp.sum(jnp.mean(mask, 0) * jnp.mean(probs.reshape(L * T, E), 0)[None])
        counts = jnp.sum(jax.nn.one_hot(idx, E), axis=(1, 2))
        got = load_balancing_loss(counts, jnp.sum(probs, axis=1), T)
        assert float(got) == pytest.approx(float(want), rel=1e-6)
        even = load_balancing_loss(jnp.full((L, E), T * k / E), jnp.full((L, E), T / E), T)
        assert float(even) == pytest.approx(k)


class TestOlmoeModel:
    def test_replaying_its_own_free_routing_is_bitwise_the_free_run(self):
        cfg = OLMOE_TINY
        params = moe_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        logits, aux, stats = moe_forward(params, toks, cfg)
        assert stats["routing"].shape == (cfg.n_layers, 32, cfg.top_k)
        again, aux2, _ = moe_forward(params, toks, cfg, routing=stats["routing"])
        assert bool((logits == again).all()) and float(aux) == float(aux2)
        # another routing is another function
        other = (stats["routing"] + 1) % cfg.num_experts
        assert not bool((moe_forward(params, toks, cfg, routing=other)[0] == logits).all())

    def test_replay_keeps_the_routers_gradient(self):
        cfg = OLMOE_TINY
        params = moe_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        (_, stats), free = jax.value_and_grad(moe_loss_and_stats, has_aux=True)(
            params, toks, toks, cfg)
        replayed = jax.grad(moe_loss)(params, toks, toks, cfg, routing=stats["routing"])
        np.testing.assert_array_equal(np.asarray(free["layers"]["router"]),
                                      np.asarray(replayed["layers"]["router"]))
        assert float(jnp.abs(free["layers"]["router"]).max()) > 0
        assert float(stats["load_max_over_mean"]) >= 1.0
        assert float(stats["aux_loss"]) >= cfg.top_k - 1e-4

    def test_qk_norm_is_over_the_whole_projection(self):
        """RMSNorm ignores a scale of its whole input, not of a part: with
        QK-norm on, scaling all of wq leaves the logits, scaling one head's
        columns moves them (a norm per head would not see it); with it off
        both move them."""
        cfg = dataclasses.replace(OLMOE_TINY, n_layers=1)
        params = moe_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)

        def logits(p, c):
            return moe_forward(p, toks, c, remat="none")[0]

        def scaled(cols):
            wq = params["layers"]["wq"].at[:, :, cols].multiply(3.0)
            return {**params, "layers": {**params["layers"], "wq": wq}}

        base = logits(params, cfg)
        np.testing.assert_allclose(np.asarray(logits(scaled(slice(None)), cfg)),
                                   np.asarray(base), rtol=2e-4, atol=2e-5)
        one_head = logits(scaled(slice(0, cfg.head_dim)), cfg)
        assert float(jnp.abs(one_head - base).max()) > 1e-3
        off = dataclasses.replace(cfg, qk_norm=False)
        assert "q_norm" not in moe_init(jax.random.PRNGKey(0), off)["layers"]
        assert float(jnp.abs(logits(scaled(slice(None)), off) - logits(params, off)).max()) > 1e-3
        assert set(moe_param_specs(cfg)["layers"]) == set(params["layers"])

    def test_dropless_refuses_expert_parallelism(self):
        from torchft_tpu.parallel.mesh import make_hsdp_mesh, shard_params

        cfg = OLMOE_TINY
        mesh = make_hsdp_mesh(dp=1, fsdp=1, ep=2, sp=1, tp=1)
        with pytest.raises(ValueError, match="ep=1"):
            moe_param_specs(cfg, mesh)
        params = shard_params(moe_init(jax.random.PRNGKey(0), cfg), mesh, moe_param_specs(cfg))
        toks = jnp.zeros((2, 16), jnp.int32)
        with pytest.raises(ValueError, match="not sharded over ep"):
            moe_loss(params, toks, toks, cfg)
        assert moe_param_specs(cfg, make_hsdp_mesh(dp=1, fsdp=2, ep=1, sp=1, tp=1))
        assert moe_param_specs(MOE_CONFIGS["debug"], mesh)  # the capacity path may

    def test_the_published_preset_and_the_one_mapping(self):
        from torchft_tpu.models import CONFIGS, model_fns
        from torchft_tpu.models.llama import llama_init
        from torchft_tpu.parallel.mesh import llama_param_specs

        cfg = CONFIGS["olmoe_1b_7b"]
        assert cfg is MOE_CONFIGS["olmoe_1b_7b"] and CONFIGS["moe_debug"] is MOE_CONFIGS["debug"]
        assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.ffn_hidden,
                cfg.num_experts, cfg.top_k, cfg.vocab_size) == (2048, 16, 16, 16, 1024, 64, 8, 50304)
        assert cfg.capacity_factor is None and not cfg.norm_topk_prob and cfg.qk_norm
        assert 6.9e9 < cfg.num_params() < 6.93e9
        init, loss, specs, stages, frozen = model_fns(cfg)
        assert init is moe_init and specs is moe_param_specs and stages
        assert frozen == ()  # every leaf a parameter
        init, dense_loss, specs, stages, frozen = model_fns(CONFIGS["debug"])
        assert init is llama_init and specs is llama_param_specs and stages
        assert frozen == ()
        tiny = CONFIGS["debug"]
        toks = jnp.zeros((1, 8), jnp.int32)
        value, stats = dense_loss(llama_init(jax.random.PRNGKey(0), tiny), toks, toks, tiny)
        assert stats == {} and np.isfinite(float(value))
        value, stats = loss(moe_init(jax.random.PRNGKey(0), OLMOE_TINY), toks, toks, OLMOE_TINY)
        assert sorted(stats["moe_stats"]) == ["moe_aux_loss", "moe_load_max_over_mean"]


# ---- PR 35: one router, the configuration's scoring: sigmoid scores, a
# selection bias that decides and does not gate, the published epsilon

from torchft_tpu.models.moe import _choose, _dropless_ffn  # noqa: E402

SIGMOID_TINY = dataclasses.replace(
    MOE_CONFIGS["debug"], num_experts=8, top_k=4, capacity_factor=None,
    norm_topk_prob=True, router_score="sigmoid", gate_eps=1e-6)
BIASES = {
    "none": None,
    "small": 0.05 * jax.random.normal(jax.random.PRNGKey(5), (8,)),
    "one_expert_always": jnp.zeros((8,)).at[3].set(10.0),
}


def _scores(args, cfg):
    flat = args[0].reshape(-1, args[0].shape[-1])
    logits = flat @ args[1]
    return jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" else \
        jax.nn.softmax(logits, axis=-1)


def _by_definition(args, cfg, idx, gates):
    """Every expert on every token, weighted by ``gates`` at ``idx``."""
    x, _, wg, wu, wd = args
    flat = x.reshape(-1, x.shape[-1])
    weight = jnp.sum(jax.nn.one_hot(idx, cfg.num_experts) * gates[..., None], axis=1)
    every = jnp.einsum("teh,ehd->ted", jax.nn.silu(jnp.einsum("td,edh->teh", flat, wg))
                       * jnp.einsum("td,edh->teh", flat, wu), wd)
    return jnp.sum(every * weight[..., None], axis=1).reshape(x.shape)


class TestSigmoidRouterUnderABias:
    @pytest.mark.parametrize("name", sorted(BIASES))
    @pytest.mark.parametrize("norm", [False, True])
    def test_decisions_follow_scores_plus_bias_and_gates_the_scores(self, name, norm):
        cfg = dataclasses.replace(SIGMOID_TINY, norm_topk_prob=norm)
        args, bias = _layer_weights(cfg), BIASES[name]
        out, stats = moe_ffn(*args, cfg, bias=bias)
        s = _scores(args, cfg)
        decide = s if bias is None else s + bias
        top_p, top_i = jax.lax.top_k(decide, cfg.top_k + 1)
        np.testing.assert_array_equal(np.asarray(stats["routing"]), np.asarray(top_i[:, :4]))
        # the margins are of what decided
        np.testing.assert_array_equal(np.asarray(stats["p_kth"]), np.asarray(top_p[:, 3]))
        np.testing.assert_array_equal(np.asarray(stats["p_next"]), np.asarray(top_p[:, 4]))
        gates = jnp.take_along_axis(s, top_i[:, :4], axis=-1)  # WITHOUT the bias
        if norm:
            gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_by_definition(args, cfg, top_i[:, :4], gates)),
            rtol=1e-5, atol=1e-5)
        if name == "one_expert_always":
            assert bool(jnp.all(jnp.any(stats["routing"] == 3, axis=-1)))
            assert float(stats["bias_moved"]) > 0.3
        assert ("bias_moved" in stats) == (bias is not None)

    @pytest.mark.parametrize("norm", [False, True])
    def test_a_bias_that_swaps_the_fourth_and_fifth_expert_changes_exactly_their_terms(
            self, norm):
        cfg = dataclasses.replace(SIGMOID_TINY, norm_topk_prob=norm)
        x, *w = _layer_weights(cfg)
        args = (x[:1, :1], *w)  # one token: a bias moves no other decision
        s = _scores(args, cfg)[0]
        order = jnp.argsort(-s)
        fourth, fifth = int(order[3]), int(order[4])
        bias = jnp.zeros((8,)).at[fifth].set(float(s[fourth] - s[fifth]) + 1e-3)
        plain, _ = moe_ffn(*args, cfg)
        moved, stats = moe_ffn(*args, cfg, bias=bias)
        assert sorted(np.asarray(stats["routing"][0])) == sorted(
            [int(e) for e in order[:3]] + [fifth])
        assert float(stats["bias_moved"]) == 1.0
        one = lambda e, g: _by_definition(  # noqa: E731
            args, cfg, jnp.asarray([[e]]), jnp.asarray([[g]], jnp.float32))
        if not norm:  # the gates are the raw scores: nothing else changes
            want = plain - one(fourth, float(s[fourth])) + one(fifth, float(s[fifth]))
            np.testing.assert_allclose(np.asarray(moved), np.asarray(want),
                                       rtol=1e-5, atol=1e-6)
        else:  # renormalised over the new four, by the scores and not the bias
            idx = jnp.asarray([[int(e) for e in order[:3]] + [fifth]])
            g = s[idx[0]] / (jnp.sum(s[idx[0]]) + 1e-6)
            np.testing.assert_allclose(
                np.asarray(moved), np.asarray(_by_definition(args, cfg, idx, g[None])),
                rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("name", ["none", "small"])
    def test_replay_under_a_bias_uses_the_given_experts_and_hands_back_the_free_ones(
            self, name):
        args, bias = _layer_weights(SIGMOID_TINY), BIASES[name]
        _, free = moe_ffn(*args, SIGMOID_TINY, bias=bias)
        given = (free["routing"] + 1) % 8  # other experts than it would choose
        out, stats = moe_ffn(*args, SIGMOID_TINY, routing=given, bias=bias)
        np.testing.assert_array_equal(np.asarray(stats["routing"]),
                                      np.asarray(free["routing"]))
        s = _scores(args, SIGMOID_TINY)
        gates = jnp.take_along_axis(s, given, axis=-1)
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-6)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_by_definition(args, SIGMOID_TINY, given, gates)),
            rtol=1e-5, atol=1e-5)
        # the router's gradient flows through the gates of the given experts
        g = jax.grad(lambda r: jnp.sum(moe_ffn(
            args[0], r, *args[2:], SIGMOID_TINY, routing=given, bias=bias)[0]))(args[1])
        assert float(jnp.abs(g).max()) > 0

    def test_the_bias_takes_no_gradient(self):
        args, bias = _layer_weights(SIGMOID_TINY), BIASES["small"]
        g = jax.grad(lambda b: jnp.sum(moe_ffn(*args, SIGMOID_TINY, bias=b)[0]))(bias)
        assert float(jnp.abs(g).max()) == 0.0

    @pytest.mark.parametrize("norm", [False, True])
    def test_the_softmax_unbiased_path_is_bitwise_what_it_was(self, norm):
        """PR 28's ``_choose`` written out (softmax, top-k of the
        probabilities, the 1e-9) in front of the same dropless block: the
        new arguments at their defaults change no bit, and the block
        traces no sigmoid."""
        cfg = dataclasses.replace(OLMOE_TINY, norm_topk_prob=norm)
        assert (cfg.router_score, cfg.gate_eps) == ("softmax", 1e-9)
        x, router, wg, wu, wd = args = _layer_weights(cfg)

        @jax.jit
        def was(x, router, wg, wu, wd):
            flat = x.reshape(-1, x.shape[-1])
            probs = jax.nn.softmax(jnp.matmul(
                flat, router, precision=jax.lax.Precision.HIGHEST), axis=-1)
            top_p, top_i = jax.lax.top_k(probs, cfg.top_k + 1)
            idx = top_i[:, :cfg.top_k]
            gates = jnp.take_along_axis(probs, idx, axis=-1)
            if norm:
                gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
            sizes = jnp.zeros((cfg.num_experts,), jnp.int32).at[idx.reshape(-1)].add(1)
            return _dropless_ffn(flat, gates, idx, sizes, wg, wu, wd).reshape(x.shape)

        now = jax.jit(lambda *a: moe_ffn(*a, cfg)[0])
        np.testing.assert_array_equal(np.asarray(now(*args)), np.asarray(was(*args)))
        # the experts' SiLU is a logistic too: the sigmoid router is one more
        count = lambda c: str(jax.make_jaxpr(  # noqa: E731
            lambda *a: moe_ffn(*a, c)[0])(*args)).count("logistic")
        assert count(dataclasses.replace(cfg, router_score="sigmoid")) == count(cfg) + 1

    def test_chosen_gates_sum_to_one_and_an_unknown_scoring_is_refused(self):
        s = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(2), (5, 8)))
        g1, i1, _ = _choose(s, SIGMOID_TINY, None)
        g2, i2, _ = _choose(s, dataclasses.replace(SIGMOID_TINY, norm_topk_prob=False), None)
        np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
        np.testing.assert_array_equal(
            np.asarray(g2), np.take_along_axis(np.asarray(s), np.asarray(i2), -1))
        np.testing.assert_allclose(np.asarray(jnp.sum(g1, -1)), 1.0, atol=1e-5)
        with pytest.raises(ValueError, match="router_score"):
            dataclasses.replace(SIGMOID_TINY, router_score="tanh")


# ---- PR 39: the combine has a backward pass of its own (the rows' cotangent
# gathered out of the [T, d] cotangent, no [T*k, d] permuted): no bit moves

from torchft_tpu.models import moe as moe_module  # noqa: E402


def combine_as_it_was(rows, weights, inverse, order):
    """What autodiff was given until PR 39: the unsort a ``_take_rows``
    whose cotangent is gathered at ``order``."""
    (T, k), d = weights.shape, rows.shape[-1]
    picked = moe_module._take_rows(rows, inverse, order, 1).reshape(T, k, d)
    return jnp.sum(picked * weights[..., None], axis=1)


@pytest.mark.parametrize("bias", ["none", "small"])
def test_the_block_and_its_gradients_are_bitwise_what_they_were(monkeypatch, bias):
    cfg = OLMOE_TINY if bias == "none" else SIGMOID_TINY
    args = _layer_weights(cfg)
    target = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def run():
        def loss(x, router, wg, wu, wd):
            block = jax.checkpoint(lambda *a: moe_ffn(*a, cfg, bias=BIASES[bias])[0])
            out = block(x, router, wg, wu, wd)
            return jnp.sum(out * target), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(*args)

    (_, out), grads = run()
    monkeypatch.setattr(moe_module, "_combine", combine_as_it_was)
    (_, out_was), grads_was = run()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_was))
    for name, g, w in zip(("x", "router", "w_gate"), grads, grads_was):
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=name)
