"""The fifth kind of the one trainer's model (``models/ling.py``): the KDA
kernels against the recurrence one position after another, what
``moe_ffn`` gained for it (the group limit, the scaling, the shared expert,
a chip's share of the experts) without moving OLMoE's or LFM2's programs by
a bit, the shares of a layer adding up to the layer, each of the faults of
``benchmarks/ling_check_faults.py`` seen by the one mixer or block it is put
into, and ten committed steps under the Manager with a heal that carries the
frozen bias. The program against the plain reference, whole, is
``tests/chipbench/test_reference_ling.py``'s."""

import dataclasses
import hashlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import CONFIGS, model_fns, split_frozen
from torchft_tpu.models import ling as L
from torchft_tpu.models import moe
from torchft_tpu.models.ling import LING_CONFIGS, LingConfig
from torchft_tpu.ops import kda as K

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import reference_ling as reference  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "ling_check_faults", f"{ROOT}/benchmarks/ling_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


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
        q, k, v, _, _ = args
        args = (q, jnp.broadcast_to(k[:, :1], k.shape), v, jnp.zeros_like(args[3]),
                jnp.ones((B, T, H)))
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


# ----------------------------------------------------------------- the share

def _block(cfg, seed=0, T=96):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    d, E, W = cfg.dim, cfg.num_experts, cfg.moe_intermediate_size
    n = lambda k, *shape: jax.random.normal(k, shape) / np.sqrt(shape[-2])  # noqa: E731
    return {"x": jax.random.normal(ks[0], (1, T, d)), "router": n(ks[1], d, E),
            "w_gate": n(ks[2], E, d, W), "w_up": n(ks[3], E, d, W), "w_down": n(ks[4], E, W, d),
            "shared": (n(ks[5], d, W), n(ks[6], d, W), n(ks[7], W, d)),
            "bias": 0.01 * jax.random.normal(ks[8], (E,))}


def test_the_shares_add_up_to_the_uncut_layer():
    """32 toy experts in 4 shares of 8: the four partial results, with the
    shared expert (which every chip computes alike) counted once, are what
    the uncut reference gives for the whole layer, and what the program
    gives when it holds all 32; each share's counts are its experts' among
    the whole layer's, and no pair is computed twice or by nobody."""
    cfg = dataclasses.replace(LING_CONFIGS["ling_debug"], dtype=jnp.float32, num_experts=32,
                              n_group=4, topk_group=2, top_k=4, held_experts=None)
    b = _block(cfg)
    ffn = lambda c, at, shared: moe.moe_ffn(  # noqa: E731
        b["x"], b["router"], b["w_gate"][at], b["w_up"][at], b["w_down"][at], c,
        bias=b["bias"], shared=shared)
    whole, stats = ffn(cfg, slice(None), b["shared"])
    file = {"num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
            "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
            "deployment": {"experts_held": [0, 32]}}
    want, _ = reference._routed(
        b["x"][0], {**{k: b[k] for k in ("router", "w_gate", "w_up", "w_down")},
                    "shared_gate": b["shared"][0], "shared_up": b["shared"][1],
                    "shared_down": b["shared"][2]}, b["bias"], file, jnp.matmul, jnp.matmul)
    assert _rel(whole[0], want) < 2e-6
    parts, held = [], 0
    for first in range(0, 32, 8):
        share = dataclasses.replace(cfg, held_experts=(first, 8), share_room=8.0)
        out, st = ffn(share, slice(first, first + 8), None)
        parts.append(out)
        np.testing.assert_array_equal(st["counts"], stats["counts"][first:first + 8])
        assert int(st["overflow"]) == 0
        held += int(st["held_pairs"])
    assert held == 96 * 4
    once = ffn(dataclasses.replace(cfg, held_experts=(0, 8)), slice(0, 8), b["shared"])[0] \
        - parts[0]  # the shared expert alone
    assert _rel(sum(parts) + once, whole) < 2e-6


def test_a_shares_buffer_holds_its_room_and_counts_what_it_cannot():
    cfg = dataclasses.replace(LING_CONFIGS["ling_debug"], dtype=jnp.float32)
    assert cfg.share_rows(64) == 64 * 4  # room 4 of a quarter: every pair
    published = LING_CONFIGS["ling_3_0_flash_share"]
    assert published.share_rows(32768) == 6 * 16384 and published.n_held == 32
    assert dataclasses.replace(published, held_experts=(0, 16)).share_rows(32768) == 49152
    assert dataclasses.replace(published, share_room=1.5).share_rows(32768) == 24576
    b = _block(cfg)
    tight = dataclasses.replace(cfg, share_room=0.5)
    at = slice(4, 8)
    out, st = moe.moe_ffn(b["x"], b["router"], b["w_gate"][at], b["w_up"][at],
                          b["w_down"][at], tight, bias=b["bias"])
    rows = tight.share_rows(96)
    assert rows < int(st["held_pairs"]) and int(st["overflow"]) == int(st["held_pairs"]) - rows
    assert bool(jnp.all(jnp.isfinite(out)))
    with pytest.raises(ValueError, match="held_experts"):
        dataclasses.replace(cfg, held_experts=(12, 8))
    with pytest.raises(ValueError, match="dropless"):
        moe._refuse_dropless_ep(cfg, ("ep",))


PARENT = {  # examples of the two kinds that share moe_ffn, at the parent commit:
    # sha256 of the lowered value-and-grad program, the loss's bits
    "olmoe_like": ("8f3d99d1a380ce09", "0x1.8c5e1e0000000p+2"),
    "moe_debug": ("5edda971e37627ff", "0x1.91db320000000p+2"),
    "lfm2_debug": ("0cef71e6b94158fc", "0x1.5d5b340000000p+2"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_other_kinds_programs_and_losses_are_the_parents(name):
    """What ``moe_ffn`` gained is absent unless a configuration asks for
    it: OLMoE's block (dropless, softmax, no renormalisation, QK norm), the
    capacity path and LFM2's decoder lower to the very programs they lowered
    to at the parent of PR 40, and give its losses to the bit."""
    cfg = (dataclasses.replace(moe.MOE_CONFIGS["debug"], capacity_factor=None,
                               norm_topk_prob=False, qk_norm=True, num_experts=8, top_k=4)
           if name == "olmoe_like" else CONFIGS[name])
    m = model_fns(cfg)
    p = m.init(jax.random.PRNGKey(7), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(8), (2, 32), 0, cfg.vocab_size)
    f = jax.jit(jax.value_and_grad(lambda p: m.loss(p, tok, tok, cfg)[0]))
    digest = hashlib.sha256(f.lower(p).as_text().encode()).hexdigest()[:16]
    assert (digest, float(f(p)[0]).hex()) == PARENT[name]


# ------------------------------------------------------------------ the kind

def test_presets_stand_in_the_registry_and_model_fns_knows_the_kind():
    cfg = CONFIGS["ling_debug"]
    assert isinstance(cfg, LingConfig) and "ling_3_0_flash_share" in CONFIGS
    m = model_fns(cfg)
    assert m.frozen == L.LING_FROZEN == ("expert_bias",) and m.stages is None
    params = m.init(jax.random.PRNGKey(0), cfg)
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == cfg.num_params()
    specs = m.param_specs(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, specs,
                               is_leaf=lambda x: not isinstance(x, dict)))
    assert [r[0] for r in cfg.runs()] == ["00_kda_dense", "01_kda_moe", "02_mla_moe",
                                          "03_kda_moe"]
    f32 = {k for k, v in jax.tree_util.tree_leaves_with_path(params)
           if v.dtype == jnp.float32}
    assert {jax.tree_util.keystr(k[-1:]) for k in f32} == {
        "['router']", "['A_log']", "['dt_bias']", "['expert_bias']"}
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    (value, stats), grads = jax.value_and_grad(
        lambda p: m.loss({**p, **split_frozen(params, m.frozen)[1]}, tok, tok, cfg),
        has_aux=True)(split_frozen(params, m.frozen)[0])
    assert 5.0 < float(value) < 7.0 and "expert_bias" not in grads
    assert sorted(stats["moe_stats"]) == [
        "moe_bias_moved_share", "moe_groups_hit_mean", "moe_held_pair_share",
        "moe_load_max_over_mean", "moe_overflow_pairs"]
    s = {k: float(v) for k, v in stats["moe_stats"].items()}
    assert s["moe_overflow_pairs"] == 0 and s["moe_groups_hit_mean"] <= 2
    assert 0.05 < s["moe_held_pair_share"] < 0.6 and 0 < s["moe_bias_moved_share"] < 1
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))


def test_the_published_cut_counts_what_the_issue_counted():
    cfg = LING_CONFIGS["ling_3_0_flash_share"]
    assert cfg.kinds() == [("kda", "dense")] + [("kda", "moe")] * 3 + [("mla", "moe")] \
        + [("kda", "moe")] * 2
    assert cfg.num_params() == 1_671_382_976  # 32 held: ISSUE 40's 1.67B
    assert dataclasses.replace(cfg, held_experts=(0, 16)).num_params() == 1_105_151_936
    for change, match in (({"layer_types": ("kda",) * 6 + ("window",)}, "layer_types"),
                          ({"capacity_factor": 1.25, "held_experts": None}, "capacity_factor"),
                          ({"kda_lower_bound": -8.0}, "kda_lower_bound"),
                          ({"topk_group": 9}, "topk_group")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(cfg, **change)


def test_remat_loss_chunk_and_replay_work_as_for_the_other_kinds():
    cfg = dataclasses.replace(CONFIGS["ling_debug"], dtype=jnp.float32)
    params = L.ling_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 256)
    base, stats = L.ling_loss_and_stats(params, tok, tok, cfg)
    for kw in ({"remat": "none"}, {"loss_chunk": 16}, {"routing": stats["routing"]}):
        assert abs(float(L.ling_loss(params, tok, tok, cfg, **kw)) - float(base)) < 2e-6, kw
    assert stats["routing"].shape == (3, 64, 4) and stats["p_kth"].shape == (3, 64)
    # a token's output is unchanged by later tokens: every mixer is causal
    full = L.ling_forward(params, tok, cfg)
    np.testing.assert_allclose(np.asarray(full)[:, :40],
                               np.asarray(L.ling_forward(params, tok[:, :40], cfg)),
                               rtol=2e-4, atol=2e-5)


# ------------------------------------------- the faults, each where it is put

def _mixer_inputs(cfg, name, seed=0):
    params = L.ling_init(jax.random.PRNGKey(seed), cfg)
    w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
    return jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 96, cfg.dim)), w


FAULT_SEEN_IN = {
    "no_decay": ("kda", 0.05), "beta_one": ("kda", 0.05), "lost_tap": ("kda", 0.05),
    "bf16_state": ("kda", 1e-4), "bf16_kda": ("kda", 1e-4),
    "no_rope": ("mla", 0.02), "no_latent_norm": ("mla", 0.05),
    "no_group_limit": ("moe", 0.05), "no_shared": ("moe", 0.05), "scaling_one": ("moe", 0.05),
    "fp8_experts": ("moe", 0.01),
}


@pytest.mark.parametrize("name", sorted(FAULT_SEEN_IN))
def test_each_fault_moves_the_one_part_it_is_put_into(name):
    """The program's mixer or block in float32 is the reference's to
    rounding; with the fault of ``benchmarks/ling_check_faults.py`` in, it
    is off by at least the share stated. (Whether the cell's CHECK refuses
    the fault is tests/chipbench/test_reference_ling.py's, for four of them,
    and the chip's for all.)"""
    cfg = dataclasses.replace(CONFIGS["ling_debug"], dtype=jnp.float32, share_room=8.0)
    part, least = FAULT_SEEN_IN[name]
    file = {"head_dim": 16, "kda_lower_bound": -5.0, "rms_norm_eps": cfg.norm_eps,
            "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
            "v_head_dim": 16, "kv_lora_rank": 32, "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
            "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
            "deployment": {"experts_held": [4, 4]}}
    if part == "kda":
        u, w = _mixer_inputs(cfg, "01_kda_moe")
        run = lambda: L._kda_mixer(u, w, cfg)  # noqa: E731
        want = reference._kda(u, w, file, jnp.matmul)
    elif part == "mla":
        u, w = _mixer_inputs(cfg, "02_mla_moe")
        run = lambda: L._mla_mixer(u, w, cfg, L._attention)  # noqa: E731
        want = reference._mla(u, w, file, jnp.matmul)
    else:
        u, w = _mixer_inputs(cfg, "03_kda_moe")
        bias = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (16,))
        run = lambda: L.moe_ffn(  # noqa: E731
            u, w["router"], w["w_gate"], w["w_up"], w["w_down"], cfg, bias=bias,
            shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))[0]
        want = reference._routed(u[0], w, bias, file, jnp.matmul, jnp.matmul)[0][None]
    jax.clear_caches()
    assert _rel(run(), want) < 2e-5
    jax.clear_caches()
    with faults.fault(name, cfg):
        off = _rel(run(), want)
    jax.clear_caches()
    assert off > least, (name, off)


# ------------------------------------------------------------- under the Manager

def test_ten_committed_steps_under_the_manager_with_a_heal_that_carries_the_bias(tmp_path):
    """``ling_debug`` through the launcher, the lighthouse, the Manager and
    the one trainer, two groups: ten committed steps each and none
    discarded, the loss falls, group 1 heals from group 0 in step 1 and ends
    with group 0's ``expert_bias`` bitwise (its own seed's is another) and
    with bitwise-equal parameters; the new counters ride the SUMMARY line."""
    from test_trainer_model_kinds import _checksum, _train

    a, b = sorted(_train("ling_debug", tmp_path, "--steps", "10", groups=2),
                  key=lambda s: s["replica"])
    for s in (a, b):
        assert s["config"] == "ling_debug" and s["committed"] == 10 and s["discarded"] == 0, s
        assert sorted(s["model_stats"]) == [
            "moe_bias_moved_share", "moe_groups_hit_mean", "moe_held_pair_share",
            "moe_load_max_over_mean", "moe_overflow_pairs"]
        assert all(v == 0 for v in s["model_stats"]["moe_overflow_pairs"])
        assert all(v <= 2 for v in s["model_stats"]["moe_groups_hit_mean"])
        assert all(5.0 < x < 7.0 for x in s["losses"])
    assert a["losses"][-1] < a["losses"][0]
    assert b["healed"] >= 1 and a["healed"] == 0
    source, own = (_checksum(L.ling_init(jax.random.PRNGKey(r), CONFIGS["ling_debug"])[
        "expert_bias"]) for r in (0, 1))
    assert source != own
    assert a["frozen_checksum"] == b["frozen_checksum"] == source
    assert a["param_checksum"] == b["param_checksum"]
