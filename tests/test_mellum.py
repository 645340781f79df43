"""The sixth kind of the one trainer's model (``models/mellum.py``): the
causal window of ``ops/attention.py`` held to ``i - j < window`` exactly on
the XLA path and in the splash kernel, YaRN's table against its equations at
the published numbers and each kind of layer given its own table, the four
shares of a layer adding up to the layer, the share's overflow counted, the
other kinds' programs unmoved by a bit, and ten committed steps under the
Manager with a heal. The program against the plain reference, whole and under
each fault, is ``tests/chipbench/test_reference_mellum.py``'s."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import CONFIGS, model_fns
from torchft_tpu.models import mellum as M
from torchft_tpu.models.mellum import MELLUM_CONFIGS, MellumConfig
from torchft_tpu.ops import attention as A

PUBLISHED = MELLUM_CONFIGS["mellum2_12b_a2_5b_share"]


def _qkv(S, H=4, K=2, d=16, seed=0, B=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, S, H, d)), jax.random.normal(ks[1], (B, S, K, d)),
            jax.random.normal(ks[2], (B, S, K, d)))


# --------------------------------------------------------------- the window

@pytest.mark.parametrize("window", [7, 8, 9, 1, 64, 100])
def test_a_window_of_w_sees_exactly_the_last_w_keys(window):
    """Sequence 64 on the XLA path: query ``i``'s output moves with value
    ``j`` where ``j <= i and i - j < window`` and with no other (the
    Jacobian's support, exactly), and is the softmax over those keys."""
    q, k, v = _qkv(64)
    out = A.xla_attention(q, k, v, None, window=window)
    moved = jax.jacobian(lambda v: A.xla_attention(q, k, v, None, window=window)[0, :, 0, 0])(
        v)[:, 0, :, 0, 0]  # [query, key]
    i, j = np.arange(64)[:, None], np.arange(64)[None, :]
    want = (j <= i) & (i - j < window)
    np.testing.assert_array_equal(np.asarray(moved) != 0, want)
    scores = jnp.einsum("qd,kd->qk", q[0, :, 0], k[0, :, 0]) / 4.0
    probs = jax.nn.softmax(jnp.where(want, scores, -jnp.inf), axis=-1)
    np.testing.assert_allclose(np.asarray(out[0, :, 0]), np.asarray(probs @ v[0, :, 0]),
                               rtol=2e-5, atol=2e-6)
    if window >= 64:  # every earlier key: causal attention itself
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(A.xla_attention(q, k, v, None)))


def test_a_window_one_wider_or_narrower_is_another_result():
    q, k, v = _qkv(64)
    at = lambda w: np.asarray(A.xla_attention(q, k, v, None, window=w))  # noqa: E731
    for other in (7, 9):
        assert np.abs(at(other) - at(8)).max() > 1e-2
        # the first ``min(w)`` queries see the same keys under both
        np.testing.assert_array_equal(at(other)[:, :7], at(8)[:, :7])


@pytest.mark.parametrize("window,seq", [(8, 256), (100, 256), (128, 256), (300, 384),
                                        (None, 256)])
def test_the_splash_kernel_and_the_xla_path_agree_under_a_window(window, seq):
    """The interpreted kernel (built with a local mask whose out-of-window
    blocks it never visits) against the materialised mask, forward and
    backward, at windows inside a tile, a tile wide and wider."""
    q, k, v = _qkv(seq, d=64, seed=3)

    def run(f, **kw):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v, None, window=window, **kw))),
            argnums=(0, 1, 2))(q, k, v)

    (a, da), (b, db) = run(A.xla_attention), run(A.splash_attention_tpu, interpret=True)
    assert abs(float(a) - float(b)) < 1e-3
    for x, y in zip(da, db):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-4, atol=2e-5)


def test_the_kernel_skips_what_lies_outside_the_window(monkeypatch):
    """What the kernel is built to visit, known when it is built: at the
    cell's 32,768 a 1,024 window keeps under a tenth of a causal mask's
    key/value tiles, no window keeps them all, and the dispatcher hands a
    window to splash whatever the heads, never to flash."""
    assert A.window_block_share(32768, None) == 1.0
    share = A.window_block_share(32768, 1024)
    assert A._splash_tile(32768, 1024) == A.WINDOW_TILE == 512
    assert A._splash_tile(32768, None) == 1024 and A._splash_tile(256, 1024) == 256
    visited = A._visited(32768, 512, 1024)
    assert visited == (3 * 64 - 3) * 512 * 512  # three tiles a row of tiles, less the corner
    assert A._visited(32768, 1024, None) == (32 * 33 // 2) * 1024 * 1024
    assert share == (3 * 64 - 3) / (528 * 4) and share < 0.1
    # every entry the mask allows lies in a visited tile: never fewer than the pairs
    assert visited >= 1024 * 1025 // 2 + (32768 - 1024) * 1024
    assert A.window_block_share(2048, 1024) > 0.5  # at 2k a window hardly skips
    # the kernel's own mask is the (i, j) relation
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    local = sm.LocalMask((64, 64), window_size=(8 - 1, 0), offset=0)
    i, j = np.arange(64)[:, None], np.arange(64)[None, :]
    np.testing.assert_array_equal(local[:, :], (j <= i) & (i - j < 8))
    seen = {}
    monkeypatch.setattr(A, "_on_tpu", lambda: True)
    monkeypatch.setattr(A, "splash_attention_tpu",
                        lambda q, k, v, cfg, window=None: seen.setdefault("window", window))
    q, k, v = _qkv(128, H=2, K=2, d=64)  # multi-head: flash's without a window
    assert A.causal_attention(q, k, v, None, window=8) == 8 and A.LAST_DISPATCH == "splash"
    monkeypatch.setenv("TORCHFT_TPU_ATTENTION", "flash")
    with pytest.raises(ValueError, match="no window mask"):
        A.causal_attention(q, k, v, None, window=8)
    with pytest.raises(ValueError, match="window=8"):
        A.causal_attention(*_qkv(100), None, window=8)


# ---------------------------------------------------------- the rotary tables

def test_yarn_is_the_equations_at_the_published_numbers():
    """``low`` 18, ``high`` 35, factor 16, attention factor 0.1 ln 16 + 1."""
    inv = np.asarray(M.yarn_inv_freq(PUBLISHED), np.float64)
    plain = 500000.0 ** (-np.arange(64) / 64)
    pair = lambda r: 128 * np.log(8192 / (2 * np.pi * r)) / (2 * np.log(500000.0))  # noqa: E731
    low, high = int(np.floor(pair(32))), int(np.ceil(pair(1)))
    assert (low, high) == (18, 35)
    ramp = np.clip((np.arange(64) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, (1 - ramp) * plain + ramp * plain / 16, rtol=2e-6)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=2e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=2e-6)
    assert PUBLISHED.yarn_attention_factor == 1.2772588722239782 == 0.1 * np.log(16) + 1
    tables = M.rope_tables(PUBLISHED, 4096)
    at = np.arange(4096)[:, None]
    np.testing.assert_allclose(np.asarray(tables["window"][0]), np.cos(at * plain),
                               atol=2e-3)  # float32 angles up to 4,095 radians
    np.testing.assert_allclose(np.asarray(tables["full"][1])[:64],
                               1.2772588722239782 * np.sin(at[:64] * inv), atol=2e-5)
    # position 0: cos is 1, times the factor on the full layers alone
    assert float(tables["window"][0][0, 0]) == 1.0
    assert float(tables["full"][0][0, 0]) == pytest.approx(1.2772588722239782, rel=1e-6)


def test_window_layers_get_the_plain_table_and_full_layers_yarns(monkeypatch):
    """Every layer's mixer is handed its kind's table and its kind's mask,
    and the tables are made once a step."""
    cfg = dataclasses.replace(CONFIGS["mellum_debug"], dtype=jnp.float32)
    params = M.mellum_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, 256)
    mixer, tables, made, seen = M._mixer, M.rope_tables, {}, []

    def spy(u, w, cfg, kind, table, attention):
        def masked(q, k, v, cfg, window=None):
            seen.append((kind, made[id(table)], window))
            return attention(q, k, v, cfg, window=window)

        return mixer(u, w, cfg, kind, table, masked)

    def once(*a):  # each table by its cos at position 0: 1, times the factor
        assert not made
        got = tables(*a)
        made.update({id(t): float(t[0][0, 0]) for t in got.values()})
        return got

    monkeypatch.setattr(M, "_mixer", spy)
    monkeypatch.setattr(M, "rope_tables", once)
    M.mellum_hidden(params, tok, cfg, remat="none")
    scale = pytest.approx(cfg.yarn_attention_factor, rel=1e-6)
    assert seen == [("window", 1.0, 8), ("full", scale, None)] * 2


# ------------------------------------------------------------------ the share

def test_the_shares_add_up_to_the_uncut_layer():
    """A layer of 16 experts in 4 shares of 4, the softmax router deciding
    over all 16 with renormalised gates in each: the four layers' outputs,
    with attention and the residual (which every chip computes alike)
    counted once, are what the layer gives when it holds all 16; each
    share's counts are its experts' among the whole's."""
    whole = dataclasses.replace(CONFIGS["mellum_debug"], dtype=jnp.float32,
                                held_experts=None, n_layers=1, layer_types=("window",))
    w = jax.tree_util.tree_map(lambda x: x[0], M.mellum_init(
        jax.random.PRNGKey(2), whole)["layers"]["00_window"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 48, whole.dim))
    table = M.rope_tables(whole, 48)["window"]
    out, stats = M._layer_body(whole, "window", table, M._attention)(h, (w, None, None))
    # what every chip computes alike: the residual stream after attention
    no_experts = {**w, "w_down": jnp.zeros_like(w["w_down"])}
    once = M._layer_body(whole, "window", table, M._attention)(h, (no_experts, None, None))[0]
    parts, counts = [], []
    for first in range(0, 16, 4):
        share = dataclasses.replace(whole, held_experts=(first, 4), share_room=4.0)
        held = {**w, **{k: w[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}
        got, st = M._layer_body(share, "window", table, M._attention)(h, (held, None, None))
        assert int(st["overflow"]) == 0
        np.testing.assert_array_equal(np.asarray(st["routing"]), np.asarray(stats["routing"]))
        parts.append(got - once)
        counts.append(np.asarray(st["counts"]))
    np.testing.assert_allclose(np.asarray(once + sum(parts)), np.asarray(out),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(np.concatenate(counts), np.asarray(stats["counts"]))
    assert float(jnp.abs(out - once).max()) > 0.1  # the experts' part is no rounding


def test_overflow_pairs_counts_what_a_too_small_room_drops():
    cfg = dataclasses.replace(CONFIGS["mellum_debug"], dtype=jnp.float32)
    params = M.mellum_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 256)
    _, stats = M.mellum_loss_and_stats(params, tok, tok, cfg)
    assert float(stats["overflow_pairs"]) == 0
    tight = dataclasses.replace(cfg, share_room=0.5)
    rows = tight.share_rows(96)
    held = np.asarray(M.mellum_hidden(params, tok, tight)[1]["held_pairs"])
    assert (held > rows).any()  # a toy batch swings past half the even share
    loss, stats = M.mellum_loss_and_stats(params, tok, tok, tight)
    assert float(stats["overflow_pairs"]) == np.maximum(held - rows, 0).sum()
    assert np.isfinite(float(loss))
    assert PUBLISHED.share_rows(32768) == 2 * 65536 and PUBLISHED.n_held == 16


# ------------------------------------------------------------------ the kind

def test_presets_stand_in_the_registry_and_model_fns_knows_the_kind():
    cfg = CONFIGS["mellum_debug"]
    assert isinstance(cfg, MellumConfig) and "mellum2_12b_a2_5b_share" in CONFIGS
    assert cfg.head_dim == 16 != cfg.dim // cfg.n_heads
    m = model_fns(cfg)
    assert m.frozen == () and m.stages is None
    params = m.init(jax.random.PRNGKey(0), cfg)
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == cfg.num_params()
    specs = m.param_specs(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, specs,
                               is_leaf=lambda x: not isinstance(x, dict)))
    assert [r[0] for r in cfg.runs()] == ["00_window", "01_full", "02_window", "03_full"]
    assert [r[::2] for r in PUBLISHED.runs()] == [
        ("00_window", 3), ("01_full", 1), ("02_window", 3), ("03_full", 1)]
    f32 = {jax.tree_util.keystr(k[-1:]) for k, v in jax.tree_util.tree_leaves_with_path(params)
           if v.dtype == jnp.float32}
    assert f32 == {"['router']"}
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    (value, stats), grads = jax.value_and_grad(
        lambda p: m.loss(p, tok, tok, cfg), has_aux=True)(params)
    assert 5.0 < float(value) < 7.0
    assert sorted(stats) == ["attn_stats", "moe_stats"]
    assert sorted(stats["moe_stats"]) == [
        "moe_held_pair_share", "moe_load_max_over_mean", "moe_moved_row_share",
        "moe_overflow_pairs", "moe_visited_row_share"]
    s = {k: float(v) for part in stats.values() for k, v in part.items()}
    assert s["moe_overflow_pairs"] == 0 and 0.05 < s["moe_held_pair_share"] < 0.6
    assert 0 < s["moe_visited_row_share"] < 1  # the products leave the free rows out
    assert (s["attn_window_layers"], s["attn_full_layers"]) == (2, 2)
    assert s["attn_window_block_share"] == 1.0  # 64 positions are one tile
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))


def test_the_published_cut_counts_what_the_issue_counted():
    assert PUBLISHED.num_params() == 1_077_059_840
    assert dataclasses.replace(
        PUBLISHED, n_layers=4, layer_types=PUBLISHED.layer_types[:4]).num_params() == 595_154_176
    assert (PUBLISHED.head_dim, PUBLISHED.n_heads * PUBLISHED.head_dim) == (128, 4096)
    for change, match in (({"layer_types": ("window",) * 7 + ("chunked",)}, "layer_types"),
                          ({"layer_types": ("window",) * 7}, "layer_types"),
                          ({"capacity_factor": 1.25, "held_experts": None}, "capacity_factor"),
                          ({"aux_loss_weight": 0.01}, "aux_loss_weight"),
                          ({"window": 0}, "window")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(PUBLISHED, **change)


def test_remat_loss_chunk_and_replay_work_as_for_the_other_kinds():
    cfg = dataclasses.replace(CONFIGS["mellum_debug"], dtype=jnp.float32)
    params = M.mellum_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 256)
    base, stats = M.mellum_loss_and_stats(params, tok, tok, cfg)
    for kw in ({"remat": "none"}, {"loss_chunk": 16}, {"routing": stats["routing"]}):
        assert abs(float(M.mellum_loss(params, tok, tok, cfg, **kw)) - float(base)) < 2e-6, kw
    assert stats["routing"].shape == (4, 64, 4) and stats["p_kth"].shape == (4, 64)
    # a token's output is unchanged by later tokens: both masks are causal
    full = M.mellum_forward(params, tok, cfg)
    np.testing.assert_allclose(np.asarray(full)[:, :40],
                               np.asarray(M.mellum_forward(params, tok[:, :40], cfg)),
                               rtol=2e-4, atol=2e-5)


PARENT = {  # sha256 of the lowered value-and-grad program at the parent of PR 43
    "debug": "b960470496fcaaa9",  # the dense decoder (mistral-7b, internlm2-1.8b)
    "jamba_debug": "f85418fbe0152758",
    # pinned anew by PR 51 (it holds a share), by PR 53 (one short convolution
    # for every kind) and by PR 55 (the share's gathers are loops over row
    # tiles) and by PR 58 (``kda_bwd``'s body: a chunk's two halves once each,
    # the inverse's pullback in closed form) and by PR 60 (the share's two
    # adds into ``[T, d]`` in token order, ``moe._add_in_token_order``) and by
    # PR 65 (``kda_fwd``'s body: a block's four state-free halves as one
    # batch) and by PR 66 (the KDA mixer's element-wise passes through
    # ``ops/kda_passes.py``'s entries: the same operations in another order):
    # tests/test_ling.py's table says what moved
    "ling_debug": "e6aaa4cc9693283d",
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_other_kinds_programs_are_the_parents(name):
    """The window is absent unless a layer asks for it: the dense decoder,
    the Mamba hybrid and Ling's MLA layer lower to the very programs they
    lowered to at the parent of PR 43 (OLMoE's, the capacity path's and
    LFM2's are pinned in tests/test_ling.py, and hold)."""
    cfg = CONFIGS[name]
    m = model_fns(cfg)
    p = m.init(jax.random.PRNGKey(7), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(8), (2, 32), 0, cfg.vocab_size)
    f = jax.jit(jax.value_and_grad(lambda p: m.loss(p, tok, tok, cfg)[0]))
    assert hashlib.sha256(f.lower(p).as_text().encode()).hexdigest()[:16] == PARENT[name]


# ------------------------------------------------------------- under the Manager

def test_ten_committed_steps_under_the_manager_with_a_heal(tmp_path):
    """``mellum_debug`` through the launcher, the lighthouse, the Manager
    and the one trainer, two groups: ten committed steps each and none
    discarded, every loss near ln 256, group 1 heals from group 0 in step 1 and ends
    with bitwise-equal parameters; the counters ride the SUMMARY line."""
    from test_trainer_model_kinds import _train

    a, b = sorted(_train("mellum_debug", tmp_path, "--steps", "10", groups=2),
                  key=lambda s: s["replica"])
    for s in (a, b):
        assert s["config"] == "mellum_debug" and s["committed"] == 10 and s["discarded"] == 0, s
        assert sorted(s["model_stats"]) == [
            "attn_full_layers", "attn_window_block_share", "attn_window_layers",
            "moe_held_pair_share", "moe_load_max_over_mean", "moe_moved_row_share",
            "moe_overflow_pairs", "moe_visited_row_share"]
        assert all(v == 0 for v in s["model_stats"]["moe_overflow_pairs"])
        assert all(0 < v < 1 for v in s["model_stats"]["moe_visited_row_share"])
        assert s["model_stats"]["attn_window_layers"] == [2.0] * 10
        assert all(5.0 < x < 7.0 for x in s["losses"])
        assert s["frozen_checksum"] is None
    assert b["healed"] >= 1 and a["healed"] == 0
    assert a["param_checksum"] == b["param_checksum"]
