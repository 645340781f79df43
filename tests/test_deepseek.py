"""The eleventh kind of the one trainer's model (``models/deepseek.py``):
latent attention with low-rank queries under YaRN in every layer, as a share
of its heads; a dense layer, then group-limited softmax experts (a group
scored by its best) beside the shared ones, as a share of the experts; the
sequence-wise balance loss. The kind through ``model_fns`` against the plain
reference on seeded weights (the loss with the balance term, the gradient of
every leaf), THE TWO ADD-UP TESTS THAT TIE THE SHARE TO THE MODEL (all the
head shares' attention outputs add up to the uncut reference's attention,
all the expert shares' routed parts plus the shared experts once to its
expert block), the group rule, YaRN's table and the softmax factor by hand,
Ling's mixer through the shared function, the registry, the published cut's
count and the trainer's ``--config``. The cell's check is
``tests/chipbench/test_reference_deepseek.py``'s, the other kinds' lowered
programs ``tests/test_ling.py``'s pins."""

import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import reference_deepseek as reference  # noqa: E402
from torchft_tpu.models import CONFIGS, kinds, llama, mla, model_fns, moe  # noqa: E402
from torchft_tpu.models import deepseek as M  # noqa: E402
from torchft_tpu.models.deepseek import DeepseekConfig  # noqa: E402

DEBUG = dataclasses.replace(CONFIGS["deepseek_debug"], dtype=jnp.float32)
SEQ = 80  # beyond ``yarn_original_max`` (32); no whole number of blocks


def _file_of(cfg: DeepseekConfig, **changed) -> dict:
    """The configuration object as the keys the reference reads."""
    first, held = cfg.held_experts or (0, cfg.num_experts)
    return {"num_hidden_layers": cfg.n_layers, "first_k_dense_replace": cfg.num_dense_layers,
            "num_attention_heads": cfg.n_held_heads, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
            "kv_lora_rank": cfg.kv_lora_rank, "num_experts_per_tok": cfg.top_k,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "topk_method": cfg.topk_method, "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling, "aux_loss_alpha": cfg.aux_loss_weight,
            "rope_scaling": {"factor": cfg.yarn_factor, "beta_fast": cfg.yarn_beta_fast,
                             "beta_slow": cfg.yarn_beta_slow, "mscale": cfg.yarn_mscale,
                             "mscale_all_dim": cfg.yarn_mscale_all_dim,
                             "original_max_position_embeddings": cfg.yarn_original_max},
            "deployment": {"experts_held": [first, held], "router_outputs": cfg.num_experts},
            **changed}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _flat(tree):
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def both():
    """``deepseek_debug`` in float32 (a dense layer and two expert layers, 2
    of 8 heads, 4 of 16 experts in 4 groups) and the reference's loss
    differentiated as it stands, on the same seeded weights."""
    m = model_fns(DEBUG)
    params = m.init(jax.random.PRNGKey(0), DEBUG)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, DEBUG.vocab_size)
    (a, stats), ga = jax.jit(jax.value_and_grad(
        lambda p: m.loss(p, tok, tok, DEBUG), has_aux=True))(params)
    with jax.default_matmul_precision("highest"):
        b, gb = jax.jit(jax.value_and_grad(
            lambda p: reference.loss_of(p, tok, _file_of(DEBUG))))(params)
        logits, routing = jax.jit(lambda p: reference.forward(p, tok, _file_of(DEBUG)))(params)
    return {"a": float(a), "b": float(b), "ga": _flat(ga), "gb": _flat(gb), "stats": stats,
            "params": params, "tok": tok, "logits": logits, "routing": routing}


def test_the_forward_pass_is_the_plain_references(both):
    got = jax.jit(lambda p: M.deepseek_forward(p, both["tok"], DEBUG))(both["params"])
    assert got.shape == (2, SEQ, DEBUG.vocab_size) and got.dtype == jnp.float32
    assert _rel(got, both["logits"]) < 2e-5


def test_the_loss_with_the_balance_term_is_the_plain_references(both):
    """Cross-entropy plus ``aux_loss_weight`` x the expert layers' terms;
    the term is logged unweighted and is no rounding of the loss."""
    assert abs(both["a"] - both["b"]) < 3e-6
    aux = float(both["stats"]["moe_stats"]["moe_aux_loss"])
    assert abs(aux - float(jnp.sum(both["routing"]["balance"]))) < 1e-5
    assert 1.5 < aux < 4.0  # two layers near 1 each: an even load gives 1
    plain = float(reference.loss(both["logits"], both["tok"]))
    assert abs(both["a"] - plain - DEBUG.aux_loss_weight * aux) < 3e-6
    assert DEBUG.aux_loss_weight * aux > 1e-3


MIXER = ["norm", "w_dq", "q_norm", "w_uq", "w_kva", "kv_norm", "w_kvb", "wo", "ffn_norm"]
DENSE = MIXER + ["w_gate", "w_up", "w_down"]
MOE = DENSE + ["router", "shared_gate", "shared_up", "shared_down"]


@pytest.mark.parametrize("leaf", ["embed", "final_norm", "lm_head"]
                         + [f"00_dense.{n}" for n in DENSE]
                         + [f"{run}.{n}" for run in ("01_moe", "02_moe") for n in MOE])
def test_every_leafs_gradient_is_the_plain_references(both, leaf):
    ga, gb = both["ga"], both["gb"]
    key = ("['layers']['%s']['%s']" % tuple(leaf.split(".")) if "." in leaf else f"['{leaf}']")
    assert sorted(ga) == sorted(gb) and len(ga) == 3 + len(DENSE) + 2 * len(MOE)
    assert _rel(ga[key], gb[key]) < 5e-5, leaf


def test_the_counters_ride_the_loss_under_the_names_a_trainer_logs(both):
    assert sorted(both["stats"]) == ["moe_stats"]
    got = {k: float(v) for k, v in both["stats"]["moe_stats"].items()}
    assert sorted(got) == ["moe_aux_loss", "moe_groups_hit_mean", "moe_held_pair_share",
                           "moe_load_max_over_mean", "moe_moved_row_share",
                           "moe_overflow_pairs", "moe_visited_row_share"]
    assert got["moe_overflow_pairs"] == 0 and 1.0 <= got["moe_groups_hit_mean"] <= 2.0
    assert 0.05 < got["moe_held_pair_share"] < 0.6  # evenly 4 / 16


# ---- the share tied to the model (the model-configs guide, section 4)

UNCUT = dataclasses.replace(DEBUG, held_heads=None, held_experts=None)


@pytest.fixture(scope="module")
def uncut():
    """The uncut layer at the small size: all 8 heads, all 16 experts; the
    first expert layer's weights (float32) and a normalised input."""
    params = M.deepseek_init(jax.random.PRNGKey(3), UNCUT)
    w = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["01_moe"])
    u = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, UNCUT.dim))
    return w, u


def test_the_head_shares_attention_adds_up_to_the_uncut_references(uncut):
    """Four chips hold two of the eight heads each: their columns of
    ``w_uq`` and ``w_kvb``, their rows of ``wo``, both latents' projections
    and norms whole (computed alike by all, counted once: they are inputs of
    every share and no share's output). The program's mixer on each share
    adds up to the uncut reference's attention for the layer."""
    w, u = uncut
    dn, dr, dv = UNCUT.qk_nope_head_dim, UNCUT.qk_rope_head_dim, UNCUT.v_head_dim
    table = M.rope_table(UNCUT, SEQ)
    with jax.default_matmul_precision("highest"):
        want = reference._mla(u, w, _file_of(UNCUT), jnp.matmul)
        parts = []
        for first in range(0, UNCUT.n_heads, 2):
            share = dataclasses.replace(UNCUT, held_heads=(first, 2))
            hs = slice(first, first + 2)
            own = {**w, "w_uq": w["w_uq"].reshape(-1, 8, dn + dr)[:, hs].reshape(-1, 2 * (dn + dr)),
                   "w_kvb": w["w_kvb"].reshape(-1, 8, dn + dv)[:, hs].reshape(-1, 2 * (dn + dv)),
                   "wo": w["wo"].reshape(8, dv, -1)[hs].reshape(2 * dv, -1)}
            assert {k: v.shape for k, v in own.items() if k in M._mixer_leaves(share)} == {
                k: shape for k, (shape, _, _) in M._mixer_leaves(share).items()}
            parts.append(mla.mla_mixer(u, own, share, llama._attention,
                                       lambda m: M._rotate(m, table), share.n_held_heads,
                                       share.softmax_factor))
    assert _rel(sum(parts), want) < 2e-5
    assert _rel(parts[0], want) > 0.5  # a share alone is not the layer
    whole = mla.mla_mixer(u, w, UNCUT, llama._attention, lambda m: M._rotate(m, table), 8,
                          UNCUT.softmax_factor)
    assert _rel(whole, want) < 2e-5


def test_the_expert_shares_routed_parts_add_up_to_the_uncut_references(uncut):
    """Four chips hold four of the sixteen experts each, the whole router
    and the shared experts: the shares' routed parts plus the shared experts
    ONCE add up to the uncut reference's expert block; a share's own output
    is its routed part plus the shared experts, which every chip computes
    alike."""
    w, u = uncut
    x = u.reshape(-1, UNCUT.dim)
    file = _file_of(UNCUT)
    with jax.default_matmul_precision("highest"):
        want, _, routing = reference._routed(x, w, file, 2, jnp.matmul, jnp.matmul)
        shared = reference._swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                                   jnp.matmul)
        routed = []
        for first in range(0, 16, 4):
            share = dataclasses.replace(UNCUT, held_experts=(first, 4), share_room=8.0)
            es = slice(first, first + 4)
            out, stats = moe.moe_ffn(
                u, w["router"], w["w_gate"][es], w["w_up"][es], w["w_down"][es], share,
                shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))
            assert int(stats["overflow"]) == 0
            np.testing.assert_array_equal(np.asarray(stats["routing"]), routing["routing"])
            routed.append(out.reshape(-1, UNCUT.dim) - shared)
    assert _rel(sum(routed) + shared, want) < 2e-5
    assert _rel(routed[0] + shared, want) > 0.05
    whole, _ = moe.moe_ffn(u, w["router"], w["w_gate"], w["w_up"], w["w_down"], UNCUT,
                           shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))
    assert _rel(whole.reshape(-1, UNCUT.dim), want) < 2e-5


# ---- one case each: the group rule, the table, the factor, Ling's mixer

def test_a_group_is_scored_by_its_best_or_by_its_best_two():
    """On a seeded input the two rules keep other groups for some tokens;
    the maximum is the source's, and Ling's rule reads as before (the sum of
    the best two, written out here)."""
    scores = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(5), (64, 16)) * 2.0)
    greedy = dataclasses.replace(DEBUG, topk_method="group_limited_greedy")
    two = dataclasses.replace(DEBUG, topk_method="noaux_tc")
    kept = {}
    for name, cfg in (("max", greedy), ("best_two", two)):
        inside, g_kth, g_next = moe._within_groups(scores, cfg)
        kept[name] = np.isfinite(np.asarray(inside)).reshape(64, 4, 4)
        assert (kept[name].all(axis=-1) | ~kept[name].any(axis=-1)).all()  # whole groups
        assert (kept[name].any(axis=-1).sum(axis=-1) == 2).all()  # topk_group of them
        assert (np.asarray(g_kth) >= np.asarray(g_next)).all()
    grouped = np.asarray(scores).reshape(64, 4, 4)
    by_max = np.argsort(-grouped.max(axis=-1), axis=-1)[:, :2]
    by_two = np.argsort(-np.sort(grouped, axis=-1)[..., -2:].sum(axis=-1), axis=-1)[:, :2]
    for name, want in (("max", by_max), ("best_two", by_two)):
        got = kept[name].any(axis=-1)
        assert all(sorted(np.nonzero(got[t])[0]) == sorted(want[t]) for t in range(64))
    differ = (kept["max"] != kept["best_two"]).any(axis=(1, 2))
    assert 0 < differ.sum() < 64
    assert moe.MoEConfig().topk_method == "noaux_tc"  # what every other kind keeps
    with pytest.raises(ValueError, match="topk_method"):
        dataclasses.replace(DEBUG, topk_method="greedy")


def test_yarns_table_and_the_softmax_factor_by_hand():
    """The published numbers: 0.1 x 0.707 x ln 40 + 1 = 1.26080, squared
    1.58963, the factor on cos and sin 1; over the 32 rotary pairs the plain
    frequency up to pair 10, a fortieth from pair 23 on, the ramp between."""
    cfg = CONFIGS["deepseek_v2_share"]
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    assert abs(m - 1.26080) < 1e-5 and abs(cfg.softmax_factor - 1.58963) < 1e-5
    assert cfg.softmax_factor == m * m and cfg.rotary_factor == 1.0
    got = np.asarray(M.yarn_inv_freq(cfg, cfg.qk_rope_head_dim), np.float64)
    plain = 10000.0 ** (-np.arange(32) / 32)
    # pair(r) = 64 ln(4096 / (2 pi r)) / (2 ln 10000): 10.47 at r = 32, 22.5 at r = 1
    low, high = 10, 23
    assert math.floor(64 * math.log(4096 / (2 * math.pi * 32)) / (2 * math.log(1e4))) == low
    assert math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(1e4))) == high
    ratio = got / plain
    np.testing.assert_allclose(ratio[:low + 1], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[high:], 1 / 40, rtol=1e-6)
    np.testing.assert_allclose(
        ratio[low:high + 1], 1 - (np.arange(low, high + 1) - low) / (high - low) * (39 / 40),
        rtol=1e-5)
    want, factor, softmax_factor = reference.yarn({
        "qk_rope_head_dim": 64, "rope_theta": 10000, "rope_scaling": {
            "factor": 40, "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
            "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096}})
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert factor == 1.0 and abs(softmax_factor - 1.58963) < 1e-5
    cos, sin = M.rope_table(cfg, 8)
    np.testing.assert_allclose(np.asarray(cos), np.cos(np.arange(8)[:, None] * got), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sin), np.sin(np.arange(8)[:, None] * got), atol=1e-6)


def _lings_mixer_at_the_parent(u, w, cfg, attention):
    """``models/ling.py::_mla_mixer`` as it stood at this PR's parent commit,
    word for word but for the names it imported."""
    import jax.ad_checkpoint

    from torchft_tpu.models.llama import _rmsnorm, _rope
    from torchft_tpu.models.remat import ATTN_OUT_NAME

    def _pairs_apart(x):
        return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)

    def _head_gate(o, u, w_g):
        gate = jax.nn.sigmoid(jnp.matmul(u, w_g, preferred_element_type=jnp.float32))
        return (o * gate.astype(o.dtype)[..., None]).reshape(*o.shape[:2], -1)

    (B, S, _), H = u.shape, cfg.n_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
                     cfg.kv_lora_rank)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    rope = lambda m: _rope(_pairs_apart(m), cfg.rope_theta, positions)  # noqa: E731
    q = (u @ w["wq"]).reshape(B, S, H, dn + dr)
    q_r = rope(q[..., dn:])
    ckr = u @ w["w_kva"]
    c = _rmsnorm(ckr[..., :r], w["kv_norm"], cfg.norm_eps)
    k_r = rope(ckr[..., None, r:])
    kv = (c @ w["w_kvb"]).reshape(B, S, H, dn + dv)
    width = next(n for n in (64, 128, 256) if n >= dn + dr)
    zeros = jnp.zeros((B, S, H, width - dn - dr), u.dtype)
    scale = jnp.asarray(math.sqrt(width / (dn + dr)), u.dtype)
    qq = jnp.concatenate([q[..., :dn], q_r, zeros], axis=-1) * scale
    kk = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, S, H, dr)), zeros], axis=-1)
    attn = jax.ad_checkpoint.checkpoint_name(
        attention(qq, kk, kv[..., dn:], cfg), ATTN_OUT_NAME)
    return _head_gate(attn, u, w["w_g"]) @ w["wo"]


def test_lings_mixer_through_the_shared_function_is_the_parents_to_the_bit():
    """On seeded bf16 weights: the same bits forward, the same bits in the
    gradient of every leaf, and the same lowered program."""
    from torchft_tpu.models import ling

    cfg = CONFIGS["ling_debug"]
    params = ling.ling_init(jax.random.PRNGKey(0), cfg)
    w = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["02_mla_moe"])
    w = {k: v for k, v in w.items()
         if k in ("wq", "w_kva", "kv_norm", "w_kvb", "w_g", "wo")}
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 48, cfg.dim), cfg.dtype)

    def run(f):
        g = jax.jit(jax.value_and_grad(lambda u, w: jnp.sum(
            f(u, w, cfg, ling._attention).astype(jnp.float32) ** 2), argnums=(0, 1)))
        return g(u, w), g.lower(u, w).as_text()

    (a, ga), text_a = run(ling._mla_mixer)
    (b, gb), text_b = run(_lings_mixer_at_the_parent)
    assert float(a) == float(b) and np.isfinite(float(a))
    for x, y in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    assert text_a == text_b


# ---- the registry, the tree, the published cut, what is refused, the trainer

def test_the_kind_is_the_registrys_only_new_entry():
    """Eleven kinds by ISSUE 59's count of the benchmark's (ten configuration
    classes: two dense configurations share ``LlamaConfig``), each its own; a
    DeepseekConfig is an MoEConfig is a LlamaConfig and is DeepSeek's; its
    presets stand in ``CONFIGS``."""
    names = sorted(c.__name__ for c in kinds._KINDS)
    # the kinds that stood when this one came, each once; later kinds add theirs
    assert len(names) == len(set(names)) and set(names) >= {
        "BrumbyConfig", "DeepseekConfig", "JambaConfig", "Lfm2Config", "LingConfig",
        "LlamaConfig", "MellumConfig", "MoEConfig", "NemotronHConfig", "OuroConfig"}
    m = model_fns(DEBUG)
    assert m.init is M.deepseek_init and m.stages is None and m.frozen == ()
    assert model_fns(CONFIGS["moe_debug"]).init is moe.moe_init
    # without a stage the class is DeepSeek-V2; PR 67 gave it the presets of a
    # stage of DeepSeek-V3.2-Exp's continued training (tests/test_deepseek_v32.py)
    assert [n for n, c in CONFIGS.items() if isinstance(c, DeepseekConfig)
            and c.dsa_stage is None] == ["deepseek_debug", "deepseek_v2_share"]


def test_the_leaves_are_the_held_heads_and_every_one_has_a_spec(both):
    params = both["params"]
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == DEBUG.num_params()
    specs = _flat(jax.tree_util.tree_map(
        lambda s: 0, model_fns(DEBUG).param_specs(DEBUG),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
    assert sorted(specs) == sorted(_flat(params))
    w = params["layers"]["01_moe"]
    dn, dr, dv = DEBUG.qk_nope_head_dim, DEBUG.qk_rope_head_dim, DEBUG.v_head_dim
    assert (DEBUG.n_heads, DEBUG.n_held_heads, DEBUG.n_held) == (8, 2, 4)
    assert w["w_uq"].shape == (1, DEBUG.q_lora_rank, 2 * (dn + dr))
    assert w["w_kvb"].shape == (1, DEBUG.kv_lora_rank, 2 * (dn + dv))
    assert w["wo"].shape == (1, 2 * dv, DEBUG.dim)
    assert w["w_dq"].shape == (1, DEBUG.dim, DEBUG.q_lora_rank)  # whole, as the norms
    assert w["w_kva"].shape == (1, DEBUG.dim, DEBUG.kv_lora_rank + dr)
    assert w["router"].shape == (1, DEBUG.dim, 16) and w["router"].dtype == jnp.float32
    assert w["w_gate"].shape == (1, 4, DEBUG.dim, 32) and w["shared_up"].shape == (1, 64, 64)
    assert [name for name, _, _ in DEBUG.runs()] == ["00_dense", "01_moe", "02_moe"]


def test_the_published_cut_counts_what_the_issue_counted():
    """Counted without allocating: the adapter's ``num_params`` = the
    tree's count = ISSUE 59's 1,552,942,080, and its parts."""
    with open(os.path.join(ROOT, "chipbench", "configs", "deepseek-v2.json")) as f:
        cfg = json.load(f)
    from chipbench.adapters import deepseek as adapter

    pc = adapter.config(cfg)
    shapes = jax.eval_shape(lambda: M.deepseek_init(jax.random.PRNGKey(0), pc))
    count = lambda tree: sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert count(shapes) == pc.num_params() == adapter.num_params(cfg) == 1_552_942_080
    assert CONFIGS["deepseek_v2_share"].num_params() == 1_552_942_080
    assert count(shapes["layers"]["00_dense"]) == 208_220_160
    assert count(shapes["layers"]["03_moe"]) == 303_411_200
    mixer = {k: v for k, v in shapes["layers"]["03_moe"].items() if k in M._mixer_leaves(pc)}
    assert count(mixer) == 19_466_240
    assert count(shapes["embed"]) + count(shapes["lm_head"]) == 131_072_000
    assert (pc.n_heads, pc.held_heads, pc.held_experts, pc.num_experts) == (
        128, (0, 8), (0, 10), 160)
    assert (pc.loss_chunk, pc.shared_intermediate_size) == (2048, 3072)
    assert (pc.routed_scaling, pc.norm_topk_prob, pc.aux_loss_weight, pc.seq_aux) == (
        16.0, False, 0.001, True)


def test_what_the_configuration_and_the_specs_refuse():
    with pytest.raises(ValueError, match="held_heads"):
        dataclasses.replace(DEBUG, held_heads=(7, 2))
    with pytest.raises(ValueError, match="num_dense_layers"):
        dataclasses.replace(DEBUG, num_dense_layers=4)
    with pytest.raises(ValueError, match="capacity_factor"):
        dataclasses.replace(UNCUT, capacity_factor=1.25)
    # the balance term is the sequence-wise one, for the kind that asks for it
    with pytest.raises(ValueError, match="seq_aux"):
        dataclasses.replace(DEBUG, seq_aux=False)
    with pytest.raises(ValueError, match="aux_loss_weight"):
        dataclasses.replace(CONFIGS["mellum_debug"], aux_loss_weight=0.01)
    assert dataclasses.replace(DEBUG, seq_aux=False, aux_loss_weight=0.0).seq_aux is False
    devices = np.asarray(jax.devices()[:2])
    tp = jax.sharding.Mesh(devices.reshape(1, 2), ("fsdp", "tp"))
    with pytest.raises(ValueError, match="held_heads.*not sharded over tp"):
        M.deepseek_param_specs(DEBUG, tp)
    ep = jax.sharding.Mesh(devices.reshape(2, 1), ("ep", "tp"))
    with pytest.raises(ValueError, match="dropless"):
        M.deepseek_param_specs(DEBUG, ep)
    fsdp = jax.sharding.Mesh(devices.reshape(2, 1), ("fsdp", "tp"))
    assert "layers" in M.deepseek_param_specs(DEBUG, fsdp)


@pytest.mark.parametrize("name", ["ling_debug", "mellum_debug", "lfm2_debug", "nemotron_h_debug"])
def test_every_kind_over_the_dropless_block_that_asks_for_the_term_gets_it(name):
    """``seq_aux`` is ``MoEConfig``'s, so no kind may take the flag and drop
    the term: the one decoder adds ``aux_loss_weight`` x the layers' terms
    to the loss of each and logs the sum; the plain ``moe`` kind, whose
    auxiliary loss is another, refuses the flag."""
    plain = dataclasses.replace(CONFIGS[name], dtype=jnp.float32)
    asked = dataclasses.replace(plain, aux_loss_weight=1e-3, seq_aux=True)
    with pytest.raises(ValueError, match="aux_loss_weight"):
        dataclasses.replace(plain, aux_loss_weight=1e-3)
    m = model_fns(asked)
    params = m.init(jax.random.PRNGKey(0), asked)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, asked.vocab_size)
    a, stats = jax.jit(lambda p: m.loss(p, tok, tok, asked))(params)
    b, without = jax.jit(lambda p: m.loss(p, tok, tok, plain))(params)
    aux = float(stats["moe_stats"]["moe_aux_loss"])
    # the layers that choose experts: Mellum's all, the others' by their kinds
    layers = sum("moe" in kind for kind in getattr(asked, "kinds", list)()) or asked.n_layers
    # softmax scores at an even load give 1 a layer; sigmoid scores (Ling's,
    # LFM2's, Nemotron's: they do not sum to 1 over the experts) give more
    assert 0.5 * layers < aux < asked.num_experts * layers
    assert abs(float(a) - float(b) - 1e-3 * aux) < 2e-6 * max(1.0, abs(float(b)))
    assert "moe_aux_loss" not in without["moe_stats"]


def test_the_plain_moe_kind_refuses_the_sequence_wise_flag():
    with pytest.raises(ValueError, match="seq_aux"):
        dataclasses.replace(CONFIGS["moe_debug"], seq_aux=True)


def test_the_balance_terms_gradient_reaches_the_router_alone():
    """``sequence_balance_loss`` by hand on a small input: an even load
    reads 1, the load is a constant, and what it adds to the loss moves the
    router (and what feeds it) and no expert."""
    probs = jnp.full((2, 6, 4), 0.25)
    idx = jnp.tile(jnp.arange(4, dtype=jnp.int32)[None, None, :2], (2, 6, 1))
    assert abs(float(moe.sequence_balance_loss(probs, idx)) - 1.0) < 1e-6
    skew = jnp.asarray([0.7, 0.1, 0.1, 0.1]) * jnp.ones((2, 6, 4))
    # experts 0 and 1 chosen by every token: f = (2, 2, 0, 0), P = (.7, .1, .1, .1)
    assert abs(float(moe.sequence_balance_loss(skew, idx)) - 1.6) < 1e-6
    g = jax.grad(lambda p: moe.sequence_balance_loss(p, idx))(skew)
    np.testing.assert_allclose(np.asarray(g[0, 0]), [2 / 12, 2 / 12, 0, 0], atol=1e-7)
    m = model_fns(DEBUG)
    params, tok = m.init(jax.random.PRNGKey(0), DEBUG), jnp.zeros((1, 16), jnp.int32)
    g = jax.jit(jax.grad(lambda p: m.loss(p, tok, tok, DEBUG)[1]["moe_stats"]["moe_aux_loss"]))(
        params)["layers"]["02_moe"]
    assert float(jnp.abs(g["router"]).max()) > 1e-4 and float(jnp.abs(g["w_kva"]).max()) > 0
    assert all(float(jnp.abs(g[k]).max()) == 0
               for k in ("w_gate", "w_up", "w_down", "shared_gate", "shared_down"))


def test_the_staged_gradient_is_the_one_program(both):
    """A kind without stages: ``staged_value_and_grad``'s degenerate chain is
    one program and one part, the gradient of the whole loss with the
    balance term in it, and a fused AdamW step over it moves every leaf."""
    import optax

    from torchft_tpu.models.staged import staged_value_and_grad

    m, params, tok, a = model_fns(DEBUG), both["params"], both["tok"], both["a"]
    run, assemble = staged_value_and_grad(
        m.stages, lambda p, t, y: m.loss(p, t, y, DEBUG), frozen=m.frozen)
    parts = []
    b, stats = run(params, tok, tok, parts.append)
    assert len(parts) == 1 and abs(a - float(b)) < 1e-6 and "moe_stats" in stats
    grads = assemble(parts)
    for (key, x), y in zip(both["ga"].items(), jax.tree_util.tree_leaves(grads)):
        assert _rel(y, x) < 1e-5, key
    tx = optax.adamw(1e-2, weight_decay=0.1)
    updates, _ = tx.update(grads, tx.init(params), params)
    for before, after in zip(jax.tree_util.tree_leaves(params),
                             jax.tree_util.tree_leaves(optax.apply_updates(params, updates))):
        assert not bool(jnp.all(before == after))


def test_the_trainer_trains_the_debug_preset(tmp_path):
    """``--config deepseek_debug`` through the launcher and the one trainer:
    two committed steps, the counters in the SUMMARY's ``model_stats``."""
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")}
    out = subprocess.run(
        [sys.executable, "-m", "torchft_tpu.launcher",
         os.path.join(ROOT, "examples", "train_llama_hsdp.py"), "--replica-groups", "1", "--",
         "--config", "deepseek_debug", "--batch-size", "2", "--seq-len", "32", "--steps", "2",
         "--virtual-chips", "1"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    summary = [json.loads(ln.split(" SUMMARY ", 1)[1])
               for ln in out.stdout.splitlines() if " SUMMARY " in ln][0]
    assert summary["committed"] == 2 and all(np.isfinite(summary["losses"]))
    assert sorted(summary["model_stats"]) == [
        "moe_aux_loss", "moe_groups_hit_mean", "moe_held_pair_share", "moe_load_max_over_mean",
        "moe_moved_row_share", "moe_overflow_pairs", "moe_visited_row_share"]
    assert all(x == 0 for x in summary["model_stats"]["moe_overflow_pairs"])
