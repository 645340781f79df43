"""The fifth kind of the one trainer's model (``models/ling.py``) as a
kind: its presets and what ``model_fns`` hands out for them, the published
cut's counts, and remat, the chunked loss and replay as for the other kinds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import CONFIGS, model_fns, split_frozen
from torchft_tpu.models import ling as L
from torchft_tpu.models.ling import LING_CONFIGS, LingConfig


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
        "moe_load_max_over_mean", "moe_moved_row_share", "moe_overflow_pairs",
        "moe_visited_row_share"]
    s = {k: float(v) for k, v in stats["moe_stats"].items()}
    assert s["moe_overflow_pairs"] == 0 and s["moe_groups_hit_mean"] <= 2
    assert 0 < s["moe_visited_row_share"] < 1  # the products leave the free rows out
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
