"""The seventh run-kind of the one trainer's model (``models/nemotron_h.py``):
the kind against the plain reference on seeded weights at tiny widths (loss
and the gradient of every leaf), the pattern folded into runs of
single-branch layers, what it refuses, the frozen bias, the sixteen shares
of an expert layer adding up to the layer, and a few committed steps under
the Manager with a heal that carries the bias. The kernel is
``tests/test_ssd.py``'s, ``moe.py``'s two expert forms
``tests/test_moe_expert_forms.py``'s, the cell's check
``tests/chipbench/test_reference_nemotron_h.py``'s."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import reference_nemotron_h as reference  # noqa: E402
from torchft_tpu.models import CONFIGS, model_fns, split_frozen  # noqa: E402
from torchft_tpu.models import nemotron_h as N  # noqa: E402
from torchft_tpu.models.nemotron_h import NEMOTRON_H_CONFIGS, NemotronHConfig  # noqa: E402

PUBLISHED = NEMOTRON_H_CONFIGS["nemotron_3_nano_30b_a3b_share"]
DEBUG = dataclasses.replace(CONFIGS["nemotron_h_debug"], dtype=jnp.float32)


def _file_of(cfg: NemotronHConfig) -> dict:
    """The configuration object as the keys the reference reads."""
    return {"hybrid_override_pattern": cfg.pattern, "layer_norm_epsilon": cfg.norm_eps,
            "mamba_num_heads": cfg.mamba_num_heads, "mamba_head_dim": cfg.mamba_head_dim,
            "n_groups": cfg.mamba_n_groups, "ssm_state_size": cfg.ssm_state_size,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "num_experts_per_tok": cfg.top_k,
            "norm_topk_prob": cfg.norm_topk_prob, "routed_scaling_factor": cfg.routed_scaling,
            "deployment": {"experts_held": list(cfg.held_experts or (0, cfg.num_experts))}}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_the_kind_is_the_plain_reference_loss_and_every_leafs_gradient():
    """``nemotron_h_debug`` in float32 (M E M * E M, a share of 4 of 16
    experts, two heads a group, a sequence that is no whole number of
    chunks) against ``chipbench/reference_nemotron_h.py``'s forward and loss
    differentiated as they stand: the loss, the routing and the gradient of
    every trainable leaf; the bias takes none."""
    params = N.nemotron_h_init(jax.random.PRNGKey(0), DEBUG)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 150), 0, DEBUG.vocab_size)
    train, held = split_frozen(params, N.NEMOTRON_H_FROZEN)

    def ours(p, held=held):
        value, stats = N.nemotron_h_loss_and_stats({**p, **held}, tok, tok, DEBUG)
        return value, stats["routing"]

    def theirs(p):
        logits, routing = reference.forward({**p, **held}, tok, _file_of(DEBUG))
        return reference.loss(logits, tok), routing["routing"]

    (a, ra), (ga, to_bias) = jax.jit(jax.value_and_grad(ours, argnums=(0, 1), has_aux=True))(
        train, held)
    assert float(jnp.abs(to_bias["expert_bias"]).max()) == 0.0
    with jax.default_matmul_precision("highest"):
        (b, rb), gb = jax.jit(jax.value_and_grad(theirs, has_aux=True))(train)
    assert abs(float(a) - float(b)) < 2e-6
    np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
    flat = lambda g: {jax.tree_util.keystr(k): v  # noqa: E731
                      for k, v in jax.tree_util.tree_leaves_with_path(g)}
    ga, gb = flat(ga), flat(gb)
    assert sorted(ga) == sorted(gb) and len(ga) == 3 * 9 + 2 * 6 + 5 + 3
    for name in ga:  # the decay's leaves: sums over every position, both signs
        assert _rel(ga[name], gb[name]) < (2e-4 if name.endswith(("A_log']", "dt_bias']", "D']"))
                                           else 2e-5), name


def test_the_pattern_folds_into_runs_of_single_branch_layers():
    assert [r[::2] for r in PUBLISHED.runs()] == [
        ("00_mamba", 1), ("01_moe", 1), ("02_mamba", 1), ("03_moe", 1), ("04_mamba", 1),
        ("05_attn", 1), ("06_moe", 1), ("07_mamba", 1), ("08_moe", 1), ("09_mamba", 1),
        ("10_moe", 1), ("11_mamba", 1), ("12_attn", 1)]
    names = [r[0] for r in PUBLISHED.runs()]
    assert names == sorted(names) and PUBLISHED.n_moe_layers == 5
    # neighbours of a kind merge, expert layers never
    cfg = dataclasses.replace(DEBUG, pattern="MMEE**M", n_layers=7)
    assert [r[::2] for r in cfg.runs()] == [("00_mamba", 2), ("01_moe", 1), ("02_moe", 1),
                                            ("03_attn", 2), ("04_mamba", 1)]
    params = jax.eval_shape(lambda: N.nemotron_h_init(jax.random.PRNGKey(0), cfg))
    assert params["layers"]["00_mamba"]["in_proj"].shape == (2, 64, 64 + 128 + 4)
    assert params["expert_bias"].shape == (2, 16)
    assert "w_gate" not in params["layers"]["01_moe"]
    assert params["layers"]["01_moe"]["shared_up"].shape == (1, 64, 64)
    tok = jnp.zeros((1, 16), jnp.int32)
    _, stats = jax.eval_shape(lambda p: N.nemotron_h_loss_and_stats(p, tok, tok, cfg), params)
    assert stats["routing"].shape == (2, 16, 4)  # the decoder counts the E runs alone


def test_the_published_cut_counts_what_the_issue_counted():
    assert PUBLISHED.num_params() == 867_977_088 + 5 * 128  # and the bias, a buffer
    assert (PUBLISHED.d_inner, PUBLISHED.conv_dim) == (4096, 6144)
    assert PUBLISHED.d_inner != 2 * PUBLISHED.dim  # ``expand`` is not read
    assert (PUBLISHED.share_rows(16384), PUBLISHED.n_held) == (24576, 8)
    for change, match in (({"pattern": "MEMEM*EMEMEM-"}, "'-'"),
                          ({"pattern": "MEM"}, "n_layers"),
                          ({"use_conv_bias": False}, "use_conv_bias"),
                          ({"capacity_factor": 1.25, "held_experts": None}, "expert_act"),
                          ({"aux_loss_weight": 0.01}, "aux_loss_weight"),
                          ({"mamba_n_groups": 5}, "mamba_n_groups"),
                          ({"expert_act": "gelu"}, "expert_act")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(PUBLISHED, **change)
    # the router's own group limit is moe.py's and may pass
    assert dataclasses.replace(PUBLISHED, n_group=4, topk_group=2).n_group == 4


def test_presets_stand_in_the_registry_and_the_counters_ride_the_loss():
    cfg = CONFIGS["nemotron_h_debug"]
    m = model_fns(cfg)
    assert m.frozen == ("expert_bias",) and m.stages is None and m.init is N.nemotron_h_init
    params = m.init(jax.random.PRNGKey(0), cfg)
    specs = m.param_specs(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, specs, is_leaf=lambda x: not isinstance(x, dict)))
    f32 = {jax.tree_util.keystr(k[-1:]) for k, v in jax.tree_util.tree_leaves_with_path(params)
           if v.dtype == jnp.float32}
    assert f32 == {"['router']", "['A_log']", "['D']", "['dt_bias']", "['expert_bias']"}
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, 256)
    value, stats = jax.jit(lambda p: m.loss(p, tok, tok, cfg))(params)
    assert 5.0 < float(value) < 7.0 and sorted(stats) == ["moe_stats", "ssd_stats"]
    s = {k: float(v) for part in stats.values() for k, v in part.items()}
    assert sorted(s) == ["attn_layers", "moe_bias_moved_share", "moe_held_pair_share",
                         "moe_layers", "moe_load_max_over_mean", "moe_moved_row_share",
                         "moe_overflow_pairs", "moe_visited_row_share",
                         "ssd_chunk_log_decay_min", "ssd_dt_mean", "ssd_layers"]
    assert (s["ssd_layers"], s["moe_layers"], s["attn_layers"]) == (3, 2, 1)
    assert 1e-3 < s["ssd_dt_mean"] < 0.2 and -87 < s["ssd_chunk_log_decay_min"] < 0
    assert s["moe_overflow_pairs"] == 0 and 0 < s["moe_bias_moved_share"] < 1


def test_the_bias_moves_the_selection_and_replay_changes_nothing():
    params = N.nemotron_h_init(jax.random.PRNGKey(0), DEBUG)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 48), 0, 256)
    run = jax.jit(lambda p, routing=None: N.nemotron_h_loss_and_stats(
        p, tok, tok, DEBUG, routing=routing))
    base, stats = run(params)
    assert abs(float(run(params, stats["routing"])[0]) - float(base)) < 2e-6
    _, moved = run({**params, "expert_bias": 100.0 * params["expert_bias"]})
    assert not np.array_equal(np.asarray(moved["routing"]), np.asarray(stats["routing"]))
    assert float(moved["bias_moved_share"]) > float(stats["bias_moved_share"])
    # a token's output is unchanged by later tokens: every branch is causal
    full = jax.jit(lambda t: N.nemotron_h_forward(params, t, DEBUG))
    np.testing.assert_allclose(np.asarray(full(tok))[:, :40], np.asarray(full(tok[:, :40])),
                               rtol=2e-4, atol=2e-5)


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """An expert layer of 128 ungated experts in 16 shares of 8, the sigmoid
    router deciding over all 128 under the bias: the sixteen shares' routed
    parts, with the shared expert and the residual (which every chip
    computes alike) counted once, are what the UNCUT plain reference gives
    for the whole layer; each share's counts are its experts' among the
    whole's."""
    whole = dataclasses.replace(DEBUG, n_layers=1, pattern="E", num_experts=128, top_k=6,
                                held_experts=None)
    w = jax.tree_util.tree_map(lambda x: x[0], N.nemotron_h_init(
        jax.random.PRNGKey(2), whole)["layers"]["00_moe"])
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(4), (128,))
    h = jax.random.normal(jax.random.PRNGKey(3), (1, 32, whole.dim))
    with jax.default_matmul_precision("highest"):
        want, routing = reference.layer("moe", w, bias, h, _file_of(whole))
    body = lambda cfg: N._bodies(cfg, 32, None)("moe")  # noqa: E731
    # what every chip computes alike: the residual and the shared expert
    once = body(whole)(h, ({**w, "w_down": jnp.zeros_like(w["w_down"])}, bias, None))[0]
    parts, counts = [], []
    for first in range(0, 128, 8):
        share = dataclasses.replace(whole, held_experts=(first, 8), share_room=16.0)
        held = {**w, **{k: w[k][first:first + 8] for k in ("w_up", "w_down")}}
        got, st = body(share)(h, (held, bias, None))
        assert int(st["overflow"]) == 0
        np.testing.assert_array_equal(np.asarray(st["routing"]), np.asarray(routing["routing"]))
        parts.append(got - once)
        counts.append(np.asarray(st["counts"]))
    np.testing.assert_allclose(np.asarray(once + sum(parts)), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    assert np.concatenate(counts).sum() == 32 * 6
    assert float(jnp.abs(want - once).max()) > 0.1  # the routed part is no rounding


def test_committed_steps_under_the_manager_with_a_heal_that_carries_the_bias(tmp_path):
    """``nemotron_h_debug`` through the launcher, the lighthouse, the
    Manager and the one trainer, two groups: four committed steps each and
    none discarded, group 1 heals from group 0 in step 1 and ends with group
    0's ``expert_bias`` bitwise (its own seed's is another) and with
    bitwise-equal parameters; the counters ride the SUMMARY line."""
    from test_trainer_model_kinds import _checksum, _train

    a, b = sorted(_train("nemotron_h_debug", tmp_path, "--steps", "4", "--seq-len", "160",
                         groups=2), key=lambda s: s["replica"])
    for s in (a, b):
        assert s["config"] == "nemotron_h_debug" and s["committed"] == 4, s
        assert s["discarded"] == 0
        assert sorted(s["model_stats"]) == [
            "attn_layers", "moe_bias_moved_share", "moe_held_pair_share", "moe_layers",
            "moe_load_max_over_mean", "moe_moved_row_share", "moe_overflow_pairs",
            "moe_visited_row_share", "ssd_chunk_log_decay_min", "ssd_dt_mean", "ssd_layers"]
        assert all(v == 0 for v in s["model_stats"]["moe_overflow_pairs"])
        assert s["model_stats"]["ssd_layers"] == [3.0] * 4
        assert all(5.0 < x < 7.0 for x in s["losses"])
    assert b["healed"] >= 1 and a["healed"] == 0
    source, own = (_checksum(N.nemotron_h_init(jax.random.PRNGKey(r), CONFIGS[
        "nemotron_h_debug"])["expert_bias"]) for r in (0, 1))
    assert source != own
    assert a["frozen_checksum"] == b["frozen_checksum"] == source
    assert a["param_checksum"] == b["param_checksum"]
