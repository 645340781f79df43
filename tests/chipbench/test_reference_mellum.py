"""Mellum's share decoder against its plain float32 reference at tiny widths
on the CPU, through the ``bare_routed`` job kind's own check as
``mellum2-12b-a2.5b.bare-window-32k`` makes it at the published widths on
the chip: the program in float32 to rounding (decisions, arithmetic, the
router alone), in bf16 under replay, and each fault of
``benchmarks/mellum_check_faults.py`` put into the program as that script
puts it in on the chip, a window off by one among them."""

import importlib.util

import jax
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest

routed = manifest.load_module(ROOT, "jobs", "bare_routed")
mellum = manifest.load_module(ROOT, "adapters", "mellum")
reference = mellum.reference
_spec = importlib.util.spec_from_file_location(
    "mellum_check_faults", f"{ROOT}/benchmarks/mellum_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
CHECK = read(f"{ROOT}/chipbench/traffic/bare-window-32k.json")["check"]
# the cell's leaves by the names the tiny cut's four runs have (one period of
# window, full, twice)
LEAVES = mellum.GRAD_LEAVES
SAMPLE = {**CHECK["sample"], "sequences": 2, "positions": 8, "grad_leaves": LEAVES}
SEQ = 80  # several windows long, not a multiple of the reference's blocks
# tiny widths, the architecture kept: heads that do not multiply out to the
# hidden size, four query heads over two key/value heads, a window of 8, a
# YaRN table whose ramp lies inside the 8 pairs (2 to 5), a share of 8 of 32
# experts
TINY = dict(hidden_size=48, moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, vocab_size=512, num_experts=8,
            num_experts_per_tok=4, sliding_window=8, num_hidden_layers=4,
            layer_types=["sliding_attention", "full_attention"] * 2,
            mlp_layer_types=["sparse"] * 4)
DEPLOYMENT = {"experts_held": [8, 8], "router_outputs": 32, "share_room": 4.0,
              "published_layers": [0, 3]}
# float32 on the CPU: the limits a float32 program is held to here, whatever
# the chip's bf16 ones are
F32 = {"tolerances": {"logits_rel": 1e-4, "loss_abs": 2e-5, "grad_norm_rel": 5e-5,
                      "grad_leaf_rel": 5e-4},
       "routing": {"max_share": 0.0, "max_margin": 0.0}, "router": {"max_prob_rel": 1e-5}}


def tiny(dtype="float32", **deployment):
    cfg = read(f"{ROOT}/chipbench/configs/mellum2-12b-a2.5b.json")
    cfg.update(TINY)
    cfg["deployment"] = {**cfg["deployment"], **DEPLOYMENT, **deployment}
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype}
    return cfg


def _reference(cfg, **dots):
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = mellum.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), mellum.config(cfg))
    return reference.answers(params, tokens, cfg, positions, SAMPLE, **dots)


@pytest.fixture(scope="module")
def ref32():
    return _reference(tiny())


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding, routing freely: the window and
    the whole sequence against the explicit mask, both rotary tables, the
    per-head norms, grouped heads, the softmax router's share, the sliced
    loss; the router alone gives the reference's probabilities."""
    got = routed.routed_check(mellum, tiny(), SAMPLE, SEQ, ref32, F32)
    assert got["ok"], got
    assert got["decisions"]["differ_pairs"] == 0 and got["router"]["differ_pairs"] == 0
    assert got["free"]["ok"] and got["free"]["decisions"]["differ_pairs"] == 0
    assert sorted(got["arithmetic"]) == sorted(
        ["grad_norm_rel", "logits_rel", "loss_abs", "ok"]
        + ["grad_rel." + p for p in LEAVES])
    assert ref32["routing"].shape == (4, 2 * SEQ, 4)
    assert ref32["router_in"].shape[0] == 4 and ref32["logits"].shape == (2, 8, 512)


def test_the_blocks_of_answers_are_the_whole_forward(ref32):
    """``answers`` in blocks against ``forward`` and ``loss`` all at once."""
    cfg = tiny()
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = mellum.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), mellum.config(cfg))
    with jax.default_matmul_precision("highest"):
        logits, routing = reference.forward(params, tokens, cfg)
        value = reference.loss(logits, tokens)
    np.testing.assert_allclose(np.asarray(logits[:, positions]), ref32["logits"],
                               rtol=1e-3, atol=1e-4)
    assert abs(float(value) - ref32["loss"]) < 1e-5
    np.testing.assert_array_equal(np.asarray(routing["routing"]), ref32["routing"])


def test_the_reference_writes_the_mask_and_yarn_out():
    """``allowed`` is the published mask by its two indices, and the table
    the equations at the published numbers."""
    i, j = np.arange(12)[:, None], np.arange(12)[None, :]
    window = reference.allowed(i, j, "window", 4)
    assert window.sum(axis=1).tolist() == [1, 2, 3] + [4] * 9
    assert window[7].nonzero()[0].tolist() == [4, 5, 6, 7]
    assert reference.allowed(i, j, "full", 4).sum() == 12 * 13 // 2
    cfg = read(f"{ROOT}/chipbench/configs/mellum2-12b-a2.5b.json")
    plain, one = reference.rotary_table(cfg, "window")
    yarn, factor = reference.rotary_table(cfg, "full")
    assert one == 1.0 and factor == 1.2772588722239782 == 0.1 * np.log(16) + 1
    np.testing.assert_allclose(plain, 500000.0 ** (-np.arange(64) / 64), rtol=1e-12)
    ratio = yarn / plain  # kept up to pair 18, a sixteenth from 35, a ramp between
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-12)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-12)
    np.testing.assert_allclose(ratio[19:35], 1 - (np.arange(19, 35) - 18) / 17 * (15 / 16),
                               rtol=1e-12)


def test_a_pair_beyond_the_shares_room_makes_the_loss_no_number():
    """The adapter's loss is what tells the job kind: with a buffer of the
    even share and no room, a toy batch overflows it, the count is not 0 and
    the loss is NaN; the job reads that as not ``correct``."""
    cfg = tiny(share_room=0.25)
    pc = mellum.config(cfg)
    init_, loss_, _ = mellum.program()
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    value, stats = loss_(init_(jax.random.PRNGKey(0), pc), tokens, tokens, pc,
                         with_stats=True)
    assert float(stats["overflow_pairs"]) > 0 and not np.isfinite(float(value))
    roomy = mellum.config(tiny())
    value, stats = loss_(init_(jax.random.PRNGKey(0), roomy), tokens, tokens, roomy,
                         with_stats=True)
    assert float(stats["overflow_pairs"]) == 0 and np.isfinite(float(value))
    assert 0.1 < float(stats["held_pair_share"]) < 0.5  # the even share is a quarter


def test_the_adapter_refuses_what_the_program_cannot_express():
    cfg = tiny()
    for key, value, word in (("attention_bias", True, "attention_bias"),
                             ("use_sliding_window", False, "use_sliding_window"),
                             ("num_shared_experts", 1, "num_shared_experts"),
                             ("num_nextn_predict_layers", 1, "num_nextn_predict_layers"),
                             ("num_experts", 4, "held"),
                             ("mlp_layer_types", ["dense"] + ["sparse"] * 3, "sparse"),
                             ("layer_types", ["sliding_attention"] * 3, "layer_types"),
                             ("layer_types", ["chunked_attention"] * 4, "layer_types")):
        with pytest.raises(ValueError, match=word):
            mellum.config({**cfg, key: value})
    for kind, change in (("sliding_attention", {"rope_type": "linear"}),
                         ("full_attention", {"rope_theta": 10000}),
                         ("full_attention", {"mscale": 1.0})):
        rope = {**cfg["rope_parameters"], kind: {**cfg["rope_parameters"][kind], **change}}
        with pytest.raises(ValueError, match="rope_parameters"):
            mellum.config({**cfg, "rope_parameters": rope})


def test_bf16_under_replay_is_inside_what_tiny_widths_allow():
    """The chip cell's comparison: the program in bf16 replaying the
    reference's routing; where its own choices differ the reference had a
    near-tie; bf16 is visible, so the comparison is not vacuous."""
    cfg = tiny("bfloat16")
    got = routed.routed_check(mellum, cfg, SAMPLE, SEQ, _reference(cfg), CHECK)
    b = got["arithmetic"]
    assert 1e-3 < b["logits_rel"] < 0.08 and b["grad_norm_rel"] < 0.03, got
    assert all(v < 0.3 for k, v in b.items() if k.startswith("grad_rel.")), got
    assert got["router"]["ok"] and got["router"]["prob_rel"] < 1e-5, got  # float32 on the CPU
    assert got["decisions"]["differ_max_margin"] <= 0.08, got
    assert got["decisions"]["differ_share"] <= 0.15, got


# the least each fault moves the float32 program's arithmetic off the float32
# reference's, by the leaf (or logits) that shows it best
SEEN_IN = {
    "window_ignored": ("grad_rel.layers.00_window.wq", 0.05),
    "window_1023": ("grad_rel.layers.00_window.wq", 0.01),  # a window of 7 for 8
    "window_1025": ("grad_rel.layers.00_window.wq", 0.01),  # 9 for 8
    "no_yarn": ("grad_rel.layers.01_full.wq", 0.02),
    "no_attention_factor": ("grad_rel.layers.01_full.wq", 0.05),
    "tables_swapped": ("grad_rel.layers.00_window.wq", 0.05),
    "no_qk_norm": ("grad_rel.layers.00_window.q_norm", 0.99),
    "gates_not_renormalised": ("grad_rel.layers.03_full.w_down", 0.3),
    "fp8_experts": ("grad_rel.layers.03_full.w_down", 0.01),
}


@pytest.mark.parametrize("name", sorted(SEEN_IN))
def test_each_fault_in_the_program_is_refused(name, ref32):
    """The faults of ``benchmarks/mellum_check_faults.py`` in a float32
    program against the float32 reference: each is refused by the
    arithmetic under replay, at limits a float32 program passes, and the
    leaf that was put into the sample for it reads what it must."""
    cfg = tiny()
    jax.clear_caches()
    with faults.fault(name, mellum.config(cfg)):
        got = faults.reading(routed, mellum, cfg, SAMPLE, SEQ, ref32, F32)
    jax.clear_caches()
    leaf, least = SEEN_IN[name]
    assert not got["ok"] and not got["arithmetic"]["ok"], got
    assert got["arithmetic"][leaf] > least, (leaf, got["arithmetic"])


def test_the_timed_parameters_are_bare_routeds():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json")
            for n in ("bare-routed", "bare-window-32k")]
    for key in ("job", "metric", "warmup_steps", "min_steps"):
        assert both[0][key] == both[1][key]
    assert {**both[0]["check"]["sample"], "grad_leaves": mellum.GRAD_LEAVES} == \
        both[1]["check"]["sample"]
    for part in ("routing", "router"):
        assert "read on the v5e" in both[1]["check"][part]["why"]
    why = both[1]["check"]["tolerances_why"]
    assert "read on the v5e" in why
    assert all(name in why for name in faults.FAULTS)
