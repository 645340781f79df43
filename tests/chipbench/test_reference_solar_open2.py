"""Solar Open 2's share decoder against its plain float32 reference at tiny
widths on the CPU, through the ``bare_routed`` job kind's own check as
``solar-open2-250b.bare-kda-gqa-16k`` makes it at the published widths on the
chip: the program in float32 to rounding (decisions, arithmetic, the router
alone: logits, loss, the gradient's norm and every sampled leaf), in bf16
under replay, and each fault of ``benchmarks/solar_check_faults.py`` put into
the program as that script puts it in on the chip. Each fault case is a
compile of the whole check (35 s): two run with the quick tests (``QUICK``),
the others and the bf16 case are ``slow``; every fault has a quick case of
its own in ``tests/test_solar.py`` besides."""

import importlib.util

import jax
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest
from test_rehearsal_solar_open2 import tiny_config

routed = manifest.load_module(ROOT, "jobs", "bare_routed")
solar = manifest.load_module(ROOT, "adapters", "solar_open2")
reference = solar.reference
_spec = importlib.util.spec_from_file_location(
    "solar_check_faults", f"{ROOT}/benchmarks/solar_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
CHECK = read(f"{ROOT}/chipbench/traffic/bare-kda-gqa-16k.json")["check"]
LEAVES = solar.GRAD_LEAVES
SAMPLE = {**CHECK["sample"], "sequences": 2, "positions": 8, "grad_leaves": LEAVES}
SEQ = 80  # across a chunk's border of the kernels; no multiple of the reference's blocks
# float32 on the CPU: the limits a float32 program is held to here, whatever
# the chip's bf16 ones are
F32 = {"tolerances": {"logits_rel": 1e-4, "loss_abs": 2e-5, "grad_norm_rel": 5e-5,
                      "grad_leaf_rel": 5e-4},
       "routing": {"max_share": 0.0, "max_margin": 0.0}, "router": {"max_prob_rel": 1e-5}}


def tiny(dtype="float32", **deployment):
    cfg = tiny_config()
    cfg["deployment"] = {**cfg["deployment"], **deployment}
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype}
    return cfg


def _reference(cfg, **dots):
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = solar.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), solar.config(cfg))
    params["expert_bias"] = reference.expert_bias(
        **cfg["recipe"]["expert_bias"], layers=cfg["num_hidden_layers"],
        experts=cfg["deployment"]["router_outputs"])
    return reference.answers(params, tokens, cfg, positions, SAMPLE, **dots)


@pytest.fixture(scope="module")
def ref32():
    return _reference(tiny())


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding, routing freely: the unbounded
    decay through its rank, ``beta`` to 2, the channel gate, the gated GQA
    layer without positions, the sigmoid router's share under its bias, the
    shared expert, the sliced loss; the router alone gives the reference's
    probabilities."""
    got = routed.routed_check(solar, tiny(), SAMPLE, SEQ, ref32, F32)
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
    params = solar.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), solar.config(cfg))
    params["expert_bias"] = reference.expert_bias(
        **cfg["recipe"]["expert_bias"], layers=4, experts=16)
    with jax.default_matmul_precision("highest"):
        logits, routing = reference.forward(params, tokens, cfg)
        value = reference.loss(logits, tokens)
    np.testing.assert_allclose(np.asarray(logits[:, positions]), ref32["logits"],
                               rtol=1e-3, atol=1e-4)
    assert abs(float(value) - ref32["loss"]) < 1e-5
    np.testing.assert_array_equal(np.asarray(routing["routing"]), ref32["routing"])


def test_a_pair_beyond_the_shares_room_makes_the_loss_no_number():
    """The adapter's loss is what tells the job kind: with a buffer of a
    quarter of the even share, a toy batch overflows it, the count is not 0
    and the loss is NaN; the job reads that as not ``correct``."""
    cfg = tiny(share_room=0.25)
    pc = solar.config(cfg)
    init_, loss_, _ = solar.program()
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    value, stats = jax.jit(lambda: loss_(init_(jax.random.PRNGKey(0), pc), tokens, tokens, pc,
                                         with_stats=True))()
    assert float(stats["overflow_pairs"]) > 0 and not np.isfinite(float(value))
    assert 0.1 < float(stats["held_pair_share"]) < 0.5  # the even share is a quarter
    assert 0 < float(stats["beta_over_one_share"]) < 1


@pytest.mark.slow
def test_bf16_under_replay_is_inside_what_tiny_widths_allow():
    """The chip cell's comparison: the program in bf16 replaying the
    reference's routing; where its own choices differ the reference had a
    near-tie; bf16 is visible, so the comparison is not vacuous."""
    cfg = tiny("bfloat16")
    got = routed.routed_check(solar, cfg, SAMPLE, SEQ, _reference(cfg), CHECK)
    b = got["arithmetic"]
    assert 1e-3 < b["logits_rel"] < 0.08 and b["grad_norm_rel"] < 0.03, got
    assert all(v < 0.3 for k, v in b.items() if k.startswith("grad_rel.")), got
    assert got["router"]["ok"] and got["router"]["prob_rel"] < 1e-5, got  # float32 on the CPU
    assert got["decisions"]["differ_max_margin"] <= 0.1, got
    assert got["decisions"]["differ_share"] <= 0.2, got


# the reading that shows each fault best in a float32 program, and the least
# it moves off the float32 reference's
SEEN_IN = {
    "beta_not_doubled": ("grad_rel.layers.01_kda_moe.w_beta", 0.3),
    "decay_clipped": ("grad_rel.layers.01_kda_moe.A_log", 0.01),
    "head_wise_gate": ("grad_rel.layers.01_kda_moe.w_gb", 0.3),
    "gqa_rope": ("grad_rel.layers.00_gqa_moe.wq", 0.3),
    "no_gqa_gate": ("grad_rel.layers.00_gqa_moe.w_g", 0.99),
    "lost_tap": ("grad_rel.layers.01_kda_moe.conv_k", 0.3),
    "no_shared": ("grad_rel.layers.03_kda_moe.shared_down", 0.99),
    "bf16_state": ("grad_norm_rel", 1e-4),
}


QUICK = ("beta_not_doubled", "no_gqa_gate")  # one of each mixer, in the unmarked run


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=() if n in QUICK else pytest.mark.slow) for n in sorted(SEEN_IN)])
def test_each_fault_in_the_program_is_refused(name, ref32):
    """The faults of ``benchmarks/solar_check_faults.py`` in a float32
    program against the float32 reference: each is refused at limits a
    float32 program passes, by the arithmetic, and the reading that was put
    into the sample for it reads what it must."""
    cfg = tiny()
    jax.clear_caches()
    with faults.fault(name, solar.config(cfg)):
        got = faults.reading(routed, solar, cfg, SAMPLE, SEQ, ref32, F32)
    jax.clear_caches()
    reading, least = SEEN_IN[name]
    assert not got["ok"] and not got["arithmetic"]["ok"], got
    assert got["arithmetic"][reading] > least, (reading, got["arithmetic"])


def test_every_fault_of_the_script_has_its_case_here():
    assert sorted(SEEN_IN) == sorted(faults.FAULTS)
    assert set(faults.CONTROLS) == {"fp8_experts", "fp8_mixers", "bf16_kda", "bf16_decay",
                                    "router_three_passes", "bf16_router"}


def test_the_timed_parameters_are_bare_routeds():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json")
            for n in ("bare-routed", "bare-kda-gqa-16k")]
    for key in ("job", "metric", "warmup_steps", "min_steps"):
        assert both[0][key] == both[1][key]
    assert {**both[0]["check"]["sample"], "grad_leaves": solar.GRAD_LEAVES} == \
        both[1]["check"]["sample"]
    for part in ("routing", "router"):
        assert "read on the v5e" in both[1]["check"][part]["why"]
    why = both[1]["check"]["tolerances_why"]
    assert "read on the v5e" in why
    assert all(name in why for name in faults.FAULTS)
