"""DeepSeek-V2's share decoder against its plain float32 reference at tiny
widths on the CPU, through the ``bare_routed`` job kind's own check as
``deepseek-v2.bare-mla-yarn`` makes it at the published widths on the chip:
the program in float32 to rounding (decisions, arithmetic with the balance
term in the loss, the router alone), in bf16 under replay, and each fault of
``benchmarks/deepseek_check_faults.py`` put into the program as that script
puts it in on the chip. The fault cases and the bf16 case are ``slow``: each
is a compile of the whole check, 16 to 44 s, and ISSUE 59 gives the new
files 150 test-seconds of tier-1 (the group's rule, YaRN's table and factor
and the balance term have quick cases of their own in
``tests/test_deepseek.py``)."""

import importlib.util

import jax
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest
from test_rehearsal_deepseek import tiny_config

routed = manifest.load_module(ROOT, "jobs", "bare_routed")
deepseek = manifest.load_module(ROOT, "adapters", "deepseek")
reference = deepseek.reference
_spec = importlib.util.spec_from_file_location(
    "deepseek_check_faults", f"{ROOT}/benchmarks/deepseek_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
CHECK = read(f"{ROOT}/chipbench/traffic/bare-mla-yarn.json")["check"]
LEAVES = deepseek.GRAD_LEAVES
SAMPLE = {**CHECK["sample"], "sequences": 2, "positions": 8, "grad_leaves": LEAVES}
SEQ = 80  # beyond the tiny YaRN's original context; no multiple of the reference's blocks
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
    params = deepseek.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), deepseek.config(cfg))
    return reference.answers(params, tokens, cfg, positions, SAMPLE, **dots)


@pytest.fixture(scope="module")
def ref32():
    return _reference(tiny())


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding, routing freely: both latents and
    their norms, the share of the heads, YaRN's table and factor, the
    group-limited softmax router's share, the shared experts, the dense
    SwiGLU in blocks, the sliced loss WITH the balance terms; the router
    alone gives the reference's probabilities."""
    got = routed.routed_check(deepseek, tiny(), SAMPLE, SEQ, ref32, F32)
    assert got["ok"], got
    assert got["decisions"]["differ_pairs"] == 0 and got["router"]["differ_pairs"] == 0
    assert got["free"]["ok"] and got["free"]["decisions"]["differ_pairs"] == 0
    assert sorted(got["arithmetic"]) == sorted(
        ["grad_norm_rel", "logits_rel", "loss_abs", "ok"]
        + ["grad_rel." + p for p in LEAVES])
    assert ref32["routing"].shape == (4, 2 * SEQ, 4) and ref32["balance"].shape == (4,)
    assert ref32["router_in"].shape[0] == 4 and ref32["logits"].shape == (2, 8, 512)


def test_the_blocks_of_answers_are_the_whole_forward(ref32):
    """``answers`` in blocks against ``forward`` and ``loss`` all at once,
    the balance terms in both."""
    cfg = tiny()
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = deepseek.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), deepseek.config(cfg))
    with jax.default_matmul_precision("highest"):
        logits, routing = reference.forward(params, tokens, cfg)
        value = reference.loss(logits, tokens, routing["balance"], cfg["aux_loss_alpha"])
        plain = reference.loss(logits, tokens)
    np.testing.assert_allclose(np.asarray(logits[:, positions]), ref32["logits"],
                               rtol=1e-3, atol=1e-4)
    assert abs(float(value) - ref32["loss"]) < 1e-5
    np.testing.assert_allclose(np.asarray(routing["balance"]), ref32["balance"], rtol=1e-5)
    # 0.001 x four layers' terms near 1: no rounding of the loss
    assert 3e-3 < float(value) - float(plain) < 8e-3
    np.testing.assert_array_equal(np.asarray(routing["routing"]), ref32["routing"])


def test_a_pair_beyond_the_shares_room_makes_the_loss_no_number():
    """The adapter's loss is what tells the job kind: with a buffer of a
    quarter of the even share, a toy batch overflows it, the count is not 0
    and the loss is NaN; the job reads that as not ``correct`` (with room the
    loss is a number: every other case here)."""
    cfg = tiny(share_room=0.25)
    pc = deepseek.config(cfg)
    init_, loss_, _ = deepseek.program()
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    value, stats = jax.jit(lambda: loss_(init_(jax.random.PRNGKey(0), pc), tokens, tokens, pc,
                                         with_stats=True))()
    assert float(stats["overflow_pairs"]) > 0 and not np.isfinite(float(value))
    assert 0.1 < float(stats["held_pair_share"]) < 0.5  # the even share is a quarter


@pytest.mark.slow
def test_bf16_under_replay_is_inside_what_tiny_widths_allow():
    """The chip cell's comparison: the program in bf16 replaying the
    reference's routing; where its own choices differ the reference had a
    near-tie; bf16 is visible, so the comparison is not vacuous."""
    cfg = tiny("bfloat16")
    got = routed.routed_check(deepseek, cfg, SAMPLE, SEQ, _reference(cfg), CHECK)
    b = got["arithmetic"]
    assert 1e-3 < b["logits_rel"] < 0.08 and b["grad_norm_rel"] < 0.03, got
    assert all(v < 0.3 for k, v in b.items() if k.startswith("grad_rel.")), got
    assert got["router"]["ok"] and got["router"]["prob_rel"] < 1e-5, got  # float32 on the CPU
    assert got["decisions"]["differ_max_margin"] <= 0.1, got
    assert got["decisions"]["differ_share"] <= 0.2, got


# the part of the check that refuses each fault in a float32 program, and the
# least the reading that shows it best moves off the float32 reference's
SEEN_IN = {
    "group_best_two": ("decisions", "differ_share", 0.05),
    "no_mscale": ("arithmetic", "grad_rel.layers.00_dense.w_uq", 0.3),
    "no_balance": ("arithmetic", "loss_abs", 3e-3),
    "gates_renormalised": ("arithmetic", "grad_rel.layers.01_moe.router", 0.3),
    "scaling_one": ("arithmetic", "grad_rel.layers.04_moe.w_down@expert_norms", 0.3),
    "no_yarn": ("arithmetic", "grad_rel.layers.00_dense.w_uq", 0.3),
    "no_q_norm": ("arithmetic", "grad_rel.layers.00_dense.q_norm", 0.99),
    "no_kv_norm": ("arithmetic", "grad_rel.layers.00_dense.kv_norm", 0.99),
    "no_shared": ("arithmetic", "grad_rel.layers.04_moe.shared_down", 0.99),
    "fp8_experts": ("arithmetic", "grad_rel.layers.04_moe.w_down", 0.05),
    "fp8_latents": ("arithmetic", "grad_rel.layers.00_dense.kv_norm", 0.1),
}


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(SEEN_IN))
def test_each_fault_in_the_program_is_refused(name, ref32):
    """The faults of ``benchmarks/deepseek_check_faults.py`` in a float32
    program against the float32 reference: each is refused at limits a
    float32 program passes, by the part of the check that has to see it,
    and the reading that was put into the sample for it reads what it
    must. The group's score by its best two reads as the program in the
    arithmetic (the replay hands it the reference's experts): the decisions
    and the router alone refuse it."""
    cfg = tiny()
    jax.clear_caches()
    with faults.fault(name, deepseek.config(cfg)):
        got = faults.reading(routed, deepseek, cfg, SAMPLE, SEQ, ref32, F32)
    jax.clear_caches()
    part, reading, least = SEEN_IN[name]
    assert not got["ok"] and not got[part]["ok"], got
    assert got[part][reading] > least, (reading, got[part])
    if name == "group_best_two":
        assert got["arithmetic"]["ok"] and not got["router"]["ok"], got
    if name == "no_balance":  # the value shows it; its gradient is a thousandth
        assert got["arithmetic"]["grad_rel.layers.01_moe.router"] < 5e-3, got


def test_every_fault_of_the_script_has_its_case_here():
    assert sorted(SEEN_IN) == sorted(faults.FAULTS)
    assert set(faults.REPORTED) == {"balance_no_gradient"}
    assert set(faults.CONTROLS) == {"router_three_passes", "bf16_router"}


def test_the_timed_parameters_are_bare_routeds():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json")
            for n in ("bare-routed", "bare-mla-yarn")]
    for key in ("job", "metric", "warmup_steps", "min_steps"):
        assert both[0][key] == both[1][key]
    assert {**both[0]["check"]["sample"], "grad_leaves": deepseek.GRAD_LEAVES} == \
        both[1]["check"]["sample"]
    for part in ("routing", "router"):
        assert "read on the v5e" in both[1]["check"][part]["why"]
    why = both[1]["check"]["tolerances_why"]
    assert "read on the v5e" in why
    assert all(name in why for name in faults.FAULTS)
