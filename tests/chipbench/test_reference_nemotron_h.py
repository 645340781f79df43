"""Nemotron-H's share decoder against its plain float32 reference at tiny
widths on the CPU, through the ``bare_routed`` job kind's own check as
``nemotron-3-nano-30b-a3b.bare-ssd-8k`` makes it at the published widths on
the chip: the program in float32 to rounding (decisions, arithmetic, the
router alone), the reference's blocks against its whole forward pass, and
each fault of ``benchmarks/nemotron_h_check_faults.py`` put into the program
as that script puts it in on the chip, seen in the layer it breaks."""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest

routed = manifest.load_module(ROOT, "jobs", "bare_routed")
nemotron = manifest.load_module(ROOT, "adapters", "nemotron_h")
reference = nemotron.reference
_spec = importlib.util.spec_from_file_location(
    "nemotron_h_check_faults", f"{ROOT}/benchmarks/nemotron_h_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
CHECK = read(f"{ROOT}/chipbench/traffic/bare-ssd-8k.json")["check"]
# the cell's leaves by the names the tiny cut's five runs have (the attention
# layer is the fourth run there, the sixth in the cell)
LEAVES = [p.replace("05_attn", "03_attn") for p in nemotron.GRAD_LEAVES]
SAMPLE = {**CHECK["sample"], "sequences": 2, "positions": 8, "grad_leaves": LEAVES}
SEQ = 160  # a chunk and a quarter: the carry and the kernel's padding both show
# tiny widths, the architecture kept: every kind of layer, an expert layer
# behind a mixer and behind attention, heads that do not multiply out to the hidden size, two Mamba
# heads a group, a shared expert twice an expert's width, a share of 8 of 32
TINY = dict(hidden_size=48, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=512, mamba_num_heads=4, mamba_head_dim=16, ssm_state_size=16,
            n_groups=2, moe_intermediate_size=32, moe_shared_expert_intermediate_size=64,
            n_routed_experts=8, num_experts_per_tok=4, num_hidden_layers=5,
            hybrid_override_pattern="MEM*E")
DEPLOYMENT = {"experts_held": [8, 8], "router_outputs": 32, "share_room": 4.0,
              "published_layers": [0, 4]}
# float32 on the CPU: the limits a float32 program is held to here, whatever
# the chip's bf16 ones are
F32 = {"tolerances": {"logits_rel": 1e-4, "loss_abs": 2e-5, "grad_norm_rel": 5e-5,
                      "grad_leaf_rel": 5e-4},
       "routing": {"max_share": 0.0, "max_margin": 0.0}, "router": {"max_prob_rel": 1e-5}}


def tiny(dtype="float32", **deployment):
    cfg = read(f"{ROOT}/chipbench/configs/nemotron-3-nano-30b-a3b.json")
    cfg.update(TINY)
    cfg["deployment"] = {**cfg["deployment"], **DEPLOYMENT, **deployment}
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype}
    return cfg


def _params(cfg):
    params = nemotron.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), nemotron.config(cfg))
    return {**params, "expert_bias": reference.expert_bias(
        **cfg["recipe"]["expert_bias"], layers=2, experts=32)}


@pytest.fixture(scope="module")
def ref32():
    cfg = tiny()
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    return reference.answers(_params(cfg), tokens, cfg, positions, SAMPLE)


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding, routing freely: the chunked
    kernels against the scan over positions, the grouped gated norm, GQA
    without positions, the sigmoid router's share under its bias, the
    ungated experts beside the wider shared one, the sliced loss; the router
    alone gives the reference's probabilities."""
    got = routed.routed_check(nemotron, tiny(), SAMPLE, SEQ, ref32, F32)
    assert got["ok"], got
    assert got["decisions"]["differ_pairs"] == 0 and got["router"]["differ_pairs"] == 0
    assert got["free"]["ok"] and got["free"]["decisions"]["differ_pairs"] == 0
    assert sorted(got["arithmetic"]) == sorted(
        ["grad_norm_rel", "logits_rel", "loss_abs", "ok"]
        + ["grad_rel." + p for p in LEAVES])
    assert ref32["routing"].shape == (2, 2 * SEQ, 4)
    assert ref32["router_in"].shape[0] == 2 and ref32["logits"].shape == (2, 8, 512)


def test_the_blocks_of_answers_are_the_whole_forward(ref32):
    """``answers`` in blocks against ``forward`` and ``loss`` all at once."""
    cfg = tiny()
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    with jax.default_matmul_precision("highest"):
        logits, routing = jax.jit(lambda p: reference.forward(p, tokens, cfg))(_params(cfg))
        value = reference.loss(logits, tokens)
    np.testing.assert_allclose(np.asarray(logits[:, positions]), ref32["logits"],
                               rtol=1e-3, atol=1e-4)
    assert abs(float(value) - ref32["loss"]) < 1e-5
    np.testing.assert_array_equal(np.asarray(routing["routing"]), ref32["routing"])
    assert reference.where(cfg) == [(f"{i:02d}_{k}", 0) for i, k in enumerate(
        ["mamba", "moe", "mamba", "attn", "moe"])]
    assert reference.where({"hybrid_override_pattern": "MM*E"})[1] == ("00_mamba", 1)


def test_a_pair_beyond_the_shares_room_makes_the_loss_no_number():
    """The adapter's loss is what tells the job kind: with no room a toy
    batch overflows the buffer, the count is not 0 and the loss is NaN."""
    cfg = tiny(share_room=0.25)
    pc = nemotron.config(cfg)
    init_, loss_, _ = nemotron.program()
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    value, stats = jax.jit(lambda p: loss_(p, tokens, tokens, pc, with_stats=True))(
        init_(jax.random.PRNGKey(0), pc))
    assert float(stats["overflow_pairs"]) > 0 and not np.isfinite(float(value))


def test_the_adapter_refuses_what_the_program_cannot_express():
    cfg = tiny()
    for key, value, word in (("mlp_hidden_act", "silu", "mlp_hidden_act"),
                             ("attention_bias", True, "attention_bias"),
                             ("n_shared_experts", 2, "n_shared_experts"),
                             ("use_conv_bias", False, "use_conv_bias"),
                             ("n_routed_experts", 4, "held"),
                             ("norm_eps", 1e-6, "norm_eps"),
                             ("hybrid_override_pattern", "MEM*-", "'-'"),
                             ("hybrid_override_pattern", "MEM", "num_hidden_layers"),
                             ("rope_scaling", {"type": "yarn"}, "rope_scaling")):
        with pytest.raises(ValueError, match=word):
            nemotron.config({**cfg, key: value})


# the layer each fault breaks and the least it moves that layer's output, in
# float32, off the reference's
SEEN_IN = {
    "no_carry": ("mamba", 0.02), "group_zero": ("mamba", 0.1), "gate_after_norm": ("mamba", 0.1),
    "norm_over_all": ("mamba", 0.02), "no_D": ("mamba", 0.3), "bf16_state": ("mamba", 2e-4),
    "rope_added": ("attn", 0.3),
    "relu_not_squared": ("moe", 0.3), "no_shared": ("moe", 0.3), "no_bias": ("moe", 0.01),
    "scaling_one": ("moe", 0.05), "fp8_experts": ("moe", 1e-3),
}


@pytest.fixture(scope="module")
def layers():
    """One layer of each kind: its input, its weights, the reference's output."""
    cfg = tiny()
    params, at = _params(cfg), {"mamba": "00_mamba", "moe": "01_moe", "attn": "03_attn"}
    h = jax.random.normal(jax.random.PRNGKey(5), (1, SEQ, 48))
    out = {}
    for kind, name in at.items():
        w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
        bias = params["expert_bias"][0] if kind == "moe" else None
        with jax.default_matmul_precision("highest"):
            out[kind] = (w, bias, jax.jit(lambda w, kind=kind, bias=bias: reference.layer(
                kind, w, bias, h, cfg)[0])(w))
    return cfg, h, out


@pytest.mark.parametrize("name", ["program"] + sorted(SEEN_IN))
def test_each_fault_in_the_program_shows_in_its_layer(name, layers):
    """The program's layer in float32 is the reference's to rounding; with a
    fault of ``benchmarks/nemotron_h_check_faults.py`` in, the layer it
    breaks is off by at least the share stated. (Whether the cell's CHECK
    refuses the fault is the chip's to say: PERF.md section 6, PR 52.)"""
    from torchft_tpu.models import nemotron_h as N

    cfg, h, out = layers
    pc = nemotron.config(cfg)

    def off(kind):
        w, bias, want = out[kind]
        got = N._bodies(pc, SEQ, None)(kind)(h, (w, bias, None))[0]
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want - h))

    if name == "program":
        assert all(off(kind) < 2e-5 for kind in out)
        assert set(SEEN_IN) == set(faults.FAULTS + faults.CONTROLS) - set(faults.ROUTER_ONLY)
        return
    kind, least = SEEN_IN[name]
    with faults.fault(name):
        assert off(kind) > least, name


def test_the_timed_parameters_are_bare_routeds():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json") for n in ("bare-routed", "bare-ssd-8k")]
    for key in ("job", "metric", "warmup_steps", "min_steps"):
        assert both[0][key] == both[1][key]
    assert {**both[0]["check"]["sample"], "sequences": 2,
            "grad_leaves": nemotron.GRAD_LEAVES} == \
        both[1]["check"]["sample"]
    for part in ("routing", "router"):
        assert "read on the v5e" in both[1]["check"][part]["why"]
    why = both[1]["check"]["tolerances_why"]
    assert "read on the v5e" in why
    assert all(name in why for name in faults.FAULTS + faults.CONTROLS[:2])
