"""LFM2's decoder against its plain float32 reference at tiny widths on the
CPU, through the ``bare_routed`` job kind's own check as
``lfm2-8b-a1b.bare-routed-8k`` makes it at the published widths on the chip:
the program in float32 to rounding (decisions, arithmetic, the router
alone), in bf16 under replay, and the five faults the check exists for, put
into the program as ``benchmarks/lfm2_check_faults.py`` puts them in on the
chip."""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest

bare = manifest.load_module(ROOT, "jobs", "bare")
routed = manifest.load_module(ROOT, "jobs", "bare_routed")
lfm2 = manifest.load_module(ROOT, "adapters", "lfm2")
reference = lfm2.reference
_spec = importlib.util.spec_from_file_location(
    "lfm2_check_faults", f"{ROOT}/benchmarks/lfm2_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
CHECK = read(f"{ROOT}/chipbench/traffic/bare-routed-8k.json")["check"]
SAMPLE = {**CHECK["sample"], "sequences": 2, "positions": 8,
          "grad_leaves": lfm2.GRAD_LEAVES}
SEQ = 128
# tiny widths, the architecture kept: a dense convolution layer, an attention
# and three convolution layers with experts, four a token, a tied head
TINY = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
            num_experts=16, num_experts_per_tok=4)
# float32 on the CPU: the limits a float32 program is held to here, whatever
# the chip's bf16 ones are
F32 = {"tolerances": {"logits_rel": 2e-5, "loss_abs": 2e-5, "grad_norm_rel": 2e-5,
                      "grad_leaf_rel": 1e-4},
       "routing": {"max_share": 0.0, "max_margin": 0.0}, "router": {"max_prob_rel": 1e-5}}


def _tiny(dtype):
    cfg = read(f"{ROOT}/chipbench/configs/lfm2-8b-a1b.json")
    cfg.update(TINY)
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype}
    return cfg


def _reference(cfg, **dots):
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = lfm2.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), lfm2.config(cfg))
    params["expert_bias"] = reference.expert_bias(
        **cfg["recipe"]["expert_bias"], layers=4, experts=cfg["num_experts"])
    return reference.answers(params, tokens, cfg, positions, SAMPLE, **dots)


@pytest.fixture(scope="module")
def ref32():
    return _reference(_tiny("float32"))


def _bf16(x, w):
    return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)).astype(jnp.float32)


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding, routing freely: the same experts
    for every token under the same bias, the same gates, convolution, QK
    norms; the router alone gives the reference's scores."""
    got = routed.routed_check(lfm2, _tiny("float32"), SAMPLE, SEQ, ref32, F32)
    assert got["ok"], got
    assert got["decisions"]["differ_pairs"] == 0 and got["router"]["differ_pairs"] == 0
    assert got["free"]["ok"] and got["free"]["decisions"]["differ_pairs"] == 0
    assert sorted(got["arithmetic"]) == sorted(
        ["grad_norm_rel", "logits_rel", "loss_abs", "ok"]
        + ["grad_rel." + p for p in lfm2.GRAD_LEAVES])
    assert ref32["routing"].shape == (4, 2 * SEQ, 4) and ref32["router_in"].shape[0] == 4
    # what decided: sigmoid scores plus a bias of a hundredth
    assert 0.3 < float(ref32["p_kth"].min()) and float(ref32["p_kth"].max()) < 1.05


def test_the_program_is_given_the_references_bias_and_hands_the_optimizer_none():
    cfg = _tiny("float32")
    pc = lfm2.config(cfg)
    init_, loss_, _ = lfm2.program()
    params = init_(jax.random.PRNGKey(0), pc)
    assert "expert_bias" not in params  # adamw with weight decay sees every leaf of this
    assert lfm2.num_params(cfg) == pc.num_params() == 4 * 16 + sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    given = lfm2._with_bias(params, pc)["expert_bias"]
    want = reference.expert_bias(**cfg["recipe"]["expert_bias"], layers=4, experts=16)
    np.testing.assert_array_equal(np.asarray(given), np.asarray(want))
    assert 0.005 < float(jnp.std(want)) < 0.02 and cfg["recipe"]["expert_bias"] == lfm2.BIAS
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    _, stats = loss_(params, tokens, tokens, pc, with_stats=True)
    assert 0.0 < float(stats["bias_moved_share"]) < 1.0
    with pytest.raises(ValueError, match="expert_bias"):
        lfm2.config({**cfg, "recipe": {**cfg["recipe"],
                                        "expert_bias": {"seed": 1, "scale": 0.01}}})


def test_bf16_under_replay_is_inside_what_tiny_widths_allow():
    """The chip cell's comparison: the program in bf16 replaying the
    reference's routing; where its own choices differ the reference had a
    near-tie; bf16 is visible, so the comparison is not vacuous. (64 rows an
    expert and 256 tokens: the loss and the norms by expert are noisier than
    the chip's 1,024 rows and 8,192 tokens.)"""
    cfg = _tiny("bfloat16")
    got = routed.routed_check(lfm2, cfg, SAMPLE, SEQ, _reference(cfg), CHECK)
    b = got["arithmetic"]
    assert 1e-3 < b["logits_rel"] < 0.04 and b["grad_norm_rel"] < 1e-3, got
    assert all(v < 0.08 for k, v in b.items() if k.startswith("grad_rel.")), got
    assert got["router"]["ok"] and got["router"]["prob_rel"] < 1e-5, got  # float32 on the CPU
    assert got["decisions"]["differ_max_margin"] <= 0.04, got
    assert got["decisions"]["differ_share"] <= 0.1, got


# which part of the check must refuse each fault, and by what
REFUSED_BY = {
    "bias_not_in_selection": ("decisions", "router"),
    "gates_from_biased": ("arithmetic",),
    "lost_tap": ("arithmetic", "decisions"),
    "no_qk_norm": ("arithmetic",),
    # no fault of the five: the control of the payload's precision
    "fp8_experts": ("arithmetic",),
}


@pytest.mark.parametrize("name", sorted(REFUSED_BY))
def test_each_fault_in_the_program_is_refused(name, ref32):
    """The faults of ``benchmarks/lfm2_check_faults.py`` in a float32 program
    against the float32 reference: each is refused by the part that exists
    for it, at limits a float32 program passes."""
    jax.clear_caches()
    with faults.fault(name):
        got = routed.routed_check(lfm2, _tiny("float32"), SAMPLE, SEQ, ref32, F32)
    jax.clear_caches()
    assert not got["ok"], got
    for part in REFUSED_BY[name]:
        assert not got[part]["ok"], (part, got[part])
    b = got["arithmetic"]
    if name == "bias_not_in_selection":
        # under replay the arithmetic is the reference's: only the decisions tell
        assert b["ok"] and got["decisions"]["differ_share"] > 0.05
        assert got["router"]["differ_pairs"] > 10
    elif name == "gates_from_biased":
        # the router alone is right, a later layer's decisions move with its
        # input in under 2% of pairs, every element is a percent off: the
        # norms by expert see it several times better than any leaf
        assert got["router"]["ok"] and got["decisions"]["differ_share"] < 0.02
        by_expert = b["grad_rel.layers.04_conv_moe.w_down@expert_norms"]
        assert by_expert > 0.05 and by_expert > 3 * b["grad_rel.layers.04_conv_moe.w_down"]
    elif name == "lost_tap":
        assert b["logits_rel"] > 0.3 and b["grad_rel.layers.02_conv_moe.conv_w"] > 0.3
    elif name == "fp8_experts":
        # three mantissa bits on the experts' operands: percents everywhere,
        # a thousand times what a float32 program is allowed
        assert b["logits_rel"] > 0.02 and b["grad_rel.layers.04_conv_moe.w_down"] > 0.03
        assert got["router"]["ok"]
    elif name == "no_qk_norm":
        assert b["grad_rel.layers.01_attn_moe.q_norm"] > 0.99  # no gradient reaches it


def test_a_bf16_router_fails_the_router_alone(ref32):
    """Part C, on the reference's own router inputs: a router product in one
    bf16 pass (what a TPU does with float32 operands unless told otherwise;
    the CPU's default precision is float32, so the product is rounded here)
    moves the scores by some 1e-3 and is refused at the written limit;
    another summation order is not."""
    cfg = _tiny("float32")
    pc = lfm2.config(cfg)
    params = lfm2.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), pc)
    bias = reference.expert_bias(**cfg["recipe"]["expert_bias"], layers=4, experts=16)

    def routers(product):
        names = [n for n, kind, _ in pc.runs() if kind[1] == "moe"]
        s = jnp.stack([jax.nn.sigmoid(product(x, params["layers"][n]["router"][0])) + b
                       for x, n, b in zip(jnp.asarray(ref32["router_in"]), names, bias)])
        top_p, top_i = jax.lax.top_k(s, 5)
        return {"routing": np.asarray(top_i[..., :4]), "p_kth": np.asarray(top_p[..., 3]),
                "p_next": np.asarray(top_p[..., 4])}

    limit = CHECK["router"]
    low = routed.router_precision(routers(_bf16), ref32, limit)
    assert not low["ok"] and low["prob_rel"] > 10 * limit["max_prob_rel"], low
    same = routed.router_precision(
        routers(lambda x, w: sum(x[:, i::4] @ w[i::4] for i in range(4))), ref32, limit)
    assert same["ok"] and same["prob_rel"] < 1e-5, same
    # and the adapter's own router_alone is the reference's, to rounding
    own = routed.router_precision(
        routed.router_answers(lfm2, cfg, SAMPLE, ref32["router_in"]), ref32, limit)
    assert own["ok"] and own["differ_pairs"] == 0, own


def test_the_norms_by_expert_are_what_the_reference_says_they_are():
    g = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 3, 5))
    got = np.asarray(reference._expert_norms(g))
    n = np.sqrt(np.sum(np.square(np.asarray(g)), axis=(-2, -1)))[0]
    np.testing.assert_allclose(got, n - n.mean(), rtol=1e-6)
    sample = {"grad_leaves": ["a.w@expert_norms", "a.w"], "grad_elements": 7}
    out = reference.grad_answers({"a": {"w": g}}, sample)
    np.testing.assert_allclose(np.asarray(out["grad.a.w@expert_norms"]), got, rtol=1e-6)
    assert out["grad.a.w"].shape[0] <= 7 * 2 and abs(float(np.sum(got))) < 1e-5


def test_the_timed_parameters_are_bare_routeds():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json") for n in ("bare-routed", "bare-routed-8k")]
    for key in ("job", "metric", "warmup_steps", "min_steps", "trace_steps"):
        assert both[0][key] == both[1][key]
    # the same sample but for the leaves, which name this tree's: the file
    # says what the adapter makes the job sample
    assert {**both[0]["check"]["sample"], "grad_leaves": lfm2.GRAD_LEAVES} == \
        both[1]["check"]["sample"]
    for part in ("routing", "router"):
        assert "read on the v5e" in both[1]["check"][part]["why"]
    assert "read on the v5e" in both[1]["check"]["tolerances_why"]
