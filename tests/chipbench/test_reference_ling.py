"""Ling's share decoder against its plain float32 reference at tiny widths
on the CPU, through the ``bare_routed`` job kind's own check as
``ling-3.0-flash.bare-kda-32k`` makes it at the published widths on the
chip: the program in float32 to rounding (decisions, arithmetic, the router
alone), in bf16 under replay, and a fault of each layer the cell adds, put
into the program as ``benchmarks/ling_check_faults.py`` puts all nine in on
the chip."""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest

routed = manifest.load_module(ROOT, "jobs", "bare_routed")
ling = manifest.load_module(ROOT, "adapters", "ling")
reference = ling.reference
_spec = importlib.util.spec_from_file_location(
    "ling_check_faults", f"{ROOT}/benchmarks/ling_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
CHECK = read(f"{ROOT}/chipbench/traffic/bare-kda-32k.json")["check"]
# the cell's leaves by the names the tiny cut's four runs have
LEAVES = [p.replace("04_mla", "02_mla").replace("06_kda", "03_kda")
          for p in ling.GRAD_LEAVES]
SAMPLE = {**CHECK["sample"], "sequences": 2, "positions": 8, "grad_leaves": LEAVES}
SEQ = 80  # not a multiple of the kernels' block, nor of the reference's
# tiny widths and published layers 3 to 6, the architecture kept: a dense KDA
# layer, then KDA, MLA and KDA layers with a share of 8 of 32 experts in 4
# groups, four a token in two
TINY = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=32,
            num_attention_heads=4, head_dim=16, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, vocab_size=512, num_experts=8,
            num_experts_per_tok=4, n_group=4, topk_group=2)
DEPLOYMENT = {"experts_held": [8, 8], "router_outputs": 32, "share_room": 4.0,
              "published_layers": [3, 6]}
MOE_LAYERS = 3
# float32 on the CPU: the limits a float32 program is held to here, whatever
# the chip's bf16 ones are
F32 = {"tolerances": {"logits_rel": 1e-4, "loss_abs": 2e-5, "grad_norm_rel": 5e-5,
                      "grad_leaf_rel": 5e-4},
       "routing": {"max_share": 0.0, "max_margin": 0.0}, "router": {"max_prob_rel": 1e-5}}


@pytest.fixture(autouse=True, scope="module")
def one_chunk_blocks():
    """The kernels at one chunk a block: interpreted, four unrolled chunks
    (and eleven in the backward kernel) take four times as long to compile,
    and tests/test_ling.py holds the kernels at their own block."""
    from torchft_tpu.ops import kda

    was, kda.BLOCK = kda.BLOCK, kda.CHUNK
    jax.clear_caches()
    yield
    kda.BLOCK = was
    jax.clear_caches()


def tiny(dtype="float32", **deployment):
    cfg = read(f"{ROOT}/chipbench/configs/ling-3.0-flash.json")
    cfg.update(TINY)
    cfg["deployment"] = {**cfg["deployment"], **DEPLOYMENT, **deployment}
    cfg["num_experts"] = cfg["deployment"]["experts_held"][1]
    cfg["num_hidden_layers"] = 4
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype}
    return cfg


def _reference(cfg, **dots):
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = ling.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), ling.config(cfg))
    params["expert_bias"] = reference.expert_bias(
        **cfg["recipe"]["expert_bias"], layers=MOE_LAYERS, experts=32)
    return reference.answers(params, tokens, cfg, positions, SAMPLE, **dots)


@pytest.fixture(scope="module")
def ref32():
    return _reference(tiny())


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding, routing freely: the delta rule
    token by token against the chunked kernels, the latent attention, the
    group-limited choice under the same bias, the share, the sliced loss;
    the router alone gives the reference's scores."""
    got = routed.routed_check(ling, tiny(), SAMPLE, SEQ, ref32, F32)
    assert got["ok"], got
    assert got["decisions"]["differ_pairs"] == 0 and got["router"]["differ_pairs"] == 0
    assert got["free"]["ok"] and got["free"]["decisions"]["differ_pairs"] == 0
    assert sorted(got["arithmetic"]) == sorted(
        ["grad_norm_rel", "logits_rel", "loss_abs", "ok"]
        + ["grad_rel." + p for p in LEAVES])
    assert ref32["routing"].shape == (MOE_LAYERS, 2 * SEQ, 4)
    assert ref32["router_in"].shape[0] == MOE_LAYERS and ref32["logits"].shape == (2, 8, 512)
    # a token's four experts lie in two of the four groups of eight
    assert (np.array([len(set(row // 8)) for row in ref32["routing"][0]]) <= 2).all()


def test_the_blocks_of_answers_are_the_whole_forward(ref32):
    """``answers`` in blocks against ``forward`` and ``loss`` all at once."""
    cfg = tiny()
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = ling.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), ling.config(cfg))
    params["expert_bias"] = reference.expert_bias(
        **cfg["recipe"]["expert_bias"], layers=MOE_LAYERS, experts=32)
    with jax.default_matmul_precision("highest"):
        logits, routing = reference.forward(params, tokens, cfg)
        value = reference.loss(logits, tokens)
    np.testing.assert_allclose(np.asarray(logits[:, positions]), ref32["logits"],
                               rtol=1e-3, atol=1e-4)
    assert abs(float(value) - ref32["loss"]) < 1e-5
    np.testing.assert_array_equal(np.asarray(routing["routing"]), ref32["routing"])


def test_the_program_is_given_the_references_bias_and_hands_the_optimizer_none():
    cfg = tiny()
    pc = ling.config(cfg)
    init_, loss_, _ = ling.program()
    params = init_(jax.random.PRNGKey(0), pc)
    assert "expert_bias" not in params  # adamw with weight decay sees every leaf of this
    assert ling.num_params(cfg) == pc.num_params() == MOE_LAYERS * 32 + sum(
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))
    given = ling._with_bias(params, pc)["expert_bias"]
    want = reference.expert_bias(**cfg["recipe"]["expert_bias"], layers=MOE_LAYERS, experts=32)
    np.testing.assert_array_equal(np.asarray(given), np.asarray(want))
    assert cfg["recipe"]["expert_bias"] == ling.BIAS
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    _, stats = loss_(params, tokens, tokens, pc, with_stats=True)
    assert 0.0 < float(stats["bias_moved_share"]) < 1.0
    assert float(stats["overflow_pairs"]) == 0 and float(stats["groups_hit_mean"]) <= 2
    assert 0.1 < float(stats["held_pair_share"]) < 0.5  # the even share is a quarter
    with pytest.raises(ValueError, match="expert_bias"):
        ling.config({**cfg, "recipe": {**cfg["recipe"],
                                        "expert_bias": {"seed": 1, "scale": 0.01}}})


def test_a_pair_beyond_the_shares_room_makes_the_loss_no_number():
    """The adapter's loss is what tells the job kind: with a buffer of the
    even share and no room, a toy batch overflows it, the count is not 0 and
    the loss is NaN; the job reads that as not ``correct``."""
    cfg = tiny(share_room=0.25)
    pc = ling.config(cfg)
    init_, loss_, _ = ling.program()
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    value, stats = loss_(init_(jax.random.PRNGKey(0), pc), tokens, tokens, pc,
                         with_stats=True)
    assert float(stats["overflow_pairs"]) > 0 and not np.isfinite(float(value))


def test_the_adapter_refuses_what_the_program_cannot_express():
    cfg = tiny()
    for key, value, word in (("rope_interleave", False, "rope_interleave"),
                             ("mtp_loss_scaling_factor", 0.1, "multi-token"),
                             ("sliding_window", 4096, "sliding_window"),
                             ("num_experts", 4, "held")):
        with pytest.raises(ValueError, match=word):
            ling.config({**cfg, key: value})
    clamped = {**cfg, "expert_swiglu_limit_list": [0, 0, 0, 0, 4] + [0] * 37}
    with pytest.raises(ValueError, match="clamp"):
        ling.config(clamped)


def test_bf16_under_replay_is_inside_what_tiny_widths_allow():
    """The chip cell's comparison: the program in bf16 replaying the
    reference's routing; where its own choices differ the reference had a
    near-tie (of its groups or of its experts); bf16 is visible, so the
    comparison is not vacuous. (Heads of 16 values: a delta rule's scores
    over 16 channels carry a rounding of their inputs on at 2.2 times its
    size a layer where heads of 128 carry it at 1.4, so the distances here
    are several times the chip's.)"""
    cfg = tiny("bfloat16")
    got = routed.routed_check(ling, cfg, SAMPLE, SEQ, _reference(cfg), CHECK)
    b = got["arithmetic"]
    assert 1e-3 < b["logits_rel"] < 0.08 and b["grad_norm_rel"] < 0.03, got
    assert all(v < 0.3 for k, v in b.items() if k.startswith("grad_rel.")), got
    assert got["router"]["ok"] and got["router"]["prob_rel"] < 1e-5, got  # float32 on the CPU
    assert got["decisions"]["differ_max_margin"] <= 0.08, got
    assert got["decisions"]["differ_share"] <= 0.15, got


# which part of the check must refuse each fault, and what shows it: one
# fault of each layer the cell adds (tests/test_ling.py holds all thirteen
# variants of benchmarks/ling_check_faults.py to the part they are put into;
# the whole check on each is the chip's, with its readings in the traffic
# file)
REFUSED_BY = {
    "no_decay": ("arithmetic", "grad_rel.layers.01_kda_moe.A_log", 0.99),
    "no_latent_norm": ("arithmetic", "grad_rel.layers.02_mla_moe.kv_norm", 0.99),
    "no_group_limit": ("router", None, None),
    "scaling_one": ("arithmetic", "grad_rel.layers.03_kda_moe.w_down", 0.3),
}


@pytest.mark.parametrize("name", sorted(REFUSED_BY))
def test_each_fault_in_the_program_is_refused(name, ref32):
    """The faults of ``benchmarks/ling_check_faults.py`` in a float32 program
    against the float32 reference: each is refused by the part that exists
    for it, at limits a float32 program passes, and the leaf that was put
    into the sample for it reads what it must."""
    cfg = tiny()
    jax.clear_caches()
    with faults.fault(name, ling.config(cfg)):
        got = faults.reading(routed, ling, cfg, SAMPLE, SEQ, ref32, F32)
    jax.clear_caches()
    part, leaf, least = REFUSED_BY[name]
    assert not got["ok"] and not got[part]["ok"], got
    if leaf:
        assert got["arithmetic"][leaf] > least, (leaf, got["arithmetic"])
    if name == "no_group_limit":
        # under replay the arithmetic is the reference's: the decisions tell
        assert got["arithmetic"]["ok"] and got["decisions"]["differ_share"] > 0.2
        assert got["router"]["differ_pairs"] > 10


def test_a_bf16_router_fails_the_router_alone(ref32):
    """Part C, on the reference's own router inputs: a router product in one
    bf16 pass is refused at the written limit; the adapter's own
    ``router_alone`` is the reference's to rounding, group scores and
    nearer ties included."""
    cfg = tiny()
    limit = CHECK["router"]
    own = routed.router_precision(
        routed.router_answers(ling, cfg, SAMPLE, ref32["router_in"]), ref32, limit)
    assert own["ok"] and own["differ_pairs"] == 0 and own["prob_rel"] < 1e-6, own
    jax.clear_caches()
    with faults.fault("bf16_router", ling.config(cfg)):
        # the CPU multiplies float32 in float32 whatever the precision asked
        # for: round the router's operands as one bf16 pass would
        from torchft_tpu.models import moe

        matmul = jnp.matmul
        try:
            moe.jnp.matmul = lambda a, b, precision=None, **kw: (
                matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
                if precision is jax.lax.Precision.DEFAULT else matmul(a, b, **kw))
            low = routed.router_precision(
                routed.router_answers(ling, cfg, SAMPLE, ref32["router_in"]), ref32, limit)
        finally:
            moe.jnp.matmul = matmul
    jax.clear_caches()
    assert not low["ok"] and low["prob_rel"] > 10 * limit["max_prob_rel"], low


def test_the_timed_parameters_are_bare_routeds():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json") for n in ("bare-routed", "bare-kda-32k")]
    for key in ("job", "metric", "warmup_steps", "min_steps"):
        assert both[0][key] == both[1][key]
    assert {**both[0]["check"]["sample"], "grad_leaves": ling.GRAD_LEAVES} == \
        both[1]["check"]["sample"]
    for part in ("routing", "router"):
        assert "read on the v5e" in both[1]["check"][part]["why"]
    assert "read on the v5e" in both[1]["check"]["tolerances_why"]
