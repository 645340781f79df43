"""The looped decoder against its plain float32 reference at tiny widths on
the CPU, through the ``bare`` job kind's own check as
``ouro-2.6b.bare-loop-4k`` makes it at the published widths on the chip: the
program in float32 to rounding (every exit's logits, the loss, every
gradient leaf), the reference's blocks against its whole forward, bf16 inside
the cell's limits, and each fault of ``benchmarks/ouro_check_faults.py`` put
into the program as that script puts it in on the chip."""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest

bare = manifest.load_module(ROOT, "jobs", "bare")
ouro = manifest.load_module(ROOT, "adapters", "ouro")
reference = ouro.reference
_spec = importlib.util.spec_from_file_location(
    "ouro_check_faults", f"{ROOT}/benchmarks/ouro_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
CHECK = read(f"{ROOT}/chipbench/traffic/bare-loop-4k.json")["check"]
SAMPLE = {**CHECK["sample"], "sequences": 2, "positions": 8,
          "grad_leaves": ouro.GRAD_LEAVES + faults.MORE_LEAVES, "grad_elements": 4096}
SEQ = 48  # not a multiple of the reference's block of positions below
# tiny widths, the architecture kept: four passes over three sandwich-norm
# layers, four heads of 16 with as many key/value heads, an untied head
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, vocab_size=512, num_hidden_layers=3,
            layer_types=["full_attention"] * 3)
# float32 on the CPU: the limits a float32 program is held to here, whatever
# the chip's bf16 ones are
F32 = {"logits_rel": 1e-4, "loss_abs": 2e-5, "grad_norm_rel": 5e-5, "grad_leaf_rel": 5e-4}


def tiny(dtype="float32", **keys):
    cfg = read(f"{ROOT}/chipbench/configs/ouro-2.6b.json")
    cfg.update(TINY, **keys)
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype, "seq_len": SEQ, "loss_chunk": 16}
    return cfg


def _params(cfg):
    return ouro.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), ouro.config(cfg))


def _reference(cfg, **dots):
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    return reference.answers(_params(cfg), tokens, cfg, positions, SAMPLE, **dots)


@pytest.fixture(scope="module")
def ref32():
    reference.HEAD_BLOCK = 20  # three blocks of positions, the last one short
    return _reference(tiny())


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding through the cell's own check: the
    sandwich norms, the passes over one stack, the norm between them, the
    last exit's logits, the expected-exit loss in chunks, every sampled
    leaf."""
    got = bare.compare(bare.system_answers(ouro, tiny(), SAMPLE, SEQ), ref32, F32)
    assert got["ok"], got
    assert sorted(got) == sorted(["grad_norm_rel", "logits_rel", "loss_abs", "ok"]
                                 + ["grad_rel." + p for p in SAMPLE["grad_leaves"]])
    assert ref32["logits"].shape == (2, 8, 512)


def test_every_exits_logits_the_loss_and_every_gradient_leaf():
    """Not the sample: all four exits' logits, and ``value_and_grad`` of both
    sides' whole loss, leaf by leaf of the tree."""
    from torchft_tpu.models.ouro import ouro_exit_logits

    cfg = tiny()
    pc, params = ouro.config(cfg), _params(cfg)
    tokens, _ = reference.check_sample(cfg, SAMPLE, SEQ)
    with jax.default_matmul_precision("highest"):
        got = ouro_exit_logits(params, tokens, pc)
        want = reference.exit_logits(params, tokens, cfg)
        ours, g_ours = jax.value_and_grad(
            lambda p: ouro.program()[1](p, tokens, tokens, pc, remat="full"))(params)
        theirs, g_theirs = jax.value_and_grad(
            lambda p: reference.loss(p, tokens, tokens, cfg))(params)
    assert got.shape == want.shape == (4, 2, SEQ, 512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-4)
    assert abs(float(ours) - float(theirs)) < 2e-5
    assert jax.tree_util.tree_structure(g_ours) == jax.tree_util.tree_structure(g_theirs)
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))), g_ours, g_theirs)
    assert max(jax.tree_util.tree_leaves(worst)) < 1e-4, worst
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree_util.tree_leaves(g_theirs))


def test_the_blocks_of_answers_are_the_whole_forward(ref32):
    """``answers`` in blocks (a layer application at a time backwards, the
    exits in blocks of positions) against ``forward`` and ``loss`` at once."""
    cfg = tiny()
    params = _params(cfg)
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    with jax.default_matmul_precision("highest"):
        logits = reference.forward(params, tokens, cfg)
        value, grads = jax.value_and_grad(
            lambda p: reference.loss(p, tokens, tokens, cfg))(params)
        want = reference.grad_answers(grads, SAMPLE)
    np.testing.assert_allclose(np.asarray(logits[:, positions]), ref32["logits"],
                               rtol=1e-3, atol=1e-4)
    assert abs(float(value) - ref32["loss"]) < 1e-5
    for k, v in want.items():
        np.testing.assert_allclose(ref32[k], np.asarray(v), rtol=2e-3, atol=1e-6, err_msg=k)


def test_the_reference_writes_the_exit_distribution_out():
    lam = jnp.asarray([[0.5, 0.2], [0.5, 0.0], [0.5, 1.0], [0.9, 0.3]])
    p = np.asarray(reference.exit_probs(lam))
    np.testing.assert_allclose(p[:, 0], [0.5, 0.25, 0.125, 0.125])
    np.testing.assert_allclose(p[:, 1], [0.2, 0.0, 0.8, 0.0])  # the last takes what is left
    np.testing.assert_allclose(p.sum(axis=0), 1.0)


def test_the_adapter_refuses_what_the_program_cannot_express():
    cfg = tiny()
    for key, value, word in (("early_exit_threshold", 0.5, "early_exit_threshold"),
                             ("sliding_window", 4096, "sliding_window"),
                             ("use_sliding_window", True, "use_sliding_window"),
                             ("tie_word_embeddings", True, "tie_word_embeddings"),
                             ("hidden_act", "gelu", "hidden_act"),
                             ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
                             ("attention_bias", True, "attention_bias"),
                             ("num_experts", 8, "num_experts"),
                             ("head_dim", 32, "head_dim"),
                             ("layer_types", ["full_attention"] * 2, "layer_types"),
                             ("layer_types", ["sliding_attention"] * 3, "layer_types"),
                             ("total_ut_steps", 0, "total_ut_steps")):
        with pytest.raises(ValueError, match=word):
            ouro.config({**cfg, key: value})


def test_params_and_flops_count_layer_applications():
    cfg = read(f"{ROOT}/chipbench/configs/ouro-2.6b.json")
    L, T = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    layer, table = 51_388_416, 100_663_296
    assert ouro.num_params(cfg) == ouro.config(cfg).num_params() \
        == L * layer + 2 * table + 2048 + 2049
    assert ouro.num_params({**cfg, "num_hidden_layers": 16}) == 1_023_545_345
    assert ouro.layers_with(cfg, "attention") == T * L
    # 6 FLOP a matrix parameter and token for every APPLICATION of a layer
    # and of the head, nothing for the embedding's lookup, the causal
    # products on top (an eighth of it at 4,096)
    per_token = ouro.train_flops_per_token(cfg, 4096)
    matrices = 6 * T * (L * (layer - 4 * 2048) + table)
    attention = 3 * T * L * 2 * 2 * 2048 * 4097 / 2
    assert per_token == pytest.approx(matrices + attention + 3 * T * 2 * 2048, rel=1e-12)
    # counting the embedding as a matmul would add 6 x 100.7M a pass
    assert per_token - attention < 6 * T * (L * layer + table) < per_token - attention + 6 * T * table
    one = {**cfg, "total_ut_steps": 1}
    assert ouro.train_flops_per_token(one, 4096) == pytest.approx(per_token / T, rel=1e-12)
    att = ouro.KERNEL_COSTS["attention"](cfg, 2, 4096, "fwd")
    assert att["flops"] == 2 * 2 * (2 * 16 * 4096 * 4097 / 2) * 128


def test_bf16_is_inside_the_cells_limits_and_visible():
    """The chip cell's comparison at tiny widths: the program in bf16 against
    the float32 reference of the same bf16-rounded weights."""
    cfg = tiny("bfloat16")
    got = bare.compare(bare.system_answers(ouro, cfg, SAMPLE, SEQ), _reference(cfg),
                       CHECK["tolerances"])
    assert 1e-3 < got["logits_rel"] < 0.1 and got["grad_norm_rel"] < 0.05, got
    assert all(v < 0.3 for k, v in got.items() if k.startswith("grad_rel.")), got


# the least each fault moves the float32 program off the float32 reference,
# by the reading that shows it best
SEEN_IN = {
    "three_passes": ("logits_rel", 0.05),
    "no_norm_between": ("logits_rel", 0.05),
    "last_exit_not_remainder": ("grad_rel.exit_gate.w", 0.05),
    "no_entropy": ("grad_rel.exit_gate.b", 0.05),
    "no_post_norms": ("grad_rel.layers.attn_post_norm", 0.99),
    "one_pass_gradient": ("grad_rel.layers.wq", 0.05),
    "fp8_between": ("logits_rel", 0.01),
    "fp8_residual": ("logits_rel", 0.03),
}


@pytest.mark.parametrize("name", sorted(SEEN_IN))
def test_each_fault_in_the_program_is_refused(name, ref32):
    """The faults of ``benchmarks/ouro_check_faults.py`` in a float32 program
    against the float32 reference: each is refused at limits a float32
    program passes, and the reading put into the sample for it reads what it
    must."""
    assert set(SEEN_IN) == set(faults.FAULTS + faults.BELOW)
    jax.clear_caches()
    with faults.fault(name):
        got = bare.compare(bare.system_answers(ouro, tiny(), SAMPLE, SEQ), ref32, F32)
    jax.clear_caches()
    where, least = SEEN_IN[name]
    assert not got["ok"], got
    assert got[where] > least, (where, got)


def test_the_timed_parameters_are_bares():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json") for n in ("bare", "bare-loop-4k")]
    for key in ("job", "metric", "warmup_steps", "min_steps"):
        assert both[0][key] == both[1][key]
    assert both[0]["check"]["sample"] == both[1]["check"]["sample"]
    why = both[1]["check"]["tolerances_why"]
    assert "read on the v5e" in why
    assert all(name in why for name in faults.FAULTS + faults.BELOW)
