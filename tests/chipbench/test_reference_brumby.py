"""Brumby's decoder against its plain float32 reference at tiny widths on the
CPU, through the ``bare`` job kind's own check as
``brumby-14b-base.bare-retention`` makes it at the published widths on the
chip: the program in float32 to rounding (logits, loss, gradient norm, the
sampled leaves), the reference's blocks against its whole forward pass, and
each fault of ``benchmarks/brumby_check_faults.py`` put into the program as
that script puts it in on the chip, seen in the one layer it breaks."""

import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest

bare = manifest.load_module(ROOT, "jobs", "bare")
brumby = manifest.load_module(ROOT, "adapters", "brumby")
reference = brumby.reference
_spec = importlib.util.spec_from_file_location(
    "brumby_check_faults", f"{ROOT}/benchmarks/brumby_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)
CHECK = read(f"{ROOT}/chipbench/traffic/bare-retention.json")["check"]
SAMPLE = {**CHECK["sample"], "positions": 8, "grad_elements": 4096}
SEQ = 96  # three blocks of the kernel (four chunks of 8 each), six of the feed-forward
# tiny widths, the architecture kept: two query heads a key/value head, a
# head size that is not hidden / heads, a feed-forward twice the hidden size
TINY = dict(hidden_size=48, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            intermediate_size=96, vocab_size=512, num_hidden_layers=2)
RECIPE = dict(param_dtype="float32", ffn_block=16, retention_chunk=8, loss_chunk=32)
# float32 on the CPU: the limits a float32 program is held to here, whatever
# the chip's bf16 ones are
F32 = {"logits_rel": 1e-4, "loss_abs": 2e-5, "grad_norm_rel": 5e-5, "grad_leaf_rel": 5e-4}


def tiny(**recipe):
    cfg = read(f"{ROOT}/chipbench/configs/brumby-14b-base.json")
    cfg.update(TINY)
    cfg["recipe"] = {**cfg["recipe"], **RECIPE, **recipe}
    return cfg


@pytest.fixture(scope="module")
def ref32():
    cfg = tiny()
    tokens, positions = reference.check_sample(cfg, SAMPLE, SEQ)
    params = brumby.program()[0](jax.random.PRNGKey(SAMPLE["seed"]), brumby.config(cfg))
    blocks = {"ROWS": 32, "ROWS_FFN": 48, "HEAD_BLOCK": 32}
    old = {k: getattr(reference, k) for k in blocks}
    for k, v in blocks.items():  # several blocks of each at this length
        setattr(reference, k, v)
    try:
        return params, tokens, positions, reference.answers(params, tokens, cfg, positions, SAMPLE)
    finally:
        for k, v in old.items():
            setattr(reference, k, v)


def test_same_equations_in_float32(ref32):
    """In f32 both sides agree to rounding: the chunked kernels against the
    masked quadratic form, the grouped heads, the per-head norms, the gate,
    the feed-forward and the loss in blocks."""
    got = bare.compare(bare.system_answers(brumby, tiny(), SAMPLE, SEQ), ref32[3], F32)
    assert got["ok"], got
    assert sorted(k[9:] for k in got if k.startswith("grad_rel.")) == sorted(brumby.GRAD_LEAVES)


def test_the_references_blocks_are_its_whole_forward_pass(ref32):
    """``answers`` layer by layer and block by block is ``forward`` and
    ``loss`` differentiated as they stand, and its smallest normaliser the
    program's counter."""
    params, tokens, positions, got = ref32
    cfg = tiny()

    def whole(p):
        logits = reference.forward(p, tokens, cfg)
        return reference.loss(logits, tokens), logits

    with jax.default_matmul_precision("highest"):
        (value, logits), grads = jax.jit(jax.value_and_grad(whole, has_aux=True))(params)
    np.testing.assert_allclose(np.asarray(logits[:, positions]), got["logits"],
                               rtol=1e-3, atol=1e-4)
    assert abs(float(value) - got["loss"]) < 1e-5
    want = reference.grad_answers(grads, SAMPLE)
    assert abs(float(want["grad_norm"]) / got["grad_norm"] - 1) < 1e-5
    for path in brumby.GRAD_LEAVES:
        np.testing.assert_allclose(np.asarray(want["grad." + path]), got["grad." + path],
                                   rtol=2e-3, atol=1e-6)
    ours = faults.den_min(brumby, cfg, SAMPLE, SEQ)
    assert abs(ours / got["den_min"] - 1) < 5e-3 and got["den_min"] > 0


def test_the_adapter_refuses_what_the_program_cannot_express():
    cfg = tiny()
    for key, value, word in (("hidden_act", "gelu", "hidden_act"),
                             ("attention_bias", True, "attention_bias"),
                             ("tie_word_embeddings", True, "tie_word_embeddings"),
                             ("use_sliding_window", True, "use_sliding_window"),
                             ("rope_scaling", {"type": "yarn"}, "rope_scaling"),
                             ("num_experts", 8, "num_experts")):
        with pytest.raises(ValueError, match=word):
            brumby.config({**cfg, key: value})


# the least each fault moves one layer's output, in float32, off the
# reference's; ``scale_outside`` moves it by nothing that can be seen and is
# told by the normaliser (the last column: its ratio to the reference's)
SEEN = {"bf16_state": 2e-4, "no_gate": 0.05, "no_normaliser": 0.5, "no_sqrt2": 0.05,
        "no_rope": 0.1, "scale_outside": 0.0}


@pytest.fixture(scope="module")
def layer():
    """One layer: its input, its weights, the reference's output and its
    smallest normaliser."""
    cfg = tiny()
    params = brumby.program()[0](jax.random.PRNGKey(3), brumby.config(cfg))
    w = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["00_retention"])
    h = jax.random.normal(jax.random.PRNGKey(5), (1, SEQ, 48))
    with jax.default_matmul_precision("highest"):
        want, den = jax.jit(lambda w: reference.layer(w, h, cfg))(w)
    return cfg, h, w, want, float(den)


@pytest.mark.parametrize("name", ["program"] + sorted(SEEN))
def test_each_fault_in_the_program_shows_in_its_layer(name, layer):
    """The program's layer in float32 is the reference's to rounding; with a
    fault of ``benchmarks/brumby_check_faults.py`` in, the layer is off by at
    least the share stated. (Whether the cell's CHECK refuses the fault is
    the chip's to say: PERF.md section 6, PR 56.)"""
    from torchft_tpu.models import brumby as M

    cfg, h, w, want, den = layer
    pc = brumby.config(cfg)

    def off():
        got, stats = M._bodies(pc, SEQ, None)("retention")(h, (w, None, None))
        return (float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want - h)),
                float(stats["den_min"]) / den)

    if name == "program":
        moved, ratio = off()
        assert moved < 2e-5 and abs(ratio - 1) < 5e-3
        assert set(SEEN) == set(faults.FAULTS)
        return
    with faults.fault(name):
        moved, ratio = off()
    if name == "scale_outside":
        assert moved < 1e-3 and abs(ratio / 16 ** 0.5 - 1) < 5e-3
    else:
        assert moved > SEEN[name], name


def test_the_timed_parameters_are_bares():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json") for n in ("bare", "bare-retention")]
    for key in ("job", "metric", "warmup_steps", "min_steps"):
        assert both[0][key] == both[1][key]
    assert both[1]["check"]["sample"]["grad_leaves"] == brumby.GRAD_LEAVES
    assert both[1]["check"]["sample"]["sequences"] == 1
    why = both[1]["check"]["tolerances_why"]
    assert "read on the v5e" in why and all(name in why for name in faults.FAULTS)
