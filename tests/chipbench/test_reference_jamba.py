"""The Mamba / attention hybrid against its plain float32 reference at tiny
widths on the CPU, through the ``bare`` job kind's own check as
``jamba2-3b.bare-scan`` makes it at the published widths on the chip: the
program in float32 to rounding (logits, loss, every gradient leaf), in bf16
inside what the tiny widths allow, the reference's blocks against the same
equations all at once, and the two faults the check exists for."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest

bare = manifest.load_module(ROOT, "jobs", "bare")
jamba = manifest.load_module(ROOT, "adapters", "jamba")
reference = jamba.reference
CHECK = read(f"{ROOT}/chipbench/traffic/bare-scan.json")["check"]
TOL = CHECK["tolerances"]
SEQ = 320  # over one stretch of the reference's scan, and no multiple of it
# tiny widths, the architecture kept: both kinds of layer twice, one
# key/value head, a tied head, the three inner norms
TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
            num_key_value_heads=1, vocab_size=512, num_hidden_layers=8,
            attn_layer_period=4, attn_layer_offset=2, mamba_dt_rank=8)


def _tiny(dtype, **over):
    cfg = read(f"{ROOT}/chipbench/configs/jamba2-3b.json")
    cfg.update(TINY, **over)
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype, "loss_chunk": 64}
    return cfg


def _every_leaf(cfg):
    shapes = jax.eval_shape(lambda: jamba.program()[0](jax.random.PRNGKey(0),
                                                       jamba.config(cfg)))
    return [".".join(k.key for k in path)
            for path, _ in jax.tree_util.tree_leaves_with_path(shapes)]


def _sample(cfg, leaves=None):
    return {**CHECK["sample"], "sequences": 2, "positions": 8,
            "grad_leaves": leaves or jamba.GRAD_LEAVES}


def _reference(cfg, sample, **controls):
    tokens, positions = reference.check_sample(cfg, sample, SEQ)
    params = jamba.program()[0](jax.random.PRNGKey(sample["seed"]), jamba.config(cfg))
    return reference.answers(params, tokens, cfg, positions, sample, **controls)


@pytest.fixture(scope="module")
def f32():
    cfg = _tiny("float32")
    sample = _sample(cfg, _every_leaf(cfg))
    return cfg, sample, _reference(cfg, sample), bare.system_answers(jamba, cfg, sample, SEQ)


def test_same_equations_in_float32_every_gradient_leaf(f32):
    cfg, sample, ref, system = f32
    got = bare.compare(system, ref, TOL)
    leaves = _every_leaf(cfg)
    assert len(leaves) == 2 + 3 * 17 + 2 * 9  # embed, final_norm, three Mamba runs, two attention
    assert sorted(got) == sorted(["logits_rel", "loss_abs", "grad_norm_rel", "ok"]
                                 + ["grad_rel." + p for p in leaves])
    assert got["ok"] and all(v < 1e-5 for k, v in got.items() if k != "ok"), got


def test_the_references_blocks_are_its_equations_all_at_once(f32):
    cfg, sample, ref, _ = f32
    tokens, positions = reference.check_sample(cfg, sample, SEQ)
    params = jamba.program()[0](jax.random.PRNGKey(sample["seed"]), jamba.config(cfg))

    def both(p):
        logits = reference.forward(p, tokens, cfg)
        return reference.loss(logits, tokens), logits[:, positions]

    (val, logits), grads = jax.jit(jax.value_and_grad(both, has_aux=True))(params)
    whole = {"logits": logits, "loss": val, **reference.grad_answers(grads, sample)}
    got = bare.compare(whole, ref, TOL)
    assert got["ok"] and all(v < 1e-5 for k, v in got.items() if k != "ok"), got
    assert reference.kinds(cfg) == jamba.config(cfg).layers_block_type
    assert ref["grad.embed"].shape == whole["grad.embed"].shape


@pytest.mark.parametrize("fault,controls", [
    ("bf16_state", {"state_dtype": jnp.bfloat16}), ("lost_carry", {"reset_every": 16})])
def test_the_check_refuses_a_fault_in_the_recurrence(f32, fault, controls):
    """The reference itself with the fault, held against the reference: not
    correct under the cell's own limits (on the chip, at the published
    widths, the program with these faults reads 0.12 and 0.41 of the logits:
    traffic/bare-scan.json)."""
    cfg, sample, ref, _ = f32
    small = _sample(cfg)
    bad = _reference(cfg, small, **controls)
    got = bare.compare(bad, {k: ref[k] for k in bad}, TOL)
    assert not got["ok"], got
    if fault == "lost_carry":
        assert got["logits_rel"] > TOL["logits_rel"] and got["loss_abs"] > TOL["loss_abs"]
    else:  # tiny widths: the state's rounding shows where the limits are tight
        assert got["logits_rel"] > 1e-2 and got["grad_rel.layers.00_mamba.x_proj"] > 3e-2


def _faults():
    spec = importlib.util.spec_from_file_location(
        "jamba_check_faults", os.path.join(ROOT, "benchmarks", "jamba_check_faults.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("fault", ["bf16_state", "zeroed_carry"])
def test_the_program_with_a_fault_in_its_kernels_is_refused(f32, fault):
    """What benchmarks/jamba_check_faults.py reads on the chip, at tiny
    widths: the program's own kernels with the state in bf16, or with the
    carry across chunks zeroed (chunks of 16 here), against the reference."""
    from torchft_tpu.ops import selective_scan as ss

    cfg, sample, ref, system = f32
    small = _sample(cfg)
    clean = bare.compare(system, ref, TOL)
    if fault == "bf16_state":
        ss.STATE_DTYPE = jnp.bfloat16
        undo = lambda: setattr(ss, "STATE_DTYPE", jnp.float32)  # noqa: E731
    else:
        chunk, ss.CHUNK = ss.CHUNK, 16
        undo_carry = _faults().no_carry(ss)
        undo = lambda: (undo_carry(), setattr(ss, "CHUNK", chunk))  # noqa: E731
    try:
        jax.clear_caches()
        got = bare.compare(bare.system_answers(jamba, cfg, small, SEQ),
                           {k: ref[k] for k in ("logits", "loss", "grad_norm")
                            + tuple("grad." + p for p in small["grad_leaves"])}, TOL)
    finally:
        undo()
        jax.clear_caches()
    assert got["logits_rel"] > 1000 * clean["logits_rel"], (got, clean)
    assert not got["ok"] or fault == "bf16_state", got
    assert ss.STATE_DTYPE == jnp.float32 and ss.CHUNK == 256


def test_bf16_program_is_inside_the_cells_tolerances():
    """The chip cell's comparison at the tiny preset's shape, both kinds of
    layer twice: logits and every sampled leaf inside the traffic file's
    limits as they stand. Its two limits on AVERAGES (the loss over the
    tokens, the norm over the parameters) are set for 8,192 tokens and 1.6e9
    parameters; 640 tokens average 3.6 times less rounding away, so they are
    held at that multiple here."""
    cfg = _tiny("bfloat16")
    sample = _sample(cfg, jamba.GRAD_LEAVES + ["layers.01_attn.wq", "layers.02_mamba.A_log",
                                               "layers.04_mamba.dt_bias", "final_norm"])
    fewer = (8192 / (sample["sequences"] * SEQ)) ** 0.5
    got = bare.compare(bare.system_answers(jamba, cfg, sample, SEQ),
                       _reference(cfg, sample),
                       {**TOL, "loss_abs": TOL["loss_abs"] * fewer,
                        "grad_norm_rel": TOL["grad_norm_rel"] * fewer})
    assert got["ok"], got
    assert got["logits_rel"] > 1e-3  # bf16 is visible: not vacuous


def test_the_tolerances_stand_with_their_readings_and_reasons():
    both = [read(f"{ROOT}/chipbench/traffic/{n}.json") for n in ("bare", "bare-scan")]
    for key in ("job", "metric", "warmup_steps", "min_steps", "trace_steps"):
        assert both[0][key] == both[1][key]
    assert both[1]["check"]["sample"] == both[0]["check"]["sample"]
    why = both[1]["check"]["tolerances_why"]
    for reading in ("0.0361", "0.1228", "0.414", "0.0621", "0.1873"):
        assert reading in why
    # between the program's reading and the nearest precision below, both ways
    assert 0.0361 * 1.5 < TOL["logits_rel"] < 0.1228 / 1.5
    assert 0.0621 * 1.5 < TOL["grad_leaf_rel"] < 0.1873 / 1.5
    assert 0.00012 * 1.5 < TOL["loss_abs"] < 0.00134 / 1.5
    assert TOL["grad_norm_rel"] == both[0]["check"]["tolerances"]["grad_norm_rel"]


def test_sampled_elements_of_a_stack_are_those_of_its_layers_in_turn():
    g = jnp.arange(5 * 7 * 3, dtype=jnp.float32).reshape(5, 7, 3)
    whole = reference._sampled(g.reshape(-1), g.size, 10)
    parts = [reference._sampled(g[i].reshape(-1), g.size, 10, i * g[i].size)
             for i in range(5)]
    np.testing.assert_array_equal(np.concatenate(parts), whole)
    assert len(whole) <= 10
