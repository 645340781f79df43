"""The program's loss path against the plain float32 reference, at tiny
widths on the CPU, for both configurations' files — the comparison the
``bare`` job makes at the published widths on the chip."""

import jax
import pytest

from chipbench_helpers import ROOT, read

from chipbench import manifest, reference
from chipbench.worker import llama_config
from torchft_tpu.models.llama import llama_init

bare = manifest.load_module(ROOT, "jobs", "bare")
llama = manifest.load_module(ROOT, "adapters", manifest.DEFAULT_ADAPTER)
# the chip cell's own tolerances and gradient leaves, on a smaller sample
CHECK = read(f"{ROOT}/chipbench/traffic/bare.json")["check"]
TOL, SAMPLE = CHECK["tolerances"], {**CHECK["sample"], "sequences": 2, "positions": 8}
# tiny widths, GQA kept (2 query heads to a kv head)
TINY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, vocab_size=512,
            num_hidden_layers=2)


def _tiny(name, dtype):
    cfg = read(f"{ROOT}/chipbench/configs/{name}.json")
    cfg.update(TINY)
    cfg["recipe"] = {**cfg["recipe"], "param_dtype": dtype}
    return cfg


def _reference(cfg, seq=256, crush=None, **kw):
    tokens, positions = reference.check_sample(cfg, SAMPLE, seq)
    params = llama_init(jax.random.PRNGKey(SAMPLE["seed"]), llama_config(cfg))
    if crush:
        params = jax.tree_util.tree_map(crush, params)
    return reference.answers(params, tokens, cfg, positions, SAMPLE, **kw)


def _both(cfg, seq=256):
    return bare.system_answers(llama, cfg, SAMPLE, seq), _reference(cfg, seq)


@pytest.mark.parametrize("name", ["mistral-7b", "internlm2-1.8b"])
def test_same_equations_in_float32(name):
    """In f32 both sides agree to rounding: the reference and the program
    compute the same model (rotary halves, GQA head mapping, norms)."""
    got = bare.compare(*_both(_tiny(name, "float32")), TOL)
    assert sorted(got) == ["grad_norm_rel", "grad_rel.embed", "grad_rel.layers.w_down",
                           "grad_rel.layers.wq", "logits_rel", "loss_abs", "ok"]
    assert all(v < 1e-5 for k, v in got.items() if k != "ok"), got


@pytest.mark.parametrize("name", ["mistral-7b", "internlm2-1.8b"])
def test_bf16_is_inside_the_tolerance_and_far_from_float32(name):
    got = bare.compare(*_both(_tiny(name, "bfloat16")), TOL)
    assert got["ok"], got
    assert got["logits_rel"] > 1e-3  # bf16 rounding is visible: not vacuous
    assert got["grad_rel.layers.wq"] > 1e-3


def test_a_lower_precision_fails_the_tolerance():
    """Weights rounded to 4 mantissa bits (what an fp8-like path would do)
    land outside the logits tolerance."""
    import jax.numpy as jnp
    import numpy as np

    cfg = _tiny("mistral-7b", "bfloat16")
    system, ref = _both(cfg)

    def crush(x):
        x = np.asarray(x, np.float32)
        m, e = np.frexp(x)
        return jnp.asarray(np.ldexp(np.round(m * 16) / 16, e), jnp.bfloat16)

    low = bare.compare(_reference(cfg, crush=crush), ref, TOL)
    assert not low["ok"] and low["logits_rel"] > TOL["logits_rel"]
    assert bare.compare(system, ref, TOL)["ok"]


def _dot_with_backward_in(dtype):
    """x @ w whose forward pass is exact and whose backward pass rounds its
    three operands to ``dtype`` (scaled per tensor, as fp8 training does)."""
    import jax.numpy as jnp

    def q(a):
        if jnp.issubdtype(dtype, jnp.floating) and jnp.finfo(dtype).bits == 8:
            top = float(jnp.finfo(dtype).max)
            s = jnp.max(jnp.abs(a)) / top
            return (a / s).astype(dtype).astype(jnp.float32) * s
        return a.astype(dtype).astype(jnp.float32)

    @jax.custom_vjp
    def dot(x, w):
        return jnp.matmul(x, w)

    def bwd(res, g):
        x, w = (q(r) for r in res)
        g = q(g)
        return jnp.matmul(g, w.T), jnp.einsum("...i,...o->io", x, g)

    dot.defvjp(lambda x, w: (jnp.matmul(x, w), (x, w)), bwd)
    return dot


@pytest.mark.parametrize("dtype", ["float8_e4m3fn", "float8_e5m2"])
def test_a_lower_precision_backward_fails_the_tolerance(dtype):
    """A backward pass in fp8 behind an exact forward pass: logits and loss
    agree, so only the gradient checks can refuse it. The sampled leaves do;
    the global norm, where rounding averages out, hardly moves."""
    import jax.numpy as jnp

    cfg = _tiny("internlm2-1.8b", "bfloat16")
    ref = _reference(cfg)
    low = bare.compare(_reference(cfg, dot=_dot_with_backward_in(getattr(jnp, dtype))),
                       ref, TOL)
    assert low["logits_rel"] < 1e-5 and low["loss_abs"] < 1e-5
    assert not low["ok"]
    assert min(v for k, v in low.items() if k.startswith("grad_rel.")) > TOL["grad_leaf_rel"]
    assert low["grad_norm_rel"] < 0.02  # the old tolerance on the norm alone let it pass
    same = bare.compare(_reference(cfg, dot=_dot_with_backward_in(jnp.float32)), ref, TOL)
    assert same["ok"] and same["grad_rel.embed"] < 1e-5


def test_the_reference_answers_are_independent_of_the_tolerances():
    """The cache key of the reference's answers covers the sample, not the
    tolerances: tightening one does not recompute them."""
    assert set(CHECK) == {"sample", "tolerances"}
    assert not set(CHECK["sample"]) & set(TOL)


def test_unsupported_keys_are_refused():
    cfg = _tiny("mistral-7b", "bfloat16")
    with pytest.raises(ValueError):
        llama_config({**cfg, "sliding_window": 4096})
    with pytest.raises(ValueError):
        llama_config({**cfg, "head_dim": 64})


def test_the_default_adapter_names_what_stood_there():
    """``llama`` is the arithmetic that stood in worker.py, reference.py and
    flops.py, named: the same objects, so the accepted cells' numbers and the
    reference's cache key cannot move."""
    import os

    from chipbench import flops, worker

    assert llama.reference is reference
    assert llama.reference.__file__ == os.path.join(ROOT, "chipbench", "reference.py")
    assert llama.GRAD_LEAVES is None
    assert llama.train_flops_per_token is flops.train_flops_per_token
    assert llama.num_params is flops.num_params
    assert llama.KERNEL_COSTS == {"attention": flops.attention_kernel_cost}
    cfg = _tiny("mistral-7b", "bfloat16")
    assert llama.config(cfg) == llama_config(cfg) and llama.layers_with(cfg, "attention") == 2
    script, select = llama.register(dict(cfg, name="named-by-llama"))
    assert (script, select) == (worker.TRAINER, ["--config", "named-by-llama"])
    from torchft_tpu.models.llama import CONFIGS, llama_forward, llama_loss

    assert CONFIGS.pop("named-by-llama") == llama_config(cfg)
    assert llama.program() == (llama_init, llama_loss, llama_forward)


def test_the_reference_cache_key_is_the_parents(tmp_path):
    """The key of ``mistral-7b.bare``'s cached answers: the sample as the
    traffic file has it, the configuration file and reference.py, hashed as
    before this PR, so a checkout that holds the parent's .chipbench_cache
    starts no child."""
    import hashlib
    import json
    import os

    import numpy as np

    cell = manifest.Cell(ROOT, manifest.load(ROOT), "mistral-7b.bare")
    sample = bare.check_sample_of(cell, cell.adapter())
    assert sample is cell.traffic["check"]["sample"]
    digest = hashlib.sha256(json.dumps(CHECK["sample"], sort_keys=True).encode())
    for p in (f"{ROOT}/chipbench/configs/mistral-7b.json", f"{ROOT}/chipbench/reference.py"):
        digest.update(open(p, "rb").read())
    parents = tmp_path / f"reference_mistral-7b_{digest.hexdigest()[:16]}.npz"
    np.savez(parents, platform="tpu", loss=1.5)
    got = bare._reference_answers(cell, cell.adapter(), sample, str(tmp_path))  # no child
    assert float(got["loss"]) == 1.5 and os.listdir(tmp_path) == [parents.name]


@pytest.mark.parametrize("seed", [0, 2**31 - 1, 2147485001, 2**31 + 2**30])
def test_a_seed_over_int32_reaches_the_jitted_init(seed):
    """``--seed`` goes to a jitted function as an int32; the contract allows
    a little more than 2**31. What the job hands on is inside int32, the same
    for a seed that already was, and the key builds."""
    import jax.numpy as jnp

    for s in (seed % bare.SEEDS, (seed + 1) % bare.SEEDS):
        assert 0 <= s < 2**31
        key = jax.jit(lambda x: jax.random.PRNGKey(x))(s)
        assert key.shape == (2,) and key.dtype == jnp.uint32
    if seed < 2**31 - 1:
        assert (seed % bare.SEEDS, (seed + 1) % bare.SEEDS) == (seed, seed + 1)
    else:
        with pytest.raises(OverflowError):
            jax.jit(lambda x: jax.random.PRNGKey(x))(seed + 1)
