"""What ``lfm2-8b-a1b.bare-routed-8k``'s check reads on the chip, for the
program as it is and for the five faults it has to refuse:

(a) ``bias_not_in_selection``: the four experts are the top-4 of the scores
    alone;
(b) ``gates_from_biased``: the gates are taken from ``scores + bias``;
(c) ``bf16_router``: the router product in one bf16 pass (the TPU's default
    for float32 operands);
(d) ``lost_tap``: the short convolution's oldest tap zeroed;
(e) ``no_qk_norm``: the per-head RMSNorms of q and k left out.

The check is the cell's own (``chipbench/jobs/bare_routed.py``:
``routed_check`` against ``reference_lfm2.py``'s answers on the fixed sample,
at the published widths, one period, one sequence of 8,192); the faults are
put into ``torchft_tpu/models/`` from here (``FAULTS``), the program has no
switch for them, and the CPU tests put the same ones in at a small size.
More gradient leaves are sampled than the cell samples, so that the readings
say which leaf sees a fault best.

    chiprun -- python3 benchmarks/lfm2_check_faults.py [workload]

One JSON line per variant; exits 2 without a TPU.
"""

import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

LEAVES = ["embed", "layers.00_conv_dense.conv_w", "layers.00_conv_dense.w_down",
          "layers.01_attn_moe.router", "layers.01_attn_moe.q_norm",
          "layers.01_attn_moe.k_norm", "layers.01_attn_moe.wq",
          "layers.01_attn_moe.w_down", "layers.02_conv_moe.conv_w",
          "layers.02_conv_moe.in_proj", "layers.02_conv_moe.router",
          "layers.04_conv_moe.router", "layers.04_conv_moe.w_down",
          "layers.04_conv_moe.w_gate", "layers.01_attn_moe.w_down@expert_norms",
          "layers.04_conv_moe.w_down@expert_norms", "layers.04_conv_moe.w_gate@expert_norms"]


@contextlib.contextmanager
def _patched(module, name, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def _fp8_like(x):
    """``x`` rounded to three mantissa bits, to nearest even (what float8
    e4m3 keeps of a value inside its range; the exponent stays the
    operand's), straight through for the gradient: the products see the
    rounded operands forward and backward, the cotangents are not rounded."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    bits = (bits + 0x7FFFF + ((bits >> 20) & 1)) & jnp.uint32(0xFFF00000)
    q = jax.lax.bitcast_convert_type(bits, jnp.float32).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


def _faults():
    import jax

    from torchft_tpu.models import lfm2, moe

    choose, conv, norm, gmm = moe._choose, lfm2._causal_conv, lfm2._rmsnorm, moe._grouped_matmul
    return {
        "bias_not_in_selection": lambda: _patched(
            moe, "_choose", lambda s, cfg, routing, bias=None: choose(s, cfg, routing)),
        "gates_from_biased": lambda: _patched(
            moe, "_choose", lambda s, cfg, routing, bias=None: choose(
                s if bias is None else s + bias, cfg, routing)),
        "bf16_router": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.DEFAULT),
        # not one of the five: the nearest precision below float32 a TPU has
        # (three bf16 passes), for the reading check.router's limit lies under
        "router_three_passes": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.HIGH),
        # not one of the five: the nearest precision below the PAYLOAD's bf16,
        # the experts' operands (rows and matrices of all twelve grouped
        # products a layer) at fp8's three mantissa bits, for the reading the
        # four tolerances lie under
        "fp8_experts": lambda: _patched(
            moe, "_grouped_matmul", lambda rows, w, sizes: gmm(
                _fp8_like(rows), _fp8_like(w), sizes)),
        "lost_tap": lambda: _patched(
            lfm2, "_causal_conv", lambda x, w, b, **kw: conv(x, w.at[0].set(0), b, **kw)),
        # q and k are the only [B, S, heads, head_dim] a norm is given
        "no_qk_norm": lambda: _patched(
            lfm2, "_rmsnorm", lambda x, w, eps: x if x.ndim == 4 else norm(x, w, eps)),
    }


def fault(name):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults()[name]()


FAULTS = ("bias_not_in_selection", "gates_from_biased", "bf16_router", "lost_tap",
          "no_qk_norm")


def main(argv):
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, argv[0] if argv else "lfm2-8b-a1b.bare-routed-8k")
    job, adapter = cell.job(), cell.adapter()
    cfg, seq = cell.config, cell.config["recipe"]["seq_len"]
    sample = {**job.check_sample_of(cell, adapter), "grad_leaves": LEAVES}
    # a child computes the reference's answers before this process takes the chip
    ref = job._reference_answers(cell, adapter, sample,
                                 os.path.join(ROOT, ".chipbench_cache"))

    import jax

    if jax.devices()[0].platform != "tpu":
        return 2
    check = cell.traffic["check"]

    def reading(name):
        jax.clear_caches()
        got = job.routed_check(adapter, cfg, sample, seq, ref, check)
        print(json.dumps({"variant": name, **got}), flush=True)

    reading("program")
    for name in argv[1:] or FAULTS:
        with fault(name):
            reading(name)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
