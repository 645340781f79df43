"""What ``brumby-14b-base.bare-retention``'s check reads on the chip, for the
program as it is and for the six faults ISSUE 56 lists:

(a) ``bf16_state``: the retention's state and normaliser rounded to bfloat16
    after every chunk (the nearest precision below the configuration's, in
    the new kernel);
(b) ``no_gate``: the gate left out (``g`` = 0: nothing decays);
(c) ``no_normaliser``: the output not divided by the sum of its weights;
(d) ``no_sqrt2``: phi without the sqrt 2 (the products of unlike channels
    weigh 1 in what is read from the state);
(e) ``scale_outside``: ``128^-1/2 (q . k)^2`` for ``(128^-1/2 q . k)^2``;
(f) ``no_rope``: the rotary turn left out of queries and keys.

The check is the cell's own (``chipbench/jobs/bare.py``'s ``compare`` of the
program's answers with ``reference_brumby.py``'s on the fixed sample, at the
published widths, the cut's four layers, one sequence of 16,384); the faults
are put into ``torchft_tpu/`` from here, through the three constants
``ops/power_retention.py`` keeps for it and by patching, and the CPU tests
put the same ones in at a small size. ``scale_outside`` is the same function
of its inputs to one part in a million (under the normaliser a uniform
factor divides out and only eps sees it): no comparison of outputs can
refuse it. Every variant's line also carries the program's smallest
normaliser (``den_min``: the ``retention_den_min`` the trainer prints)
beside the reference's: in float32 that fault reads sqrt(head_dim) there
(the CPU tests); at bf16 the number is ``(q . k)^2`` at a first position
where ``q . k`` is near zero, and rounding q and k moves it by as much
(PERF.md section 6, PR 56), so it is reported and decides nothing.

    chiprun -- python3 benchmarks/brumby_check_faults.py [workload [variant ...]]

One JSON line per variant; exits 2 without a TPU.
"""

import contextlib
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "lfm2_check_faults", os.path.join(ROOT, "benchmarks", "lfm2_check_faults.py"))
_lfm2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_lfm2)
_patched = _lfm2._patched

FAULTS = ("bf16_state", "no_gate", "no_normaliser", "no_sqrt2", "scale_outside", "no_rope")


def _faults():
    import jax.numpy as jnp

    from torchft_tpu.models import brumby, llama
    from torchft_tpu.ops import power_retention as ops

    retention = brumby.power_retention
    return {
        "bf16_state": lambda: _patched(ops, "STATE_DTYPE", jnp.bfloat16),
        "no_gate": lambda: _patched(
            brumby, "power_retention",
            lambda q, k, v, g, **kw: retention(q, k, v, jnp.zeros_like(g), **kw)),
        "no_normaliser": lambda: _patched(ops, "NORMALISED", False),
        "no_sqrt2": lambda: _patched(ops, "CROSS", 1.0),
        "scale_outside": lambda: _patched(
            brumby, "power_retention",
            lambda q, k, v, g, **kw: retention(q, k, v, g, scale=q.shape[-1] ** -0.25, **kw)),
        "no_rope": lambda: _patched(llama, "_rope", lambda x, theta, positions: x),
    }


def fault(name):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults()[name]()


def den_min(adapter, cfg, sample, seq) -> float:
    """The program's ``retention_den_min`` on the check's sample."""
    import jax

    from torchft_tpu.models.brumby import brumby_loss_and_stats

    pc = adapter.config(cfg)
    tokens, _ = adapter.reference.check_sample(cfg, sample, seq)
    params = jax.jit(lambda: adapter.program()[0](jax.random.PRNGKey(sample["seed"]), pc))()
    return float(jax.jit(lambda p: brumby_loss_and_stats(p, tokens, tokens, pc)[1]["den_min"])(
        params))


def reading(bare, adapter, cfg, sample, seq, ref, tol) -> dict:
    """``bare.compare``'s verdict and numbers, and beside them the smallest
    normaliser over the reference's."""
    got = bare.compare(bare.system_answers(adapter, cfg, sample, seq), ref, tol)
    got["den_min"] = den_min(adapter, cfg, sample, seq)
    got["den_min_over_reference"] = got["den_min"] / float(ref["den_min"])
    return got


def main(argv):
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, argv[0] if argv else "brumby-14b-base.bare-retention")
    bare, adapter = cell.job(), cell.adapter()
    cfg, seq = cell.config, cell.config["recipe"]["seq_len"]
    sample = bare.check_sample_of(cell, adapter)
    # a child computes the reference's answers before this process takes the chip
    ref = bare._reference_answers(cell, adapter, sample,
                                  os.path.join(ROOT, ".chipbench_cache"))

    import jax

    if jax.devices()[0].platform != "tpu":
        return 2
    tol = cell.traffic["check"]["tolerances"]

    def show(name, context=contextlib.nullcontext()):
        jax.clear_caches()
        with context:
            got = reading(bare, adapter, cfg, sample, seq, ref, tol)
        print(json.dumps({"variant": name, **got}), flush=True)

    show("program")
    for name in argv[1:] or FAULTS:
        show(name, fault(name))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
