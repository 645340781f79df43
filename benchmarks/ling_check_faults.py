"""What ``ling-3.0-flash.bare-kda-32k``'s check reads on the chip, for the
program as it is and for the nine faults it has to refuse:

(a) ``no_decay``: the delta rule's decay left out (``g = 0``);
(b) ``beta_one``: ``beta = 1`` for every position and head;
(c) ``lost_tap``: the short convolutions' oldest tap zeroed;
(d) ``no_rope``: the rotary embedding left off MLA's 64;
(e) ``no_latent_norm``: the RMSNorm of MLA's latent left out;
(f) ``no_group_limit``: the eight experts the top-8 of all 512;
(g) ``no_shared``: the shared expert left out;
(h) ``scaling_one``: the gates times 1 for 2.5;
(i) ``bf16_state``: the delta rule's state carried in bf16;

and for four controls of precision, none of them a fault of the nine: the
nearest precision below the payload's (``fp8_experts``: the grouped
products' operands at three mantissa bits; ``bf16_kda``: the delta-rule
kernels' products in one bf16 pass, decays and state among their operands)
and below the router's (``router_three_passes``, ``bf16_router``).

The check is the cell's own (``chipbench/jobs/bare_routed.py`` against
``reference_ling.py``'s answers on the fixed sample, at the published
widths, the cut's seven layers, one sequence of 32,768); the faults are put
into ``torchft_tpu/`` from here, the program has no switch for them, and
the CPU tests put the same ones in at a small size. The free-routing
comparison, which judges nothing, is made for the program alone.

    chiprun -- python3 benchmarks/ling_check_faults.py [workload [variant ...]]

One JSON line per variant; exits 2 without a TPU.
"""

import contextlib
import dataclasses
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
_patched, _fp8_like = _lfm2._patched, _lfm2._fp8_like


def _faults(pc):
    """``pc``: the program's config object (the latent's width tells its
    norm from the others)."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import kda, ling, mla, moe
    from torchft_tpu.ops import kda as kda_ops

    # the delta rule, the taps and the latent's norm are the shared mixers'
    # (models/kda.py, models/mla.py)
    scan, conv, rope, norm = kda.kda, kda._short_conv, ling._rope, mla._rmsnorm
    ffn, choose, gmm, dot = ling.moe_ffn, moe._choose, moe._grouped_matmul, kda_ops._dot

    def ungrouped(decide, cfg):
        clear = jnp.ones(decide.shape[:1], decide.dtype)
        return decide, clear, 0 * clear  # no group dropped, no tie between groups

    return {
        "no_decay": lambda: _patched(
            kda, "kda", lambda q, k, v, g, beta, **kw: scan(q, k, v, 0 * g, beta, **kw)),
        "beta_one": lambda: _patched(
            kda, "kda", lambda q, k, v, g, beta, **kw: scan(
                q, k, v, g, jnp.ones_like(beta), **kw)),
        "lost_tap": lambda: _patched(
            kda, "_short_conv", lambda x, w: conv(x, w.at[0].set(0))),
        "no_rope": lambda: _patched(ling, "_rope", lambda x, theta, positions: x),
        "no_latent_norm": lambda: _patched(
            mla, "_rmsnorm", lambda x, w, eps: x if x.shape[-1] == pc.kv_lora_rank
            else norm(x, w, eps)),
        "no_group_limit": lambda: _patched(moe, "_within_groups", ungrouped),
        "no_shared": lambda: _patched(
            ling, "moe_ffn", lambda *a, shared=None, **kw: ffn(*a, **kw)),
        "scaling_one": lambda: _patched(
            moe, "_choose", lambda s, cfg, routing, bias=None: choose(
                s, dataclasses.replace(cfg, routed_scaling=1.0), routing, bias)),
        "bf16_state": lambda: _patched(kda_ops, "STATE_DTYPE", jnp.bfloat16),
        "fp8_experts": lambda: _patched(
            moe, "_grouped_matmul", lambda rows, w, sizes: gmm(
                _fp8_like(rows), _fp8_like(w), sizes)),
        "bf16_kda": lambda: _patched(
            kda_ops, "_dot", lambda a, b, dims=kda_ops._NN: jax.lax.dot_general(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
                preferred_element_type=jnp.float32)),
        "router_three_passes": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.HIGH),
        "bf16_router": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.DEFAULT),
    }


def fault(name, pc):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults(pc)[name]()


FAULTS = ("no_decay", "beta_one", "lost_tap", "no_rope", "no_latent_norm",
          "no_group_limit", "no_shared", "scaling_one", "bf16_state")
CONTROLS = ("fp8_experts", "bf16_kda", "router_three_passes", "bf16_router")


def reading(job, adapter, cfg, sample, seq, ref, check, free=False, router_only=False):
    """``routed_check``'s three parts (and, where asked, the free run that
    judges nothing); ``router_only``: part C alone, for a variant that
    changes the router's product and nothing else."""
    out = {"router": job.router_precision(
        job.router_answers(adapter, cfg, sample, ref["router_in"]), ref, check["router"])}
    if not router_only:
        replayed = job.system_answers(adapter, cfg, sample, seq, routing=ref["routing"])
        out["decisions"] = job.decisions(replayed["routing"], ref, check["routing"])
        out["arithmetic"] = job.compare(replayed, ref, check["tolerances"])
    out["ok"] = all(part["ok"] for part in out.values())
    if free:
        got = job.system_answers(adapter, cfg, sample, seq)
        out["free"] = {**job.compare(got, ref, check["tolerances"]),
                       "decisions": job.decisions(got["routing"], ref, check["routing"])}
    return out


def main(argv):
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench, argv[0] if argv else "ling-3.0-flash.bare-kda-32k")
    job, adapter = cell.job(), cell.adapter()
    cfg, seq = cell.config, cell.config["recipe"]["seq_len"]
    sample = job.check_sample_of(cell, adapter)
    # a child computes the reference's answers before this process takes the chip
    ref = job._reference_answers(cell, adapter, sample,
                                 os.path.join(ROOT, ".chipbench_cache"))

    import jax

    if jax.devices()[0].platform != "tpu":
        return 2
    check, pc = cell.traffic["check"], adapter.config(cfg)

    def show(name, **kw):
        jax.clear_caches()
        got = reading(job, adapter, cfg, sample, seq, ref, check, **kw)
        print(json.dumps({"variant": name, **got}), flush=True)

    show("program", free=True)
    for name in argv[1:] or FAULTS + CONTROLS:
        with fault(name, pc):
            show(name, router_only=name in ("router_three_passes", "bf16_router"))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
