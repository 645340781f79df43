"""What ``mellum2-12b-a2.5b.bare-window-32k``'s check reads on the chip, for
the program as it is and for the seven faults it has to refuse:

(a) ``window_ignored``: the window layers causal over the whole sequence;
(b) ``no_yarn``: the full layers turned by the plain table (the attention
    factor kept);
(c) ``no_attention_factor``: YaRN's frequencies with cos and sin unscaled;
(d) ``tables_swapped``: the window layers given the full layers' table;
(e) ``no_qk_norm``: the per-head RMSNorm of queries and keys left out;
(f) ``gates_not_renormalised``: the gates the softmax's own, not over their sum;
(g) ``fp8_experts``: the held experts' operands at three mantissa bits, the
    control of the payload's precision;

for a window one narrower or wider (``window_1023``, ``window_1025``:
reported with what they read; the CPU test holds the window exactly), and
for the two controls of the router's precision (``router_three_passes``,
``bf16_router``).

The check is the cell's own (``chipbench/jobs/bare_routed.py`` against
``reference_mellum.py``'s answers on the fixed sample, at the published
widths, the cut's eight layers, one sequence of 32,768); the faults are put
into ``torchft_tpu/`` from here, the program has no switch for them, and the
CPU tests put the same ones in at a small size.

    chiprun -- python3 benchmarks/mellum_check_faults.py [workload [variant ...]]

One JSON line per variant; exits 2 without a TPU. With ``share_room`` for a
variant it prints instead, for eight seeds of fresh weights and tokens, each
layer's pairs that reach the held experts over the even share: what
``deployment.share_room`` is sized from.
"""

import dataclasses
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import manifest  # noqa: E402


_spec = importlib.util.spec_from_file_location(
    "ling_check_faults", os.path.join(ROOT, "benchmarks", "ling_check_faults.py"))
_ling = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ling)
_patched, _fp8_like, reading = _ling._patched, _ling._fp8_like, _ling.reading


def _faults(pc):
    """``pc``: the program's config object."""
    import jax

    from torchft_tpu.models import mellum, moe

    attention, tables, norm = mellum._attention, mellum.rope_tables, mellum._rmsnorm
    choose, gmm = moe._choose, moe._grouped_matmul

    def windowed(by):  # a window ``by`` wider; None: none at all
        return lambda q, k, v, cfg, window=None: attention(
            q, k, v, cfg, window=None if by is None or window is None else window + by)

    def swapped(cfg, seq):
        full = tables(cfg, seq)["full"]
        return {"window": full, "full": full}

    return {
        "window_ignored": lambda: _patched(mellum, "_attention", windowed(None)),
        "window_1023": lambda: _patched(mellum, "_attention", windowed(-1)),
        "window_1025": lambda: _patched(mellum, "_attention", windowed(1)),
        "no_yarn": lambda: _patched(mellum, "yarn_inv_freq", mellum._plain_inv_freq),
        "no_attention_factor": lambda: _patched(
            mellum, "rope_tables", lambda cfg, seq: tables(
                dataclasses.replace(cfg, yarn_attention_factor=1.0), seq)),
        "tables_swapped": lambda: _patched(mellum, "rope_tables", swapped),
        "no_qk_norm": lambda: _patched(
            mellum, "_rmsnorm", lambda x, w, eps: x if x.ndim == 4 else norm(x, w, eps)),
        "gates_not_renormalised": lambda: _patched(
            moe, "_choose", lambda s, cfg, routing, bias=None: choose(
                s, dataclasses.replace(cfg, norm_topk_prob=False), routing, bias)),
        "fp8_experts": lambda: _patched(
            moe, "_grouped_matmul", lambda rows, w, sizes: gmm(
                _fp8_like(rows), _fp8_like(w), sizes)),
        "router_three_passes": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.HIGH),
        "bf16_router": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.DEFAULT),
    }


def fault(name, pc):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults(pc)[name]()


FAULTS = ("window_ignored", "no_yarn", "no_attention_factor", "tables_swapped",
          "no_qk_norm", "gates_not_renormalised", "fp8_experts")
REPORTED = ("window_1023", "window_1025")
CONTROLS = ("router_three_passes", "bf16_router")


def share_room(adapter, cfg, seeds=8):
    """For ``seeds`` seeds of fresh weights and tokens: each layer's pairs
    that reach the held experts over the even share."""
    import jax
    import numpy as np

    from torchft_tpu.models.mellum import mellum_hidden

    pc = dataclasses.replace(adapter.config(cfg), share_room=4.0)  # every pair counted
    B, S = cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]
    even = B * S * pc.top_k * pc.n_held / pc.num_experts

    @jax.jit
    def held(seed):
        params = adapter.program()[0](jax.random.PRNGKey(seed), pc)
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, pc.vocab_size)
        return mellum_hidden(params, tokens, pc)[1]["held_pairs"]

    for seed in range(seeds):
        got = np.asarray(held(1000 * seed + 7)) / even
        print(json.dumps({"share_room_seed": seed, "held_over_even": [
            round(float(x), 4) for x in got]}), flush=True)


def main(argv):
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench,
                         argv[0] if argv else "mellum2-12b-a2.5b.bare-window-32k")
    job, adapter = cell.job(), cell.adapter()
    cfg, seq = cell.config, cell.config["recipe"]["seq_len"]
    sample = job.check_sample_of(cell, adapter)
    if argv[1:] == ["share_room"]:
        import jax

        if jax.devices()[0].platform != "tpu":
            return 2
        share_room(adapter, cfg)
        return 0
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
    for name in argv[1:] or FAULTS + REPORTED + CONTROLS:
        with fault(name, pc):
            show(name, router_only=name in CONTROLS)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
