"""What ``solar-open2-250b.bare-kda-gqa-16k``'s check reads on the chip, for the
program as it is and for the eight faults it has to refuse:

(a) ``beta_not_doubled``: ``beta = sigmoid(.)`` in (0, 1), no x 2;
(b) ``decay_clipped``: the step's log decay clipped at -5 (Ling's bound);
(c) ``head_wise_gate``: the KDA output gate one value a head (every channel
    of a head takes its first channel's);
(d) ``gqa_rope``: a rotary turn on the GQA layer's queries and keys;
(e) ``no_gqa_gate``: the GQA layer's output gate left out;
(f) ``lost_tap``: the short convolutions' oldest tap zeroed;
(g) ``no_shared``: the shared expert left out;
(h) ``bf16_state``: the delta rule's state carried in bf16;

and for six controls of precision, none of them a fault of the eight: the
nearest precision below the payload's, in the experts (``fp8_experts``: the
grouped products' operands at three mantissa bits) and in the mixers, which
are four fifths of the cell's work (``fp8_mixers``: the KDA and the GQA
layers' input and every projection matrix of theirs at three mantissa bits,
so the q/k/v, decay, beta, gate and output products; ``bf16_kda``: the
delta-rule kernels' products in one bf16 pass, decays and state among their
operands, as ``ling_check_faults.py``'s; ``bf16_decay``: the step's log decay
rounded to bf16 before the kernels, by ``reduce_precision``: XLA drops a
pair of converts there and back on the TPU), and below the router's
(``router_three_passes``, ``bf16_router``).

The check is the cell's own (``chipbench/jobs/bare_routed.py`` against
``reference_solar_open2.py``'s answers on the fixed sample, at the published
widths, the cut's four layers, one sequence of 16,384); the faults are put
into ``torchft_tpu/`` from here, the program has no switch for them, and the
CPU tests put the same ones in at a small size
(``tests/chipbench/test_reference_solar_open2.py``). The free-routing
comparison, which judges nothing, is made for the program alone.

    chiprun -- python3 benchmarks/solar_check_faults.py [workload [variant ...]]
    chiprun -- python3 benchmarks/solar_check_faults.py loads [seed ...]

One JSON line per variant; ``loads`` prints, per seed, the held experts'
pairs of every layer over the even share (what ``deployment.share_room`` has
to hold) and the KDA layers' two counters; exits 2 without a TPU.
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
    "ling_check_faults", os.path.join(ROOT, "benchmarks", "ling_check_faults.py"))
_ling = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_ling)
_patched, _fp8_like, reading = _ling._patched, _ling._fp8_like, _ling.reading


def _faults(pc):
    """``pc``: the program's config object (its head size cuts the gate's
    channels into heads)."""
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import kda, llama, moe, solar
    from torchft_tpu.ops import kda as kda_ops

    scan, conv, mixer, gqa = kda.kda, kda._short_conv, solar.kda_mixer, solar._gqa_mixer
    attend, ffn, gmm = solar._attention, solar.moe_ffn, moe._grouped_matmul

    def first_of_head(m):  # [.., H dk] -> every channel of a head its first one's
        heads = m.reshape(*m.shape[:-1], -1, pc.kda_head_dim)
        return jnp.broadcast_to(heads[..., :1], heads.shape).reshape(m.shape)

    def lowered(u, w):  # a mixer's input and its projection matrices (not the taps)
        return _fp8_like(u), {name: _fp8_like(m) if m.ndim == 2 and not name.startswith("conv")
                              else m for name, m in w.items()}

    @contextlib.contextmanager
    def fp8_mixers():
        with _patched(solar, "kda_mixer", lambda u, w, *a, **kw: mixer(*lowered(u, w), *a, **kw)), \
                _patched(solar, "_gqa_mixer", lambda u, w, *a: gqa(*lowered(u, w), *a)):
            yield

    def turned(q, k, v, cfg, **kw):
        at = jnp.broadcast_to(jnp.arange(q.shape[1]), q.shape[:2])
        return attend(llama._rope(q, cfg.rope_theta, at), llama._rope(k, cfg.rope_theta, at),
                      v, cfg, **kw)

    return {
        "beta_not_doubled": lambda: _patched(solar, "BETA_MAX", 1.0),
        "decay_clipped": lambda: _patched(
            kda, "kda", lambda q, k, v, g, beta, **kw: scan(
                q, k, v, jnp.maximum(g, kda_ops.BOUNDED_FLOOR), beta, **kw)),
        "head_wise_gate": lambda: _patched(
            solar, "kda_mixer", lambda u, w, *a, **kw: mixer(
                u, {**w, "w_gb": first_of_head(w["w_gb"]), "b_g": first_of_head(w["b_g"])},
                *a, **kw)),
        "gqa_rope": lambda: _patched(solar, "_attention", turned),
        # sigmoid(0) x 2 = 1, and the output projection is linear
        "no_gqa_gate": lambda: _patched(
            solar, "_gqa_mixer", lambda u, w, *a: 2 * gqa(
                u, {**w, "w_g": jnp.zeros_like(w["w_g"])}, *a)),
        "lost_tap": lambda: _patched(
            kda, "_short_conv", lambda x, w: conv(x, w.at[0].set(0))),
        "no_shared": lambda: _patched(
            solar, "moe_ffn", lambda *a, shared=None, **kw: ffn(*a, **kw)),
        "bf16_state": lambda: _patched(kda_ops, "STATE_DTYPE", jnp.bfloat16),
        "fp8_experts": lambda: _patched(
            moe, "_grouped_matmul", lambda rows, w, sizes: gmm(
                _fp8_like(rows), _fp8_like(w), sizes)),
        "fp8_mixers": fp8_mixers,
        "bf16_kda": lambda: _patched(
            kda_ops, "_dot", lambda a, b, dims=kda_ops._NN: jax.lax.dot_general(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
                preferred_element_type=jnp.float32)),
        "bf16_decay": lambda: _patched(
            kda, "kda", lambda q, k, v, g, beta, **kw: scan(
                q, k, v, jax.lax.reduce_precision(g, 8, 7), beta, **kw)),
        "router_three_passes": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.HIGH),
        "bf16_router": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.DEFAULT),
    }


def fault(name, pc):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults(pc)[name]()


FAULTS = ("beta_not_doubled", "decay_clipped", "head_wise_gate", "gqa_rope", "no_gqa_gate",
          "lost_tap", "no_shared", "bf16_state")
CONTROLS = ("fp8_experts", "fp8_mixers", "bf16_kda", "bf16_decay", "router_three_passes",
            "bf16_router")


def loads(cell, seeds):
    """The held experts' pairs of every layer over the even share, and the
    KDA layers' two counters, for ``seeds`` fresh initialisations and batches
    at the cell's shapes: what ``deployment.share_room`` has to hold. One
    JSON line a seed."""
    import jax

    from torchft_tpu.models.solar import solar_hidden

    adapter, cfg = cell.adapter(), cell.config
    pc, recipe = adapter.config(cfg), cfg["recipe"]
    shape = (recipe["batch_size"], recipe["seq_len"])
    even = shape[0] * shape[1] * pc.top_k * pc.n_held / pc.num_experts
    keep = ("held_pairs", "overflow", "decay_past_bound_share", "beta_over_one_share")

    @jax.jit
    def read(seed):
        k_init, k_tokens = jax.random.split(jax.random.PRNGKey(seed))
        params = adapter._with_bias(adapter.program()[0](k_init, pc), pc)
        tokens = jax.random.randint(k_tokens, shape, 0, cfg["vocab_size"])
        stats = solar_hidden(params, tokens, pc)[1]
        return {k: stats[k] for k in keep}

    for seed in seeds:
        got = {k: [float(x) for x in v] for k, v in jax.device_get(read(seed)).items()}
        got["held_over_even"] = [x / even for x in got.pop("held_pairs")]
        print(json.dumps({"seed": seed, **got}), flush=True)


def main(argv):
    bench = manifest.load(ROOT)
    if argv[:1] == ["loads"]:
        import jax

        if jax.devices()[0].platform != "tpu":
            return 2
        loads(manifest.Cell(ROOT, bench, "solar-open2-250b.bare-kda-gqa-16k"),
              [int(s) for s in argv[1:]] or list(range(100, 112)))
        return 0
    cell = manifest.Cell(ROOT, bench, argv[0] if argv else "solar-open2-250b.bare-kda-gqa-16k")
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
