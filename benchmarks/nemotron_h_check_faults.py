"""What ``nemotron-3-nano-30b-a3b.bare-ssd-8k``'s check reads on the chip,
for the program as it is and for the ten faults it has to refuse:

(a) ``no_carry``: the SSD's state zeroed at every chunk's start;
(b) ``group_zero``: every head reading group 0's ``B`` and ``C``;
(c) ``gate_after_norm``: ``rmsnorm_groups(y) * silu(z)`` for ``rmsnorm_groups(y * silu(z))``;
(d) ``norm_over_all``: the gated norm over all 4,096 channels, not groups of 512;
(e) ``relu_not_squared``: the experts' ``relu`` for ``relu^2``;
(f) ``no_shared``: the shared expert left out;
(g) ``no_bias``: the selection on the scores alone;
(h) ``scaling_one``: the gates times 1 for 2.5;
(i) ``no_D``: ``D x`` left out of the mixer;
(j) ``rope_added``: a rotary turn (``rope_theta``) on the attention layers' queries and keys;

and for four controls of precision, none of them a fault of the ten: the
nearest precision below the configuration's in the new kernel
(``bf16_state``: the SSD's state and running log-decay rounded to bfloat16
after every chunk), in the payload (``fp8_experts``: the grouped products'
operands at three mantissa bits) and in the router
(``router_three_passes``, ``bf16_router``).

The check is the cell's own (``chipbench/jobs/bare_routed.py`` against
``reference_nemotron_h.py``'s answers on the fixed sample, at the published
widths, the cut's thirteen layers, two sequences of 8,192); the faults are
put into ``torchft_tpu/`` from here, the program has no switch for them, and
the CPU tests put the same ones in at a small size.

    chiprun -- python3 benchmarks/nemotron_h_check_faults.py [workload [variant ...]]

One JSON line per variant; exits 2 without a TPU. With ``share_room`` for a
variant it prints instead, for eight seeds of fresh weights, bias and tokens,
each expert layer's pairs that reach the held experts over the even share:
what ``deployment.share_room`` is sized from.
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


def _faults():
    import jax
    import jax.numpy as jnp

    from torchft_tpu.models import moe
    from torchft_tpu.models import nemotron_h as nh
    from torchft_tpu.models.llama import _rope
    from torchft_tpu.ops import ssd as ssd_ops

    scan, norm, mixer, ffn, attention = (nh.ssd, nh._gated_norm, nh._mamba_mixer, nh.moe_ffn,
                                         nh._attention)
    choose, gmm = moe._choose, moe._grouped_matmul
    Q = ssd_ops.CHUNK

    def every_chunk_alone(x, dt, a, bm, cm):
        B, T = x.shape[:2]
        cut = lambda m: jnp.pad(  # noqa: E731
            m, ((0, 0), (0, -T % Q)) + ((0, 0),) * (m.ndim - 2)).reshape(-1, Q, *m.shape[2:])
        y = scan(cut(x), cut(dt), a, cut(bm), cut(cm))
        return y.reshape(B, -1, *x.shape[2:])[:, :T]

    def first_group(x, dt, a, bm, cm):
        first = lambda m: jnp.broadcast_to(m[:, :, :1], m.shape)  # noqa: E731
        return scan(x, dt, a, first(bm), first(cm))

    def gate_after(y, z, w, cfg):
        g = y.astype(jnp.float32).reshape(*y.shape[:2], cfg.mamba_n_groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + cfg.norm_eps)
        return (g.reshape(y.shape) * w.astype(jnp.float32)
                * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)

    def turned(q, k, v, cfg, **kw):
        at = jnp.broadcast_to(jnp.arange(q.shape[1]), q.shape[:2])
        return attention(_rope(q, cfg.rope_theta, at), _rope(k, cfg.rope_theta, at), v, cfg, **kw)

    return {
        "no_carry": lambda: _patched(nh, "ssd", every_chunk_alone),
        "group_zero": lambda: _patched(nh, "ssd", first_group),
        "gate_after_norm": lambda: _patched(nh, "_gated_norm", gate_after),
        "norm_over_all": lambda: _patched(
            nh, "_gated_norm", lambda y, z, w, cfg: norm(
                y, z, w, dataclasses.replace(cfg, mamba_n_groups=1))),
        "relu_not_squared": lambda: _patched(
            moe, "_hidden", lambda rows, w_gate, w_up, product, act: jax.nn.relu(
                product(rows, w_up))),
        "no_shared": lambda: _patched(
            nh, "moe_ffn", lambda *a, shared=None, **kw: ffn(*a, **kw)),
        "no_bias": lambda: _patched(
            moe, "_choose", lambda s, cfg, routing, bias=None: choose(s, cfg, routing)),
        "scaling_one": lambda: _patched(
            moe, "_choose", lambda s, cfg, routing, bias=None: choose(
                s, dataclasses.replace(cfg, routed_scaling=1.0), routing, bias)),
        "no_D": lambda: _patched(
            nh, "_mamba_mixer", lambda u, w, cfg: mixer(
                u, {**w, "D": jnp.zeros_like(w["D"])}, cfg)),
        "rope_added": lambda: _patched(nh, "_attention", turned),
        "bf16_state": lambda: _patched(ssd_ops, "STATE_DTYPE", jnp.bfloat16),
        "fp8_experts": lambda: _patched(
            moe, "_grouped_matmul", lambda rows, w, sizes: gmm(
                _fp8_like(rows), _fp8_like(w), sizes)),
        "router_three_passes": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.HIGH),
        "bf16_router": lambda: _patched(
            moe, "ROUTER_PRECISION", jax.lax.Precision.DEFAULT),
    }


def fault(name):
    """A context in which the program has the fault ``name`` (a key of
    :func:`_faults`); compiled functions made outside it do not."""
    return _faults()[name]()


FAULTS = ("no_carry", "group_zero", "gate_after_norm", "norm_over_all", "relu_not_squared",
          "no_shared", "no_bias", "scaling_one", "no_D", "rope_added")
CONTROLS = ("bf16_state", "fp8_experts", "router_three_passes", "bf16_router")
ROUTER_ONLY = ("router_three_passes", "bf16_router")


def share_room(adapter, cfg, seeds=8):
    """For ``seeds`` seeds of fresh weights, bias and tokens: each expert
    layer's pairs that reach the held experts over the even share."""
    import jax
    import numpy as np

    from torchft_tpu.models.nemotron_h import nemotron_h_hidden, nemotron_h_init

    pc = dataclasses.replace(adapter.config(cfg), share_room=16.0)  # every pair counted
    B, S = cfg["recipe"]["batch_size"], cfg["recipe"]["seq_len"]
    even = B * S * pc.top_k * pc.n_held / pc.num_experts

    @jax.jit
    def held(seed):
        params = nemotron_h_init(jax.random.PRNGKey(seed), pc)
        tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S), 0, pc.vocab_size)
        return nemotron_h_hidden(params, tokens, pc)[1]["held_pairs"]

    for seed in range(seeds):
        got = np.asarray(held(1000 * seed + 7)) / even
        print(json.dumps({"share_room_seed": seed, "held_over_even": [
            round(float(x), 4) for x in got]}), flush=True)


def main(argv):
    bench = manifest.load(ROOT)
    cell = manifest.Cell(ROOT, bench,
                         argv[0] if argv else "nemotron-3-nano-30b-a3b.bare-ssd-8k")
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
    check = cell.traffic["check"]

    def show(name, **kw):
        jax.clear_caches()
        got = reading(job, adapter, cfg, sample, seq, ref, check, **kw)
        print(json.dumps({"variant": name, **got}), flush=True)

    show("program", free=True)
    for name in argv[1:] or FAULTS + CONTROLS:
        with fault(name):
            show(name, router_only=name in ROUTER_ONLY)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
