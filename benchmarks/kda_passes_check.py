"""The KDA mixer's two element-wise passes alone on the chip, at the shapes of
the two cells that run them: the ``jax.numpy`` forms differentiated by XLA
(what both cells ran until PR 66: ``ops/kda_passes.py::qkg_reference`` and
``gate_reference``, the latter in rematerialised blocks of positions where
the cell's recipe says so) beside the module's two kernel pairs.

``qkg``: the L2 norms of q and k with the decay's activation, before the
delta rule; ``gate``: the head-wise RMSNorm with the output gate, after it.
For each shape, pass and form: the seconds of the forward call and of the
forward-and-backward call (every output and every cotangent returned, so
that none is dropped), the bytes an element that is at the HBM rate
(``chipbench/peaks.json``) against the floors (every row read once and
written once: ``qkg`` 16 forward and 40 with the backward pass, ``gate`` 6
and 16 with a gate a head, 8 and 22 with one a channel), ms a LAYER a step
under ``remat="full"`` (forward, forward again, backward) with its bytes an
element and floor (56; 22; 30), and the kernels' largest error against the
``jax.numpy`` form in every output and cotangent.

    chiprun -- python3 benchmarks/kda_passes_check.py [rows lanes elems ...]

One JSON line per (shape, pass, form), the kernels once for every (``_ROWS``,
``_LANES``, ``_ELEMS``) triple given (none: the module's own); exits 2
without a TPU.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from torchft_tpu.ops import kda_passes as kp  # noqa: E402

_F32, _BF16 = jnp.float32, jnp.bfloat16
# (cell, [B, S, H, d_k], decay_floor, a gate a head, the parent's blocks of positions)
SHAPES = (("ling-3.0-flash", (1, 32768, 32, 128), -5.0, True, 0),
          ("solar-open2-250b", (1, 16384, 64, 128), None, False, 2048))
# bytes an element: (forward, forward and backward, a step under remat)
FLOORS = {"qkg": (16, 40, 56), "gate.head": (6, 16, 22), "gate.channel": (8, 22, 30)}
EPS = 1e-5


def timed(f, *args, n: int = 10) -> float:
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / n


def rel(a, b) -> float:
    a, b = a.astype(_F32), b.astype(_F32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def both(fn):
    """-> (forward, forward and backward) of ``fn(*args)``, jitted; the last
    argument of the second is the cotangent."""
    def fwd_bwd(*args):
        out, pull = jax.vjp(fn, *args[:-1])
        return out, pull(args[-1])
    return jax.jit(fn), jax.jit(fwd_bwd)


def in_blocks(fn, block):
    """``fn(o, logits, o_norm)`` over rematerialised blocks of positions, as
    ``kda_mixer``'s ``out_block`` runs the ``jax.numpy`` form."""
    if not block:
        return fn

    def blocked(o, logits, w):
        B, S = o.shape[:2]
        cut = lambda m: jnp.swapaxes(  # noqa: E731
            m.reshape(B, S // block, block, *m.shape[2:]), 0, 1)
        out = jax.lax.map(jax.checkpoint(lambda ol: fn(*ol, w)), (cut(o), cut(logits)))
        return jnp.swapaxes(out, 0, 1).reshape(o.shape)
    return blocked


def main(argv):
    if jax.devices()[0].platform != "tpu":
        sys.exit(2)
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        rate = json.load(f)["device_kinds"][jax.devices()[0].device_kind]["hbm_bytes_s"]
    triples = [tuple(int(a) for a in argv[i:i + 3]) for i in range(0, len(argv), 3)] \
        or [(kp._ROWS, kp._LANES, kp._ELEMS)]
    for cell, (B, S, H, dk), floor, per_head, block in SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(0), 12)
        wide = (B, S, H * dk)
        rows = lambda key, dtype=_BF16, scale=1.0: (  # noqa: E731
            scale * jax.random.normal(key, wide)).astype(dtype)
        passes = {
            "qkg": (
                lambda *a: kp.qkg_reference(*a, floor), lambda *a: kp.kda_qkg(*a, floor),
                (rows(ks[0]), rows(ks[1]), rows(ks[2], _F32, 2.0),
                 jax.random.normal(ks[3], (H * dk,)) - 3.0,
                 jnp.log(jax.random.uniform(ks[4], (H,), _F32, 1.0, 2.0))),
                (rows(ks[5]), rows(ks[6]), rows(ks[7], _F32)), "qkg"),
            "gate": (
                in_blocks(lambda *a: kp.gate_reference(*a, EPS), block),
                lambda *a: kp.kda_gate(*a, EPS),
                (rows(ks[8]), 3.0 * jax.random.normal(ks[9], (B, S, H)) if per_head
                 else rows(ks[9], scale=3.0),
                 (1.0 + 0.1 * jax.random.normal(ks[10], (dk,))).astype(_BF16)),
                rows(ks[11]), "gate.head" if per_head else "gate.channel"),
        }
        for name, (plain, kernels, args, cts, floors) in passes.items():
            n = B * S * H * dk

            def line(form, fns, **more):
                fwd_s, both_s = timed(fns[0], *args), timed(fns[1], *args, cts)
                print(json.dumps({
                    "cell": cell, "shape": [B, S, H, dk], "pass": name, "form": form, **more,
                    "fwd_ms": 1e3 * fwd_s, "fwd_bwd_ms": 1e3 * both_s,
                    "layer_step_ms": 1e3 * (fwd_s + both_s),
                    "fwd_bytes_elem": fwd_s * rate / n, "fwd_bwd_bytes_elem": both_s * rate / n,
                    "step_bytes_elem": (fwd_s + both_s) * rate / n,
                    "floors": FLOORS[floors]}), flush=True)

            parent = both(plain)
            line(f"jax.numpy, blocks of {block}" if block and name == "gate" else "jax.numpy",
                 parent)
            want = jax.tree.leaves(parent[1](*args, cts))
            for triple in triples:
                kp._ROWS, kp._LANES, kp._ELEMS = triple
                jax.clear_caches()
                kernel = both(kernels)
                got = jax.tree.leaves(kernel[1](*args, cts))
                line("kernels", kernel, rows=triple[0],
                     blocks=kp._blocks(S, H * dk, dk, per_head and name == "gate"),
                     rel=[rel(g, r) for g, r in zip(got, want)])


if __name__ == "__main__":
    main(sys.argv[1:])
