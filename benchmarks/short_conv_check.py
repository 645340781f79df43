"""The mixers' short convolution alone on the chip, at the shapes of the three
cells whose mixers run it with a SiLU (and LFM2's, which has none): the
``jax.numpy`` form differentiated by XLA (what every cell ran until PR 53;
``widen_late`` was Ling's) beside ``ops/short_conv.py``'s kernel pair.

For each shape and form: the seconds of the forward call and of the
forward-and-backward call (``y`` and every cotangent returned, so that
neither is dropped), the bytes an element that is at the HBM rate
(``chipbench/peaks.json``) against the floors of 4 (forward: the narrow rows
read once and written once) and 10 (forward and backward: 6 more), the
bytes an element a STEP under ``remat="full"`` (forward, forward again,
backward; floor 14), and the kernel's largest error against the
``jax.numpy`` form in ``y`` and every cotangent.

    chiprun -- python3 benchmarks/short_conv_check.py [tile lanes ...]

One JSON line per (shape, form), the kernel once for every (tile, lanes)
pair given (none: the module's own); exits 2 without a TPU.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from torchft_tpu.ops import short_conv as sc  # noqa: E402

_F32 = jnp.float32
# (cell, [B, T, di], taps, bias, SiLU, the parent widened late)
SHAPES = (("nemotron-3-nano-30b-a3b", (2, 8192, 6144), 4, True, True, False),
          ("jamba2-3b", (1, 8192, 5120), 4, True, True, False),
          ("ling-3.0-flash", (1, 32768, 4096), 4, False, True, True),
          ("lfm2-8b-a1b", (1, 8192, 2048), 3, False, False, False))


def parent_form(x, w, b, activation, widen_late=False):
    """``models/decoder.py::_causal_conv`` as it stood before PR 53."""
    k, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    if not widen_late:
        padded = padded.astype(_F32)
    out = sum(padded[:, j:j + T].astype(_F32) * w[j].astype(_F32) for j in range(k))
    if b is not None:
        out = out + b.astype(_F32)
    return (activation(out) if activation else out).astype(x.dtype)


def timed(f, *args, n: int = 20) -> float:
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


def both(conv):
    """-> (forward, forward and backward) of ``conv(x, w, b)``, jitted."""
    def fwd_bwd(x, w, b, dy):
        y, pull = jax.vjp(conv, x, w, b)
        return y, pull(dy)
    return jax.jit(conv), jax.jit(fwd_bwd)


def main(argv):
    if jax.devices()[0].platform != "tpu":
        sys.exit(2)
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        rate = json.load(f)["device_kinds"][jax.devices()[0].device_kind]["hbm_bytes_s"]
    pairs = [(int(argv[i]), int(argv[i + 1])) for i in range(0, len(argv), 2)] \
        or [(sc.TILE, sc._LANES)]
    for cell, shape, k, bias, silu, late in SHAPES:
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        x = jax.random.normal(ks[0], shape).astype(jnp.bfloat16)
        w = (jax.random.normal(ks[1], (k, shape[2])) * 0.5).astype(jnp.bfloat16)
        b = jax.random.normal(ks[2], (shape[2],)).astype(jnp.bfloat16) if bias else None
        dy = jax.random.normal(ks[3], shape).astype(jnp.bfloat16)
        act = jax.nn.silu if silu else None
        n = x.size

        def line(form, fns, **more):
            fwd_s, both_s = timed(fns[0], x, w, b), timed(fns[1], x, w, b, dy)
            print(json.dumps({
                "cell": cell, "shape": list(shape), "taps": k, "bias": bias, "silu": silu,
                "form": form, **more, "fwd_s": fwd_s, "fwd_bwd_s": both_s,
                "fwd_bytes_elem": fwd_s * rate / n, "fwd_bwd_bytes_elem": both_s * rate / n,
                "step_bytes_elem": (fwd_s + both_s) * rate / n, "floors": [4, 10, 14],
                "step_s": fwd_s + both_s}), flush=True)

        parent = both(lambda x, w, b: parent_form(x, w, b, act, late))
        line("jax.numpy, widened late" if late else "jax.numpy", parent)
        want_y, want_g = parent[1](x, w, b, dy)
        for tile, lanes in pairs:
            sc.TILE, sc._LANES = tile, lanes
            if not sc._tiles(x, w):
                continue
            kernel = both(lambda x, w, b: sc.short_conv(x, w, b, act))
            got_y, got_g = kernel[1](x, w, b, dy)
            line("kernel", kernel, tile=tile, lanes=sc._lanes(shape[2]),
                 y_rel=rel(got_y, want_y),
                 **{f"d{name}_rel": rel(g, r) for name, g, r in zip("xwb", got_g, want_g)
                    if g is not None})


if __name__ == "__main__":
    main(sys.argv[1:])
