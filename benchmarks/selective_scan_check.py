"""The selective-scan kernels alone on the chip, at Jamba2-3B's shape (batch
1, T 8192, d_inner 5120, d_state 16), against the sequential recurrence in
float32: the largest error of ``y`` and of every cotangent, the seconds of
the forward and of the forward-and-backward call, and their share of the
roofline (the bytes that must move over the HBM rate;
chipbench/adapters/jamba.py keeps the count). Also the two faults the benchmark's check must see, as the
error they make in ``y``: the state in bfloat16 and the carry across chunks
zeroed.

    chiprun -- python3 benchmarks/selective_scan_check.py [chunk block ...]

Writes one JSON line per (chunk, block) pair; exits 2 without a TPU.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import flops, manifest  # noqa: E402
from torchft_tpu.ops import selective_scan as ss  # noqa: E402

T, DI, N = 8192, 5120, 16


def inputs(seed: int = 0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(k[0], (1, T, DI)).astype(jnp.bfloat16)
    # Mamba's initialisation: steps log-uniform in [1e-3, 1e-1], A = -(1..16)
    dt = jnp.exp(jax.random.uniform(k[1], (1, T, DI), minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (DI, N))
    B, C = jax.random.normal(k[2], (1, T, N)), jax.random.normal(k[3], (1, T, N))
    D = jnp.ones((DI,), jnp.float32)
    z = jax.random.normal(k[4], (1, T, DI)).astype(jnp.bfloat16)
    w = jax.random.normal(k[5], (1, T, DI))
    return (x, dt, A, B, C, D, z), w


def timed(f, *args, n: int = 5) -> float:
    jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / n


def rel(a, b) -> float:
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def main(argv):
    if jax.devices()[0].platform != "tpu":
        sys.exit(2)
    pairs = [(int(argv[i]), int(argv[i + 1])) for i in range(0, len(argv), 2)] \
        or [(ss.CHUNK, ss.BLOCK)]
    args, w = inputs()
    names = "x dt A B C D z".split()

    def loss_of(scan):
        return lambda *a: jnp.sum(scan(*a).astype(jnp.float32) * w)

    ref_y = jax.jit(ss.selective_scan_reference)(*args)
    ref_g = jax.jit(jax.grad(loss_of(ss.selective_scan_reference),
                             argnums=range(7)))(*args)
    # the least seconds the chip could take: the benchmark's own count
    cost = manifest.load_module(manifest.ROOT, "adapters", "jamba").selective_scan_cost
    shape = {"hidden_size": DI // 2, "mamba_expand": 2, "mamba_d_state": N}
    kind = jax.devices()[0].device_kind
    floor = {p: flops.roofline_floor_s(cost(shape, 1, T, p), kind)[0]
             for p in ("fwd", "bwd")}
    for chunk, block in pairs:
        scan = lambda *a: ss.selective_scan(*a, chunk=chunk, block=block)  # noqa: E731
        fwd = jax.jit(scan)
        both = jax.jit(jax.grad(loss_of(scan), argnums=range(7)))
        try:
            y, g = fwd(*args), both(*args)
        except Exception as e:  # noqa: BLE001 - a tile the compiler refuses
            print(json.dumps({"chunk": chunk, "block": block,
                              "refused": str(e).splitlines()[0][:200]}), flush=True)
            continue
        fwd_s, both_s = timed(fwd, *args), timed(both, *args)
        out = {"chunk": chunk, "block": block, "y_rel_max": rel(y, ref_y),
               **{f"d{n}_rel_max": rel(a, b) for n, a, b in zip(names, g, ref_g)},
               "fwd_s": fwd_s, "fwd_bwd_s": both_s,
               "fwd_roofline_pct": 100 * floor["fwd"] / fwd_s,
               "fwd_bwd_roofline_pct": 100 * (floor["fwd"] + floor["bwd"]) / both_s}
        print(json.dumps(out), flush=True)

    # what the cell's check must refuse, as each fault's error in y
    x, dt, A, B, C, D, z = args
    bf = lambda m: m.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731

    def bf16_state(h, inp):  # the recurrence with its state rounded to bf16
        x_t, dt_t, b_t, c_t = inp
        h = bf(bf(jnp.exp(dt_t[:, None] * A)) * h
               + (dt_t * x_t)[:, None] * b_t[None, :])
        return h, h @ c_t

    _, y16 = jax.lax.scan(bf16_state, jnp.zeros((DI, N)),
                          (x[0].astype(jnp.float32), dt[0], B[0], C[0]))
    core = ss.selective_scan_reference(x, dt, A, B, C)
    fold = lambda m: m.reshape(T // ss.CHUNK, ss.CHUNK, m.shape[-1])  # noqa: E731
    cut = ss.selective_scan_reference(  # every chunk starts from a zero state
        fold(x), fold(dt), A, fold(B), fold(C)).reshape(1, T, DI)
    print(json.dumps({"fault_bf16_state_y_rel_max": rel(y16[None], core),
                      "fault_zeroed_carry_y_rel_max": rel(cut, core)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
