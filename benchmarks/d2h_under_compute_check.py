"""Chip script: how fast does a device-to-host fetch run WHILE a program
runs on the chip? (`chiprun -- python3 benchmarks/d2h_under_compute_check.py`)

Two chained jitted matmul programs of about 0.2 s each. The first one's
output is 0.5 GB in 16 MiB pieces (what ``bucketing.capture`` hands the
staging thread); every piece's ``copy_to_host_async`` is issued between the
two dispatches, and the pieces are copied into a warm host buffer
(``bucketing.fetch_into``). Read: the fetch's rate with the chip idle behind
it, the rate under the second program, and whether the second program runs
longer for it. One JSON line; PERF.md section 6 (PR 34) has the reading.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu import bucketing

N, PIECES, MATMULS = 8192, 32, 36  # 32 x [1024, 8192] bf16 = 0.5 GB


@jax.jit
def produce(x):
    y = jax.lax.fori_loop(0, MATMULS, lambda _, y: (y @ x) * 0.01, x)
    rows = N // 8  # flat pieces of 16 MiB, as the capture's
    return [(y[(i % 8) * rows:(i % 8 + 1) * rows] + i).reshape(-1)
            for i in range(PIECES)]


@jax.jit
def compute(x):
    return jax.lax.fori_loop(0, MATMULS, lambda _, y: (y @ x) * 0.01, x)


def one(x, out, under: bool):
    """-> seconds: program 1 alone, the fetch after its end, and where a
    second program was enqueued behind it, from program 1's end to that
    program's end."""
    t0 = time.perf_counter()
    arrays = produce(x)
    for a in arrays:
        a.copy_to_host_async()
    tail = compute(x) if under else None
    jax.block_until_ready(arrays)
    t1 = time.perf_counter()
    n = arrays[0].size
    pieces = bucketing.Pieces(
        arrays, [(k * n, (k + 1) * n) for k in range(PIECES)], n * PIECES,
        np.dtype(arrays[0].dtype))
    bucketing.fetch_into(pieces, out)
    t2 = time.perf_counter()
    if tail is not None:
        tail.block_until_ready()
    t3 = time.perf_counter()
    return {"produce_s": t1 - t0, "fetch_s": t2 - t1, "tail_s": t3 - t1}


def main() -> None:
    dev = jax.devices()[0]
    x = jnp.eye(N, dtype=jnp.bfloat16) + 0.001
    bucketing._keep_freed_blocks_mapped()
    out = np.zeros(PIECES * (N // 8) * N, dtype=jnp.bfloat16)  # touched: its pages are mapped
    gb = out.nbytes / 1e9
    for under in (False, True):  # compile, and warm the runtime's buffers
        one(x, out, under)
    t0 = time.perf_counter()
    compute(x).block_until_ready()
    compute_alone_s = time.perf_counter() - t0
    runs = {"idle": [one(x, out, False) for _ in range(5)],
            "under": [one(x, out, True) for _ in range(5)]}
    med = lambda rows, k: sorted(r[k] for r in rows)[len(rows) // 2]  # noqa: E731
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "gb": gb, "compute_alone_s": compute_alone_s,
        "fetch_gb_s_chip_idle": gb / med(runs["idle"], "fetch_s"),
        "fetch_gb_s_under_program": gb / med(runs["under"], "fetch_s"),
        "tail_s_under": med(runs["under"], "tail_s"),
        "runs": runs}))


if __name__ == "__main__":
    main()
