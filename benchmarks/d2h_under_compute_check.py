"""Chip script: how fast does a device-to-host fetch run, WHILE a program
runs on the chip and behind an idle one, and what bounds it: the transfers
or the one thread that copies them?
(`chiprun -- python3 benchmarks/d2h_under_compute_check.py`)

Two chained jitted matmul programs of about 0.2 s each. The first one's
output is 0.5 GB in 32 pieces of 16 MiB (what ``bucketing.capture`` hands
the staging thread); every piece's ``copy_to_host_async`` is issued between
the two dispatches. From the moment the first program's pieces are ready,
three readings of the same bytes, each with the chip idle behind the fetch
and under the second program, medians of five:

- ``transfers``: ``np.asarray`` of every piece in turn and no copy: the
  transfers alone, the ceiling no host change can pass;
- ``loop``: ``bucketing.fetch_into`` on this thread alone, into a warm host
  buffer (what the staging thread did until PR 36);
- ``t<w>``: ``fetch_into`` with a pool, ``bucketing.FETCH_WIDTH`` set to
  ``w`` for the call: the pieces handed out in order to ``w`` threads.

``loop`` and ``t<w>`` once straight after the program and once ``_done``:
after 0.5 s more, the transfers long over, which is the copies alone. One
JSON line; PERF.md section 6 (PR 34, PR 36) has the readings.
"""

import json
import os
import queue
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from torchft_tpu import bucketing

N, PIECES, MATMULS = 8192, 32, 36  # 32 x [1024, 8192] bf16 = 0.5 GB
WIDTHS = (2, 3, 4, 6, 8)
REPEATS = 5


@jax.jit
def produce(x):
    y = jax.lax.fori_loop(0, MATMULS, lambda _, y: (y @ x) * 0.01, x)
    rows = N // 8  # flat pieces of 16 MiB, as the capture's
    return [(y[(i % 8) * rows:(i % 8 + 1) * rows] + i).reshape(-1)
            for i in range(PIECES)]


@jax.jit
def compute(x):
    return jax.lax.fori_loop(0, MATMULS, lambda _, y: (y @ x) * 0.01, x)


def diagnose(how, pieces, out, pool):
    """What holds the transfers back while a thread copies? ``await<w>``:
    ``w`` threads wait for the pieces in order and nobody copies;
    ``keep<w>``: ``w`` threads wait and copy and no piece is dropped before
    the end; ``ahead<w>``: one thread only waits, in order, and ``w`` copy
    what it has waited for. -> what to drop after the clock has stopped."""
    arrays, bounds = list(pieces.arrays), pieces.bounds
    kind = how.rstrip("0123456789")
    w = int(how[len(kind):])
    todo = bucketing._InOrder(len(arrays))

    def take(copy):
        for k in todo:
            host = np.asarray(arrays[k])
            if copy:
                np.copyto(out[bounds[k][0]:bounds[k][1]], host)

    if kind in ("await", "keep"):
        futs = [pool.submit(take, kind == "keep") for _ in range(w - 1)]
        take(kind == "keep")
    else:
        assert kind == "ahead", how
        waited = queue.SimpleQueue()

        def copier():
            while (k := waited.get()) is not None:
                np.copyto(out[bounds[k][0]:bounds[k][1]], np.asarray(arrays[k]))
                pieces.arrays[k] = arrays[k] = None

        futs = [pool.submit(copier) for _ in range(w)]
        for k in todo:
            np.asarray(arrays[k])
            waited.put(k)
        for _ in futs:
            waited.put(None)
    for f in futs:
        f.result()
    pieces.arrays[:] = [None] * len(arrays)
    return arrays


def one(x, out, under: bool, how: str, pool):
    """-> seconds: program 1 alone, the fetch after its end (after 0.5 s
    more where ``how`` ends in ``_done``), and where a second program was
    enqueued behind it, from program 1's end to that program's end; the
    threads' summed seconds inside a piece over the fetch's own."""
    t0 = time.perf_counter()
    arrays = produce(x)
    for a in arrays:
        a.copy_to_host_async()
    tail = compute(x) if under else None
    jax.block_until_ready(arrays)
    t1 = time.perf_counter()
    how, _, done = how.partition("_")
    if done:
        time.sleep(0.5)
    n = arrays[0].size
    pieces = bucketing.Pieces(
        arrays, [(k * n, (k + 1) * n) for k in range(PIECES)], n * PIECES,
        np.dtype(arrays[0].dtype))
    del arrays
    row = {}
    t_f = time.perf_counter()
    if how == "transfers":
        for k in range(PIECES):
            np.asarray(pieces.arrays[k])
        pieces.arrays[:] = [None] * PIECES
    elif how == "loop":
        bucketing.fetch_into(pieces, out)
    elif how[0] == "t":
        bucketing.FETCH_WIDTH = int(how[1:])
        width, busy_s, copy_s = bucketing._fetch(pieces, out, None, pool)
        assert width == int(how[1:]), (width, how)
        row = {"busy_s": busy_s, "copy_s": copy_s}
    else:
        held = diagnose(how, pieces, out, pool)
    t2 = time.perf_counter()
    held = None
    if tail is not None:
        tail.block_until_ready()
    t3 = time.perf_counter()
    return {"produce_s": t1 - t0, "fetch_s": t2 - t_f, "tail_s": t3 - t1, **row}


def main() -> None:
    dev = jax.devices()[0]
    x = jnp.eye(N, dtype=jnp.bfloat16) + 0.001
    bucketing._keep_freed_blocks_mapped()
    out = np.zeros(PIECES * (N // 8) * N, dtype=jnp.bfloat16)  # touched: its pages are mapped
    gb = out.nbytes / 1e9
    pool = ThreadPoolExecutor(max_workers=max(WIDTHS))
    hows = ["transfers", "loop", "loop_done"]
    for w in WIDTHS:
        hows += [f"t{w}", f"t{w}_done"]
    if "--diagnose" in sys.argv:
        hows = ["transfers", "loop", "t4", "await2", "await4", "keep1",
                "keep4", "ahead1", "ahead2", "ahead3", "ahead5"]
    for under in (False, True):  # compile, and warm the runtime's buffers and the threads
        for how in ("loop", f"t{max(WIDTHS)}"):
            one(x, out, under, how, pool)
    t0 = time.perf_counter()
    compute(x).block_until_ready()
    compute_alone_s = time.perf_counter() - t0
    runs = {}
    for _ in range(REPEATS):  # the readings interleaved, so a drift touches all alike
        for under in (False, True):
            for how in hows:
                runs.setdefault(("under" if under else "idle", how), []).append(
                    one(x, out, under, how, pool))
    pool.shutdown()
    med = lambda rows, k: sorted(r[k] for r in rows)[len(rows) // 2]  # noqa: E731
    table = {}
    for (where, how), rows in runs.items():
        cell = {"gb_s": gb / med(rows, "fetch_s"),
                "gb_s_all": [gb / r["fetch_s"] for r in rows]}
        if "busy_s" in rows[0]:
            cell["concurrency"] = med(
                [{"c": r["busy_s"] / r["fetch_s"]} for r in rows], "c")
            cell["copy_s"] = med(rows, "copy_s")
        if where == "under":
            cell["tail_s"] = med(rows, "tail_s")
        table.setdefault(where, {})[how] = cell
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "cores": len(os.sched_getaffinity(0)),
        "gb": gb, "compute_alone_s": compute_alone_s,
        # the two names PR 34 printed, for its readings' sake
        "fetch_gb_s_chip_idle": table["idle"]["loop"]["gb_s"],
        "fetch_gb_s_under_program": table["under"]["loop"]["gb_s"],
        "table": table}))


if __name__ == "__main__":
    main()
