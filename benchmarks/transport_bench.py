"""Host-plane data-path benchmarks.

1. Checkpoint transports (reference: checkpointing/pg_transport_bench.py and
   http_transport_bench.py — 12GB state dict timed over
   send_checkpoint/recv_checkpoint), with peak-RSS delta:

    python benchmarks/transport_bench.py --transport http --size-mb 1024
    python benchmarks/transport_bench.py --transport pg --size-mb 1024 --inplace

2. Cross-replica-group allreduce: the ring (reduce-scatter + allgather over
   raw frames) vs the naive full-mesh exchange, across world sizes, with
   measured per-rank bytes — the ring's traffic must be ~2x payload and
   world-size-independent:

    python benchmarks/transport_bench.py --transport allreduce --size-mb 64

   and where one ring's time goes, with the ranks as processes and a
   step's buckets as the trainer sends them (bf16, back to back):

    python benchmarks/transport_bench.py --transport allreduce --world 4 \
        --elements 189532160,315367424 --donate [--lanes 1|2|4|8]

   (``--lanes``: how many connections a ring neighbour the frames ride, the
   sweep that sets ``process_group._RING_LANES``; the split it prints,
   ``recv_wait_s`` / ``recv_s`` / ``fold_s`` / ``send_s`` / ``handoff_s`` /
   ``slot_wait_s`` / ``recv_span_s`` as [the lanes' mean, the largest
   lane's], is the ring's
   own count, the one a traced trainer's ``allreduce/ring_stream`` spans
   carry: nothing is patched), and the two readings
   under it, with no ring: what N loopback streams carry between the ranks,
   and what the fold's add does on several threads:

    python benchmarks/transport_bench.py --transport streams --world 4 \
        --size-mb 1536
    python benchmarks/transport_bench.py --transport bf16_add

Prints one JSON line per run.
"""

import argparse
import json
import os
import resource
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def _rss_mb() -> float:
    """Peak RSS of THIS process. VmHWM, not ru_maxrss: on Linux ru_maxrss
    survives fork+exec, so a subprocess inherits its parent's peak and the
    two-process bench would report a zero receiver delta."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmHWM"):
                    return int(line.split()[1]) / 1024  # KiB -> MiB
    except OSError:
        pass
    div = 1 << 20 if sys.platform == "darwin" else 1024
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / div


# one leaf size for the synthetic state, its template, and the --check leaf
# bound: these three must agree or the in-place template stops matching the
# sender's leaves and the regression guard computes the wrong ceiling
CHUNK_MB = 64


def _leaf_sizes(size_mb: int, chunk_mb: int = CHUNK_MB):
    """(n_chunks, floats_per_chunk) for a ~size_mb tree of chunk_mb leaves."""
    n_chunks = max(1, size_mb // chunk_mb)
    return n_chunks, size_mb * (1 << 20) // n_chunks // 4


def make_state(size_mb: int, chunk_mb: int = CHUNK_MB) -> dict:
    """A state pytree of ~size_mb in chunk_mb float32 leaves (mimics a
    sharded param/optimizer tree)."""
    n_chunks, per = _leaf_sizes(size_mb, chunk_mb)
    rng = np.random.RandomState(0)
    return {
        f"layer_{i}": rng.randn(per).astype(np.float32) for i in range(n_chunks)
    }


def make_template(size_mb: int, chunk_mb: int = CHUNK_MB) -> dict:
    """Same tree shape as ``make_state`` but zero-filled without the RNG —
    the in-place receiver must not inflate its RSS baseline (or its startup
    time) with a full random regeneration before the measurement.

    ``np.full`` rather than ``np.zeros``: zeros is calloc-lazy, so the
    template's pages would only become resident when the in-place copy
    writes them — charging the template's own footprint to the receive
    phase. A real trainer's live state is resident; make the template so.
    """
    n_chunks, per = _leaf_sizes(size_mb, chunk_mb)
    return {
        "user": {
            f"layer_{i}": np.full(per, 0, np.float32) for i in range(n_chunks)
        }
    }


def bench_http(state: dict, num_chunks: int, timeout: float) -> float:
    from torchft_tpu.checkpointing import HTTPTransport

    send = HTTPTransport(timeout=timeout, num_chunks=num_chunks)
    recv = HTTPTransport(timeout=timeout, num_chunks=num_chunks)
    try:
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=1) as ex:
            sf = ex.submit(
                send.send_checkpoint,
                dst_ranks=[1], step=1, state_dict={"user": state}, timeout=timeout,
            )
            got = recv.recv_checkpoint(
                src_rank=0, metadata=send.metadata(), step=1, timeout=timeout
            )
            sf.result(timeout=timeout)
        dt = time.perf_counter() - t0
        assert set(got["user"]) == set(state)
        return dt
    finally:
        send.shutdown()
        recv.shutdown()


def bench_pg(state: dict, inplace: bool, timeout: float) -> float:
    from torchft_tpu.checkpointing import PGTransport
    from torchft_tpu.coordination import KvStoreServer
    from torchft_tpu.process_group import ProcessGroupHost

    store = KvStoreServer("127.0.0.1:0")
    pgs = [ProcessGroupHost(timeout=timeout) for _ in range(2)]
    addr = f"127.0.0.1:{store.port}/bench"
    with ThreadPoolExecutor(max_workers=2) as ex:
        list(ex.map(lambda r: pgs[r].configure(addr, r, 2, quorum_id=1), range(2)))

    template = (
        {"user": {k: np.zeros_like(v) for k, v in state.items()}} if inplace else None
    )
    sender = PGTransport(pgs[0], timeout=timeout)
    receiver = PGTransport(
        pgs[1], timeout=timeout,
        state_dict_template=(lambda: template) if inplace else None,
    )
    try:
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=1) as ex:
            sf = ex.submit(
                sender.send_checkpoint,
                dst_ranks=[1], step=1, state_dict={"user": state}, timeout=timeout,
            )
            got = receiver.recv_checkpoint(
                src_rank=0, metadata=sender.metadata(), step=1, timeout=timeout
            )
            sf.result(timeout=timeout)
        dt = time.perf_counter() - t0
        assert set(got["user"]) == set(state)
        return dt
    finally:
        sender.shutdown()
        receiver.shutdown()
        for pg in pgs:
            pg.shutdown()
        store.shutdown()


def _add_steady_stats(stats: dict, recv_stats: dict, size_mb: int) -> None:
    """Fold the child's per-round times into the report: round 1 is the
    headline, min of the later rounds is the steady state."""
    if "seconds_rounds" in recv_stats:
        stats["seconds_rounds"] = recv_stats["seconds_rounds"]
        steady = min(recv_stats["seconds_rounds"][1:])
        stats["seconds_steady"] = steady
        stats["gb_per_s_steady"] = round(size_mb / 1024 / steady, 3)


def bench_pg_two_process(size_mb: int, timeout: float, inplace: bool,
                         repeat: int = 1,
                         snapshot_send: bool = True) -> dict:
    """Per-side RSS for the PG transport: parent = rank 0 sender, child =
    rank 1 receiver, each its own process over a shared KV store. With
    ``inplace`` the child preallocates a template and receives into it.

    ``repeat`` > 1 heals the same pair repeatedly (the production pattern —
    a live template absorbs every heal). Round 1 pays this host's
    first-touch page-fault tax on freshly allocated buffers (see
    docs/performance.md "microVM paging"); the steady-state rounds measure
    the transport itself."""
    import subprocess

    from torchft_tpu.checkpointing import PGTransport
    from torchft_tpu.coordination import KvStoreServer
    from torchft_tpu.process_group import ProcessGroupHost

    state = make_state(size_mb)
    payload_mb = sum(v.nbytes for v in state.values()) / 2**20
    store = KvStoreServer("127.0.0.1:0")
    addr = f"127.0.0.1:{store.port}/bench2p"
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--transport", "pg",
         "--size-mb", str(size_mb), "--timeout", str(timeout),
         "--repeat", str(repeat),
         *(["--inplace"] if inplace else []),
         "--_recv-child", f"pg:{addr}"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    pg = ProcessGroupHost(timeout=timeout)
    # snapshot_send=False is the zero-copy row: this sender mutates nothing
    # mid-stream, which is the contract that mode requires
    sender = PGTransport(pg, timeout=timeout, snapshot_send=snapshot_send)
    try:
        rss_before = _rss_mb()
        pg.configure(addr, 0, 2, quorum_id=1)  # rendezvous with the child
        for r in range(repeat):
            sender.send_checkpoint(
                dst_ranks=[1], step=r + 1, state_dict={"user": state},
                timeout=timeout,
            )
        sender_delta = _rss_mb() - rss_before
        try:
            out, err = child.communicate(timeout=timeout + 120)
        except subprocess.TimeoutExpired:
            child.kill()
            out, err = child.communicate()
            sys.exit(f"pg recv child wedged:\n{err[-2000:]}")
        if child.returncode != 0:
            sys.exit(f"pg recv child failed:\n{err[-2000:]}")
        recv_stats = json.loads(out.strip().splitlines()[-1])
    finally:
        # a parent-side failure (configure timeout, send error) must not
        # orphan the child blocked in recv for its full timeout
        if child.poll() is None:
            child.kill()
            child.communicate()
        sender.shutdown()
        pg.shutdown()
        store.shutdown()
    stats = {
        "transport": "pg-2proc",
        "size_mb": size_mb,
        "inplace": inplace,
        "seconds": recv_stats["seconds"],
        "gb_per_s": round(size_mb / 1024 / recv_stats["seconds"], 3),
        "sender_send_rss_x_payload": round(sender_delta / payload_mb, 2),
        "receiver_rss_x_payload": round(
            recv_stats["rss_delta_mb"] / payload_mb, 2
        ),
    }
    _add_steady_stats(stats, recv_stats, size_mb)
    print(json.dumps(stats), flush=True)
    return stats


def _verify_and_report_recv(got: dict, dt: float, delta: float,
                            rounds: "list | None" = None) -> None:
    """Shared tail of both recv children: verify content cheaply (make_state
    seeds RandomState(0) and layer_0 is its first draw, so the first 64
    values match regardless of total size — no multi-GB regeneration after
    the measurement), then print the stats the parent parses."""
    expect = np.random.RandomState(0).randn(64).astype(np.float32)
    np.testing.assert_array_equal(got["user"]["layer_0"][:64], expect)
    stats = {"seconds": round(dt, 3), "rss_delta_mb": round(delta, 1)}
    if rounds is not None and len(rounds) > 1:
        stats["seconds_rounds"] = rounds
    print(json.dumps(stats))


def _pg_recv_child(addr: str, size_mb: int, timeout: float, inplace: bool,
                   repeat: int = 1) -> None:
    from torchft_tpu.checkpointing import PGTransport
    from torchft_tpu.process_group import ProcessGroupHost

    template = make_template(size_mb) if inplace else None
    pg = ProcessGroupHost(timeout=timeout)
    recv = PGTransport(
        pg, timeout=timeout,
        state_dict_template=(lambda: template) if inplace else None,
    )
    rounds = []
    try:
        pg.configure(addr, 1, 2, quorum_id=1)
        rss0 = _rss_mb()
        for r in range(repeat):
            t0 = time.perf_counter()
            got = recv.recv_checkpoint(
                src_rank=0, metadata=recv.metadata(), step=r + 1,
                timeout=timeout,
            )
            rounds.append(round(time.perf_counter() - t0, 3))
        delta = _rss_mb() - rss0
    finally:
        recv.shutdown()
        pg.shutdown()
    _verify_and_report_recv(got, rounds[0], delta, rounds)


def bench_http_two_process(size_mb: int, num_chunks: int, timeout: float,
                           inplace: bool = False, repeat: int = 1) -> dict:
    """Per-SIDE peak RSS (the streaming bound is ~1x payload + one leaf per
    side; the single-process bench necessarily shows ~2x because both ends
    share one address space). Parent stages + serves; a fresh child fetches
    and reports its own delta."""
    import subprocess

    from torchft_tpu.checkpointing import HTTPTransport

    state = make_state(size_mb)
    payload_mb = sum(v.nbytes for v in state.values()) / 2**20
    rss_before_stage = _rss_mb()
    send = HTTPTransport(timeout=timeout, num_chunks=num_chunks)
    try:
        send.send_checkpoint(
            dst_ranks=[1], step=1, state_dict={"user": state}, timeout=timeout
        )
        sender_delta = _rss_mb() - rss_before_stage
        child = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--transport",
             "http", "--size-mb", str(size_mb),
             "--num-chunks", str(num_chunks),
             "--timeout", str(timeout), "--repeat", str(repeat),
             *(["--inplace"] if inplace else []),
             "--_recv-child", send.metadata()],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        # restage per round: disallow_checkpoint waits (bounded) for the
        # child to finish fetching the staged step before the swap, so the
        # child's retry loop only ever spans the restage gap
        for r in range(1, repeat):
            # dead child: let communicate() surface its stderr now instead
            # of stalling grace=timeout for each remaining round
            if child.poll() is not None:
                break
            # full-timeout grace: the child may still be allocating its
            # template before its first fetch; a short grace would restage
            # early and strand the child's step-r retry loop
            send.disallow_checkpoint(grace=timeout)
            send.send_checkpoint(
                dst_ranks=[1], step=r + 1, state_dict={"user": state},
                timeout=timeout,
            )
        try:
            out, err = child.communicate(
                # budget beyond the fetch timeout: interpreter/numpy
                # startup and the post-measurement payload verification
                timeout=timeout + 120,
            )
        except subprocess.TimeoutExpired:
            child.kill()
            out, err = child.communicate()
            sys.exit(f"recv child wedged past {timeout + 120}s:\n{err[-2000:]}")
        if child.returncode != 0:
            sys.exit(f"recv child failed:\n{err[-2000:]}")
        recv_stats = json.loads(out.strip().splitlines()[-1])
    finally:
        send.shutdown()
    stats = {
        "transport": "http-2proc",
        "size_mb": size_mb,
        "inplace": inplace,
        "seconds": recv_stats["seconds"],
        "gb_per_s": round(size_mb / 1024 / recv_stats["seconds"], 3),
        "sender_stage_rss_x_payload": round(sender_delta / payload_mb, 2),
        "receiver_rss_x_payload": round(
            recv_stats["rss_delta_mb"] / payload_mb, 2
        ),
    }
    _add_steady_stats(stats, recv_stats, size_mb)
    print(json.dumps(stats), flush=True)
    return stats


def _recv_child(metadata: str, size_mb: int, num_chunks: int, timeout: float,
                inplace: bool = False, repeat: int = 1) -> None:
    """Receiver half of the two-process bench: fetch, verify, report RSS."""
    import urllib.error

    from torchft_tpu.checkpointing import HTTPTransport

    template = make_template(size_mb) if inplace else None
    recv = HTTPTransport(
        timeout=timeout, num_chunks=num_chunks,
        state_dict_template=(lambda: template) if inplace else None,
    )
    rounds = []
    try:
        rss0 = _rss_mb()
        for r in range(repeat):
            # the sender restages between rounds; retry through the gap
            # where step r+1 is not yet staged (metadata fetch 400s)
            deadline = time.monotonic() + timeout
            t0 = time.perf_counter()
            while True:
                try:
                    got = recv.recv_checkpoint(
                        src_rank=0, metadata=metadata, step=r + 1,
                        timeout=timeout,
                    )
                    break
                except urllib.error.HTTPError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.05)
                    t0 = time.perf_counter()  # don't bill the restage gap
            rounds.append(round(time.perf_counter() - t0, 3))
        delta = _rss_mb() - rss0
    finally:
        recv.shutdown()
    _verify_and_report_recv(got, rounds[0], delta, rounds)


def bench_allreduce(size_mb: int, timeout: float) -> None:
    """Ring vs naive exchange across world sizes, with per-rank bytes from
    the _Comm traffic counters (VERDICT round-2 item 2's 'Done' numbers)."""
    import torchft_tpu.process_group as pg_mod
    from torchft_tpu.coordination import KvStoreServer
    from torchft_tpu.process_group import ProcessGroupHost, ReduceOp

    n = size_mb * (1 << 20) // 4
    payload = n * 4
    for world in (2, 4):
        for algo in ("ring", "naive", "fp8"):
            store = KvStoreServer("127.0.0.1:0")
            pgs = [ProcessGroupHost(timeout=timeout) for _ in range(world)]
            addr = f"127.0.0.1:{store.port}/bench_ar"
            with ThreadPoolExecutor(world) as ex:
                list(ex.map(
                    lambda r: pgs[r].configure(addr, r, world, quorum_id=1),
                    range(world),
                ))
            old_thresh = pg_mod._RING_MIN_BYTES
            pg_mod._RING_MIN_BYTES = 0 if algo == "ring" else 1 << 62
            try:
                vals = [np.full(n, float(r + 1), np.float32) for r in range(world)]

                if algo == "fp8":
                    from torchft_tpu.collectives import allreduce_quantized

                    def step(r):
                        return (
                            allreduce_quantized(
                                [vals[r]], ReduceOp.SUM, pgs[r]
                            ).get_future().wait(timeout)
                        )
                else:
                    def step(r):
                        return (
                            pgs[r].allreduce([vals[r]], ReduceOp.SUM)
                            .get_future().wait(timeout)
                        )

                with ThreadPoolExecutor(world) as ex:  # warmup + correctness
                    outs = list(ex.map(step, range(world)))
                assert np.allclose(outs[0][0][:8], world * (world + 1) / 2)

                base = [pg._gen.comm.bytes_sent for pg in pgs]
                iters = 3
                t0 = time.perf_counter()
                for _ in range(iters):
                    with ThreadPoolExecutor(world) as ex:
                        list(ex.map(step, range(world)))
                dt = (time.perf_counter() - t0) / iters
                sent = max(
                    pg._gen.comm.bytes_sent - b for pg, b in zip(pgs, base)
                ) / iters
            finally:
                pg_mod._RING_MIN_BYTES = old_thresh
                for pg in pgs:
                    pg.shutdown()
                store.shutdown()
            print(json.dumps({
                "transport": "allreduce",
                "algo": algo,
                "world": world,
                "size_mb": size_mb,
                "seconds": round(dt, 4),
                "gbit_per_s": round(payload * 8 / dt / 1e9, 2),
                "per_rank_sent_x_payload": round(sent / payload, 2),
            }), flush=True)


def _spawn_ranks(flag: str, spec: dict, world: int, timeout: float) -> list:
    """``world`` copies of this script as ranks of ``spec`` (each gets the
    KV store's address in it); their last stdout lines, parsed."""
    import subprocess

    from torchft_tpu.coordination import KvStoreServer

    store = KvStoreServer("127.0.0.1:0")
    text = json.dumps({**spec, "addr": f"127.0.0.1:{store.port}/bench",
                       "world": world})
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 flag, text, "--_ring-rank", str(r)],
                stdout=subprocess.PIPE, text=True,
            )
            for r in range(world)
        ]
        return [json.loads(p.communicate(timeout=timeout)[0]
                           .strip().splitlines()[-1]) for p in procs]
    finally:
        store.shutdown()


# what the ring counts of a lane's threads (process_group._RING_TERMS), as
# this bench prints it: ``recv_s`` was one number, socket time with the
# waits for the peer in it, while the ring was timed from outside; the
# ring's own clock tells the header waits (``recv_wait_s``) from the
# payloads (``recv_s``)
_SPLIT = {"recv_wait_s": "recv_wait", "recv_s": "recv", "fold_s": "fold",
          "send_s": "send", "handoff_s": "handoff",
          "slot_wait_s": "slot_wait", "recv_span_s": "recv_span"}


def bench_ring_split(world: int, elements: list, donate: bool, iters: int,
                     chunk_mb: float, lanes: int, python_frames: bool,
                     dtype: str, timeout: float) -> None:
    """One step's buckets through the plain ring, ``world`` ranks as
    processes on this host: the step's wall time and where the ring's
    threads spent it, by the ring's own count (see :func:`_ring_child`)."""
    ranks = _spawn_ranks("--_ring-child", {
        "elements": elements, "donate": donate, "iters": iters,
        "chunk_mb": chunk_mb, "lanes": lanes, "python_frames": python_frames,
        "dtype": dtype, "timeout": timeout,
    }, world, timeout * (iters + 2))
    assert len({r["crc"] for r in ranks}) == 1, "ranks disagree"
    nbytes = ranks[0]["itemsize"] * sum(elements)
    print(json.dumps({
        "transport": "allreduce", "algo": "ring_split", "world": world,
        "dtype": dtype, "elements": elements, "donate": donate,
        "chunk_mb": ranks[0]["chunk_mb"], "lanes": ranks[0]["lanes"],
        "native_fold": ranks[0]["native_fold"],
        "native_frames": ranks[0]["native_frames"], "iters": iters,
        # each the median over iterations of one rank, then the slowest
        # rank: the step; fn(comm) on the dispatch thread, summed over the
        # step's ops; of that, until the first peer byte; and seconds a step
        # in each of the ring's terms, [the lanes' mean, the largest lane's]
        # (a lane's receive, fold and send are three threads)
        **{k: round(max(r[k] for r in ranks), 4)
           for k in ("step_s", "ring_s", "entry_wait_s")},
        **{k: [round(max(r[k][i] for r in ranks), 4) for i in (0, 1)]
           for k in _SPLIT},
        "gbit_per_s": round(
            nbytes * 8 / max(r["step_s"] for r in ranks) / 1e9, 2),
        "inplace": ranks[0]["inplace"],
        "result_crc": ranks[0]["crc"],
    }), flush=True)


def _ring_child(spec: dict, rank: int) -> None:
    """One rank of :func:`bench_ring_split`. Nothing of the library is
    wrapped or timed here: the split is what the ring counted of itself and
    left on each op's future (``fut.ring``: process_group._ring_allreduce),
    ``ring_s`` and ``entry_wait_s`` come from the dispatch thread's stamps
    beside it (``fut.stamps``), all summed over the step's ops.
    ``lanes`` and ``chunk_mb`` are the bench's own arguments: they set the
    module's constants in this process, the library takes none."""
    import statistics
    import threading
    import zlib

    import ml_dtypes

    import torchft_tpu.process_group as pg_mod
    from torchft_tpu.process_group import ProcessGroupHost, ReduceOp

    if spec["chunk_mb"]:
        pg_mod._RING_CHUNK_BYTES = int(spec["chunk_mb"] * 2**20)
    if spec["lanes"]:
        pg_mod._RING_LANES = spec["lanes"]
    pg_mod._RING_LANE_FLOOR_BYTES = (
        2 * pg_mod._RING_LANES * pg_mod._RING_CHUNK_BYTES)
    real_native = pg_mod._native_ring

    if spec["python_frames"]:
        # the two calls that move a frame's bytes are told the library has
        # no native ones; the lanes and the fold keep theirs
        in_frame = threading.local()
        pg_mod._native_ring = (
            lambda: None if getattr(in_frame, "on", False) else real_native())

        def in_python(fn):
            def call(*args, **kwargs):
                in_frame.on = True
                try:
                    return fn(*args, **kwargs)
                finally:
                    in_frame.on = False
            return call

        pg_mod._send_all = in_python(pg_mod._send_all)
        pg_mod._recv_all = in_python(pg_mod._recv_all)

    pg = ProcessGroupHost(timeout=spec["timeout"])
    pg.configure(spec["addr"], rank, spec["world"], quorum_id=1)
    rng = np.random.default_rng(rank)
    dtype = np.dtype(getattr(ml_dtypes, spec["dtype"], spec["dtype"]))
    grads = [
        (rng.standard_normal(n, np.float32) * 0.01).astype(dtype)
        for n in spec["elements"]
    ]
    # the staging buffers of a pool: made once, warm, refilled every step
    bufs = [np.empty_like(g) for g in grads]
    rows, info, outs = [], {}, []
    for _ in range(spec["iters"] + 1):  # the first warms up
        for b, g in zip(bufs, grads):
            np.copyto(b, g)
        pg.barrier().wait(spec["timeout"])
        t0 = time.perf_counter()
        futs = [
            pg.allreduce([b], ReduceOp.SUM, donate=spec["donate"]).get_future()
            for b in bufs
        ]
        outs = [f.wait(spec["timeout"])[0] for f in futs]
        row = {"step_s": time.perf_counter() - t0, "ring_s": 0.0,
               "entry_wait_s": 0.0,
               **{(k, end): 0.0 for k in _SPLIT for end in ("", "_max")}}
        for f in futs:
            info, (_, t_run0, t_run1) = f.ring, f.stamps
            row["ring_s"] += t_run1 - t_run0
            row["entry_wait_s"] += info["t_first"] - t_run0
            for k, term in _SPLIT.items():
                for end in ("", "_max"):
                    row[k, end] += info[term + "_us" + end] / 1e6
        rows.append(row)
    pg.shutdown()
    rows = rows[1:]
    med = {k: statistics.median(r[k] for r in rows) for k in rows[0]}
    row = {k: med[k] for k in ("step_s", "ring_s", "entry_wait_s")}
    for k in _SPLIT:
        row[k] = [med[k, ""], med[k, "_max"]]
    row["inplace"] = info.get("inplace", 0)
    row["lanes"] = info.get("lanes", 1)
    row["native_fold"] = int(real_native() is not None)
    row["native_frames"] = int(
        real_native() is not None and not spec["python_frames"])
    row["chunk_mb"] = pg_mod._RING_CHUNK_BYTES / 2**20
    row["itemsize"] = dtype.itemsize
    row["crc"] = "%08x" % zlib.crc32(b"".join(
        o.view(np.uint8).tobytes()[:1 << 24] for o in outs))
    print(json.dumps(row), flush=True)


def bench_streams(world: int, size_mb: int, timeout: float) -> None:
    """What the host's loopback carries between ring neighbours, with no
    ring: every rank (a process) sends ``size_mb`` to its right neighbour
    and receives as much from its left at once, over 1 / 2 / 4 / 8 sockets,
    each with a sender and a receiver thread and 4 MiB ``sendall`` /
    ``recv_into`` calls. GB/s a direction a rank, the slowest rank's."""
    for socks in (1, 2, 4, 8):
        ranks = _spawn_ranks("--_streams-child", {
            "size_mb": size_mb, "socks": socks, "timeout": timeout,
        }, world, timeout)
        print(json.dumps({
            "transport": "streams", "world": world, "sockets": socks,
            "size_mb": size_mb,
            "seconds": round(max(r["seconds"] for r in ranks), 4),
            "gb_per_s_a_direction": round(
                size_mb / 1024 / max(r["seconds"] for r in ranks), 3),
        }), flush=True)


def _streams_child(spec: dict, rank: int) -> None:
    import socket
    import threading

    from torchft_tpu.coordination import KvClient

    world, socks, timeout = spec["world"], spec["socks"], spec["timeout"]
    host_port, _, prefix = spec["addr"].partition("/")
    kv = KvClient(host_port, connect_timeout=timeout)
    listener = socket.socket()
    listener.bind(("127.0.0.1", 0))
    listener.listen(socks)
    key = f"{prefix}/s{socks}"
    kv.set(f"{key}/addr_{rank}", str(listener.getsockname()[1]),
           timeout=timeout)
    port = int(kv.get(f"{key}/addr_{(rank + 1) % world}", timeout=timeout))
    outs = [socket.create_connection(("127.0.0.1", port)) for _ in range(socks)]
    ins = [listener.accept()[0] for _ in range(socks)]
    frame = 4 << 20
    each = spec["size_mb"] * 2**20 // socks // frame * frame
    src = np.ones(each, np.uint8)
    dsts = [np.empty(each, np.uint8) for _ in range(socks)]

    def send(sock):
        mv = memoryview(src)
        for a in range(0, each, frame):
            sock.sendall(mv[a:a + frame])

    def recv(sock, dst):
        mv, got = memoryview(dst), 0
        while got < each:
            got += sock.recv_into(mv[got:], min(each - got, 1 << 20))

    def once():
        threads = [threading.Thread(target=send, args=(s,)) for s in outs]
        threads += [threading.Thread(target=recv, args=(s, d))
                    for s, d in zip(ins, dsts)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0

    took = []
    for i in range(4):  # the first warms the pages
        kv.set(f"{key}/go{i}_{rank}", "1", timeout=timeout)
        for r in range(world):
            kv.get(f"{key}/go{i}_{r}", timeout=timeout)
        took.append(once())
    print(json.dumps({"seconds": sorted(took[1:])[1]}), flush=True)


def bench_bf16_add(frame_mb: int = 4, total_mb: int = 512) -> None:
    """The fold's add over bf16 frames of ``frame_mb`` on 1 / 2 / 4 threads
    of one process: ml_dtypes' ``dst += src`` (holds the interpreter's lock)
    against the native loop (lets go of it). G elements a second, all
    threads together."""
    import threading

    import ml_dtypes

    import torchft_tpu.process_group as pg_mod
    from torchft_tpu.process_group import ReduceOp

    n = frame_mb * 2**20 // 2
    reps = total_mb // frame_mb
    native = pg_mod._native_ring()
    for how in ("ml_dtypes", "native"):
        if how == "native" and native is None:
            continue
        for threads in (1, 2, 4):
            bufs = [
                (np.full(n, 0.5, np.float32).astype(ml_dtypes.bfloat16),
                 np.full(n, 1e-3, np.float32).astype(ml_dtypes.bfloat16))
                for _ in range(threads)
            ]

            def work(dst, src):
                for _ in range(reps // threads):
                    if how == "native":
                        pg_mod._fold(ReduceOp.SUM, dst, src)
                    else:
                        pg_mod._accum(ReduceOp.SUM, dst, src)

            took = []
            for _ in range(4):  # the first warms up
                ts = [threading.Thread(target=work, args=b) for b in bufs]
                t0 = time.perf_counter()
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                took.append(time.perf_counter() - t0)
            dt = sorted(took[1:])[1]
            print(json.dumps({
                "transport": "bf16_add", "how": how, "threads": threads,
                "frame_mb": frame_mb,
                "g_elements_per_s": round(
                    reps // threads * threads * n / dt / 1e9, 3),
            }), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--transport",
        choices=["http", "pg", "allreduce", "streams", "bf16_add"],
        default="http",
    )
    parser.add_argument("--size-mb", type=int, default=256)
    parser.add_argument("--num-chunks", type=int, default=8,
                        help="http parallel chunk fetches")
    parser.add_argument("--inplace", action="store_true",
                        help="pg/http: receive into a preallocated template")
    parser.add_argument("--timeout", type=float, default=600.0)
    parser.add_argument("--two-process", action="store_true",
                        help="http/pg: sender and receiver in separate "
                             "processes, per-side peak RSS")
    parser.add_argument("--check", action="store_true",
                        help="two-process: exit 1 if a side's peak RSS "
                             "exceeds --rss-bound x payload (regression "
                             "guard for the streaming paths)")
    parser.add_argument("--rss-bound", type=float, default=1.15,
                        help="per-side peak-RSS/payload ceiling for --check "
                             "(streaming bound is ~1x + one leaf)")
    parser.add_argument("--inplace-recv-bound", type=float, default=0.15,
                        help="receiver-side ceiling for --check with "
                             "--inplace: the template absorbs the payload, "
                             "so receiver RSS growth must stay ~one leaf; "
                             "the general --rss-bound (~1x) would pass even "
                             "a fully-materializing regression")
    parser.add_argument("--no-snapshot-send", action="store_true",
                        help="pg: stream straight from the sender's arrays "
                             "(PGTransport snapshot_send=False — no "
                             "per-heal checkpoint copy; requires nothing "
                             "mutates state mid-stream)")
    parser.add_argument("--repeat", type=int, default=1,
                        help="two-process: heal the same pair N times; "
                             "rounds >1 report the steady state (round 1 "
                             "pays this host's first-touch paging tax)")
    parser.add_argument("--elements", default="",
                        help="allreduce: a step's bf16 bucket sizes, comma "
                             "separated; runs the ring alone with the ranks "
                             "as processes and prints its split")
    parser.add_argument("--world", type=int, default=4,
                        help="allreduce --elements: ranks (processes)")
    parser.add_argument("--donate", action="store_true",
                        help="allreduce --elements: donate the buckets, as "
                             "the bucket pipeline does its staging buffers")
    parser.add_argument("--iters", type=int, default=3,
                        help="allreduce --elements: measured steps")
    parser.add_argument("--chunk-mb", type=float, default=0.0,
                        help="allreduce --elements: the ring's frame size "
                             "(default: the module's)")
    parser.add_argument("--lanes", type=int, default=0,
                        help="allreduce --elements: the ring's lanes, "
                             "connections a neighbour (default: the "
                             "module's); the sweep that sets the constant")
    parser.add_argument("--dtype", default="bfloat16",
                        choices=["bfloat16", "float32"],
                        help="allreduce --elements: the buckets' dtype (the "
                             "native fold is bfloat16's; float32 is numpy's)")
    parser.add_argument("--python-frames", action="store_true",
                        help="allreduce --elements: move the frames with "
                             "Python's sendall / recv_into, not the native "
                             "calls (what the ring falls back to)")
    parser.add_argument("--_recv-child", default="", help=argparse.SUPPRESS)
    parser.add_argument("--_streams-child", default="",
                        help=argparse.SUPPRESS)
    parser.add_argument("--_ring-child", default="", help=argparse.SUPPRESS)
    parser.add_argument("--_ring-rank", type=int, default=0,
                        help=argparse.SUPPRESS)
    args = parser.parse_args()

    if args._ring_child:
        _ring_child(json.loads(args._ring_child), args._ring_rank)
        return
    if args._streams_child:
        _streams_child(json.loads(args._streams_child), args._ring_rank)
        return
    if args.transport == "streams":
        bench_streams(args.world, args.size_mb, args.timeout)
        return
    if args.transport == "bf16_add":
        bench_bf16_add()
        return

    if args.check and not args.two_process:
        # the single-process bench shares one address space (~2x RSS by
        # design) — a --check there would be meaningless, and silently
        # skipping it would be a green CI signal with no guard evaluated
        parser.error("--check requires --two-process (per-side RSS)")
    if (args.inplace and args.transport == "http" and not args.two_process
            and not args._recv_child):
        # the single-process http bench has no template path; silently
        # dropping the flag would report a non-inplace run as requested.
        # (_recv_child IS the receiver half of a two-process run — it gets
        # --inplace without --two-process and must not trip this guard.)
        parser.error("--transport http --inplace requires --two-process")
    if args._recv_child:
        if args._recv_child.startswith("pg:"):
            _pg_recv_child(args._recv_child[3:], args.size_mb, args.timeout,
                           args.inplace, args.repeat)
        else:
            _recv_child(args._recv_child, args.size_mb, args.num_chunks,
                        args.timeout, args.inplace, args.repeat)
        return
    if args.transport == "allreduce" and args.elements:
        bench_ring_split(
            args.world, [int(n) for n in args.elements.split(",")],
            args.donate, args.iters, args.chunk_mb, args.lanes,
            args.python_frames, args.dtype, args.timeout,
        )
        return
    if args.transport == "allreduce":
        bench_allreduce(args.size_mb, args.timeout)
        return
    if args.two_process:
        if args.transport == "http":
            stats = bench_http_two_process(
                args.size_mb, args.num_chunks, args.timeout, args.inplace,
                args.repeat,
            )
        else:  # "pg" — argparse choices exclude everything else
            stats = bench_pg_two_process(
                args.size_mb, args.timeout, args.inplace, args.repeat,
                snapshot_send=not args.no_snapshot_send,
            )
        if args.check:
            # in-place receive holds ~1-2 transient CHUNK_MB leaves besides
            # the resident template, so the receiver ceiling is
            # leaf-granular; budget THREE leaves — one more than the
            # worst-case legitimate transient — so allocator/measurement
            # noise can't flake the guard while a materializing regression
            # (1x+ payload) still fails by a wide margin. At 12 GB that's
            # ~0.016x payload, at 1 GB ~0.19x; below ~512 MB the ratio is
            # leaf-dominated and the check loses discriminating power.
            leaf_x_payload = 3 * float(CHUNK_MB) / max(args.size_mb, 1)

            # one leaf of slack for EVERY bound: at small payloads a single
            # transient 64 MB buffer coinciding with the peak is legitimate
            # noise, not a regression (at 12 GB the slack is ~0.005x)
            one_leaf = float(CHUNK_MB) / max(args.size_mb, 1)

            def bound_for(key: str) -> float:
                # gate on the stat the run actually produced, not the raw
                # flag (both http and pg two-process runs report it)
                if stats.get("inplace") and key == "receiver_rss_x_payload":
                    return max(args.inplace_recv_bound, leaf_x_payload)
                return args.rss_bound + one_leaf

            over = {
                k: (v, bound_for(k)) for k, v in stats.items()
                if k.endswith("rss_x_payload") and v > bound_for(k)
            }
            if over:
                sys.exit(
                    f"RSS regression: {over} exceeds its (value, bound)x "
                    "payload ceiling — a streaming/in-place path is "
                    "materializing the full checkpoint"
                )
        return

    state = make_state(args.size_mb)
    rss0 = _rss_mb()
    if args.transport == "http":
        dt = bench_http(state, args.num_chunks, args.timeout)
    else:
        dt = bench_pg(state, args.inplace, args.timeout)
    payload_mb = sum(v.nbytes for v in state.values()) / 2**20
    rss_delta = _rss_mb() - rss0
    print(json.dumps({
        "transport": args.transport,
        "size_mb": args.size_mb,
        "inplace": bool(args.inplace and args.transport == "pg"),
        "seconds": round(dt, 3),
        "gb_per_s": round(args.size_mb / 1024 / dt, 3),
        "peak_rss_delta_mb": round(rss_delta, 1),
        "rss_delta_x_payload": round(rss_delta / payload_mb, 2),
    }))


if __name__ == "__main__":
    main()
