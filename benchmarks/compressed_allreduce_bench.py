"""Compressed vs uncompressed streamed managed allreduce (host loopback).

PR 6's compressed streaming collectives claim the bucketed pipeline's wire
stage gets ≥2× effective bandwidth once buckets ride the ring fp8/int8-
compressed (1 code byte + 4/512 scale bytes per element instead of 4 f32
bytes, at the price of per-hop dequantize→accumulate→requantize compute
and pack-side codec cost absorbed by the pipeline's pack stage). This
harness measures that claim: two replica groups exchange the SAME
multi-bucket gradient tree through real Managers (live lighthouse,
per-step quorum + two-phase vote, loopback ProcessGroupHost) once per
compress mode — ``off`` (the bit-identical default), ``fp8``, ``int8`` —
and reports each mode's median step wall, pipeline stage splits
(``pack_s`` / ``wire_s`` / ``unpack_s`` from ``Manager.timings()``),
``overlap_efficiency``, the bytes each mode actually framed onto the
link (``wire_mb_per_step``), and the EFFECTIVE wire bandwidth: logical
(uncompressed f32) gradient bytes divided by the send-side wire
occupancy — seconds the transport spent inside sendall pushing frames
(``ProcessGroupHost.wire_stats``), NOT the manager's dispatch-to-done
``wire_s`` spans, which also count bucket queueing and (on small hosts)
codec CPU contention. The quotient reads directly as "bytes of gradient
delivered per second the wire was busy". ``bandwidth_ratio_fp8`` /
``bandwidth_ratio_int8`` are each mode's effective bandwidth over
``off``'s.

Medians throughout, same policy as the other harnesses.

    python benchmarks/compressed_allreduce_bench.py [--size-mb 64] [--cap-mb 4]

Prints one JSON line; ``bench.py --compressed-allreduce`` runs it in a
CPU-pinned subprocess (the committed BENCH_COMPRESS.json numbers) and
``--compressed-allreduce --smoke`` is the fast-tier CI gate
(tests/test_bench_smoke.py) asserting the per-mode split keys.
"""

import argparse
import json
import os
import statistics
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

MODES = ("off", "fp8", "int8")


def _median(xs):
    return statistics.median(xs) if xs else 0.0


def _make_tree(size_mb: float, leaves: int) -> dict:
    n_total = int(size_mb * (1 << 20)) // 4
    per = max(1, n_total // leaves)
    rng = np.random.RandomState(0)
    return {
        f"w{i}": rng.randn(per).astype(np.float32) for i in range(leaves)
    }


def _run_mode(mode: str, tree: dict, cap_bytes: int, steps: int,
              warmup: int) -> dict:
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.process_group import ProcessGroupHost

    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=5000,
        quorum_tick_ms=20, heartbeat_timeout_ms=5000,
    )
    barrier = threading.Barrier(2)
    step_times: list = []
    snaps: list = []
    wire_snaps: list = []
    errors: list = []

    def replica(rid: int) -> None:
        manager = None
        pg = ProcessGroupHost(timeout=60.0)
        try:
            manager = Manager(
                pg=pg,
                load_state_dict=lambda sd: None,
                state_dict=lambda: {"x": np.zeros(1, np.float32)},
                min_replica_size=2,
                replica_id=f"compress_{mode}_{rid}",
                lighthouse_addr=f"127.0.0.1:{lh.port}",
                timeout=60.0,
                bucket_cap_bytes=cap_bytes,
                compress=mode,
            )
            for i in range(steps):
                barrier.wait(timeout=180)
                t0 = time.perf_counter()
                manager.start_quorum()
                manager.allreduce_streamed(tree).wait(timeout=120)
                if not manager.should_commit():
                    errors.append(f"commit failed rid={rid} step={i}")
                if rid == 0:
                    step_times.append(time.perf_counter() - t0)
                    wire_snaps.append(pg.wire_stats())
                    if i >= warmup:
                        snaps.append(manager.timings())
        except Exception as e:  # noqa: BLE001
            errors.append(f"rid={rid}: {type(e).__name__}: {e}")
            barrier.abort()
        finally:
            if manager is not None:
                manager.shutdown(wait=False)

    threads = [
        threading.Thread(target=replica, args=(rid,), daemon=True)
        for rid in (0, 1)
    ]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
    finally:
        lh.shutdown()
    if errors:
        raise RuntimeError("; ".join(errors[:3]))

    out = {"step_s": round(_median(step_times[warmup:]), 6)}
    for key, snap_key in (
        ("pack_s", "allreduce_pack_s"),
        ("wire_s", "allreduce_wire_s"),
        ("unpack_s", "allreduce_unpack_s"),
        ("buckets", "allreduce_buckets"),
        ("overlap_efficiency", "overlap_efficiency"),
    ):
        vals = [s[snap_key] for s in snaps if snap_key in s]
        if vals:
            out[key] = round(_median(vals), 6)
    # transport occupancy over the measured (post-warmup) steps: bytes this
    # rank's sender actually framed onto the link, and the seconds sendall
    # spent pushing them (ProcessGroupHost.wire_stats)
    if len(wire_snaps) > warmup:
        first, last = wire_snaps[warmup - 1], wire_snaps[-1]
        measured = len(wire_snaps) - warmup
        out["wire_mb_per_step"] = round(
            (last["bytes_sent"] - first["bytes_sent"])
            / (1 << 20) / measured, 3
        )
        out["wire_busy_s_per_step"] = round(
            (last["busy_s"] - first["busy_s"]) / measured, 6
        )
    return out


def run(
    size_mb: float = 64,
    leaves: int = 16,
    cap_mb: float = 4,
    steps: int = 8,
    warmup: int = 2,
) -> dict:
    """Time the two-replica loopback exchange per compress mode.

    Returns per-mode stage splits + effective wire bandwidth (logical
    uncompressed bytes / wire_s, in MB/s) and the fp8/int8 bandwidth
    ratios over the uncompressed run.
    """
    from torchft_tpu.observability import log_timing_event

    tree = _make_tree(size_mb, leaves)
    logical_mb = sum(v.nbytes for v in tree.values()) / (1 << 20)
    cap_bytes = int(cap_mb * (1 << 20))

    modes = {}
    for mode in MODES:
        m = _run_mode(mode, tree, cap_bytes, steps, warmup)
        # effective wire bandwidth: logical (uncompressed f32) gradient MB
        # delivered per second of send-side wire occupancy. Occupancy, not
        # the manager's dispatch-to-done wire_s spans: the spans also count
        # bucket queueing and (on small hosts) codec CPU contention, which
        # would charge compute time to the wire
        busy = m.get("wire_busy_s_per_step") or 0.0
        m["effective_wire_mb_s"] = (
            round(logical_mb / busy, 3) if busy > 0 else None
        )
        modes[mode] = m

    off_bw = modes["off"]["effective_wire_mb_s"]
    result = {"modes": modes, "size_mb": size_mb, "leaves": leaves,
              "cap_mb": cap_mb, "steps": steps,
              "logical_mb": round(logical_mb, 3)}
    for mode in ("fp8", "int8"):
        bw = modes[mode]["effective_wire_mb_s"]
        result[f"bandwidth_ratio_{mode}"] = (
            round(bw / off_bw, 3) if bw and off_bw else None
        )
        step_off, step_m = modes["off"]["step_s"], modes[mode]["step_s"]
        result[f"step_speedup_pct_{mode}"] = (
            round((step_off - step_m) / step_off * 100.0, 2)
            if step_off > 0 else None
        )
    log_timing_event(phase="compressed_allreduce_bench",
                     replica_id="compress_bench", **{
                         k: v for k, v in result.items() if k != "modes"
                     })
    return result


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--size-mb", type=float, default=64)
    p.add_argument("--leaves", type=int, default=16)
    p.add_argument("--cap-mb", type=float, default=4)
    p.add_argument("--steps", type=int, default=8)
    p.add_argument("--warmup", type=int, default=2)
    a = p.parse_args()
    print(json.dumps(run(a.size_mb, a.leaves, a.cap_mb, a.steps, a.warmup)))
