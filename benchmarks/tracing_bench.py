"""Tracing-plane cost on the real example trainer + /metrics under load.

The fleet tracing pitch is spans cheap enough to leave on by default: a
span record is one O(1) dict append behind one lock, and the Prometheus
registry only syncs gauges when a scrape actually arrives. This harness
measures that claim instead of asserting it, three ways in one run:

- **managed loop with tracing + /metrics live**: the ft_overhead trainer
  (examples/train_ddp.py ``build_trainer``) under a Manager with the span
  recorder on and the manager-side /metrics endpoint serving, while
  scraper threads hammer ``GET /metrics`` until ``scrapes`` responses
  land — the under-load leg; every response must parse as Prometheus
  text.
- **direct per-span cost**: the exact record paths the hot loop runs
  (``span()`` context exit, ``record_rel``, ``instant``) timed in a tight
  loop; ``tracing_overhead_pct`` is per-span cost × observed spans/step
  as a share of the measured managed step — the number the <1% gate
  holds, on a batch that makes the toy's step 0.1-0.2 s (since PR 24 a
  step records about 23 spans with one bucket, not 5; ``bench.py`` caps
  the count too). (An end-to-end A/B of two full loops would measure the 1-vCPU
  host's scheduler, not the machinery — same reasoning as
  healthwatch_bench.)
- **coverage sanity**: the loop's spans must actually be in the ring
  (quorum + commit categories present) and a dump must merge into a
  valid Chrome trace — cost without coverage would be the worst trade.

    python benchmarks/tracing_bench.py

Prints one JSON line; ``bench.py --tracing`` runs it in a CPU-pinned
subprocess and merges the row into the bench artifact (committed as
BENCH_TRACE.json), and ``bench.py --tracing --smoke`` is the fast-tier
CI gate (tests/test_bench_smoke.py).
"""

import json
import os
import statistics
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))


def _median(xs):
    return statistics.median(xs) if xs else 0.0


def _parse_prometheus(text: str) -> int:
    """Count series, raising on any malformed exposition line."""
    n = 0
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        _name, value = line.rsplit(" ", 1)
        float(value)
        n += 1
    return n


def run(steps: int = 30, warmup: int = 5, batch_size: int = 4096,
        scrapers: int = 4, scrapes: int = 10000,
        span_calls: int = 2000) -> dict:
    """Time the example trainer under a tracing+metrics Manager while
    hammering /metrics, then micro-time the span record paths.

    Returns ``tracing_overhead_pct`` (spans-per-step × per-span cost as a
    share of the managed step), the scrape-under-load tallies, and the
    merged-trace sanity fields.
    """
    import optax

    from train_ddp import build_trainer

    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.observability import log_timing_event
    from torchft_tpu.process_group import ProcessGroupHost
    from torchft_tpu.tracing import merge_traces

    total = warmup + steps

    def apply_update(state, optimizer, grads):
        updates, new_opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        state["params"] = optax.apply_updates(state["params"], updates)
        state["opt_state"] = new_opt_state

    state, grad_fn, optimizer, make_batch = build_trainer(0, batch_size)
    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20, heartbeat_timeout_ms=2000,
    )
    manager = Manager(
        pg=ProcessGroupHost(timeout=30.0),
        load_state_dict=lambda sd: None,
        state_dict=lambda: {"params": state["params"]},
        min_replica_size=1,
        replica_id="trace_bench",
        lighthouse_addr=f"127.0.0.1:{lh.port}",
        timeout=30.0,
        heartbeat_interval=0.05,
        tracing=True,
        metrics_port=0,
    )
    metrics_url = f"http://127.0.0.1:{manager.metrics_port}/metrics"

    # /metrics under load: scraper threads hammer the endpoint through the
    # whole managed loop and keep going until the scrape budget is spent;
    # every response must parse (the gate asserts zero failures)
    stop = threading.Event()
    scrape_lock = threading.Lock()
    scrape_ms: list = []
    scrape_failures: list = []
    series_seen = [0]

    def scrape_loop():
        while not stop.is_set():
            with scrape_lock:
                if len(scrape_ms) + len(scrape_failures) >= scrapes:
                    return
            t0 = time.perf_counter()
            try:
                with urllib.request.urlopen(metrics_url, timeout=5.0) as resp:
                    body = resp.read().decode()
                n = _parse_prometheus(body)
                if n == 0:
                    raise RuntimeError("empty /metrics exposition")
                with scrape_lock:
                    series_seen[0] = max(series_seen[0], n)
                    scrape_ms.append((time.perf_counter() - t0) * 1000.0)
            except Exception as e:  # noqa: BLE001 — tallied, asserted below
                with scrape_lock:
                    scrape_failures.append(str(e)[:200])

    threads = [threading.Thread(target=scrape_loop, daemon=True)
               for _ in range(scrapers)]

    ft_times: list = []
    committed = 0
    try:
        for t in threads:
            t.start()
        for _ in range(total):
            x, y = make_batch()
            t0 = time.perf_counter()
            manager.start_quorum()
            loss, grads = grad_fn(state["params"], x, y)
            reduced = manager.allreduce(grads).get_future().wait(timeout=60)
            if manager.should_commit():
                apply_update(state, optimizer, reduced)
                committed += 1
            float(loss)
            ft_times.append(time.perf_counter() - t0)

        # snapshot BEFORE the micro-timing loop below: its bench spans
        # must not count toward the managed loop's spans-per-step
        loop_stats = manager.tracer.stats()

        # the loop's trace must be real: spans in the ring, categories the
        # taxonomy promises, and a dump that merges into valid Chrome JSON
        export = manager.tracer.export()
        cats = {s["cat"] for s in export["spans"]}
        trace = merge_traces([export])
        merged_events = len(trace["traceEvents"])

        # direct per-span cost of every hot-loop record shape, amortized
        t0 = time.perf_counter()
        for i in range(span_calls):
            with manager.tracer.span("bench_span", cat="commit"):
                pass
            pc = time.perf_counter()
            manager.tracer.record_rel(
                "bench_rel", cat="allreduce", t0_pc=pc - 1e-4, t1_pc=pc,
                bucket=i,
            )
            manager.tracer.instant("bench_instant", cat="rpc")
        span_cost_s = (time.perf_counter() - t0) / (span_calls * 3)

        # drain the scrape budget even if the loop finished first: "10k
        # scrapes answered" is the claim, and a short training loop must
        # not quietly shrink it
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            with scrape_lock:
                if len(scrape_ms) + len(scrape_failures) >= scrapes:
                    break
            time.sleep(0.05)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=5.0)
        manager.shutdown(wait=False)
        lh.shutdown()

    ft_step_s = _median(ft_times[warmup:])
    stats = loop_stats
    spans_per_step = stats["recorded"] / max(total, 1)
    overhead_s = span_cost_s * spans_per_step
    result = {
        "tracing_overhead_pct": round(
            overhead_s / ft_step_s * 100.0, 4
        ) if ft_step_s > 0 else None,
        "tracing_span_cost_us": round(span_cost_s * 1e6, 4),
        "tracing_spans_per_step": round(spans_per_step, 2),
        "trace_spans_recorded": int(stats["recorded"]),
        "trace_spans_dropped": int(stats["dropped"]),
        "trace_categories": sorted(cats),
        "trace_merged_events": merged_events,
        "ft_step_s": round(ft_step_s, 6),
        "metrics_scrapes_ok": len(scrape_ms),
        "metrics_scrapes_failed": len(scrape_failures),
        "metrics_scrape_p50_ms": round(_median(scrape_ms), 3),
        "metrics_series": series_seen[0],
        "steps": steps,
        "committed": committed,
        "batch_size": batch_size,
    }
    if scrape_failures:
        result["metrics_scrape_first_error"] = scrape_failures[0]
    # same artifact policy as the other rows: the measurement rides the
    # observability stream next to the snapshots it is about
    log_timing_event(phase="tracing_bench", replica_id="trace_bench",
                     **result)
    return result


if __name__ == "__main__":
    print(json.dumps(run()))
