"""Benchmark: training throughput of the flagship Llama model on this host's
TPU. Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Times the bf16 adamw train step of a ~1.07B-param Llama (`bench_1b` at
batch 4, seq 2048; ~6 GiB adamw state on a 16 GiB v5e), then the ~349M
batch-8 config into `bench_350m_*` fields on the same line. This is a bare
optax loop: no lighthouse, no Manager, no collective (`chip_smoke.py` runs
the managed trainer). Without a TPU it fails: a CPU timing is never written
under a device metric's name. vs_baseline is achieved/peak model FLOP/s by
the 6N convention (attention FLOPs not counted) against the per-device_kind
peak table in torchft_tpu/utils.py.

The host-plane FT rows are their own modes (`--smoke`, `--ft-overhead`,
`--compressed-allreduce`, ...): CPU measurements in CPU-pinned children, never
part of the chip record.

`timed_train_step` is the single measurement harness — benchmarks/mfu_sweep.py
imports it so the sweep and the headline bench can't diverge.
"""

import json
import sys
import time

# tok/s of each timing window from the most recent timed_train_step call
# (same module-global reporting pattern as ops.attention.LAST_DISPATCH):
# the return signature stays (tok/s, mfu) so sweep children never break
LAST_WINDOWS: "list[float]" = []


def timed_train_step(cfg, batch, seq, steps, remat="full", lr=3e-4,
                     loss_chunk=0, master_f32=False):
    """Compile and time the bf16 adamw train step; returns (tokens/s, mfu).

    One shared harness for bench.py and the sweep: jit with donated
    params/opt-state, one warmup step forced to a host scalar, then a timed
    loop chained through the donated state and ended by a value fetch.

    ``master_f32`` switches to the mixed-precision training recipe: master
    params and adamw moments in f32, weights cast to bf16 at use so the
    matmuls still hit the MXU at bf16 rate. The default (False) trains
    pure-bf16 end to end — params, moments, and update arithmetic — which
    is the historical headline configuration; the f32-master variant is the
    numerically production-grade one and its measured cost is recorded in
    docs/performance.md.
    """
    import jax
    import jax.numpy as jnp
    import optax

    from torchft_tpu.models.llama import llama_init, llama_loss
    from torchft_tpu.utils import peak_flops_per_chip

    # reset up front so a failed call can't leave the previous call's
    # windows attributed to this config by an error-path reader
    global LAST_WINDOWS
    LAST_WINDOWS = []
    peak = peak_flops_per_chip()  # unknown device_kind: fail before compiling

    params = llama_init(jax.random.PRNGKey(0), cfg)
    if master_f32:
        compute_dtype = cfg.dtype
        params = jax.tree.map(
            lambda x: (x.astype(jnp.float32)
                       if x.dtype == compute_dtype else x),
            params,
        )

        def loss_fn(p, tokens, targets):
            pb = jax.tree.map(
                lambda x: (x.astype(compute_dtype)
                           if x.dtype == jnp.float32 else x),
                p,
            )
            return llama_loss(pb, tokens, targets, cfg, remat=remat,
                              loss_chunk=loss_chunk)
    else:
        def loss_fn(p, tokens, targets):
            return llama_loss(p, tokens, targets, cfg, remat=remat,
                              loss_chunk=loss_chunk)

    tx = optax.adamw(lr)
    opt_state = tx.init(params)

    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    # remat="full" is the measured winner on v5e for the bench config
    # (0.450 MFU vs 0.438 for "dots", 4 paired runs): recomputing the layer
    # in backward beats writing every matmul output to HBM — the step is
    # bandwidth-bound, not FLOP-bound, at these shapes.
    jstep = jax.jit(step, donate_argnums=(0, 1))
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq), 0, cfg.vocab_size
    )

    params, opt_state, loss = jstep(params, opt_state, tokens, tokens)
    float(loss)

    # best-of-2 timing windows: the device repeats the same cached
    # executable, so window spread is the 1-vCPU host's scheduler (observed
    # 41.8-43.1k tok/s across replays of identical work, docs/performance.md)
    # — the max is the closer estimate of the chip's rate, and the spread
    # rides in the artifact so the noise stays visible
    window_tps = []
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(steps):
            params, opt_state, loss = jstep(params, opt_state, tokens, tokens)
        float(loss)  # steps chain through donated params; value fetch = barrier
        dt = time.perf_counter() - t0
        window_tps.append(batch * seq * steps / dt)

    LAST_WINDOWS = list(window_tps)
    tokens_per_sec = max(window_tps)
    flops_per_token = 6 * cfg.num_params()  # fwd+bwd dense approximation
    mfu = tokens_per_sec * flops_per_token / peak
    return tokens_per_sec, mfu


def peak_hbm_gb() -> float:
    """Peak device-memory use of the local chip in GiB (process lifetime)."""
    import jax

    peak = jax.local_devices()[0].memory_stats()["peak_bytes_in_use"]
    return round(peak / 2**30, 2)


def fault_tolerance_metrics(size_mb: int = 8, steps: int = 12, kill_at: int = 4,
                            plane: str = "host", transport: str = "http",
                            prefix: "str | None" = None,
                            collective_timeout: float = 3.0):
    """Fault tolerance in the measured loop (the BASELINE.md north-star):
    two replica groups through a real lighthouse + Managers + the host
    data plane, one replica killed mid-run. Returns steady per-step FT
    overhead and the recovery wall-clock (VERDICT round-2 item 4).

    Runs in a SUBPROCESS pinned to the CPU platform: this is a host-plane
    scenario on virtual CPU devices, and a CPU child never contends for a
    chip its parent may hold.
    """
    import json as _json
    import os
    import subprocess
    import sys

    child = (
        "from torchft_tpu.utils import force_virtual_cpu_devices\n"
        f"force_virtual_cpu_devices({2 if plane == 'device' else 1})\n"
        "import sys, json\n"
        f"sys.path.insert(0, {os.path.join(os.path.dirname(os.path.abspath(__file__)), 'benchmarks')!r})\n"
        "from recovery_bench import run\n"
        f"print('FTRESULT ' + json.dumps(run(size_mb={size_mb}, steps={steps}, "
        f"kill_at={kill_at}, plane={plane!r}, transport={transport!r}, "
        f"collective_timeout={collective_timeout})))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        # GB-scale payloads need room: steps + heal can take minutes on a
        # loaded 1-vCPU host (first-touch paging, docs/performance.md)
        timeout=420 + size_mb,
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("FTRESULT "):
            r = _json.loads(line[len("FTRESULT "):])
            if prefix is None:
                # "virtual", not "device": the device-plane rows run
                # ProcessGroupXLA over force_virtual_cpu_devices loopback —
                # the field name says what was measured, a real-chip row
                # would pass its own prefix
                prefix = "ft_virtual_" if plane == "device" else "ft_"
            return {
                f"{prefix}steady_step_s": r["steady_step_s"],
                f"{prefix}recovery_s": r["recovery_s"],
                f"{prefix}rejoin_s": r["rejoin_s"],
                f"{prefix}payload_mb": r["size_mb"],
                **(
                    {
                        f"{prefix}detection_quorum_s": r["detection_quorum_s"],
                        f"{prefix}pg_configure_s": r["pg_configure_s"],
                        f"{prefix}heal_recv_s": r["heal_recv_s"],
                        # prepare/commit split: overlapped control plane vs
                        # the serialized commit, + heal chunk streaming
                        f"{prefix}quorum_overlap_s": r.get("quorum_overlap_s"),
                        f"{prefix}configure_prepare_s": r.get("configure_prepare_s"),
                        f"{prefix}configure_commit_s": r.get("configure_commit_s"),
                        f"{prefix}heal_chunks": r.get("heal_chunks"),
                        f"{prefix}heal_mb_per_s": r.get("heal_mb_per_s"),
                    }
                    if plane == "device"
                    else {}
                ),
            }
    raise RuntimeError(
        f"recovery bench child failed rc={out.returncode}: "
        f"{(out.stderr or out.stdout)[-300:]}"
    )


def ft_overhead_metrics(steps: int = 30, warmup: int = 5,
                        batch_size: int = 8) -> dict:
    """Steady-state FT overhead on the real example trainer: bare loop vs
    live Manager (real lighthouse, real per-step vote), with the per-phase
    splits from Manager.timings(). Runs in a CPU-pinned subprocess for the
    same reason fault_tolerance_metrics does (the scenario never needs the
    accelerator; keep it out of the driver's process tree)."""
    import json as _json
    import os
    import subprocess
    import sys

    child = (
        "from torchft_tpu.utils import force_virtual_cpu_devices\n"
        "force_virtual_cpu_devices(1)\n"
        "import sys, json\n"
        f"sys.path.insert(0, {os.path.join(os.path.dirname(os.path.abspath(__file__)), 'benchmarks')!r})\n"
        "from ft_overhead_bench import run\n"
        f"print('FTOVERHEAD ' + json.dumps(run(steps={steps}, "
        f"warmup={warmup}, batch_size={batch_size})))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        timeout=300,
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("FTOVERHEAD "):
            return _json.loads(line[len("FTOVERHEAD "):])
    raise RuntimeError(
        f"ft_overhead child failed rc={out.returncode}: "
        f"{(out.stderr or out.stdout)[-300:]}"
    )


def healthwatch_metrics(steps: int = 30, warmup: int = 5,
                        batch_size: int = 8) -> dict:
    """Healthwatch steady-state cost + /health under load: the example
    trainer under a Manager whose lighthouse runs the health ledger, with
    poller threads hammering the /health endpoint the whole time, then the
    per-step publish+fold path micro-timed directly. CPU-pinned subprocess,
    same isolation policy as the other FT rows."""
    import json as _json
    import os
    import subprocess
    import sys

    child = (
        "from torchft_tpu.utils import force_virtual_cpu_devices\n"
        "force_virtual_cpu_devices(1)\n"
        "import sys, json\n"
        f"sys.path.insert(0, {os.path.join(os.path.dirname(os.path.abspath(__file__)), 'benchmarks')!r})\n"
        "from healthwatch_bench import run\n"
        f"print('HEALTHWATCH ' + json.dumps(run(steps={steps}, "
        f"warmup={warmup}, batch_size={batch_size})))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        timeout=300,
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("HEALTHWATCH "):
            return _json.loads(line[len("HEALTHWATCH "):])
    raise RuntimeError(
        f"healthwatch child failed rc={out.returncode}: "
        f"{(out.stderr or out.stdout)[-300:]}"
    )


def compressed_allreduce_metrics(size_mb: float = 64, leaves: int = 16,
                                 cap_mb: float = 4, steps: int = 8,
                                 warmup: int = 2) -> dict:
    """Compressed vs uncompressed streamed managed allreduce: two live
    replica groups exchange the same multi-bucket gradient tree through
    real Managers once per compress mode (off / fp8 / int8) and report
    per-mode stage splits plus effective wire bandwidth (logical
    uncompressed bytes over measured wire seconds) and the fp8/int8
    bandwidth ratios. CPU-pinned subprocess, same isolation policy as the
    other FT rows."""
    import json as _json
    import os
    import subprocess
    import sys

    child = (
        "from torchft_tpu.utils import force_virtual_cpu_devices\n"
        "force_virtual_cpu_devices(1)\n"
        "import sys, json\n"
        f"sys.path.insert(0, {os.path.join(os.path.dirname(os.path.abspath(__file__)), 'benchmarks')!r})\n"
        "from compressed_allreduce_bench import run\n"
        f"print('COMPRESS ' + json.dumps(run(size_mb={size_mb}, "
        f"leaves={leaves}, cap_mb={cap_mb}, steps={steps}, "
        f"warmup={warmup})))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        timeout=560,
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("COMPRESS "):
            return _json.loads(line[len("COMPRESS "):])
    raise RuntimeError(
        f"compressed-allreduce child failed rc={out.returncode}: "
        f"{(out.stderr or out.stdout)[-300:]}"
    )


def compressed_allreduce(smoke: bool = False) -> None:
    """``python bench.py --compressed-allreduce [--smoke]``: one JSON
    line with per-mode (off/fp8/int8) stage splits, effective wire
    bandwidth, and the fp8/int8 bandwidth ratios over the uncompressed
    run. Smoke mode shrinks the payload and asserts every per-mode key is
    present — the fast-tier CI gate (tests/test_bench_smoke.py) that
    fails loudly if the compressed pipeline or its instrumentation
    regresses. The full run's output is the committed
    BENCH_COMPRESS.json."""
    if smoke:
        metrics = compressed_allreduce_metrics(
            size_mb=8, leaves=8, cap_mb=2, steps=4, warmup=1
        )
    else:
        metrics = compressed_allreduce_metrics()
    for mode in ("off", "fp8", "int8"):
        m = metrics.get("modes", {}).get(mode) or {}
        missing = [k for k in ("step_s", "pack_s", "wire_s", "unpack_s",
                               "buckets", "effective_wire_mb_s")
                   if m.get(k) is None]
        if missing:
            raise RuntimeError(
                f"compressed-allreduce: mode {mode} missing {missing}"
            )
        if not m["buckets"] > 1:
            raise RuntimeError(
                f"compressed-allreduce: mode {mode} ran a single bucket — "
                "the plan no longer splits into per-bucket collectives"
            )
    if metrics.get("bandwidth_ratio_fp8") is None:
        raise RuntimeError("compressed-allreduce: no fp8 bandwidth ratio")
    print(json.dumps({
        "metric": "fp8 effective wire bandwidth vs uncompressed "
                  "(host loopback)",
        "value": metrics["bandwidth_ratio_fp8"],
        "unit": "x",
        "vs_baseline": 1,
        **metrics,
    }))


def ft_overhead(smoke: bool = False) -> None:
    """``python bench.py --ft-overhead [--smoke]``: one JSON line with
    ``ft_overhead_pct`` + the allreduce / vote-RPC / bookkeeping splits.
    Smoke mode shrinks the loop and asserts the splits are present — the
    fast-tier CI gate that fails loudly if the hot-loop instrumentation
    (Manager.timings) regresses."""
    if smoke:
        metrics = ft_overhead_metrics(steps=6, warmup=2)
    else:
        metrics = ft_overhead_metrics()
    required = [
        "ft_overhead_pct",
        "allreduce_s",
        "should_commit_rpc_s",
        "bookkeeping_s",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"ft-overhead: missing splits: {missing}")
    if not metrics["allreduce_s"] > 0:
        raise RuntimeError(
            "ft-overhead: allreduce_s=0 — the managed collective is no "
            "longer timed through Manager.timings()"
        )
    if not metrics["should_commit_rpc_s"] > 0:
        raise RuntimeError(
            "ft-overhead: should_commit_rpc_s=0 — the vote RPC is no "
            "longer timed through Manager.timings()"
        )
    print(json.dumps({
        "metric": "ft steady-state overhead (example trainer, host plane)",
        "value": metrics["ft_overhead_pct"],
        "unit": "%",
        "vs_baseline": 1,
        **metrics,
    }))


def healthwatch(smoke: bool = False) -> None:
    """``python bench.py --healthwatch [--smoke]``: one JSON line with
    ``healthwatch_overhead_pct`` (per-step telemetry publish + health fold
    as a share of the managed step) and the /health-under-load tallies.
    The gates hold the subsystem's two promises: the telemetry plane costs
    under 1% of a step, and the /health endpoint answers every poll while
    training is live."""
    if smoke:
        metrics = healthwatch_metrics(steps=8, warmup=2)
    else:
        metrics = healthwatch_metrics()
    required = [
        "healthwatch_overhead_pct",
        "healthwatch_publish_s",
        "health_polls_ok",
        "health_polls_failed",
        "health_replicas_tracked",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"healthwatch: missing keys: {missing}")
    if not metrics["healthwatch_overhead_pct"] < 1.0:
        raise RuntimeError(
            f"healthwatch: overhead {metrics['healthwatch_overhead_pct']}% "
            ">= 1% of the managed step — the telemetry publish or health "
            "fold grew a real cost"
        )
    if not metrics["health_polls_ok"] > 0:
        raise RuntimeError("healthwatch: no successful /health polls")
    if metrics["health_polls_failed"] != 0:
        raise RuntimeError(
            f"healthwatch: {metrics['health_polls_failed']} /health polls "
            f"failed under load: {metrics.get('health_poll_first_error')}"
        )
    if not metrics["health_replicas_tracked"] >= 1:
        raise RuntimeError(
            "healthwatch: the ledger never tracked the benched replica — "
            "telemetry is not reaching the lighthouse"
        )
    print(json.dumps({
        "metric": "healthwatch steady-state cost (example trainer)",
        "value": metrics["healthwatch_overhead_pct"],
        "unit": "%",
        "vs_baseline": 1,
        **metrics,
    }))


def tracing_metrics(steps: int = 30, warmup: int = 5, batch_size: int = 4096,
                    scrapes: int = 10000) -> dict:
    """Tracing-plane steady-state cost + /metrics under load: the example
    trainer under a Manager with the span recorder on and the Prometheus
    endpoint serving, scraper threads hammering /metrics until the scrape
    budget lands, then the span record paths micro-timed directly.
    CPU-pinned subprocess, same isolation policy as the other FT rows.
    The batch makes the toy's step 0.1-0.2 s here (8 ms at a batch of 8):
    since PR 24 a step records a span per piece of bucket-pipeline work
    (about 20 with the toy's one bucket, 5 before), so the 1% gate needs a
    step that is a step — still a fiftieth of the chip cells' (PERF.md)."""
    import json as _json
    import os
    import subprocess
    import sys

    child = (
        "from torchft_tpu.utils import force_virtual_cpu_devices\n"
        "force_virtual_cpu_devices(1)\n"
        "import sys, json\n"
        f"sys.path.insert(0, {os.path.join(os.path.dirname(os.path.abspath(__file__)), 'benchmarks')!r})\n"
        "from tracing_bench import run\n"
        f"print('TRACING ' + json.dumps(run(steps={steps}, "
        f"warmup={warmup}, batch_size={batch_size}, scrapes={scrapes})))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", child], capture_output=True, text=True,
        timeout=420,
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    for line in reversed(out.stdout.splitlines()):
        if line.startswith("TRACING "):
            return _json.loads(line[len("TRACING "):])
    raise RuntimeError(
        f"tracing child failed rc={out.returncode}: "
        f"{(out.stderr or out.stdout)[-300:]}"
    )


# a steady step of the one-bucket toy records 19: the bucket's tree is 9
# (pack, wire, unpack and their six children), the step's own are 8 and
# the watcher's device/forward and device/landed 2 (the toy's update is
# not the trainer's: no device/update); the first step's compiles and
# configure bring a short run's mean to 22.6
TRACING_MAX_SPANS_PER_STEP = 24


def tracing(smoke: bool = False) -> None:
    """``python bench.py --tracing [--smoke]``: one JSON line with
    ``tracing_overhead_pct`` (per-span record cost × observed spans/step
    as a share of the managed step) and the /metrics-under-load tallies.
    The gates hold the subsystem's two promises: default-on tracing costs
    under 1% of a managed step, and the Prometheus endpoint answers every
    scrape of a 10k-scrape hammering while training is live (smoke mode
    shrinks the loop and the scrape budget, not the assertions). The full
    run's output is the committed BENCH_TRACE.json."""
    if smoke:
        metrics = tracing_metrics(steps=8, warmup=2, scrapes=300)
    else:
        metrics = tracing_metrics()
    required = [
        "tracing_overhead_pct",
        "tracing_span_cost_us",
        "tracing_spans_per_step",
        "trace_merged_events",
        "metrics_scrapes_ok",
        "metrics_scrapes_failed",
        "metrics_series",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"tracing: missing keys: {missing}")
    if not metrics["tracing_overhead_pct"] < 1.0:
        raise RuntimeError(
            f"tracing: overhead {metrics['tracing_overhead_pct']}% >= 1% "
            "of the managed step — span recording grew a real cost"
        )
    if not metrics["tracing_spans_per_step"] > 0:
        raise RuntimeError(
            "tracing: zero spans per step — the Manager's hot-loop "
            "instrumentation is no longer reaching the recorder"
        )
    if not metrics["tracing_spans_per_step"] < TRACING_MAX_SPANS_PER_STEP:
        raise RuntimeError(
            f"tracing: {metrics['tracing_spans_per_step']} spans per "
            f"one-bucket step >= {TRACING_MAX_SPANS_PER_STEP} — the count "
            "is the half of the cost the share of a longer step would hide"
        )
    if metrics["metrics_scrapes_failed"] != 0:
        raise RuntimeError(
            f"tracing: {metrics['metrics_scrapes_failed']} /metrics "
            "scrapes failed under load: "
            f"{metrics.get('metrics_scrape_first_error')}"
        )
    expected_scrapes = 300 if smoke else 10000
    if metrics["metrics_scrapes_ok"] < expected_scrapes:
        raise RuntimeError(
            f"tracing: only {metrics['metrics_scrapes_ok']} of "
            f"{expected_scrapes} /metrics scrapes answered"
        )
    print(json.dumps({
        "metric": "tracing steady-state cost (example trainer)",
        "value": metrics["tracing_overhead_pct"],
        "unit": "%",
        "vs_baseline": 1,
        **metrics,
    }))


def fleet_metrics(smoke: bool = False) -> dict:
    """Run benchmarks/fleet_bench.py in a subprocess (it stands up native
    lighthouse/aggregator servers plus hundreds of loopback sockets — own
    process keeps fd/thread blast radius away from the bench harness) and
    parse its one-line JSON summary."""
    import json as _json
    import os
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks",
        "fleet_bench.py",
    )
    cmd = [sys.executable, script] + (["--smoke"] if smoke else [])
    proc = subprocess.run(
        cmd, capture_output=True, text=True,
        timeout=600 if smoke else 3000,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"fleet bench failed (rc={proc.returncode}): "
            f"{proc.stderr.strip().splitlines()[-8:]}"
        )
    last = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")][-1]
    return _json.loads(last)


def fleet(smoke: bool = False) -> None:
    """``python bench.py --fleet [--smoke]``: one JSON line with the flat vs
    two-level control-plane scaling summary. The gates hold the aggregator
    tier's two promises: batching + delta-encoding cuts root heartbeat
    fan-in by a real factor, and quorum convergence through the tier does
    not degrade with fleet size. Full runs also write BENCH_FLEET.json."""
    metrics = fleet_metrics(smoke=smoke)
    required = [
        "fleet_fanin_ratio_at_max",
        "fleet_two_level_latency_scaling",
        "fleet_two_level_convergence_ms_at_max",
        "fleet_all_converged",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"fleet: missing keys: {missing}")
    if not metrics["fleet_all_converged"]:
        raise RuntimeError(
            "fleet: a quorum round failed to converge — the control plane "
            "dropped joiners somewhere between replica and root"
        )
    # Smoke fleets (40 replicas / 2 aggregators) are far below the batching
    # tier's design point, so the fan-in win is gated lower there.
    min_ratio = 2.0 if smoke else 5.0
    if not metrics["fleet_fanin_ratio_at_max"] >= min_ratio:
        raise RuntimeError(
            f"fleet: fan-in reduction {metrics['fleet_fanin_ratio_at_max']:.2f}x "
            f"< {min_ratio}x — aggregator batching/delta-encoding regressed"
        )
    if not smoke and not metrics["fleet_two_level_latency_scaling"] <= 2.0:
        raise RuntimeError(
            "fleet: two-level quorum convergence slowed "
            f"{metrics['fleet_two_level_latency_scaling']:.2f}x from the "
            "smallest to the largest fleet (budget: 2x)"
        )
    print(json.dumps({
        "metric": "fleet fan-in reduction (flat / two-level)",
        "value": metrics["fleet_fanin_ratio_at_max"],
        "unit": "x",
        "vs_baseline": metrics["fleet_fanin_ratio_at_max"],
        **metrics,
    }))


def serving_metrics(smoke: bool = False) -> dict:
    """Run benchmarks/serving_bench.py in a subprocess (it stands up a
    registry + publishers + workers, dozens of loopback sockets and
    threads — own process keeps the blast radius away from the harness)
    and parse its one-line JSON summary."""
    import json as _json
    import os
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks",
        "serving_bench.py",
    )
    cmd = [sys.executable, script] + (["--smoke"] if smoke else [])
    proc = subprocess.run(
        cmd, capture_output=True, text=True,
        timeout=300 if smoke else 1800,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"serving bench failed (rc={proc.returncode}): "
            f"{proc.stderr.strip().splitlines()[-8:]}"
        )
    last = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")][-1]
    return _json.loads(last)


def serving(smoke: bool = False) -> None:
    """``python bench.py --serving [--smoke]``: one JSON line with the
    serving-plane load summary. The gates hold the plane's three promises
    (docs/serving.md): a replica kill + quorum reconfigure mid-traffic
    fails ZERO requests, every worker's final params are bitwise-equal to
    the fleet's published snapshot, and per-step delta pulls move >= 3x
    fewer bytes than full pulls at fp8. Full runs also write
    BENCH_SERVE.json."""
    metrics = serving_metrics(smoke=smoke)
    required = [
        "serving_failed_requests",
        "serving_bitwise_equal",
        "serving_converged",
        "serving_delta_savings_x",
        "serving_p99_ms",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"serving: missing keys: {missing}")
    if metrics["serving_failed_requests"] != 0:
        raise RuntimeError(
            f"serving: {metrics['serving_failed_requests']} request(s) "
            "failed through the chaos turn — the request plane must answer "
            "from the last-applied version no matter what the fleet does"
        )
    if not metrics["serving_converged"]:
        raise RuntimeError(
            "serving: workers never converged to the fleet's final "
            "snapshot version after the kill"
        )
    if not metrics["serving_bitwise_equal"]:
        raise RuntimeError(
            "serving: a worker's final params diverged from the published "
            "snapshot — the delta/full bitwise invariant broke"
        )
    if not metrics["serving_delta_savings_x"] >= 3.0:
        raise RuntimeError(
            f"serving: delta pulls move only "
            f"{metrics['serving_delta_savings_x']:.2f}x fewer bytes than "
            "full pulls (gate: 3x at fp8) — the compressed delta wire "
            "regressed"
        )
    print(json.dumps({
        "metric": "serving delta-pull byte savings (full / delta)",
        "value": metrics["serving_delta_savings_x"],
        "unit": "x",
        "vs_baseline": metrics["serving_delta_savings_x"],
        **metrics,
    }))


def recovery_metrics(smoke: bool = False) -> dict:
    """Run benchmarks/redundancy_bench.py in a subprocess (it stands up a
    shard directory, throttled shard stores, and a managed two-replica
    fleet — own process keeps fd/thread blast radius away from the bench
    harness) and parse its one-line JSON summary."""
    import json as _json
    import os
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks",
        "redundancy_bench.py",
    )
    cmd = [sys.executable, script] + (["--smoke"] if smoke else [])
    proc = subprocess.run(
        cmd, capture_output=True, text=True,
        timeout=600 if smoke else 3600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"recovery bench failed (rc={proc.returncode}): "
            f"{proc.stderr.strip().splitlines()[-8:]}"
        )
    last = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")][-1]
    return _json.loads(last)


def recovery(smoke: bool = False) -> None:
    """``python bench.py --recovery [--smoke]``: one JSON line with the
    redundancy-plane recovery summary. The gates hold the plane's two
    promises (docs/operations.md): reconstructing a lost replica's state
    from k+m erasure shards pulled off k+m peers in parallel beats the
    single-source heal wire by a real factor at large state (>= 4x at
    1 GB under the per-peer NIC egress model), and the commit-path cost
    of staging shards stays under 1% of the managed step. Full runs also
    write BENCH_RECOVERY.json."""
    metrics = recovery_metrics(smoke=smoke)
    required = [
        "recovery_reconstruct_speedup_x",
        "recovery_single_source_s_at_max",
        "recovery_parallel_s_at_max",
        "staging_overhead_pct",
        "staging_kept_up",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"recovery: missing keys: {missing}")
    # Smoke states (8 MB) barely cover the parallel path's fixed costs
    # (k+m HTTP round-trips + decode on one vCPU), so the gate is lower.
    min_speedup = 1.5 if smoke else 4.0
    if not metrics["recovery_reconstruct_speedup_x"] >= min_speedup:
        raise RuntimeError(
            f"recovery: parallel reconstruct only "
            f"{metrics['recovery_reconstruct_speedup_x']:.2f}x faster than "
            f"the single-source heal (gate: {min_speedup}x) — per-shard "
            "parallelism regressed"
        )
    max_overhead = 5.0 if smoke else 1.0
    if not metrics["staging_overhead_pct"] < max_overhead:
        raise RuntimeError(
            f"recovery: shard staging costs "
            f"{metrics['staging_overhead_pct']:.2f}% of the managed step "
            f"(budget: {max_overhead}%) — the hot path must pay only the "
            "snapshot copy + queue put"
        )
    if not metrics["staging_kept_up"]:
        raise RuntimeError(
            "recovery: the background stager fell behind the commit "
            "cadence — newest-wins draining regressed"
        )
    print(json.dumps({
        "metric": "parallel reconstruct speedup over single-source heal",
        "value": metrics["recovery_reconstruct_speedup_x"],
        "unit": "x",
        "vs_baseline": metrics["recovery_reconstruct_speedup_x"],
        **metrics,
    }))


def degrade_metrics(smoke: bool = False) -> dict:
    """Run benchmarks/degrade_bench.py in a subprocess (it stands up two
    managed fleets, a lighthouse, and loopback shard/checkpoint HTTP —
    own process keeps fd/thread blast radius away from the bench
    harness) and parse its one-line JSON summary."""
    import json as _json
    import os
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks",
        "degrade_bench.py",
    )
    cmd = [sys.executable, script] + (["--smoke"] if smoke else [])
    proc = subprocess.run(
        cmd, capture_output=True, text=True,
        timeout=600 if smoke else 3600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"degrade bench failed (rc={proc.returncode}): "
            f"{proc.stderr.strip().splitlines()[-8:]}"
        )
    last = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")][-1]
    return _json.loads(last)


def degrade(smoke: bool = False) -> None:
    """``python bench.py --degrade [--smoke]``: one JSON line with the
    degrade-plane summary. The gates hold the plane's promises
    (docs/operations.md "Degraded replicas"): the in-place reshard
    latency (``degraded_reshard_s`` — the cost the degrade adds to the
    one re-planned slow step, during which the replica never leaves the
    loop) is a real factor faster than the classic leave-heal-rejoin
    cycle's rejoin wall (>= 3x at the largest state — the in-place path
    moves state/k bytes where the classic path restarts the process and
    moves all of them), the quorum never shrinks through the degrade,
    and the shrunken layout is bitwise-equal to the full one. Full runs
    also write BENCH_DEGRADE.json."""
    metrics = degrade_metrics(smoke=smoke)
    required = [
        "degrade_speedup_x",
        "degrade_in_place_s_at_max",
        "degrade_classic_rejoin_s_at_max",
        "degrade_quorum_never_shrank",
        "degrade_bitwise_ok",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"degrade: missing keys: {missing}")
    if not metrics["degrade_quorum_never_shrank"]:
        raise RuntimeError(
            "degrade: the quorum shrank during an in-place degrade — the "
            "replica left instead of resharding"
        )
    if not metrics["degrade_bitwise_ok"]:
        raise RuntimeError(
            "degrade: the shrunken layout is not bitwise-equal to the "
            "full one"
        )
    # Smoke states (8 MB) barely cover the classic path's fixed costs
    # (restart + quorum rejoin dominate the heal), so the gate is lower.
    min_speedup = 1.5 if smoke else 3.0
    if not metrics["degrade_speedup_x"] >= min_speedup:
        raise RuntimeError(
            f"degrade: in-place reshard only "
            f"{metrics['degrade_speedup_x']:.2f}x faster than "
            f"leave-heal-rejoin (gate: {min_speedup}x) — the gather-free "
            "shard-sourced path regressed"
        )
    print(json.dumps({
        "metric": "in-place degrade speedup over leave-heal-rejoin",
        "value": metrics["degrade_speedup_x"],
        "unit": "x",
        "vs_baseline": metrics["degrade_speedup_x"],
        **metrics,
    }))


def policy_metrics(smoke: bool = False) -> dict:
    """Run benchmarks/policy_bench.py in a subprocess (it stands up a
    lighthouse with the policy engine attached plus a managed loop — own
    process keeps fd/thread/env blast radius away from the bench harness)
    and parse its one-line JSON summary."""
    import json as _json
    import os
    import subprocess
    import sys

    script = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks",
        "policy_bench.py",
    )
    cmd = [sys.executable, script] + (["--smoke"] if smoke else [])
    proc = subprocess.run(
        cmd, capture_output=True, text=True,
        timeout=600 if smoke else 3600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"policy bench failed (rc={proc.returncode}): "
            f"{proc.stderr.strip().splitlines()[-8:]}"
        )
    last = [l for l in proc.stdout.strip().splitlines() if l.startswith("{")][-1]
    return _json.loads(last)


def policy(smoke: bool = False) -> None:
    """``python bench.py --policy [--smoke]``: one JSON line with the
    policy-plane summary. The gates hold the plane's promises
    (docs/operations.md "Adaptive policies"): the engine's fold over a
    1000-replica window amortizes to <0.5% of a managed step (its duty
    cycle at the default 5 s cadence), the offline replay ranks >=2
    candidate policies against the committed fixture at useful
    throughput, and at least one versioned frame reached a live
    manager's quorum safe point (``policy_intents`` in timings — the
    zero-new-RPC piggyback works end to end). Full runs also write
    BENCH_POLICY.json."""
    metrics = policy_metrics(smoke=smoke)
    required = [
        "policy_fold_duty_cycle_pct",
        "replay_events_per_s",
        "replay_ranking",
        "replay_winner",
        "policy_intents",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"policy: missing keys: {missing}")
    if not metrics["policy_fold_duty_cycle_pct"] < 0.5:
        raise RuntimeError(
            f"policy: engine fold duty cycle "
            f"{metrics['policy_fold_duty_cycle_pct']:.3f}% of the managed "
            "step budget (gate: <0.5%) — the fold left the advisory-cost "
            "envelope"
        )
    if len(metrics["replay_ranking"]) < 2:
        raise RuntimeError(
            "policy: replay must rank >=2 candidate policies, got "
            f"{metrics['replay_ranking']}"
        )
    if not metrics["replay_events_per_s"] >= 1000:
        raise RuntimeError(
            f"policy: replay throughput {metrics['replay_events_per_s']} "
            "events/s under the 1000/s floor — offline scoring regressed"
        )
    if not metrics["policy_intents"] >= 1:
        raise RuntimeError(
            "policy: no frame reached the manager safe point in observe "
            "mode — the heartbeat/agg_tick piggyback is broken"
        )
    print(json.dumps({
        "metric": "policy engine fold duty cycle (1000-replica window)",
        "value": metrics["policy_fold_duty_cycle_pct"],
        "unit": "%",
        "vs_baseline": metrics["policy_fold_duty_cycle_pct"],
        **metrics,
    }))


def main() -> None:
    from torchft_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py times the train step on a TPU; JAX found platform="
            f"{devices[0].platform!r}. A CPU timing is not written under a "
            "device metric's name (the host-plane rows are --smoke, "
            "--ft-overhead, ...)"
        )

    from torchft_tpu.models.llama import CONFIGS
    from torchft_tpu.ops import attention as _attn

    cfg_name, batch, seq, steps = "bench_1b", 4, 2048, 10
    cfg = CONFIGS[cfg_name]
    # the default dispatch (TORCHFT_TPU_ATTENTION pins a kernel); whatever
    # fails, fails the bench — there is no slower kernel to fall back to
    tokens_per_sec, mfu = timed_train_step(cfg, batch, seq, steps)
    windows = list(LAST_WINDOWS)
    n_params = cfg.num_params()

    record = {
        "metric": (
            f"tokens/sec/chip (llama {n_params/1e6:.0f}M, bf16 adamw "
            f"train step, bare optax loop)"
        ),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu, 4),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        # the kernel that produced the number, as the dispatcher resolved it
        "attention": _attn.LAST_DISPATCH,
        # both timing windows (tok/s): value is the max, the spread stays
        # visible rather than averaged in
        "windows_tok_s": [round(w, 1) for w in windows],
        "model": cfg_name,
        "batch": batch,
        "seq": seq,
        "peak_hbm_gb": peak_hbm_gb(),
    }

    # continuity row: the 350M config at batch 8, same kernel
    tps_350m, mfu_350m = timed_train_step(CONFIGS["bench_350m"], 8, seq, steps)
    record["bench_350m_tok_s"] = round(tps_350m, 1)
    record["bench_350m_mfu"] = round(mfu_350m, 4)

    print(json.dumps(record))


def smoke() -> None:
    """``python bench.py --smoke``: run ONLY the tiny device-plane FT row
    and assert the prepare/commit overlap keys are present with
    ``quorum_overlap_s > 0`` — a fast CI gate (no TPU, no model compile)
    that fails loudly if the device plane regresses to a synchronous
    quorum or the heal stops streaming. Wired as a non-slow tier-1 test
    (tests/test_bench_smoke.py)."""
    metrics = fault_tolerance_metrics(
        size_mb=4, steps=6, kill_at=2, plane="device"
    )
    required = [
        "ft_virtual_quorum_overlap_s",
        "ft_virtual_configure_prepare_s",
        "ft_virtual_configure_commit_s",
        "ft_virtual_heal_chunks",
        "ft_virtual_heal_mb_per_s",
        "ft_virtual_recovery_s",
    ]
    missing = [k for k in required if metrics.get(k) is None]
    if missing:
        raise RuntimeError(f"smoke: overlap-timing keys missing: {missing}")
    overlap = metrics["ft_virtual_quorum_overlap_s"]
    if not overlap > 0:
        raise RuntimeError(
            f"smoke: quorum_overlap_s={overlap} — the device-plane quorum "
            "cycle is no longer measured on the quorum thread"
        )
    print(json.dumps({
        "metric": "ft smoke (device-plane quorum overlap)",
        "value": overlap,
        "unit": "s",
        "vs_baseline": 1,
        **metrics,
    }))


if __name__ == "__main__":
    if "--ft-overhead" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        ft_overhead(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--compressed-allreduce" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        compressed_allreduce(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--healthwatch" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        healthwatch(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--tracing" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        tracing(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--fleet" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        fleet(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--serving" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        serving(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--recovery" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        recovery(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--degrade" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        degrade(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--policy" in sys.argv[1:]:
        # loud-failure gate, same policy as --smoke
        policy(smoke="--smoke" in sys.argv[1:])
        sys.exit(0)
    if "--smoke" in sys.argv[1:]:
        # no always-emit wrapper here: the smoke gate must fail loudly
        # (nonzero rc + traceback) so CI catches overlap regressions
        smoke()
        sys.exit(0)
    main()
