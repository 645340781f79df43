"""Environment diagnostic: ``python -m torchft_tpu.doctor``.

One command an operator runs on a fresh host (or in a wedged job's
postmortem) to answer "is this machine able to run a torchft_tpu replica
group right now": native control plane builds and serves, JAX backend
initializes (probed in a subprocess, so a backend that never comes up is a
bounded FAIL and the doctor itself holds no chip), the virtual multi-device
CPU mesh works
(what tests and dryruns rely on), a lighthouse round-trip completes, the
``TORCHFT_RETRY_*`` env knobs are sane (parseable, and the worst-case
backoff budget ordered below the quorum timeout), the ``TORCHFT_HEALTH_*``
healthwatch knobs validate (eject above warn, probation window wide enough
for probe heartbeats to land) with a loopback ``GET /health`` probe of the
lighthouse ledger endpoint, the ``TORCHFT_TRACE_*`` tracing knobs validate
strictly (with a writability probe of the trace dump dir) and both
Prometheus ``/metrics`` exporters (lighthouse native + manager-side
Python) answer a loopback scrape with parseable text, and a loopback
live-heal round-trip through the default HTTP transport lands in place —
with one mid-transfer connection drop injected so the ranged-resume path
(the tier-1 recovery behavior a rejoining replica depends on) is
exercised, not just the happy path. The ``TORCHFT_REDUNDANCY_*`` knobs
validate (k/m sanity plus a live-peer count against k+m when a directory
is configured) and a loopback erasure round-trip encodes a state, corrupts
one stored shard, and reconstructs bitwise via the parity shard. The
``TORCHFT_DEGRADE_*`` knobs validate and a loopback 2→1 reshard probe
asserts the degrade plane's bitwise param-equality invariant on both
engine paths.

Exit code 0 iff every check passes (the accelerator check passes as
"cpu-only" — a legitimate dev box). Prints one line per check:

    ok   native          built (.../libtorchft_tpu.so)
    ok   accelerator     tpu (1 device)
    ...
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Callable, List, Tuple

# (status, detail); status: True=ok, False=fail, None=warn
Result = Tuple["bool | None", str]


def check_native() -> Result:
    try:
        from torchft_tpu.coordination import ensure_native_built

        return True, f"built ({ensure_native_built()})"
    except Exception as e:  # noqa: BLE001
        return False, f"native build/load failed: {e}"


def check_accelerator(timeout_s: float = 60.0) -> Result:
    """Subprocess probe: bounded, and the doctor never holds the chip."""
    from torchft_tpu.utils import probe_backend

    status, detail = probe_backend(timeout_s)
    if status == "hung":
        return False, detail
    if status == "crash":
        return False, f"backend init crashed: {detail}"
    if status == "cpu":
        return None, "cpu only (no accelerator — fine for a dev box)"
    return True, detail


def check_virtual_mesh(timeout_s: float = 120.0) -> Result:
    """The 8-device CPU mesh that tests/dryruns use."""
    code = (
        "from torchft_tpu.utils import force_virtual_cpu_devices\n"
        "force_virtual_cpu_devices(8)\n"
        "import jax, jax.numpy as jnp\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "import numpy as np\n"
        "mesh = Mesh(np.array(jax.devices()[:8]), ('x',))\n"
        "y = jax.device_put(jnp.arange(8.0), NamedSharding(mesh, P('x')))\n"
        "assert float(y.sum()) == 28.0\n"
        "print('ok')\n"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=timeout_s,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
    except subprocess.TimeoutExpired:
        return False, f"virtual mesh hung >{timeout_s:.0f}s"
    if out.returncode != 0:
        return False, f"virtual mesh failed: {out.stderr.strip()[-200:]}"
    return True, "8-device CPU mesh shards + reduces"


def check_lighthouse_roundtrip() -> Result:
    try:
        from torchft_tpu.coordination import LighthouseClient, LighthouseServer

        lh = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=500,
            quorum_tick_ms=20, heartbeat_timeout_ms=2000,
        )
        try:
            client = LighthouseClient(
                f"127.0.0.1:{lh.port}", connect_timeout=5.0
            )
            client.heartbeat("doctor", timeout=5.0)
            q = client.quorum(replica_id="doctor", timeout=10.0)
            ok = any(m.replica_id == "doctor" for m in q.participants)
            return (True, f"quorum_id={q.quorum_id} formed") if ok else (
                False, "quorum formed without this replica"
            )
        finally:
            lh.shutdown()
    except Exception as e:  # noqa: BLE001
        return False, f"lighthouse round-trip failed: {e}"


def check_retry_env() -> Result:
    """TORCHFT_RETRY_* env sanity: the values parse, and the worst-case
    retry sleep budget is ordered BELOW the quorum timeout — a backoff
    schedule that can out-sleep the quorum window turns every control-plane
    blip into a quorum failure instead of a slower step."""
    try:
        from torchft_tpu.retry import RetryPolicy

        policy = RetryPolicy.from_env()
    except ValueError as e:
        return False, f"TORCHFT_RETRY_* env invalid: {e}"
    quorum_timeout_s = float(
        os.environ.get(
            "TORCHFT_QUORUM_TIMEOUT_SEC",
            os.environ.get("TORCHFT_TIMEOUT_SEC", "60.0"),
        )
    )
    # worst case: every sleep hits the ceiling, jitter draws nothing
    worst_sleep_s = sum(
        policy.backoff_s(attempt) for attempt in range(2, policy.max_attempts + 1)
    )
    detail = (
        f"attempts={policy.max_attempts} base={policy.base_s}s "
        f"ceiling={policy.max_backoff_s}s jitter={policy.jitter} "
        f"(worst sleep {worst_sleep_s:.2f}s vs quorum {quorum_timeout_s:.0f}s)"
    )
    if policy.max_backoff_s >= quorum_timeout_s:
        return False, (
            f"backoff ceiling {policy.max_backoff_s}s >= quorum timeout "
            f"{quorum_timeout_s}s — one retry sleep can eat the whole "
            "quorum window; lower TORCHFT_RETRY_MAX_BACKOFF_S"
        )
    if worst_sleep_s >= quorum_timeout_s:
        return None, (
            f"worst-case retry sleep {worst_sleep_s:.2f}s >= quorum "
            f"timeout {quorum_timeout_s}s — retries may burn the quorum "
            "window sleeping; lower TORCHFT_RETRY_MAX_ATTEMPTS or the "
            "backoff knobs"
        )
    if not policy.enabled:
        return None, f"retries disabled (max_attempts=1); {detail}"
    return True, detail


def check_health_env() -> Result:
    """TORCHFT_HEALTH_* env sanity: the knobs parse and validate (which
    enforces eject_z > warn_z — ordered thresholds are what makes warn an
    early warning), and the probation window is long enough to actually
    observe recovery: readmission needs probe heartbeats to land INSIDE
    the window, so probation_ms must comfortably exceed the heartbeat
    interval or a readmitted replica is judged on zero samples."""
    try:
        from torchft_tpu.healthwatch import HealthConfig

        config = HealthConfig.from_env()
    except ValueError as e:
        return False, f"TORCHFT_HEALTH_* env invalid: {e}"
    detail = (
        f"mode={config.mode} warn_z={config.warn_z} eject_z={config.eject_z} "
        f"eject_steps={config.eject_steps} probation_ms={config.probation_ms}"
    )
    if config.mode == "off":
        return None, f"healthwatch disabled; {detail}"
    # the default Manager heartbeat interval (manager.py) — the cadence
    # probe beats arrive at during probation
    heartbeat_ms = float(os.environ.get("TORCHFT_HEARTBEAT_INTERVAL_MS", "100"))
    if config.probation_ms <= heartbeat_ms:
        return False, (
            f"TORCHFT_HEALTH_PROBATION_MS={config.probation_ms} <= heartbeat "
            f"interval {heartbeat_ms:.0f}ms — the probation window closes "
            "before a single probe heartbeat lands; raise it"
        )
    if config.probation_ms < heartbeat_ms * config.probe_ok:
        return None, (
            f"probation_ms={config.probation_ms} < heartbeat interval × "
            f"probe_ok ({heartbeat_ms:.0f}×{config.probe_ok}) — readmission "
            "may need several windows; consider raising it"
        )
    return True, detail


def check_compress_env() -> Result:
    """``TORCHFT_COMPRESS`` sanity: the value resolves to a known codec
    (funnelled through the same ``resolve_compress_mode`` the Manager
    uses, so the doctor and the trainer reject identically)."""
    try:
        from torchft_tpu.ops.quantization import resolve_compress_mode

        mode = resolve_compress_mode()
    except ValueError as e:
        return False, (
            f"TORCHFT_COMPRESS invalid: {e}; unset it or pick one of "
            "off/fp8/int8"
        )
    if mode == "off":
        return True, "compression off (default wire, bit-identical path)"
    return True, f"compression {mode} (rowwise codec, error feedback on)"


def check_health_endpoint() -> Result:
    """Loopback /health probe: a lighthouse with the healthwatch ledger
    enabled serves the JSON an operator's dashboard would scrape, and the
    payload reflects a heartbeat it just ingested."""
    try:
        import json as _json
        import urllib.request

        from torchft_tpu.coordination import LighthouseClient, LighthouseServer

        lh = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=500,
            quorum_tick_ms=20, heartbeat_timeout_ms=2000,
            health={"mode": "observe"},
        )
        try:
            client = LighthouseClient(f"127.0.0.1:{lh.port}", connect_timeout=5.0)
            client.heartbeat(
                "doctor", timeout=5.0,
                telemetry={"step": 1, "step_s": 0.1, "wire_s": 0.01},
            )
            with urllib.request.urlopen(
                f"http://127.0.0.1:{lh.port}/health", timeout=5.0
            ) as resp:
                payload = _json.loads(resp.read().decode())
        finally:
            lh.shutdown()
        if "doctor" not in payload.get("replicas", {}):
            return False, f"/health missing the beating replica: {payload}"
        return True, (
            f"/health serves mode={payload.get('mode')} "
            f"({len(payload.get('replicas', {}))} replica tracked)"
        )
    except Exception as e:  # noqa: BLE001
        return False, f"/health probe failed: {e}"


def check_heal_roundtrip() -> Result:
    """Loopback live-heal: send a small composite through the default
    HTTPTransport and receive it in place — the tier-1 recovery path a
    rejoining replica depends on. The serve of chunk 1 is armed to drop
    mid-transfer once, so the check also exercises one ranged re-fetch:
    the receiver must resume from its last verified byte, not restart."""
    try:
        import numpy as np

        from torchft_tpu.checkpointing import HTTPTransport
        from torchft_tpu.retry import RetryPolicy

        state = {"user": {"w": np.arange(256, dtype=np.float32)},
                 "torchft": {"step": 3, "batches_committed": 6}}
        template = {"user": {"w": np.zeros(256, np.float32)},
                    "torchft": {"step": 0, "batches_committed": 0}}
        # pin loopback: gethostname() can be locally unresolvable on
        # minimal containers (the fleet problem `hostname` exists for),
        # and this check diagnoses the transport, not DNS
        send = HTTPTransport(timeout=10.0, num_chunks=2,
                             hostname="127.0.0.1")
        # explicit policy: the check must re-fetch deterministically even
        # when the operator's env disables retries (that env shape is
        # check_retry_env's job to flag, not this one's to inherit)
        recv = HTTPTransport(timeout=10.0,
                             state_dict_template=lambda: template,
                             retry_policy=RetryPolicy(
                                 max_attempts=3, base_s=0.01, jitter=0.0))
        events: list = []
        try:
            send.send_checkpoint([1], 3, state, 10.0)
            send.inject_chunk_fault(1, "die", times=1)
            got = recv.recv_checkpoint_multi(
                [("loopback", send.metadata)], 3, 10.0,
                on_event=lambda kind, **f: events.append((kind, f)),
            )
        finally:
            send.shutdown()
            recv.shutdown()
        if got["user"]["w"] is not template["user"]["w"]:
            return False, "heal received but not in place (template unused)"
        if not np.array_equal(got["user"]["w"], state["user"]["w"]):
            return False, "heal payload mismatch"
        resumed = [
            f for kind, f in events
            if kind == "heal_retry" and f.get("resume_offset", 0) > 0
        ]
        if not resumed:
            return False, (
                "mid-transfer drop never produced a ranged resume "
                f"(events: {[k for k, _ in events]})"
            )
        return True, (
            "http heal round-trip in place; ranged re-fetch resumed at "
            f"byte {resumed[0]['resume_offset']}"
        )
    except Exception as e:  # noqa: BLE001
        return False, f"heal round-trip failed: {e}"


def check_trace_env() -> Result:
    """``TORCHFT_TRACE_*`` env sanity, validated STRICTLY (the Manager's
    ``TraceConfig.from_env`` falls back to defaults on garbage so a typo
    can't kill a trainer — which is exactly why the doctor must flag it:
    silently-defaulted knobs are the ones operators chase for hours), plus
    a writability probe of the configured dump directory — an unwritable
    dump dir only surfaces at the worst moment (a postmortem auto-dump)."""
    from torchft_tpu.tracing import (
        TRACE_BUFFER_ENV,
        TRACE_DIR_ENV,
        TRACE_ENV,
        TraceConfig,
    )

    raw_buffer = os.environ.get(TRACE_BUFFER_ENV, "")
    if raw_buffer:
        try:
            buf = int(raw_buffer)
        except ValueError:
            return False, (
                f"{TRACE_BUFFER_ENV}={raw_buffer!r} is not an integer — the "
                "Manager silently falls back to the default ring size"
            )
        if buf < 16:
            return None, (
                f"{TRACE_BUFFER_ENV}={buf} below the floor of 16 — clamped; "
                "a ring that small drops most of a step's spans"
            )
    cfg = TraceConfig.from_env()
    if cfg.dump_dir:
        try:
            os.makedirs(cfg.dump_dir, exist_ok=True)
            probe = os.path.join(cfg.dump_dir, ".doctor_probe")
            with open(probe, "w") as f:
                f.write("ok")
            os.remove(probe)
        except OSError as e:
            return False, (
                f"{TRACE_DIR_ENV}={cfg.dump_dir!r} not writable ({e}) — "
                "postmortem trace auto-dumps will be lost"
            )
    detail = (
        f"enabled={cfg.enabled} buffer={cfg.buffer} "
        f"dump_dir={cfg.dump_dir or '(flight-recorder fallback)'}"
    )
    if not cfg.enabled:
        return None, f"tracing disabled ({TRACE_ENV}); {detail}"
    return True, detail


def _parse_prometheus(text: str) -> "dict[str, float]":
    """Minimal exposition-format parse: series name (labels folded in) ->
    value. Raises on malformed lines, which is the point of the probe."""
    series = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        series[name] = float(value)
    return series


def check_metrics_endpoints() -> Result:
    """Loopback /metrics probes of BOTH exporters: the lighthouse's native
    endpoint (beside /health) and the manager-side Python MetricsServer.
    Each response must parse as Prometheus text and carry its signature
    series — a scrape config written against docs/observability.md works."""
    try:
        import urllib.request

        from torchft_tpu.coordination import LighthouseClient, LighthouseServer
        from torchft_tpu.observability import MetricsRegistry, MetricsServer

        lh = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=500,
            quorum_tick_ms=20, heartbeat_timeout_ms=2000,
            health={"mode": "observe"},
        )
        try:
            client = LighthouseClient(f"127.0.0.1:{lh.port}", connect_timeout=5.0)
            client.heartbeat(
                "doctor", timeout=5.0,
                telemetry={"step": 1, "step_s": 0.1, "wire_s": 0.01},
            )
            with urllib.request.urlopen(
                f"http://127.0.0.1:{lh.port}/metrics", timeout=5.0
            ) as resp:
                lh_series = _parse_prometheus(resp.read().decode())
        finally:
            lh.shutdown()
        if "torchft_lighthouse_fleet_size" not in lh_series:
            return False, (
                "lighthouse /metrics parsed but is missing "
                f"torchft_lighthouse_fleet_size: {sorted(lh_series)[:5]}..."
            )
        registry = MetricsRegistry()
        registry.gauge_set("torchft_doctor_probe", 1.0, "Doctor loopback.")
        server = MetricsServer(registry, port=0)
        try:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=5.0
            ) as resp:
                mgr_series = _parse_prometheus(resp.read().decode())
        finally:
            server.shutdown()
        if mgr_series.get("torchft_doctor_probe") != 1.0:
            return False, f"manager-side /metrics lost the probe gauge: {mgr_series}"
        return True, (
            f"lighthouse /metrics ({len(lh_series)} series) + manager "
            f"/metrics both parse as Prometheus text"
        )
    except Exception as e:  # noqa: BLE001
        return False, f"/metrics probe failed: {e}"


def check_aggregator() -> Result:
    """Two-level control plane: validate the TORCHFT_LIGHTHOUSE_AGGREGATOR
    wiring, then prove the aggregator path works end to end on loopback —
    a beat sent to an AggregatorServer must surface at the root lighthouse
    via a batched agg_tick (not a direct heartbeat)."""
    import time as _time

    try:
        from torchft_tpu.coordination import (
            AggregatorServer,
            LighthouseClient,
            LighthouseServer,
        )
        from torchft_tpu.manager import AGGREGATOR_ENV, LIGHTHOUSE_ENV

        env_note = "flat fleet (no aggregator env)"
        agg_env = os.environ.get(AGGREGATOR_ENV, "")
        if agg_env:
            host, sep, port = agg_env.replace("http://", "").rpartition(":")
            if not sep or not host or not port.isdigit():
                return False, (
                    f"{AGGREGATOR_ENV}={agg_env!r} is not host:port — "
                    "managers will fail to start"
                )
            if not os.environ.get(LIGHTHOUSE_ENV, ""):
                return False, (
                    f"{AGGREGATOR_ENV} is set but {LIGHTHOUSE_ENV} is not: "
                    "the pod cannot fail over to the root if its "
                    "aggregator dies — set both"
                )
            env_note = f"two-level ({agg_env} -> {os.environ[LIGHTHOUSE_ENV]})"

        root = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=500,
            quorum_tick_ms=20, heartbeat_timeout_ms=2000,
        )
        agg = None
        try:
            agg = AggregatorServer(
                root_addr=f"127.0.0.1:{root.port}", bind="127.0.0.1:0",
                agg_id="doctor_pod", tick_ms=50,
            )
            pod_client = LighthouseClient(
                f"127.0.0.1:{agg.port}", connect_timeout=5.0
            )
            resp = pod_client.heartbeat("doctor", timeout=5.0)
            if not resp.get("aggregated"):
                return False, "aggregator heartbeat response not marked aggregated"
            root_client = LighthouseClient(
                f"127.0.0.1:{root.port}", connect_timeout=5.0
            )
            deadline = _time.monotonic() + 10.0
            while _time.monotonic() < deadline:
                st = root_client.status(timeout=5.0)
                if "doctor" in st.get("heartbeat_ages_ms", {}):
                    if st.get("rx", {}).get("heartbeat", {}).get("calls", 0):
                        return False, (
                            "beat reached the root as a DIRECT heartbeat — "
                            "the aggregator forwarded instead of batching"
                        )
                    ticks = st["aggregators"]["doctor_pod"]["ticks"]
                    return True, (
                        f"{env_note}; loopback pod beat surfaced at root "
                        f"via agg_tick (ticks={ticks})"
                    )
                _time.sleep(0.1)
            return False, "pod beat never surfaced at the root within 10s"
        finally:
            if agg is not None:
                agg.shutdown()
            root.shutdown()
    except Exception as e:  # noqa: BLE001
        return False, f"aggregator probe failed: {e}"


def check_serve_env() -> Result:
    """``TORCHFT_SERVE_*`` sanity: the env contract parses into a valid
    ServeConfig (same validation path the worker CLI, the registry, and
    the publisher all funnel through — doctor and serving plane reject
    identically).  A configured-but-unreachable registry is a warn, not a
    fail: the serving plane is optional and workers retry."""
    try:
        from torchft_tpu.serving import ServeConfig

        cfg = ServeConfig.from_env()
    except ValueError as e:
        return False, f"TORCHFT_SERVE_* invalid: {e}"
    if not cfg.registry:
        return True, (
            f"serving plane unconfigured (compress={cfg.compress}, "
            f"max_lag={cfg.max_lag}, drain_on={cfg.drain_on}); set "
            "TORCHFT_SERVE_REGISTRY to enable"
        )
    import urllib.request

    try:
        with urllib.request.urlopen(
            f"{cfg.registry.rstrip('/')}/serve/sources", timeout=3.0
        ) as r:
            listing = json.loads(r.read().decode())
    except Exception as e:  # noqa: BLE001 — unreachable is a warn
        return None, (
            f"TORCHFT_SERVE_REGISTRY={cfg.registry} unreachable ({e!r}); "
            "workers will retry, but check the lighthouse --serve-registry "
            "flag / the registry process"
        )
    return True, (
        f"registry at {cfg.registry}: {len(listing.get('sources', []))} "
        f"source(s), latest={listing.get('latest')}, "
        f"epoch={listing.get('epoch')}"
    )


def check_serving_roundtrip() -> Result:
    """Loopback serving probe: registry + one publisher + one worker pull.
    Publishes two tiny versions, asserts the worker lands on the newest
    via a full pull then a compressed delta, bitwise-equal to the
    publisher's reference — the whole plane (announce, source ordering,
    ranged full pull, delta walk, error-feedback replay) in one breath."""
    import numpy as np

    from torchft_tpu.serving import (
        ServeConfig,
        ServeWorker,
        SnapshotPublisher,
        SnapshotRegistry,
    )

    registry = SnapshotRegistry()
    cfg = ServeConfig(
        registry=registry.url, max_lag=4, compress="fp8",
        poll_s=0.02, timeout_s=10.0,
    )
    publisher = SnapshotPublisher(
        "doctor_replica", config=cfg, registry_url=registry.url
    )
    worker = ServeWorker(registry.url, config=cfg, name="doctor_worker")
    try:
        rng = np.random.RandomState(7)
        params = {"w": rng.randn(4096).astype(np.float32)}
        publisher.publish(1, 0, params)
        if not worker.wait_version((1, 0), timeout=10.0):
            return False, (
                f"worker never reached (1, 0): counters={worker.counters}"
            )
        params["w"] = params["w"] + 0.01
        publisher.publish(1, 1, params)
        if not worker.wait_version((1, 1), timeout=10.0):
            return False, (
                f"worker stuck at {worker.version} (want (1, 1)): "
                f"counters={worker.counters}"
            )
        if not np.array_equal(worker.params_flat(), publisher.ref_flat()):
            return False, (
                "worker params != publisher reference after pull — the "
                "bitwise delta/full invariant broke"
            )
        c = worker.counters
        return True, (
            f"worker converged to (1, 1): {c['full_pulls_total']} full + "
            f"{c['delta_pulls_total']} delta pull(s), "
            f"{c['delta_bytes_total']}B delta vs {c['full_bytes_total']}B full"
        )
    finally:
        worker.shutdown()
        publisher.shutdown()
        registry.shutdown()


def check_redundancy_env() -> Result:
    """``TORCHFT_REDUNDANCY_*`` sanity: the env contract parses into a
    valid RedundancyConfig (same validation the Manager funnels through),
    and when the plane is on, the shard directory answers and holds
    enough live non-spare peers for k+m distinct shard holders. Too few
    peers is a warn, not a fail: placement wraps and the plane still
    works — with degraded distinct-peer durability."""
    try:
        from torchft_tpu.redundancy import DirectoryClient, RedundancyConfig

        cfg = RedundancyConfig.from_env()
    except ValueError as e:
        return False, f"TORCHFT_REDUNDANCY_* invalid: {e}"
    if cfg.k == 0:
        return True, (
            "redundancy plane off (k=0 — peer heal only); set "
            "TORCHFT_REDUNDANCY_K/_M/_DIRECTORY to enable erasure staging"
        )
    if not cfg.directory:
        return None, (
            f"TORCHFT_REDUNDANCY_K={cfg.k} but no "
            "TORCHFT_REDUNDANCY_DIRECTORY — staging stays off; point it at "
            "the lighthouse's /redundancy endpoint"
        )
    try:
        peers = DirectoryClient(cfg.directory, timeout=3.0).peers()
    except Exception as e:  # noqa: BLE001 — unreachable is a warn
        return None, (
            f"TORCHFT_REDUNDANCY_DIRECTORY={cfg.directory} unreachable "
            f"({e!r}); stagers retry, but check the lighthouse "
            "--redundancy-directory flag / the directory process"
        )
    live = [p for p in peers if not p.get("spare")]
    if len(live) < cfg.k + cfg.m:
        return None, (
            f"k+m={cfg.k + cfg.m} but only {len(live)} live non-spare "
            "peer(s) registered — placement wraps holders; distinct-peer "
            "durability degraded until the fleet grows"
        )
    return True, (
        f"k={cfg.k} m={cfg.m} interval={cfg.interval}, directory at "
        f"{cfg.directory}: {len(live)} live peer(s), "
        f"{len(peers) - len(live)} spare(s)"
    )


def check_degrade_env() -> Result:
    """``TORCHFT_DEGRADE_*`` sanity: the env contract parses into a valid
    DegradeConfig (same validation the Manager funnels through), and a
    loopback 2→1 reshard probe runs both engine paths — full
    redistribution and gather-free peer-sourced — asserting the shrunken
    layout reassembles bitwise-identical to the original params (the
    invariant the degrade plane's correctness rests on)."""
    try:
        from torchft_tpu.parallel.degrade import DegradeConfig

        cfg = DegradeConfig.from_env()
    except ValueError as e:
        return False, f"TORCHFT_DEGRADE_* invalid: {e}"
    try:
        import numpy as np

        from torchft_tpu.parallel.degrade import (
            assemble,
            reshard_from_survivors,
            reshard_full,
        )

        rng = np.random.default_rng(0)
        full = {
            "w": rng.standard_normal((6, 4)).astype(np.float32),
            "b": rng.standard_normal((3,)).astype(np.float32),
        }
        axes = {"w": 0, "b": None}
        two_chip, _ = reshard_full(full, axes, 2)
        # full path: 2 -> 1
        one_chip, _ = reshard_full(full, axes, 1)
        back = assemble(one_chip, axes)
        if not all(
            np.array_equal(back[k], full[k]) for k in full
        ):
            return False, "full-path 2->1 reshard probe not bitwise equal"
        # peer path: kill rank 1, source its shard from the old layout
        dead_shards = {"w": np.asarray(two_chip[1]["w"])}
        survivors, _ = reshard_from_survivors(
            [two_chip[0], None],
            dead_rank=1,
            axes=axes,
            shard_source=lambda path: dead_shards["w"],
        )
        back = assemble(survivors, axes)
        if not all(
            np.array_equal(back[k], full[k]) for k in full
        ):
            return False, "peer-path 2->1 reshard probe not bitwise equal"
    except Exception as e:  # noqa: BLE001
        return False, f"degrade reshard probe failed: {e}"
    if not cfg.enabled:
        return True, (
            "degrade plane off (TORCHFT_DEGRADE=off — chip loss costs the "
            "whole replica); reshard probe bitwise ok"
        )
    return True, (
        f"on: min_degree={cfg.min_degree} restore={cfg.restore}; "
        "2->1 reshard probe bitwise ok (full + peer paths)"
    )


def check_redundancy_roundtrip() -> Result:
    """Loopback redundancy probe: encode a state across k=2/m=1 shards on
    three stores, corrupt one data shard's stored bytes, and reconstruct —
    crc32 must catch the corruption and the parity shard must repair it to
    a bitwise-identical state. The whole plane (placement announce, shard
    GETs, corrupt-shard detection, GF(256) decode) in one breath."""
    import numpy as np

    from torchft_tpu.checkpointing.erasure import encode_shards, shard_crc
    from torchft_tpu.redundancy import (
        DirectoryClient,
        ShardDirectory,
        ShardStore,
        pack_state_blob,
        put_shard,
        reconstruct_state,
    )

    k, m = 2, 1
    directory = ShardDirectory()
    client = DirectoryClient(directory.url, timeout=5.0)
    stores = [ShardStore(f"doctor_holder_{i}") for i in range(k + m)]
    try:
        rng = np.random.RandomState(11)
        state = {"w": rng.randn(65536).astype(np.float32)}
        blob = pack_state_blob(state)
        shards = encode_shards(blob, k, m)
        epoch = client.register("doctor_red", "doctor", stores[0].url)
        entries = []
        for idx, body in enumerate(shards):
            # shard 0 is stored corrupted but announced with the true crc:
            # the GET must fail verification, not silently decode garbage
            stored = (bytes([body[0] ^ 0xFF]) + body[1:]) if idx == 0 else body
            put_shard(stores[idx].url, "doctor_red", 1, idx, stored, timeout=5.0)
            entries.append({
                "idx": idx, "holder": stores[idx].replica_id,
                "url": stores[idx].url, "crc": shard_crc(body),
            })
        code, resp = client.announce({
            "replica_id": "doctor_red", "epoch": epoch, "seq": 1, "step": 1,
            "k": k, "m": m, "data_len": len(blob), "shards": entries,
        })
        if code != 200:
            return False, f"directory rejected announce: {resp}"
        _, got, stats = reconstruct_state(
            directory.url, owner="doctor_red", timeout=30.0
        )
        if stats.get("shards_corrupt", 0) < 1:
            return False, (
                "corrupted shard was not detected — crc32 verification on "
                f"the shard GET path regressed (stats={stats})"
            )
        if not np.array_equal(np.asarray(got["w"]), state["w"]):
            return False, (
                "reconstructed state != original — GF(256) parity repair "
                "broke the bitwise round-trip"
            )
        return True, (
            f"k={k}+m={m} reconstruct repaired 1 corrupt shard bitwise "
            f"({stats['shards_ok']} ok / {stats['shards_corrupt']} corrupt, "
            f"{stats['mb_per_s']:.0f} MB/s loopback)"
        )
    finally:
        for s in stores:
            s.shutdown()
        directory.shutdown()


def check_tuning_env() -> Result:
    """Registry-driven sanity for every tuning knob that has no
    plane-specific doctor check: each ``TORCHFT_*`` value set in the
    environment must parse per its declared type in the knob registry
    (torchft_tpu/knobs.py), JSON knobs must decode to objects, and enums
    must name a declared member. Catches the classic fleet-rollout typo
    (``TORCHFT_BUCKET_CAP_MB=32mb``) before it silently falls back."""
    from torchft_tpu import knobs

    checked = 0
    problems: List[str] = []
    for name, knob in sorted(knobs.all_knobs().items()):
        if knob.doctor != "tuning-env":
            continue
        raw = os.environ.get(name)
        checked += 1
        if raw is None or raw.strip() == "":
            continue
        try:
            if knob.type == "int":
                int(raw)
            elif knob.type == "float":
                float(raw)
            elif knob.type == "bool":
                if raw.strip().lower() not in (
                    "0", "1", "true", "false", "yes", "no", "on", "off"
                ):
                    raise ValueError(f"not a boolean: {raw!r}")
            elif knob.type.startswith("enum("):
                members = knob.type[5:-1].split("|")
                if raw not in members:
                    raise ValueError(f"{raw!r} not in {members}")
            elif name.endswith("_JSON"):
                if not isinstance(json.loads(raw), dict):
                    raise ValueError("must decode to a JSON object")
        except ValueError as e:
            problems.append(f"{name}={raw!r} ({e})")
    if problems:
        return False, "; ".join(problems)
    n_set = sum(
        1
        for name, knob in knobs.all_knobs().items()
        if knob.doctor == "tuning-env" and os.environ.get(name)
    )
    return True, f"{checked} tuning knob(s) registered, {n_set} set, all parse"


def check_policy_env() -> Result:
    """``TORCHFT_POLICY*`` sanity plus a loopback observe probe: the mode
    names a known member, the numeric knobs parse, the spec (builtin or
    the ``TORCHFT_POLICY_SPEC`` file) loads and validates, and a
    throwaway engine in observe mode folds a synthetic churn burst into a
    well-formed frame — the exact fold/evaluate pipeline a lighthouse
    runs live, so a bad spec fails here instead of at fleet start."""
    from torchft_tpu import knobs
    from torchft_tpu.policy import POLICY_MODES, PolicyEngine, PolicySpec

    mode = os.environ.get("TORCHFT_POLICY", "").strip() or "off"
    if mode not in POLICY_MODES:
        return False, (
            f"TORCHFT_POLICY={mode!r} invalid: pick one of "
            f"{'/'.join(POLICY_MODES)}"
        )
    try:
        knobs.env_float("TORCHFT_POLICY_INTERVAL_S", 5.0)
        window_s = knobs.env_float("TORCHFT_POLICY_WINDOW_S", 300.0)
        knobs.env_int("TORCHFT_POLICY_RING", 4096)
        knobs.env_int("TORCHFT_SYNC_EVERY", 0)
    except ValueError as e:
        return False, f"TORCHFT_POLICY_* numeric knob invalid: {e}"
    spec_src = os.environ.get("TORCHFT_POLICY_SPEC", "").strip() or "builtin"
    try:
        spec = PolicySpec.load(spec_src)
    except (ValueError, OSError, KeyError) as e:
        return False, f"policy spec {spec_src!r} failed to load: {e}"
    try:
        # loopback observe probe on synthetic history (no lighthouse, no
        # wall clock): a hot churn burst must fold and evaluate cleanly
        from torchft_tpu._test.event_injector import churn_burst

        engine = PolicyEngine(spec, mode="observe", window_s=window_s)
        engine.feed(churn_burst(8, period_s=5.0))
        frame = engine.evaluate()
        if "policy_seq" not in frame:
            raise ValueError(f"malformed frame: {frame!r}")
    except Exception as e:  # noqa: BLE001 — probe failure is the finding
        return False, f"observe probe failed on spec {spec_src!r}: {e}"
    if mode == "off":
        return True, (
            f"policy off (byte-identical path); spec {spec_src!r} "
            f"validates ({len(spec.rules)} rule(s)) and probes clean"
        )
    return True, (
        f"policy {mode}: spec {spec_src!r} ({len(spec.rules)} rule(s)) "
        f"probed clean, frame seq={frame['policy_seq']}"
    )


def check_fleetlint() -> Result:
    """In-process fleetlint env-contract run: every TORCHFT_* read in the
    package is registered/documented/doctored, and no finding beyond the
    committed baseline (torchft_tpu/analysis/baseline.json). The full
    five-checker run lives in CI (`python -m torchft_tpu.analysis --ci`);
    the env contract is the part that drifts with operator-facing
    surface, so the doctor re-validates it on any host."""
    from torchft_tpu.analysis import core

    findings = core.run_all(checkers=["env-contract"])
    baseline = core.load_baseline()
    new, stale = core.diff_baseline(findings, baseline)
    if new:
        head = "; ".join(f"{f.rule}:{f.key}" for f in new[:5])
        more = f" (+{len(new) - 5} more)" if len(new) > 5 else ""
        return False, (
            f"{len(new)} env-contract finding(s) beyond baseline: "
            f"{head}{more} — run python -m torchft_tpu.analysis"
        )
    detail = (
        f"{len(findings)} finding(s), all baselined"
        if findings
        else "env contract clean"
    )
    if stale:
        return None, f"{detail}; {len(stale)} stale baseline entr(y/ies)"
    return True, detail


CHECKS: List[Tuple[str, Callable[[], Result]]] = [
    ("native", check_native),
    ("accelerator", check_accelerator),
    ("virtual-mesh", check_virtual_mesh),
    ("lighthouse", check_lighthouse_roundtrip),
    ("aggregator", check_aggregator),
    ("retry-env", check_retry_env),
    ("health-env", check_health_env),
    ("compress-env", check_compress_env),
    ("serve-env", check_serve_env),
    ("redundancy-env", check_redundancy_env),
    ("degrade-env", check_degrade_env),
    ("trace-env", check_trace_env),
    ("policy-env", check_policy_env),
    ("tuning-env", check_tuning_env),
    ("fleetlint", check_fleetlint),
    ("health-http", check_health_endpoint),
    ("metrics-http", check_metrics_endpoints),
    ("heal", check_heal_roundtrip),
    ("serving", check_serving_roundtrip),
    ("redundancy", check_redundancy_roundtrip),
]


def main() -> None:
    failed = False
    for name, fn in CHECKS:
        try:
            status, detail = fn()
        except Exception as e:  # noqa: BLE001 - a crashing check is a failure
            status, detail = False, f"check crashed: {e}"
        tag = {True: "ok  ", None: "warn", False: "FAIL"}[status]
        print(f"{tag} {name:<14} {detail}", flush=True)
        failed |= status is False
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
