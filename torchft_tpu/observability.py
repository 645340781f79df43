"""Structured event logging + profiler spans (reference: torchft otel.py:44-99
and the ``record_function`` spans on manager hot paths, manager.py:410-936).

Three structured event streams mirror the reference's loggers:

- ``torchft_quorums`` — one record per quorum change (quorum id, replicas,
  participation, heal/recovery roles);
- ``torchft_commits`` — one record per ``should_commit`` decision;
- ``torchft_errors`` — one record per reported error / PG abort;
- ``torchft_timings`` — per-phase wall-clock snapshots of a reconfigure
  cycle (quorum overlap, configure prepare/commit, heal transfer) and of
  the data plane: each streamed allreduce emits a
  ``phase="allreduce_pipeline"`` snapshot carrying the per-bucket stage
  splits (``allreduce_pack_s`` / ``allreduce_wire_s`` /
  ``allreduce_unpack_s``, ``allreduce_buckets``) plus
  ``overlap_efficiency`` — the fraction of wire time hidden behind other
  buckets' pipeline stages;
- ``torchft_health`` — healthwatch lifecycle transitions observed by the
  Manager in heartbeat health summaries: ``straggler_warn`` when the
  lighthouse's quorum-relative straggler score crosses the warn
  threshold, ``eject`` when a replica is proactively excluded from the
  next quorum, ``readmit`` when a probationary replica rejoins. Each
  record carries the score, state, and cumulative ejection/readmission
  counts (see healthwatch.py).

Records are JSON-serialised into the standard ``logging`` stream, and — when
``TORCHFT_USE_OTEL=1`` and the ``opentelemetry`` packages are importable —
additionally exported over OTLP with resource attributes taken from
``TORCHFT_OTEL_RESOURCE_ATTRIBUTES_JSON``. The OTLP path is optional and
degrades silently to console-only, matching the reference's opt-in design.

Profiler spans (the ``torch.profiler.record_function`` analog) are not
here: ``tracing.SpanRecorder.span`` enters a ``jax.profiler.TraceAnnotation``
beside every ring span.
"""

from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time
from typing import Any, Dict, Optional, Tuple

USE_OTEL_ENV = "TORCHFT_USE_OTEL"
OTEL_RESOURCE_ATTRS_ENV = "TORCHFT_OTEL_RESOURCE_ATTRIBUTES_JSON"

QUORUM_EVENTS = "torchft_quorums"
COMMIT_EVENTS = "torchft_commits"
ERROR_EVENTS = "torchft_errors"
# per-phase wall-clock snapshots of a quorum/reconfigure cycle
# (quorum_overlap_s, configure_prepare_s, configure_commit_s, heal_*) and
# of the streamed allreduce pipeline (phase=ALLREDUCE_PIPELINE_PHASE:
# allreduce_pack_s/wire_s/unpack_s, allreduce_buckets, overlap_efficiency)
TIMING_EVENTS = "torchft_timings"
ALLREDUCE_PIPELINE_PHASE = "allreduce_pipeline"
# healthwatch lifecycle transitions (straggler_warn / eject / readmit) as
# the Manager observes them in heartbeat health summaries — the replica's
# own view of the lighthouse health ledger (healthwatch.py)
HEALTH_EVENTS = "torchft_health"
# adaptive policy plane (policy.py): frame arrivals and observe/enforce
# actions at the Manager's quorum safe point — policy_seq, mode, the
# override set, and which rules were active when it was built
POLICY_EVENTS = "torchft_policy"

_otel_providers: Dict[str, Any] = {}


def _shutdown_quietly(provider: Any) -> None:
    try:
        provider.shutdown()
    except Exception:  # noqa: BLE001 - exit path must never raise
        pass


def _resource_attributes() -> Dict[str, Any]:
    raw = os.environ.get(OTEL_RESOURCE_ATTRS_ENV)
    if not raw:
        return {}
    try:
        attrs = json.loads(raw)
        return attrs if isinstance(attrs, dict) else {}
    except json.JSONDecodeError:
        logging.getLogger(__name__).warning(
            "invalid %s; ignoring", OTEL_RESOURCE_ATTRS_ENV
        )
        return {}


def _maybe_otel_logger(name: str) -> Optional[Any]:
    """Build (and cache) an OTLP logger for ``name`` if opted in and the
    opentelemetry SDK is available; else None."""
    if os.environ.get(USE_OTEL_ENV, "0") not in ("1", "true", "True"):
        return None
    if name in _otel_providers:
        return _otel_providers[name]
    try:
        from opentelemetry._logs import set_logger_provider  # noqa: F401
        from opentelemetry.exporter.otlp.proto.grpc._log_exporter import (
            OTLPLogExporter,
        )
        from opentelemetry.sdk._logs import LoggerProvider, LoggingHandler
        from opentelemetry.sdk._logs.export import BatchLogRecordProcessor
        from opentelemetry.sdk.resources import Resource

        provider = LoggerProvider(
            resource=Resource.create({"service.name": name, **_resource_attributes()})
        )
        provider.add_log_record_processor(BatchLogRecordProcessor(OTLPLogExporter()))
        handler = LoggingHandler(logger_provider=provider)
        otel_logger = logging.getLogger(f"{name}.otlp")
        otel_logger.addHandler(handler)
        otel_logger.propagate = False
        _otel_providers[name] = otel_logger
        # flush the batch processor at exit: the records that matter most
        # (the error event right before a fatal exit) are exactly the ones a
        # never-shut-down BatchLogRecordProcessor would drop
        import atexit

        atexit.register(lambda: _shutdown_quietly(provider))
        return otel_logger
    except Exception:  # noqa: BLE001 — SDK missing or exporter misconfigured
        _otel_providers[name] = None
        return None


class EventLogger:
    """A named structured-event stream."""

    def __init__(self, name: str) -> None:
        self.name = name
        self._logger = logging.getLogger(name)

    def log(self, **fields: Any) -> None:
        record = {"event_time": time.time(), **fields}
        line = json.dumps(record, default=str)
        self._logger.info(line)
        otel = _maybe_otel_logger(self.name)
        if otel is not None:
            otel.info(line)


_event_loggers: Dict[str, EventLogger] = {}


def get_event_logger(name: str) -> EventLogger:
    if name not in _event_loggers:
        _event_loggers[name] = EventLogger(name)
    return _event_loggers[name]


def log_quorum_event(**fields: Any) -> None:
    get_event_logger(QUORUM_EVENTS).log(**fields)


def log_commit_event(**fields: Any) -> None:
    get_event_logger(COMMIT_EVENTS).log(**fields)


def log_error_event(**fields: Any) -> None:
    get_event_logger(ERROR_EVENTS).log(**fields)


def log_timing_event(**fields: Any) -> None:
    get_event_logger(TIMING_EVENTS).log(**fields)


def log_health_event(**fields: Any) -> None:
    get_event_logger(HEALTH_EVENTS).log(**fields)


def log_policy_event(**fields: Any) -> None:
    get_event_logger(POLICY_EVENTS).log(**fields)


class EventDrain:
    """Bounded async event emitter for hot-path callers.

    The synchronous ``log_*`` functions above serialize + write on the
    calling thread — fine for rare events (quorum changes, errors), but a
    per-step caller (``Manager.should_commit``) would pay JSON encoding and
    logging I/O on the training-critical path every step. ``submit`` only
    enqueues; one daemon worker drains the queue through the same
    :class:`EventLogger` streams (console + optional OTLP).

    Bounded and lossy by design: when the queue is full the NEW event is
    dropped and counted (``dropped``) rather than blocking the trainer —
    observability must never become backpressure. ``flush`` waits until
    everything queued so far has been written (e.g. before shutdown).
    """

    _FLUSH = "__flush__"

    def __init__(self, maxsize: int = 1024, autostart: bool = True) -> None:
        self._q: "queue.Queue[Tuple[str, Any]]" = queue.Queue(maxsize)
        self._lock = threading.Lock()
        self._thread: Optional[threading.Thread] = None
        self._autostart = autostart
        self._dropped = 0

    @property
    def dropped(self) -> int:
        """Events discarded because the queue was full."""
        with self._lock:
            return self._dropped

    def start(self) -> None:
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._thread = threading.Thread(
                target=self._run, name="torchft_event_drain", daemon=True
            )
            self._thread.start()

    def _emit(self, stream: str, fields: Dict[str, Any]) -> None:
        try:
            get_event_logger(stream).log(**fields)
        except Exception:  # noqa: BLE001 — a bad event must not kill the drain
            logging.getLogger(__name__).exception(
                "event drain failed to emit %s event", stream
            )

    def _run(self) -> None:
        while True:
            stream, payload = self._q.get()
            try:
                if stream == self._FLUSH:
                    payload.set()
                else:
                    self._emit(stream, payload)
            finally:
                self._q.task_done()

    def submit(self, stream: str, fields: Dict[str, Any]) -> bool:
        """Enqueue an event; returns False (and counts a drop) if full."""
        if self._autostart:
            self.start()
        try:
            self._q.put_nowait((stream, dict(fields)))
            return True
        except queue.Full:
            with self._lock:
                self._dropped += 1
            return False

    def flush(self, timeout: Optional[float] = 5.0) -> bool:
        """Block until everything queued before this call is written.
        With no live worker (autostart=False), drains inline instead."""
        with self._lock:
            alive = self._thread is not None and self._thread.is_alive()
        if not alive:
            while True:
                try:
                    stream, payload = self._q.get_nowait()
                except queue.Empty:
                    return True
                try:
                    if stream == self._FLUSH:
                        payload.set()
                    else:
                        self._emit(stream, payload)
                finally:
                    self._q.task_done()
        done = threading.Event()
        try:
            self._q.put((self._FLUSH, done), timeout=timeout)
        except queue.Full:
            return False
        return done.wait(timeout)


_event_drain: Optional[EventDrain] = None
_event_drain_lock = threading.Lock()


def get_event_drain() -> EventDrain:
    """Process-wide drain shared by every hot-path emitter."""
    global _event_drain
    with _event_drain_lock:
        if _event_drain is None:
            _event_drain = EventDrain()
        return _event_drain


def emit_event_async(stream: str, **fields: Any) -> bool:
    """Hot-path event emission: enqueue onto the bounded drain and return
    immediately. Use the synchronous ``log_*`` helpers for rare events
    whose loss at a crash would matter (errors)."""
    return get_event_drain().submit(stream, fields)


# ---------------------------------------------------------------- /metrics
# Manager-side Prometheus text exposition (the lighthouse serves its own
# /metrics natively beside /health). One registry per Manager: timing
# splits as histograms (fed by Manager._record_timing at write time),
# counters/gauges synced from Manager.timings() + wire_stats() at scrape
# time via the refresh hook.

METRICS_PORT_ENV = "TORCHFT_METRICS_PORT"

# Exponential-ish bucket bounds in SECONDS for phase-timing histograms:
# control-plane phases span ~100us (vote RPC on loopback) to tens of
# seconds (a full heal), so fixed linear buckets would waste either end.
DEFAULT_TIME_BUCKETS = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)


class MetricsRegistry:
    """Thread-safe registry rendering Prometheus text exposition 0.0.4."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._gauges: Dict[str, Tuple[float, str]] = {}
        self._counters: Dict[str, Tuple[float, str]] = {}
        # name -> (help, bucket bounds, per-bucket counts, sum, count)
        self._hists: Dict[str, Any] = {}

    def gauge_set(self, name: str, value: float, help_: str = "") -> None:
        with self._lock:
            self._gauges[name] = (float(value), help_)

    def counter_set(self, name: str, value: float, help_: str = "") -> None:
        """Set a counter's ABSOLUTE cumulative value (Manager counters are
        already cumulative; re-counting them here would double-book)."""
        with self._lock:
            self._counters[name] = (float(value), help_)

    def observe(
        self,
        name: str,
        value: float,
        help_: str = "",
        buckets: Tuple[float, ...] = DEFAULT_TIME_BUCKETS,
    ) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = [help_, tuple(buckets), [0] * (len(buckets) + 1), 0.0, 0]
                self._hists[name] = h
            bounds = h[1]
            i = len(bounds)
            for j, b in enumerate(bounds):
                if value <= b:
                    i = j
                    break
            h[2][i] += 1
            h[3] += float(value)
            h[4] += 1

    def render(self) -> str:
        out = []
        with self._lock:
            for name in sorted(self._gauges):
                value, help_ = self._gauges[name]
                if help_:
                    out.append(f"# HELP {name} {help_}")
                out.append(f"# TYPE {name} gauge")
                out.append(f"{name} {value}")
            for name in sorted(self._counters):
                value, help_ = self._counters[name]
                if help_:
                    out.append(f"# HELP {name} {help_}")
                out.append(f"# TYPE {name} counter")
                out.append(f"{name} {value}")
            for name in sorted(self._hists):
                help_, bounds, counts, total, n = self._hists[name]
                if help_:
                    out.append(f"# HELP {name} {help_}")
                out.append(f"# TYPE {name} histogram")
                cum = 0
                for b, c in zip(bounds, counts):
                    cum += c
                    out.append(f'{name}_bucket{{le="{b}"}} {cum}')
                cum += counts[-1]
                out.append(f'{name}_bucket{{le="+Inf"}} {cum}')
                out.append(f"{name}_sum {total}")
                out.append(f"{name}_count {n}")
        return "\n".join(out) + "\n"


class MetricsServer:
    """Tiny threaded HTTP server exposing one registry at ``/metrics``.

    ``refresh`` (optional) runs before each render — the Manager uses it
    to sync timings()/wire_stats() into the registry only when someone
    actually scrapes, keeping the training hot path untouched."""

    def __init__(
        self,
        registry: MetricsRegistry,
        port: int = 0,
        host: str = "127.0.0.1",
        refresh: Optional[Any] = None,
    ) -> None:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        registry_ref = registry
        refresh_ref = refresh

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self) -> None:  # noqa: N802 — http.server API
                if self.path not in ("/metrics", "/"):
                    self.send_error(404)
                    return
                try:
                    if refresh_ref is not None:
                        refresh_ref()
                    body = registry_ref.render().encode()
                except Exception:  # noqa: BLE001 — scrape must not crash
                    self.send_error(500)
                    return
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args: Any) -> None:  # silence per-scrape
                pass

        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self._thread = threading.Thread(
            target=self._httpd.serve_forever,
            name="torchft_metrics",
            daemon=True,
        )
        self._thread.start()

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    def shutdown(self) -> None:
        try:
            self._httpd.shutdown()
            self._httpd.server_close()
        except Exception:  # noqa: BLE001 — teardown must not raise
            pass
