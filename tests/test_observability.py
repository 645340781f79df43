"""Tests for structured event logging + trace spans (observability.py)."""

import json
import logging

from torchft_tpu.observability import (
    COMMIT_EVENTS,
    ERROR_EVENTS,
    QUORUM_EVENTS,
    get_event_logger,
    log_commit_event,
    log_error_event,
    log_quorum_event,
)
from torchft_tpu.tracing import SpanRecorder


def _capture(caplog, name, fn, **fields):
    with caplog.at_level(logging.INFO, logger=name):
        fn(**fields)
    records = [r for r in caplog.records if r.name == name]
    assert len(records) == 1
    payload = json.loads(records[0].getMessage())
    assert "event_time" in payload
    return payload


def test_quorum_event_structured(caplog):
    payload = _capture(
        caplog, QUORUM_EVENTS, log_quorum_event, quorum_id=3, replica_rank=1
    )
    assert payload["quorum_id"] == 3
    assert payload["replica_rank"] == 1


def test_commit_event_structured(caplog):
    payload = _capture(
        caplog, COMMIT_EVENTS, log_commit_event, step=7, committed=True
    )
    assert payload["step"] == 7
    assert payload["committed"] is True


def test_error_event_serializes_exceptions(caplog):
    payload = _capture(
        caplog, ERROR_EVENTS, log_error_event, error=ValueError("boom")
    )
    assert "boom" in payload["error"]


def test_event_logger_cached():
    assert get_event_logger("x_stream") is get_event_logger("x_stream")


def test_recorder_span_noop_and_with_jax():
    # the one span API (ring + profiler annotation) must not raise with or
    # without jax loaded, with or without an active profiler, on or off
    import jax  # noqa: F401 — loaded: the span also enters an annotation

    from torchft_tpu.tracing import TraceConfig

    for enabled in (True, False):
        rec = SpanRecorder("r0", TraceConfig(enabled=enabled))
        with rec.span("span", cat="test"):
            x = 1 + 1
        assert x == 2
        assert len(rec.export()["spans"]) == (1 if enabled else 0)


def test_manager_events_emitted_on_report_error(caplog):
    """Manager.report_error should emit a torchft_errors record."""
    from torchft_tpu.manager import Manager

    # Construct a Manager shell without running __init__ networking.
    import threading

    m = Manager.__new__(Manager)
    m._errored = None
    m._replica_id = "test:0"
    m._group_rank = 0
    m._step = 5
    m._quorum_id = 2
    m._metrics_lock = threading.Lock()
    m._metrics = {"errors": 0}

    with caplog.at_level(logging.INFO, logger=ERROR_EVENTS):
        m.report_error(RuntimeError("injected"))
    records = [r for r in caplog.records if r.name == ERROR_EVENTS]
    assert len(records) == 1
    payload = json.loads(records[0].getMessage())
    assert payload["step"] == 5
    assert "injected" in payload["error"]
    assert m.errored() is not None


class TestEventDrain:
    def test_flush_inline_without_worker(self, caplog):
        from torchft_tpu.observability import COMMIT_EVENTS, EventDrain

        drain = EventDrain(autostart=False)
        for i in range(3):
            assert drain.submit(COMMIT_EVENTS, {"step": i, "committed": True})
        with caplog.at_level(logging.INFO, logger=COMMIT_EVENTS):
            assert drain.flush()
        records = [r for r in caplog.records if r.name == COMMIT_EVENTS]
        assert [json.loads(r.getMessage())["step"] for r in records] == [0, 1, 2]

    def test_worker_drains_and_flush_blocks_until_written(self, caplog):
        from torchft_tpu.observability import TIMING_EVENTS, EventDrain

        drain = EventDrain()
        with caplog.at_level(logging.INFO, logger=TIMING_EVENTS):
            for i in range(5):
                assert drain.submit(TIMING_EVENTS, {"phase": "t", "i": i})
            assert drain.flush(timeout=10)
        records = [r for r in caplog.records if r.name == TIMING_EVENTS]
        assert len(records) == 5

    def test_overflow_drops_newest_and_counts(self):
        from torchft_tpu.observability import COMMIT_EVENTS, EventDrain

        drain = EventDrain(maxsize=2, autostart=False)
        assert drain.submit(COMMIT_EVENTS, {"step": 0})
        assert drain.submit(COMMIT_EVENTS, {"step": 1})
        assert not drain.submit(COMMIT_EVENTS, {"step": 2})  # full: dropped
        assert drain.dropped == 1
        # the queued (oldest) events survive; the overflow event is gone
        assert drain.flush()

    def test_bad_event_does_not_kill_drain(self, caplog):
        from torchft_tpu.observability import COMMIT_EVENTS, EventDrain

        drain = EventDrain(autostart=False)
        drain.submit(COMMIT_EVENTS, {"bad": object()})  # default=str handles it
        drain.submit(COMMIT_EVENTS, {"step": 1})
        with caplog.at_level(logging.INFO, logger=COMMIT_EVENTS):
            assert drain.flush()
        records = [r for r in caplog.records if r.name == COMMIT_EVENTS]
        assert len(records) == 2

    def test_process_wide_singleton(self):
        from torchft_tpu.observability import get_event_drain

        assert get_event_drain() is get_event_drain()


class TestObservabilityHonestyCounters:
    """Both observability planes are deliberately lossy (they must never
    stall the step); timings() therefore carries the loss counters and
    warns ONCE per Manager when either queue has saturated."""

    def _manager_shell(self, tracer_buffer=16):
        import threading

        from torchft_tpu.manager import Manager, _ManagerLogger
        from torchft_tpu.tracing import SpanRecorder, TraceConfig

        m = Manager.__new__(Manager)
        m._replica_id = "drop_test:0"
        m._group_rank = 0
        m._step = 0
        m._metrics_lock = threading.Lock()
        m._timings = {}
        m._tracer = SpanRecorder(
            "drop_test", TraceConfig(enabled=True, buffer=tracer_buffer)
        )
        m._dropped_events_warned = False
        m._logger = _ManagerLogger(m, m._replica_id, 0)
        return m

    def test_saturated_queues_surface_and_warn_once(self, caplog,
                                                    monkeypatch):
        from types import SimpleNamespace

        from torchft_tpu import manager as manager_mod

        m = self._manager_shell(tracer_buffer=16)
        # overflow the span ring by 4 and pretend the telemetry drain
        # already shed 3 events under saturation
        for i in range(20):
            m._tracer.instant("e", cat="rpc", i=i)
        monkeypatch.setattr(
            manager_mod, "get_event_drain",
            lambda: SimpleNamespace(dropped=3),
        )
        with caplog.at_level(logging.WARNING, logger="torchft_tpu.manager"):
            t1 = m.timings()
            t2 = m.timings()
        assert t1["dropped_events"] == 3.0
        assert t1["trace_dropped"] == 4.0
        assert t2["dropped_events"] == 3.0
        warns = [r for r in caplog.records
                 if "observability queues saturated" in r.getMessage()]
        assert len(warns) == 1, "saturation warning must fire exactly once"
        assert "3 telemetry event(s)" in warns[0].getMessage()
        assert "4 span(s)" in warns[0].getMessage()

    def test_clean_queues_report_zero_and_stay_quiet(self, caplog,
                                                     monkeypatch):
        from types import SimpleNamespace

        from torchft_tpu import manager as manager_mod

        m = self._manager_shell()
        m._tracer.instant("e", cat="rpc")  # recorded, not dropped
        monkeypatch.setattr(
            manager_mod, "get_event_drain",
            lambda: SimpleNamespace(dropped=0),
        )
        with caplog.at_level(logging.WARNING, logger="torchft_tpu.manager"):
            t = m.timings()
        assert t["dropped_events"] == 0.0
        assert t["trace_dropped"] == 0.0
        assert not [r for r in caplog.records
                    if "observability queues saturated" in r.getMessage()]


class TestMetricsRegistry:
    def test_render_is_valid_prometheus_text(self):
        from torchft_tpu.observability import MetricsRegistry

        reg = MetricsRegistry()
        reg.gauge_set("torchft_test_gauge", 2.5, "A gauge.")
        reg.counter_set("torchft_test_total", 7.0, "A counter.")
        for v in (0.005, 0.05, 0.05, 5.0):
            reg.observe("torchft_test_seconds", v, "A histogram.")
        text = reg.render()
        assert "# HELP torchft_test_gauge A gauge." in text
        assert "# TYPE torchft_test_gauge gauge" in text
        assert "torchft_test_gauge 2.5" in text
        assert "# TYPE torchft_test_total counter" in text
        assert "torchft_test_total 7" in text
        # histogram: cumulative buckets + _sum/_count
        assert "# TYPE torchft_test_seconds histogram" in text
        assert 'torchft_test_seconds_bucket{le="+Inf"} 4' in text
        assert "torchft_test_seconds_count 4" in text
        lines = [l for l in text.splitlines() if "_bucket{" in l]
        counts = [float(l.rsplit(" ", 1)[1]) for l in lines]
        assert counts == sorted(counts), "buckets must be cumulative"

    def test_server_serves_and_refreshes(self):
        import urllib.request

        from torchft_tpu.observability import MetricsRegistry, MetricsServer

        reg = MetricsRegistry()
        calls = []

        def refresh():
            calls.append(1)
            reg.gauge_set("torchft_refresh_gauge", float(len(calls)),
                          "Scrape-time refresh.")

        srv = MetricsServer(reg, port=0, refresh=refresh)
        try:
            url = f"http://127.0.0.1:{srv.port}/metrics"
            with urllib.request.urlopen(url, timeout=5.0) as resp:
                body = resp.read().decode()
            assert "torchft_refresh_gauge 1" in body
            with urllib.request.urlopen(url, timeout=5.0) as resp:
                body = resp.read().decode()
            assert "torchft_refresh_gauge 2" in body
            assert len(calls) == 2
            # anything but /metrics is a 404, not a crash
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/other"
            )
            try:
                urllib.request.urlopen(req, timeout=5.0)
                raise AssertionError("expected HTTP 404")
            except urllib.error.HTTPError as e:
                assert e.code == 404
        finally:
            srv.shutdown()
