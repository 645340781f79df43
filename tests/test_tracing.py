"""Fleet tracing plane: span recorder, skew-corrected merge, /metrics,
and the recorded-history parity pin.

Covers the tracing module bottom-up — config env parsing, the bounded
ring's drop accounting and what it holds at its default size, perf-counter
anchoring, the watcher thread's device milestones (``when_ready``) — then
the cross-replica guarantees that only hold end to end:

- **skew correction** (``merge_traces``): replicas with injected clock
  offsets (``EventInjector.skew_clock``) produce raw timestamps that
  mis-order cross-replica events; the merged timeline must restore the
  true order within the estimated-skew bound.
- **history parity**: the SAME JSONL folded through the native read path
  (``coordination.history_replay`` -> native/history.cc) and the Python
  fold (``tracing.history_fold``) must agree field-for-field, including
  on a history file a live lighthouse actually wrote.
- **/metrics**: both exposition endpoints — the lighthouse's native one
  and the Manager's Python one — must serve text that parses as
  Prometheus exposition with the documented series present
  (docs/observability.md is the reference table).
- **acceptance**: a 3-replica fleet that suffers one mid-collective link
  kill (reroute) and one injected step corruption (False vote -> one
  discarded step -> live heal) under large injected clock offsets must
  merge — through the real ``python -m torchft_tpu.trace merge`` entry
  point — into one valid Chrome-trace JSON where the heal spans and the
  victim's discarded commit vote are visible and cross-replica spans of
  the same step line up on the corrected timeline.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu import trace as trace_cli
from torchft_tpu.tracing import (
    SpanRecorder,
    TraceConfig,
    clear_clock_offsets,
    history_fold,
    merge_traces,
    parse_history,
    set_clock_offset_ms,
)

LR = 0.05


@pytest.fixture(autouse=True)
def _clean_clock_offsets():
    yield
    clear_clock_offsets()


def _cfg(buffer: int = 64, enabled: bool = True,
         dump_dir: str = "") -> TraceConfig:
    return TraceConfig(enabled=enabled, buffer=buffer, dump_dir=dump_dir)


def _parse_prometheus(text: str) -> dict:
    """name (labels included) -> value; raises on malformed exposition."""
    assert "# HELP" in text and "# TYPE" in text, text[:200]
    series = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        series[name] = float(value)
    return series


def _bare_names(series: dict) -> set:
    return {k.split("{")[0] for k in series}


# ------------------------------------------------------------------- config
class TestTraceConfig:
    def test_defaults(self, monkeypatch):
        for env in ("TORCHFT_TRACE", "TORCHFT_TRACE_BUFFER",
                    "TORCHFT_TRACE_DIR"):
            monkeypatch.delenv(env, raising=False)
        cfg = TraceConfig.from_env()
        assert cfg.enabled is True
        assert cfg.buffer == 65536
        assert cfg.dump_dir == ""

    @pytest.mark.parametrize("val,expect", [
        ("0", False), ("off", False), ("false", False), ("no", False),
        ("1", True), ("on", True), ("yes", True),
    ])
    def test_master_switch(self, monkeypatch, val, expect):
        monkeypatch.setenv("TORCHFT_TRACE", val)
        assert TraceConfig.from_env().enabled is expect

    def test_buffer_floor_and_garbage(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_TRACE_BUFFER", "4")
        assert TraceConfig.from_env().buffer == 16  # floor, not crash
        monkeypatch.setenv("TORCHFT_TRACE_BUFFER", "lots")
        assert TraceConfig.from_env().buffer == 65536

    def test_dump_dir(self, monkeypatch, tmp_path):
        monkeypatch.setenv("TORCHFT_TRACE_DIR", str(tmp_path))
        assert TraceConfig.from_env().dump_dir == str(tmp_path)


# ----------------------------------------------------------------- recorder
class TestSpanRecorder:
    def test_span_context_stamps_context_and_args(self):
        rec = SpanRecorder("ctx", _cfg())
        rec.set_context(quorum_id=7, step=3)
        with rec.span("quorum_rpc", cat="quorum", attempt=2):
            pass
        (span,) = rec.export()["spans"]
        assert span["name"] == "quorum_rpc"
        assert span["cat"] == "quorum"
        assert span["quorum_id"] == 7
        assert span["step"] == 3
        assert span["args"] == {"attempt": 2}
        assert span["dur_us"] >= 1

    def test_ring_bound_counts_drops_honestly(self):
        rec = SpanRecorder("ring", _cfg(buffer=16))
        for i in range(40):
            rec.instant("e", cat="rpc", i=i)
        stats = rec.stats()
        assert stats["spans"] == 16.0
        assert stats["recorded"] == 40.0
        assert stats["dropped"] == 24.0
        # the ring keeps the newest spans (postmortem wants the end)
        kept = [s["args"]["i"] for s in rec.export()["spans"]]
        assert kept == list(range(24, 40))

    def test_disabled_is_a_noop(self):
        rec = SpanRecorder("off", _cfg(enabled=False))
        with rec.span("x", cat="quorum"):
            pass
        rec.instant("y", cat="rpc")
        rec.record_rel("z", cat="allreduce", t0_pc=0.0, t1_pc=1.0)
        assert rec.stats() == {"spans": 0.0, "recorded": 0.0, "dropped": 0.0}

    def test_record_rel_anchors_to_wall_clock(self):
        rec = SpanRecorder("rel", _cfg())
        now_pc = time.perf_counter()
        now_us = time.time_ns() // 1000
        rec.record_rel("w", cat="allreduce", t0_pc=now_pc - 0.05,
                       t1_pc=now_pc, bucket=1)
        (span,) = rec.export()["spans"]
        assert abs(span["dur_us"] - 50_000) < 20_000
        # the interval ends "now" on the wall clock, within scheduler noise
        assert abs((span["ts_us"] + span["dur_us"]) - now_us) < 30_000

    def test_injected_offset_shifts_clock_and_exported_skew(self):
        set_clock_offset_ms("offrep", 250.0)
        rec = SpanRecorder("offrep", _cfg())
        rec.set_skew(5.0, rtt_ms=2.0, samples=3)
        rec.instant("tick", cat="rpc")
        wall_us = time.time_ns() // 1000
        export = rec.export()
        # a fast clock is fast in BOTH the stamps and the measured skew,
        # so the merge correction cancels it
        assert export["skew_ms"] == pytest.approx(255.0)
        assert export["rtt_ms"] == 2.0
        assert export["skew_samples"] == 3
        (span,) = export["spans"]
        assert abs(span["ts_us"] - (wall_us + 250_000)) < 50_000

    def test_offset_prefix_matching(self):
        set_clock_offset_ms("fleet", 100.0)
        assert SpanRecorder("fleet_3", _cfg()).export()["skew_ms"] == 100.0
        assert SpanRecorder("other", _cfg()).export()["skew_ms"] == 0.0

    def test_dump_round_trip_creates_parents(self, tmp_path):
        rec = SpanRecorder("dumper", _cfg())
        rec.instant("tick", cat="rpc")
        path = rec.dump(tmp_path / "deep" / "nest" / "d.json")
        assert path is not None and path.exists()
        loaded = json.loads(path.read_text())
        assert loaded["replica_id"] == "dumper"
        assert loaded["clock"] == "epoch_us"
        assert len(loaded["spans"]) == 1

    def test_dump_default_destinations(self, tmp_path, monkeypatch):
        monkeypatch.delenv("TORCHFT_FR_BASE_PATH", raising=False)
        # no dump dir, no flight-recorder base -> disabled, not an error
        assert SpanRecorder("nowhere", _cfg()).dump() is None
        # configured dump dir wins
        rec = SpanRecorder("dirrep", _cfg(dump_dir=str(tmp_path)))
        path = rec.dump()
        assert path is not None and path.parent == tmp_path
        assert path.name.startswith("trace_dirrep_")
        # falls back next to the flight-recorder base path
        monkeypatch.setenv("TORCHFT_FR_BASE_PATH", str(tmp_path / "fr"))
        path = SpanRecorder("frrep", _cfg()).dump()
        assert path is not None
        assert path.parent == tmp_path / "fr_traces"

    def test_dump_never_raises(self, tmp_path):
        rec = SpanRecorder("safe", _cfg())
        # target is a directory -> open() fails -> None, no exception
        assert rec.dump(tmp_path) is None


class _Handle:
    """What ``when_ready`` takes: ready when the test says (or from the
    start), or failing like a donated array."""

    def __init__(self, ready=False, raises=False):
        self._ready = threading.Event()
        self.raises = raises
        if ready:
            self._ready.set()

    def set(self):
        self._ready.set()

    def is_ready(self):
        if self.raises:
            raise RuntimeError("Array has been deleted.")
        return self._ready.is_set()

    def block_until_ready(self):
        if self.raises:
            raise RuntimeError("Array has been deleted.")
        assert self._ready.wait(10)


def _milestones(rec, n, timeout=10.0):
    """The ``device/*`` spans, once the watcher has recorded ``n``."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = [s for s in rec.export()["spans"] if s["cat"] == "device"]
        if len(got) >= n:
            return got
        time.sleep(0.005)
    raise AssertionError(f"{len(got)} of {n} milestones after {timeout} s")


class TestDeviceMilestones:
    """``when_ready``: one watcher thread a recorder blocks on the handles
    in the order they were registered."""

    def test_fifo_order_registering_step_and_the_chain_through_an_instant(self):
        rec = SpanRecorder("dev", _cfg())
        fwd, landed, upd = _Handle(), _Handle(), _Handle()
        rec.set_context(quorum_id=4, step=7)
        with rec.span("grad_dispatch", cat="trainer") as host:
            rec.when_ready("forward", "device", fwd, segment=0)
        t_fwd_registered = time.time_ns() // 1000
        rec.when_ready("landed", "device", landed, span=False, parent=99,
                       bucket=2, segment=0)
        rec.set_context(step=8)  # the commit: the update is the next number's
        rec.when_ready("update", "device", upd)
        # the device's order is the order of registration: set them so
        for h in (fwd, landed, upd):
            time.sleep(0.02)
            h.set()
        got = _milestones(rec, 3)
        rec.close()
        assert [(s["name"], s["step"]) for s in got] == [
            ("forward", 7), ("landed", 7), ("update", 8)]
        f, l, u = got
        assert f["parent"] == host.id and l["parent"] == 99 and u["parent"] is None
        assert f["quorum_id"] == 4
        assert f["args"]["segment"] == 0 and l["args"]["bucket"] == 2
        # nothing was pending when forward was registered: from there
        assert abs(f["ts_us"] - t_fwd_registered) < 5_000
        assert 15_000 < f["dur_us"] < 2_000_000
        assert all(s["args"]["late"] == 0 and s["args"]["waited_us"] > 5_000
                   for s in got)
        # an instant covers nothing and is a link all the same: the update
        # reaches from the landing's readiness, not from the forward's
        assert l["dur_us"] == 1
        assert l["ts_us"] >= f["ts_us"] + f["dur_us"]
        assert u["ts_us"] == l["ts_us"]
        assert 15_000 < u["dur_us"] < 2_000_000

    def test_late_is_a_handle_that_was_ready_when_the_watcher_came(self):
        rec = SpanRecorder("late", _cfg())
        first, second = _Handle(), _Handle(ready=True)
        rec.when_ready("backward", "device", first, segment=1)
        rec.when_ready("backward", "device", second, segment=2)
        time.sleep(0.02)
        first.set()
        one, two = _milestones(rec, 2)
        rec.close()
        assert (one["args"]["late"], two["args"]["late"]) == (0, 1)
        assert two["args"]["waited_us"] < 5_000
        # still a link: it starts where the one before it ended
        assert two["ts_us"] == one["ts_us"] + one["dur_us"]

    def test_a_span_starts_at_its_registration_if_nothing_was_pending(self):
        rec = SpanRecorder("idle", _cfg())
        rec.when_ready("update", "device", _Handle(ready=True))
        (upd,) = _milestones(rec, 1)
        time.sleep(0.05)  # the device has nothing of ours: no span over it
        t_reg = time.time_ns() // 1000
        fwd = _Handle()
        rec.when_ready("forward", "device", fwd)
        fwd.set()
        got = _milestones(rec, 2)
        rec.close()
        assert got[1]["ts_us"] >= upd["ts_us"] + upd["dur_us"] + 40_000
        assert abs(got[1]["ts_us"] - t_reg) < 5_000

    def test_a_handle_that_raises_is_dropped_and_the_chain_starts_anew(self):
        rec = SpanRecorder("raise", _cfg())
        before, after = _Handle(), _Handle()
        rec.when_ready("forward", "device", before)
        rec.when_ready("backward", "device", _Handle(raises=True))
        before.set()
        _milestones(rec, 1)
        time.sleep(0.03)
        t_reg = time.time_ns() // 1000
        rec.when_ready("update", "device", after)
        after.set()
        got = _milestones(rec, 2)
        rec.close()
        assert [s["name"] for s in got] == ["forward", "update"]
        assert abs(got[1]["ts_us"] - t_reg) < 5_000  # not from forward's end

    def test_close_with_handles_pending_ends_the_thread_and_drops_them(self):
        rec = SpanRecorder("close", _cfg())
        held, never = _Handle(), _Handle()
        rec.when_ready("forward", "device", held)
        rec.when_ready("backward", "device", never)
        watcher = rec._watcher
        assert watcher.is_alive() and watcher.daemon
        threading.Timer(0.05, held.set).start()
        rec.close()  # waits a moment for the handle being watched
        watcher.join(5)
        assert not watcher.is_alive()
        assert rec._watched.empty()
        assert [s["name"] for s in rec.export()["spans"]] == ["forward"]
        rec.when_ready("update", "device", never)  # registers nothing now
        assert rec._watcher is None and rec._watched.empty()
        never.set()
        rec.instant("still_records", cat="rpc")
        assert len(rec.export()["spans"]) == 2

    def test_a_disabled_recorder_has_no_watcher_and_keeps_no_handle(self):
        import sys

        rec = SpanRecorder("off", _cfg(enabled=False))
        handle = _Handle()
        refs = sys.getrefcount(handle)
        rec.when_ready("forward", "device", handle)
        assert rec._watcher is None and rec._watched.empty()
        assert sys.getrefcount(handle) == refs
        assert not any(t.name == "torchft_trace_watch"
                       for t in threading.enumerate())
        rec.close()

    def test_the_watcher_lets_go_of_a_handle_once_it_is_ready(self):
        import weakref

        rec = SpanRecorder("drop", _cfg())
        handle = _Handle(ready=True)
        gone = weakref.ref(handle)
        rec.when_ready("forward", "device", handle)
        del handle
        _milestones(rec, 1)
        deadline = time.monotonic() + 5
        while gone() is not None and time.monotonic() < deadline:
            time.sleep(0.005)
        assert gone() is None  # while the watcher waits for its next handle
        rec.close()


class TestDefaultRing:
    def test_it_holds_500_steps_of_a_five_op_step(self):
        """The densest benchmark cell's step: 13 spans of the step itself,
        4 an allreduce and 10 a bucket (docs/observability.md's tree), the
        watcher's forward, four backward, a landed a bucket and the update:
        a 48 s window is 82 such steps."""
        rec = SpanRecorder("full", TraceConfig())
        a_step = 13 + 5 * 4 + 5 * 10 + 1 + 4 + 5 + 1
        for step in range(500):
            rec.set_context(step=step)
            for i in range(a_step):
                rec.instant("span", cat="allreduce", bucket=i, segment=i % 5)
        stats = rec.stats()
        assert stats["recorded"] == 500 * a_step == 47_000
        assert stats["dropped"] == 0.0
        spans = rec.export()["spans"]
        assert len(spans) == 47_000 and spans[0]["step"] == 0
        assert set(spans[0]) == {"name", "cat", "ts_us", "dur_us", "quorum_id",
                                 "step", "id", "parent", "args"}


class TestSpanTree:
    """ids, parents, args and the profiler annotation of the one span API."""

    def test_ids_and_parents_on_one_thread(self):
        rec = SpanRecorder("tree", _cfg())
        with rec.span("step", cat="trainer") as outer:
            assert rec.current() == outer.id
            with rec.span("allreduce_wait", cat="trainer") as inner:
                assert rec.current() == inner.id
            with rec.span("update", cat="trainer"):
                pass
        assert rec.current() is None
        spans = {s["name"]: s for s in rec.export()["spans"]}
        assert spans["step"]["parent"] is None
        assert spans["allreduce_wait"]["parent"] == spans["step"]["id"]
        assert spans["update"]["parent"] == spans["step"]["id"]
        assert len({s["id"] for s in spans.values()}) == 3

    def test_parent_crosses_threads_in_the_closure(self):
        """The staging, dispatch and unpack workers: the parent's id is
        handed over; a thread's own stack never leaks into another."""
        rec = SpanRecorder("threads", _cfg())
        pack_id = rec.new_id()          # recorded after the fact, id known now

        def worker(name):
            assert rec.current() is None
            with rec.span(name, cat="allreduce", parent=pack_id, bucket=0):
                with rec.span(name + "_inner", cat="allreduce"):
                    pass

        with rec.span("allreduce_call", cat="trainer") as main:
            with ThreadPoolExecutor(max_workers=3) as ex:
                list(ex.map(worker, ["d2h", "wire_run", "h2d"]))
        rec.record_rel("pack", "allreduce", time.perf_counter() - 0.01,
                       time.perf_counter(), id=pack_id, parent=main.id, bucket=0)
        spans = {s["name"]: s for s in rec.export()["spans"]}
        for name in ("d2h", "wire_run", "h2d"):
            assert spans[name]["parent"] == pack_id
            assert spans[name + "_inner"]["parent"] == spans[name]["id"]
        assert spans["pack"]["id"] == pack_id
        assert spans["pack"]["parent"] == spans["allreduce_call"]["id"]

    def test_args_fill_until_exit_and_export_gains_keys_only(self):
        rec = SpanRecorder("args", _cfg())
        rec.set_context(quorum_id=2, step=5)
        with rec.span("d2h", cat="allreduce", bucket=1, queued_us=7) as sp:
            sp.args["bytes"] = 4096
        (span,) = rec.export()["spans"]
        assert span["args"] == {"bucket": 1, "queued_us": 7, "bytes": 4096}
        assert sorted(span) == ["args", "cat", "dur_us", "id", "name", "parent",
                                "quorum_id", "step", "ts_us"]
        # the merger and the CLI read a dump with the new keys unchanged
        (ev,) = [e for e in merge_traces([rec.export()])["traceEvents"]
                 if e["ph"] == "X"]
        assert ev["name"] == "d2h" and ev["args"]["bytes"] == 4096

    def test_a_span_belongs_to_the_step_it_started_in(self):
        rec = SpanRecorder("steps", _cfg())
        rec.set_context(step=4)
        with rec.span("step", cat="trainer"):
            rec.set_context(step=5)     # the commit advances the step
            with rec.span("update", cat="trainer"):
                pass
        spans = {s["name"]: s["step"] for s in rec.export()["spans"]}
        assert spans == {"step": 4, "update": 5}

    def test_disabled_recorder_hands_out_a_writable_null_span(self):
        rec = SpanRecorder("off", _cfg(enabled=False))
        with rec.span("d2h", cat="allreduce") as sp:
            sp.args["bytes"] = 1
        assert sp.id is None and rec.export()["spans"] == []

    def test_annotation_entered_once_per_span_named_as_the_benchmark_names_it(
            self, monkeypatch):
        from torchft_tpu import tracing

        seen = []

        class Fake:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                seen.append(("enter", self.name))

            def __exit__(self, *exc):
                seen.append(("exit", self.name))

        monkeypatch.setattr(tracing, "_trace_annotation", Fake)
        rec = SpanRecorder("ann", _cfg())
        with rec.span("step", cat="trainer"):
            with rec.span("d2h", cat="allreduce", bucket=0):
                pass
        rec.record_rel("pack", "allreduce", 0.0, 1.0)   # after the fact: ring only
        rec.instant("reroute", cat="rpc")
        assert seen == [("enter", "manager.trainer.step"),
                        ("enter", "manager.allreduce.d2h"),
                        ("exit", "manager.allreduce.d2h"),
                        ("exit", "manager.trainer.step")]
        off = SpanRecorder("ann_off", _cfg(enabled=False))
        with off.span("step", cat="trainer"):
            pass
        assert len(seen) == 4

    def test_tracing_never_imports_jax(self):
        """A process without jax records to the ring alone, and this module
        is never the one to load it."""
        import subprocess
        import sys

        code = (
            "import sys\n"
            "from torchft_tpu.tracing import SpanRecorder\n"
            "rec = SpanRecorder('nojax')\n"
            "with rec.span('step', cat='trainer'):\n"
            "    with rec.span('d2h', cat='allreduce'):\n"
            "        pass\n"
            "assert len(rec.export()['spans']) == 2\n"
            "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
        )
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr

    def test_compiles_are_spans_of_the_step_that_made_them(self):
        import jax
        import jax.numpy as jnp

        rec = SpanRecorder("compile", _cfg(buffer=256))
        off = SpanRecorder("compile_off", _cfg(enabled=False))
        rec.set_context(step=9)
        with rec.span("grad_dispatch", cat="trainer") as cause:
            # a function nothing else in the process has compiled
            jax.jit(lambda x: x * 3.25 + 0.125, inline=False)(
                jnp.ones((3, 5))).block_until_ready()
        spans = [s for s in rec.export()["spans"] if s["cat"] == "compile"]
        assert [s["name"] for s in spans].count("backend_compile") >= 1
        assert {s["name"] for s in spans} <= {"backend_compile", "cache_retrieval"}
        assert all(s["step"] == 9 and s["parent"] == cause.id for s in spans)
        made = sum(s["dur_us"] for s in spans if s["name"] == "backend_compile")
        assert rec.compile_total_s() == pytest.approx(made / 1e6, abs=1e-3)
        assert off.compile_total_s() == 0.0 and not off.export()["spans"]

    def test_process_start_is_before_now_and_not_long_ago(self):
        from torchft_tpu.tracing import process_start_us

        start, now = process_start_us(), time.time_ns() // 1000
        assert start is not None and 0 < now - start < 3600 * 1_000_000


# -------------------------------------------------------------------- merge
class TestMergeTraces:
    def _dump(self, rid, skew_ms, spans):
        return {"replica_id": rid, "clock": "epoch_us", "skew_ms": skew_ms,
                "rtt_ms": 0.0, "skew_samples": 1, "dropped": 0,
                "spans": spans}

    def test_structure_and_skew_shift(self):
        span = {"name": "x", "cat": "quorum", "ts_us": 1_000_000,
                "dur_us": 10, "quorum_id": 1, "step": 2,
                "args": {"k": "v"}}
        trace = merge_traces([
            self._dump("bbb", 100.0, [span]),
            self._dump("aaa", -50.0, [dict(span, cat="heal")]),
        ])
        assert trace["displayTimeUnit"] == "ms"
        evs = trace["traceEvents"]
        assert all(e["ph"] in ("X", "M") for e in evs)
        procs = {e["args"]["name"]: e["pid"] for e in evs
                 if e["ph"] == "M" and e["name"] == "process_name"}
        # pids ordered by replica_id, labelled with the applied skew
        assert procs == {"aaa (skew -50.000ms)": 0, "bbb (skew +100.000ms)": 1}
        xs = {e["args"]["replica_id"]: e for e in evs if e["ph"] == "X"}
        assert xs["bbb"]["ts"] == 1_000_000 - 100_000
        assert xs["aaa"]["ts"] == 1_000_000 + 50_000
        assert xs["bbb"]["args"]["step"] == 2
        assert xs["bbb"]["args"]["quorum_id"] == 1
        assert xs["bbb"]["args"]["k"] == "v"
        threads = {(e["pid"], e["args"]["name"]) for e in evs
                   if e["ph"] == "M" and e["name"] == "thread_name"}
        assert (procs["bbb (skew +100.000ms)"], "quorum") in threads
        assert (procs["aaa (skew -50.000ms)"], "heal") in threads

    def test_skewed_clocks_reorder_raw_but_not_merged(self):
        """Satellite: inject fixed clock offsets via the event injector and
        assert the merged timeline restores true cross-replica order within
        the estimated-skew bound (here exact: offset == estimated skew)."""
        from torchft_tpu._test.event_injector import EventInjector

        injector = EventInjector()
        injector.skew_clock("skewfast", 1500.0).skew_clock(
            "skewslow", -1500.0
        )
        try:
            fast = SpanRecorder("skewfast", _cfg())
            slow = SpanRecorder("skewslow", _cfg())
            for r in (fast, slow):
                r.set_context(quorum_id=1, step=1)
            fast.instant("mark", cat="quorum")  # true time t0
            time.sleep(0.12)
            slow.instant("mark", cat="quorum")  # true time t0 + 120ms
            d_fast, d_slow = fast.export(), slow.export()
        finally:
            injector.clear_clock_skew()
        # raw stamps lie: the later event appears ~3s EARLIER
        raw_fast = d_fast["spans"][0]["ts_us"]
        raw_slow = d_slow["spans"][0]["ts_us"]
        assert raw_slow < raw_fast - 1_000_000
        # merged timeline restores the truth
        evs = merge_traces([d_fast, d_slow])["traceEvents"]
        ts = {e["args"]["replica_id"]: e["ts"] for e in evs
              if e["ph"] == "X"}
        gap_us = ts["skewslow"] - ts["skewfast"]
        assert gap_us > 0, "skew correction lost the true ordering"
        # within the estimated-skew bound (exact offsets, so the residual
        # is just the sleep's scheduler jitter)
        assert abs(gap_us - 120_000) < 100_000, gap_us


# ------------------------------------------------------------------ history
_HISTORY_EVENTS = [
    {"kind": "quorum", "quorum_id": 1, "step": 0, "ts_ms": 1000,
     "participants": ["r0", "r1"]},
    {"kind": "heal", "replica_id": "r1", "to_step": 5, "ts_ms": 2000},
    {"kind": "straggler_warn", "replica_id": "r2", "ts_ms": 2500},
    {"kind": "eject", "replica_id": "r2", "ts_ms": 3000},
    {"kind": "readmit", "replica_id": "r2", "ts_ms": 4000},
    {"kind": "telemetry", "replica_id": "r0", "step": 7, "ts_ms": 4500},
    {"kind": "quorum", "quorum_id": 2, "step": 7, "ts_ms": 5000,
     "participants": ["r0", "r1", "r2"]},
    {"no_kind_at_all": True},
]


class TestHistory:
    def test_parse_history_skips_blanks(self):
        text = "\n" + json.dumps({"kind": "quorum"}) + "\n\n" + \
            json.dumps({"kind": "heal"}) + "\n   \n"
        assert [e["kind"] for e in parse_history(text)] == ["quorum", "heal"]

    def test_fold_covers_every_field(self):
        summary = history_fold(_HISTORY_EVENTS)
        assert summary["count"] == 8
        assert summary["kinds"] == {
            "quorum": 2, "heal": 1, "straggler_warn": 1, "eject": 1,
            "readmit": 1, "telemetry": 1, "unknown": 1,
        }
        assert summary["replicas"] == ["r0", "r1", "r2"]
        assert summary["quorum_transitions"] == 2
        assert summary["last_quorum_id"] == 2
        assert summary["heals"] == 1
        assert summary["ejections"] == 1
        assert summary["readmissions"] == 1
        assert summary["warns"] == 1
        assert summary["max_step"] == 7
        assert summary["first_ts_ms"] == 1000
        assert summary["last_ts_ms"] == 5000

    def test_native_replay_matches_python_fold(self):
        """Parity pin: tft_history_replay (native/history.cc) and the
        canonical Python fold must agree field-for-field on the same
        JSONL — same convention as the healthwatch replay hooks."""
        from torchft_tpu import coordination

        text = "\n".join(json.dumps(e) for e in _HISTORY_EVENTS) + "\n\n"
        native = coordination.history_replay(text)
        assert native["summary"] == history_fold(parse_history(text))
        assert len(native["events"]) == len(_HISTORY_EVENTS)


# ---------------------------------------------------------------------- CLI
class TestTraceCLI:
    @pytest.mark.parametrize("argv", [
        [], ["merge"], ["merge", "out.json"], ["history"],
        ["history", "a", "b"], ["bogus"],
    ])
    def test_usage(self, argv, capsys):
        assert trace_cli.main(argv) == 2
        assert "usage:" in capsys.readouterr().err

    def test_merge_writes_chrome_trace(self, tmp_path, capsys):
        paths = []
        for rid in ("r0", "r1"):
            rec = SpanRecorder(rid, _cfg())
            rec.set_context(quorum_id=1, step=1)
            rec.instant("tick", cat="quorum")
            paths.append(str(rec.dump(tmp_path / f"{rid}.json")))
        out = tmp_path / "fleet.json"
        assert trace_cli.main(["merge", str(out), *paths]) == 0
        assert "merged 2 replica dumps" in capsys.readouterr().out
        trace = json.loads(out.read_text())
        rids = {e["args"]["replica_id"] for e in trace["traceEvents"]
                if e["ph"] == "X"}
        assert rids == {"r0", "r1"}

    def test_history_prints_fold(self, tmp_path, capsys):
        p = tmp_path / "history.jsonl"
        p.write_text("\n".join(json.dumps(e) for e in _HISTORY_EVENTS))
        assert trace_cli.main(["history", str(p)]) == 0
        printed = json.loads(capsys.readouterr().out)
        assert printed == history_fold(_HISTORY_EVENTS)


# ------------------------------------------------- live endpoints + history
def test_manager_and_lighthouse_metrics_serve_prometheus(tmp_path):
    """Acceptance: both /metrics endpoints serve valid Prometheus text
    (parsed in-test), and the lighthouse's recorded-history JSONL replays
    through the native read path with Python parity."""
    from torchft_tpu import coordination
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.process_group import ProcessGroupHost

    hist_path = tmp_path / "history.jsonl"
    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20, heartbeat_timeout_ms=2000,
        history_path=str(hist_path),
    )
    manager = Manager(
        pg=ProcessGroupHost(timeout=10.0),
        load_state_dict=lambda sd: None,
        state_dict=lambda: {"w": np.zeros(4, np.float32)},
        min_replica_size=1,
        replica_id="metrics_probe",
        lighthouse_addr=f"127.0.0.1:{lh.port}",
        timeout=10.0,
        heartbeat_interval=0.05,
        tracing=True,
        metrics_port=0,
    )
    try:
        for _ in range(3):
            manager.start_quorum()
            manager.allreduce(
                {"w": np.ones(4, np.float32)}
            ).get_future().wait(30)
            manager.should_commit()

        with urllib.request.urlopen(
            f"http://127.0.0.1:{manager.metrics_port}/metrics", timeout=5.0
        ) as resp:
            mgr_series = _parse_prometheus(resp.read().decode())
        names = _bare_names(mgr_series)
        assert mgr_series["torchft_manager_step"] >= 3
        assert mgr_series["torchft_manager_commits_total"] >= 1
        assert mgr_series["torchft_manager_trace_spans_total"] > 0
        assert "torchft_manager_dropped_events_total" in names
        assert "torchft_manager_clock_skew_ms" in names
        # at least one phase histogram filled at _record_timing write time
        assert any(n.startswith("torchft_manager_")
                   and n.endswith("_seconds_bucket") for n in names), names

        with urllib.request.urlopen(
            f"http://127.0.0.1:{lh.port}/metrics", timeout=5.0
        ) as resp:
            lh_series = _parse_prometheus(resp.read().decode())
        lh_names = _bare_names(lh_series)
        assert lh_series["torchft_lighthouse_fleet_size"] >= 1
        assert "torchft_lighthouse_quorum_id" in lh_names
        assert "torchft_lighthouse_heartbeat_age_ms" in lh_names
        assert lh_series["torchft_lighthouse_history_events_total"] >= 1
    finally:
        manager.shutdown(wait=False)
        lh.shutdown()

    # the history the live lighthouse recorded replays with native parity
    text = hist_path.read_text()
    events = parse_history(text)
    assert any(e.get("kind") == "quorum" for e in events), events
    native = coordination.history_replay(text)
    assert native["summary"] == history_fold(events)
    assert native["summary"]["quorum_transitions"] >= 1


def test_manager_survives_metrics_port_in_use(tmp_path):
    """An observability knob must never take down training: with
    TORCHFT_METRICS_PORT fixed and >1 Manager per host (multiple group
    ranks, or a restart racing TIME_WAIT), the second bind raises
    EADDRINUSE — the Manager must warn and run without /metrics, not
    crash at startup."""
    import socket

    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.process_group import ProcessGroupHost

    blocker = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    taken_port = blocker.getsockname()[1]

    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20, heartbeat_timeout_ms=2000,
    )
    manager = None
    try:
        manager = Manager(
            pg=ProcessGroupHost(timeout=10.0),
            load_state_dict=lambda sd: None,
            state_dict=lambda: {"w": np.zeros(4, np.float32)},
            min_replica_size=1,
            replica_id="metrics_port_clash",
            lighthouse_addr=f"127.0.0.1:{lh.port}",
            timeout=10.0,
            heartbeat_interval=0.05,
            metrics_port=taken_port,
        )
        assert manager.metrics_port is None
        # the Manager still trains: one managed step end to end
        manager.start_quorum()
        manager.allreduce(
            {"w": np.ones(4, np.float32)}
        ).get_future().wait(30)
        assert manager.should_commit()
    finally:
        if manager is not None:
            manager.shutdown(wait=False)
        lh.shutdown()
        blocker.close()


# --------------------------------------------------------------- acceptance
def test_fleet_chaos_merge_produces_skew_corrected_timeline(tmp_path):
    """3-replica run with one mid-collective link kill (reroute) and one
    injected step corruption (False vote -> discarded step -> live heal),
    under +/-1.5s injected clock offsets; the per-replica dumps merged via
    the real CLI must show the heal spans and the discarded commit vote on
    a timeline where cross-replica spans of the same step line up."""
    from torchft_tpu._test.event_injector import EventInjector
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.process_group import ProcessGroupHost

    n_replicas = 3
    rounds = 8
    kill_step = 3
    error_step = 5
    victim = 2
    victim_rid = f"tracefleet_{victim}"

    injector = EventInjector().kill_link(0, 1, step=kill_step, at_hop=1)
    # replicas 0/1 run on clocks 1.5s fast/slow; the victim keeps true time
    injector.skew_clock("tracefleet_0", 1500.0)
    injector.skew_clock("tracefleet_1", -1500.0)

    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=n_replicas, join_timeout_ms=5000,
        quorum_tick_ms=20, heartbeat_timeout_ms=5000,
    )
    barrier = threading.Barrier(n_replicas)
    finals: dict = {}
    reroutes: dict = {}
    healed_steps: dict = {}
    dump_paths: dict = {}
    failure: list = []

    def replica(rid: int) -> None:
        grad_base = np.random.RandomState(40 + rid).randn(1024).astype(
            np.float32
        )
        params = {"w": np.zeros(1024, np.float32)}

        def load(sd):
            params["w"] = np.array(np.asarray(sd["w"]), dtype=np.float32)

        pg = ProcessGroupHost(timeout=30.0)
        manager = Manager(
            pg=pg,
            load_state_dict=load,
            state_dict=lambda: {"w": params["w"].copy()},
            min_replica_size=n_replicas,
            use_async_quorum=False,
            replica_id=f"tracefleet_{rid}",
            lighthouse_addr=f"127.0.0.1:{lh.port}",
            timeout=30.0,
            quorum_timeout=30.0,
            # multi-leaf tree + small cap -> multi-bucket streaming plan,
            # the path the link kill reroutes
            bucket_cap_bytes=1024,
            compress="fp8",
            tracing=True,
        )
        try:
            for _ in range(rounds):
                barrier.wait(timeout=120)
                manager.start_quorum()
                if manager.last_quorum_healed():
                    healed_steps[rid] = manager.current_step()
                step = manager.current_step()
                injector.check(rid, step, pg=pg)
                g = (grad_base * (1.0 + 0.01 * step)).astype(np.float32)
                grads = {"a": g[:512].copy(), "b": g[512:].copy()}
                avg = manager.allreduce(grads).get_future().wait(60)
                if rid == victim and step == error_step:
                    # corrupt THIS step only: the vote discards it, the
                    # next quorum live-heals the replica back to the fleet
                    manager.report_error(
                        RuntimeError("injected step corruption")
                    )
                if manager.should_commit():
                    flat = np.concatenate(
                        [np.asarray(avg["a"]), np.asarray(avg["b"])]
                    ).astype(np.float32)
                    params["w"] = (params["w"] - LR * flat).astype(
                        np.float32
                    )
            finals[rid] = params["w"].copy()
            reroutes[rid] = manager.timings().get("collective_reroute", 0.0)
            dump_paths[rid] = manager.dump_trace(
                tmp_path / f"dump_{rid}.json"
            )
        except BaseException as e:  # noqa: BLE001
            failure.append(e)
            raise
        finally:
            manager.shutdown(wait=False)

    ex = ThreadPoolExecutor(max_workers=n_replicas)
    try:
        futs = [ex.submit(replica, r) for r in range(n_replicas)]
        for f in futs:
            f.result(timeout=240)
    finally:
        ex.shutdown(wait=False, cancel_futures=True)
        lh.shutdown()
        injector.clear_clock_skew()

    assert not failure, failure
    assert set(finals) == set(range(n_replicas)), finals.keys()

    # both chaos events actually happened
    assert sum(reroutes.values()) >= 1, reroutes
    assert victim in healed_steps, (
        "the corrupted replica never live-healed", healed_steps
    )
    # the heal restored lockstep: every replica ends bitwise-identical
    for rid in range(1, n_replicas):
        np.testing.assert_array_equal(
            finals[0], finals[rid],
            err_msg=f"replica {rid} diverged across discard+heal",
        )
    assert np.isfinite(finals[0]).all()

    # --- merge through the real CLI entry point
    assert all(dump_paths.get(r) is not None for r in range(n_replicas))
    out = tmp_path / "fleet.json"
    rc = trace_cli.main(
        ["merge", str(out)] + [str(dump_paths[r]) for r in range(n_replicas)]
    )
    assert rc == 0
    trace = json.loads(out.read_text())
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    assert evs and all(e["ph"] in ("X", "M") for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    procs = [e for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert len(procs) == n_replicas

    # the control-plane taxonomy is present
    names = {e["name"] for e in xs}
    assert {"quorum_rpc", "commit_vote"} <= names, names

    # heal spans: the victim's receive leg must be on the timeline
    heal_spans = [e for e in xs if e["cat"] == "heal"]
    assert any(
        e["name"] == "heal_recv"
        and e["args"]["replica_id"].startswith(victim_rid)
        for e in heal_spans
    ), heal_spans

    # the victim's discarded step is visible: its commit vote at the
    # corrupted step went False while the peers' votes stayed True
    votes = [e for e in xs if e["name"] == "commit_vote"]
    assert any(
        e["args"]["replica_id"].startswith(victim_rid)
        and e["args"].get("local") is False
        and e["args"].get("step") == error_step
        for e in votes
    ), votes
    assert any(
        not e["args"]["replica_id"].startswith(victim_rid)
        and e["args"].get("local") is True
        and e["args"].get("step") == error_step
        for e in votes
    ), votes

    # skew correction: replicas 0 (+1.5s clock) and 1 (-1.5s clock) enter
    # every quorum together (barrier + min_replicas), so their quorum_rpc
    # spans of the same step must line up on the corrected timeline even
    # though their raw stamps disagree by ~3s
    raw = {}
    for rid in (0, 1):
        d = json.loads(dump_paths[rid].read_text())
        assert abs(d["skew_ms"] - (1500.0 if rid == 0 else -1500.0)) < 500.0
        raw[rid] = {
            s["step"]: s["ts_us"] for s in reversed(d["spans"])
            if s["name"] == "quorum_rpc" and s["step"] is not None
        }
    corrected = {0: {}, 1: {}}
    for e in xs:
        if e["name"] != "quorum_rpc" or e["args"]["step"] is None:
            continue
        for rid in (0, 1):
            if e["args"]["replica_id"].startswith(f"tracefleet_{rid}:"):
                corrected[rid].setdefault(e["args"]["step"], e["ts"])
    common = sorted(set(corrected[0]) & set(corrected[1]))
    assert common, (corrected, "no common quorum_rpc steps")
    for s in common:
        assert raw[0][s] - raw[1][s] > 1_500_000, (
            s, raw, "raw clocks should disagree by ~3s"
        )
        assert abs(corrected[0][s] - corrected[1][s]) < 1_000_000, (
            s, corrected, "corrected timeline did not line up"
        )


@pytest.fixture(scope="module")
def profiled_trainer(tmp_path_factory):
    """The managed trainer (examples/train_llama_hsdp.py under a lighthouse,
    three tiny steps on the CPU, no benchmark harness) under a
    ``jax.profiler.trace``, its span ring dumped as its Manager shuts down:
    ``(finished process, profile directory, the ring's dump)``."""
    import os
    import subprocess
    import sys

    from torchft_tpu.coordination import LighthouseServer

    tmp_path = tmp_path_factory.mktemp("profiled")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    trainer = os.path.join(root, "examples", "train_llama_hsdp.py")
    script = tmp_path / "profiled.py"
    script.write_text(
        "import runpy, sys\n"
        "import jax\n"
        "from torchft_tpu.manager import Manager\n"
        "out, trainer = sys.argv[1], sys.argv[2]\n"
        "sys.argv = [trainer, *sys.argv[3:]]\n"
        "shutdown = Manager.shutdown\n"
        "def dump_then_shutdown(self, *a, **kw):\n"
        "    self.dump_trace(out + '.ring.json')\n"
        "    return shutdown(self, *a, **kw)\n"
        "Manager.shutdown = dump_then_shutdown\n"
        "opts = jax.profiler.ProfileOptions()\n"
        "opts.python_tracer_level = 0\n"
        "jax.profiler.start_trace(out, profiler_options=opts)\n"
        "try:\n"
        "    runpy.run_path(trainer, run_name='__main__')\n"
        "finally:\n"
        "    jax.profiler.stop_trace()\n"
    )
    lh = LighthouseServer(bind="127.0.0.1:0", min_replicas=1,
                          join_timeout_ms=200, quorum_tick_ms=20)
    addr = f"127.0.0.1:{lh.port}"
    try:
        proc = subprocess.run(
            [sys.executable, str(script), str(tmp_path / "prof"), trainer,
             "--config", "tiny", "--steps", "3", "--batch-size", "2",
             "--seq-len", "32", "--virtual-chips", "1"],
            capture_output=True, text=True, timeout=300,
            env=dict(os.environ, TORCHFT_LIGHTHOUSE=addr, REPLICA_GROUP_ID="0",
                     JAX_PLATFORMS="cpu", PYTHONPATH=root,
                     # several buckets a step, like the real sizes have
                     TORCHFT_BUCKET_CAP_MB="0.5"),
        )
    finally:
        lh.shutdown()
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    with open(str(tmp_path / "prof") + ".ring.json") as f:
        ring = json.load(f)
    return proc, tmp_path / "prof", ring


def test_the_managed_trainer_leaves_the_devices_chain_in_its_ring(profiled_trainer):
    """What the device finished, and when, in a step of the real trainer
    (two ops a step at the tiny preset): one ``device/forward``, one
    ``device/backward``, a ``device/landed`` a bucket and one
    ``device/update``, in that order on the clock, and each inside its
    ``trainer/step`` by its ancestors (the update's end is the device's, and
    may lie past the step's: the loop does not wait for it)."""
    _proc, _prof, ring = profiled_trainer
    spans = ring["spans"]
    by_id = {s["id"]: s for s in spans}
    dev = [s for s in spans if s["cat"] == "device"]
    steps = [s for s in spans if (s["cat"], s["name"]) == ("trainer", "step")]
    assert len(steps) == 3

    def step_of(s):
        while s is not None and (s["cat"], s["name"]) != ("trainer", "step"):
            s = by_id.get(s["parent"])
        return s

    for n, step in enumerate(sorted(steps, key=lambda s: s["ts_us"])):
        # in the order the watcher recorded them: that of registration
        mine = sorted((s for s in dev if step_of(s) is step),
                      key=lambda s: s["id"])
        buckets = [s for s in spans if s["name"] == "unpack"
                   and step_of(s) is step]
        assert len(buckets) >= 4
        chain = ["forward", "backward"] + ["landed"] * len(buckets) + ["update"]
        assert sorted(s["name"] for s in mine) == sorted(chain)
        assert [s["ts_us"] + s["dur_us"] for s in mine] == sorted(
            s["ts_us"] + s["dur_us"] for s in mine)
        if n == 0:
            # a compile between two dispatches: the head's buckets may land
            # before the layers' program is even enqueued
            continue
        assert [s["name"] for s in mine] == chain
        fwd, bwd, upd = mine[0], mine[1], mine[-1]
        assert fwd["ts_us"] >= step["ts_us"]
        assert bwd["ts_us"] == fwd["ts_us"] + fwd["dur_us"]  # a chain
        assert upd["ts_us"] >= bwd["ts_us"] + bwd["dur_us"]
        assert all(s["dur_us"] == 1 for s in mine[2:-1])
        # the registering step: the update follows the commit
        assert {s["step"] for s in mine[:-1]} == {step["step"]}
        assert upd["step"] == step["step"] + 1
    assert len(dev) == sum(len([s for s in dev if step_of(s) is st])
                           for st in steps)
    assert ring["dropped"] == 0


def test_profiler_trace_of_the_managed_trainer_holds_the_programs_spans(
    profiled_trainer,
):
    """The program's spans are in the profiler's own trace: a
    ``jax.profiler.trace`` over the managed trainer holds the bucket
    pipeline's and the trainer's annotations under the names the benchmark
    gives the ring's spans. The same run's SUMMARY carries the start-up and
    first-step timings, and its ring dropped nothing at the default
    buffer."""
    from jax.profiler import ProfileData

    proc, prof, _ring = profiled_trainer
    (pb,) = prof.glob("plugins/profile/*/*.xplane.pb")
    names = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("manager."):
                        names[e.name] = names.get(e.name, 0) + 1
    assert names["manager.trainer.step"] == 3
    assert names["manager.trainer.loss_fetch"] == 3
    assert names["manager.allreduce.d2h"] >= 2 * 3      # per bucket, per step
    assert names["manager.allreduce.d2h"] == names["manager.allreduce.h2d"] \
        == names["manager.allreduce.dispatch"] == names["manager.allreduce.divide"]
    for n in ("manager.allreduce.capture", "manager.allreduce.grad_wait",
              "manager.quorum.quorum_rpc", "manager.commit.commit_vote",
              "manager.commit.should_commit", "manager.quorum.async_quorum",
              "manager.trainer.grad_dispatch", "manager.trainer.allreduce_wait",
              "manager.trainer.update"):
        assert names.get(n, 0) >= 3, (n, names)
    assert not any("codec" in n or "decode" in n for n in names)   # no compress mode
    line = next(ln for ln in proc.stdout.splitlines() if " SUMMARY " in ln)
    timings = json.loads(line.split(" SUMMARY ", 1)[1])["timings"]
    assert timings["trace_dropped"] == 0.0
    for key in ("startup_spawn_to_main_s", "startup_imports_s",
                "startup_backend_init_s", "startup_state_init_s",
                "startup_manager_init_s", "first_step_compile_s"):
        assert timings[key] >= 0.0, key
    assert timings["startup_imports_s"] > 0 and timings["first_step_compile_s"] > 0
