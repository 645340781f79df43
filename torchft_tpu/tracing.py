"""Fleet tracing plane: per-manager span recorder, skew-corrected
Chrome-trace merge, and the recorded-history fold.

The repo's telemetry was per-replica (Manager.timings(), flight-recorder
breadcrumbs, /health) — useful for one process, useless for "which replica
stalled bucket 7 of step 412" across a fleet. This module closes that gap:

- :class:`SpanRecorder` — a bounded ring buffer of structured spans the
  Manager records around its control-plane and wire phases (quorum /
  prepare / commit, per-bucket pack / wire / unpack, heal chunks, RPC
  retries, reroutes). Every span carries ``(quorum_id, step)`` and the
  recorder's ``replica_id``, so spans from different replicas of the same
  step correlate without a global clock, and ``id`` / ``parent`` so one
  step's spans form a tree (a thread-local stack gives the parent on one
  thread; across threads the parent's id travels in the closure).
  Recording is an O(1) tuple append behind one lock — cheap enough to stay
  on by default (PERF.md section 6, PR 24 and PR 37: ``TORCHFT_TRACE=0``
  against the default on the chip). A context span also enters a
  ``jax.profiler.TraceAnnotation`` named ``manager.<cat>.<name>``, so the
  program's spans lie in a ``jax.profiler.trace`` on the device trace's
  own clock; free while no profiler session runs, and this module never
  imports jax itself (a process without jax records to the ring alone).
  :meth:`SpanRecorder.when_ready` adds what no host thread's work shows:
  the moment the DEVICE finished something, taken by one watcher thread a
  recorder that blocks on the registered handles in the order they were
  registered, each span reaching from the handle before it.
- **Skew correction** — each export stamps the replica's clock-skew
  estimate vs the lighthouse (``ManagerServer.clock_skew()``: the beat
  loop's RPC round-trip midpoint minus the response ``server_ms`` —
  replica-minus-lighthouse, positive when this clock runs ahead; best
  = minimum-RTT sample). :func:`merge_traces` shifts every replica onto
  the lighthouse's clock, so cross-replica ordering is correct within the
  estimated-skew bound (~RTT/2 on a quiet network).
- :func:`merge_traces` / ``python -m torchft_tpu.trace merge`` — N span
  dumps in, one Chrome-trace JSON out (load in Perfetto or
  chrome://tracing): one process row per replica, one thread row per span
  category.
- :func:`history_fold` — the canonical Python fold over the lighthouse's
  recorded-history JSONL (quorum transitions / heals / health events /
  telemetry snapshots). The native read path ``tft_history_replay``
  (coordination.history_replay) computes the SAME summary; parity is
  pinned by test, same convention as the healthwatch replay hooks. This
  is the replay substrate the ROADMAP's adaptive policy engine consumes.

Env knobs (read once per Manager via :meth:`TraceConfig.from_env`):

- ``TORCHFT_TRACE``: ``1``/``0`` — master switch (default on).
- ``TORCHFT_TRACE_BUFFER``: ring capacity in spans (default 65536: the
  densest benchmark cell's 48 s window eight times over; a full ring is
  tens of MB, CHANGES.md, PR 37).
- ``TORCHFT_TRACE_DIR``: auto-dump directory; empty falls back next to
  the flight-recorder dump path (``TORCHFT_FR_BASE_PATH``).
"""

from __future__ import annotations

import itertools
import json
import os
import queue
import sys
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Deque, Dict, Iterable, List, Optional, Tuple

TRACE_ENV = "TORCHFT_TRACE"
TRACE_BUFFER_ENV = "TORCHFT_TRACE_BUFFER"
TRACE_DIR_ENV = "TORCHFT_TRACE_DIR"

_DEFAULT_BUFFER = 1 << 16

__all__ = [
    "TraceConfig",
    "SpanRecorder",
    "merge_traces",
    "history_fold",
    "parse_history",
    "set_clock_offset_ms",
    "clear_clock_offsets",
    "process_start_us",
]


# --------------------------------------------------------------- test hooks
# Injected per-replica clock offsets (event_injector.skew_clock): shifts the
# recorder's own clock, which self-consistently shifts its estimated skew vs
# the lighthouse by the same amount — exactly what a genuinely skewed host
# looks like, so the merge-corrects-ordering test exercises the real path.
_clock_offsets: Dict[str, float] = {}
_clock_offsets_lock = threading.Lock()


def set_clock_offset_ms(replica_id: str, offset_ms: float) -> None:
    """TEST ONLY: pretend ``replica_id``'s wall clock runs ``offset_ms``
    ahead of true time (matched exactly or by prefix, like
    ``slow_replica``)."""
    with _clock_offsets_lock:
        _clock_offsets[replica_id] = float(offset_ms)


def clear_clock_offsets() -> None:
    with _clock_offsets_lock:
        _clock_offsets.clear()


def _offset_ms_for(replica_id: str) -> float:
    with _clock_offsets_lock:
        if not _clock_offsets:
            return 0.0
        if replica_id in _clock_offsets:
            return _clock_offsets[replica_id]
        for key, off in _clock_offsets.items():
            if replica_id.startswith(key):
                return off
    return 0.0


# ------------------------------------------------------------------- config
@dataclass
class TraceConfig:
    enabled: bool = True
    buffer: int = _DEFAULT_BUFFER
    dump_dir: str = ""

    @classmethod
    def from_env(cls) -> "TraceConfig":
        cfg = cls()
        cfg.enabled = os.environ.get(TRACE_ENV, "1").strip() not in (
            "0", "off", "false", "no",
        )
        try:
            cfg.buffer = max(16, int(os.environ.get(TRACE_BUFFER_ENV, "")))
        except ValueError:
            cfg.buffer = _DEFAULT_BUFFER
        cfg.dump_dir = os.environ.get(TRACE_DIR_ENV, "")
        return cfg


# ----------------------------------------------------------------- recorder
_trace_annotation: Any = None  # jax.profiler.TraceAnnotation once jax is loaded
_CURRENT = object()  # _append's default step: the recorder's context now


def _annotation(cat: str, name: str) -> Any:
    """An entered ``jax.profiler.TraceAnnotation`` ``manager.<cat>.<name>``
    (a TraceMe: a flag test while no profiler session runs), or None in a
    process that has not loaded jax — this module is never the one to
    import it."""
    global _trace_annotation
    if _trace_annotation is None:
        if "jax" not in sys.modules:
            return None
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # noqa: BLE001 — a broken jax must not break spans
            _trace_annotation = False
            return None
        _trace_annotation = TraceAnnotation
    if _trace_annotation is False:
        return None
    ann = _trace_annotation(f"manager.{cat}.{name}")
    ann.__enter__()
    return ann


def process_start_us() -> Optional[int]:
    """Epoch microseconds at which the kernel created this process, from
    ``/proc/self/stat`` (start time in clock ticks since boot) against the
    boot-time clock; None where ``/proc`` does not say. Resolution is one
    clock tick (10 ms)."""
    try:
        with open("/proc/self/stat") as f:
            # the command name may hold spaces: fields count from the ")"
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_s = time.clock_gettime(time.CLOCK_BOOTTIME) - (
            start_ticks / os.sysconf("SC_CLK_TCK")
        )
        return time.time_ns() // 1000 - int(age_s * 1e6)
    except (OSError, ValueError, IndexError, AttributeError):
        return None


class _NullSpan:
    """What a disabled recorder hands out: nothing recorded, nothing
    annotated, ``args`` writable so call sites never branch."""

    __slots__ = ("args",)
    id = None

    def __init__(self) -> None:
        self.args: dict = {}

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


class _SpanHandle:
    """Context manager for an in-progress span; records on exit. ``id`` is
    known from construction (hand it to another thread as ``parent=``);
    ``args`` may be filled in until exit (``bytes`` known only after the
    copy)."""

    __slots__ = ("_rec", "name", "cat", "args", "id", "parent",
                 "_t0_us", "_t0_pc", "_ann", "_step")

    def __init__(self, rec: "SpanRecorder", name: str, cat: str, args: dict,
                 parent: Optional[int]):
        self._rec = rec
        self.name = name
        self.cat = cat
        self.args = args
        self.id = rec.new_id()
        self.parent = parent

    def __enter__(self) -> "_SpanHandle":
        stack = self._rec._stack()
        if self.parent is None and stack:
            self.parent = stack[-1]
        stack.append(self.id)
        # a span belongs to the step it STARTED in: trainer/step opens
        # before the commit advances the Manager's step and closes after
        self._step = self._rec._step
        self._ann = _annotation(self.cat, self.name)
        self._t0_us = self._rec._now_us()
        self._t0_pc = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dur_us = int((time.perf_counter() - self._t0_pc) * 1e6)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._rec._stack().pop()
        self._rec._append(
            self.name, self.cat, self._t0_us, max(dur_us, 1), self.args,
            self.id, self.parent, self._step,
        )


# jax.monitoring duration events recorded as ``compile/<name>`` spans
_COMPILE_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_compile_watchers: "weakref.WeakSet[SpanRecorder]" = weakref.WeakSet()
_compile_listener_lock = threading.Lock()
_compile_listener_on = False


def _watch_compiles(rec: "SpanRecorder") -> None:
    """Feed ``rec`` the process's backend compiles and compilation-cache
    retrievals. ONE ``jax.monitoring`` listener per process, installed with
    the first enabled recorder that finds jax loaded (so never in a process
    without a Manager, and never the reason jax is imported); every live
    enabled recorder hears it."""
    global _compile_listener_on
    with _compile_listener_lock:
        _compile_watchers.add(rec)
        if _compile_listener_on or "jax" not in sys.modules:
            return
        try:
            from jax import monitoring
        except Exception:  # noqa: BLE001
            return

        def _on_duration(event: str, duration_secs: float, **kw: Any) -> None:
            name = _COMPILE_EVENTS.get(event)
            if name is None:
                return
            for r in list(_compile_watchers):
                r._on_compile(name, duration_secs, kw.get("fun_name"))

        monitoring.register_event_duration_secs_listener(_on_duration)
        _compile_listener_on = True


# a span's keys in a dump, in the order its tuple in the ring holds them;
# the tuple's last element is ``args`` (a dump has the key only if non-empty)
_SPAN_KEYS = ("name", "cat", "ts_us", "dur_us", "quorum_id", "step", "id",
              "parent")


class SpanRecorder:
    """Bounded ring of structured spans for ONE replica.

    Thread-safe; every mutator is a no-op when disabled, so Manager call
    sites never branch. Timestamps are epoch microseconds from the local
    wall clock (plus any injected test offset); the skew estimate stamped
    into :meth:`export` is what lets the merger move them onto the
    lighthouse's clock.
    """

    def __init__(
        self,
        replica_id: str,
        config: Optional[TraceConfig] = None,
    ) -> None:
        self._replica_id = replica_id
        self._config = config if config is not None else TraceConfig.from_env()
        # a span is a tuple in the order of _SPAN_KEYS with its args dict
        # (or None) last: 400-420 bytes a span at a full ring where a dict
        # of the keys took 605; export() gives it its keys back
        self._spans: Deque[Tuple[Any, ...]] = deque(maxlen=self._config.buffer)
        self._lock = threading.Lock()
        self._quorum_id: Optional[int] = None
        self._step: Optional[int] = None
        self._skew_ms = 0.0
        self._rtt_ms = 0.0
        self._skew_samples = 0
        self._dropped = 0
        self._recorded = 0
        self._ids = itertools.count(1)
        self._tls = threading.local()  # per-thread stack of open span ids
        # seconds of backend compiles this process has made since the
        # recorder was built (compile_total_s())
        self._compile_s = 0.0
        # when_ready: the handles the watcher thread has not taken yet, and
        # the thread, started with the first of them
        self._watched: "queue.SimpleQueue[Any]" = queue.SimpleQueue()
        self._watcher: Optional[threading.Thread] = None
        self._closed = False
        if self._config.enabled:
            _watch_compiles(self)

    @property
    def enabled(self) -> bool:
        return self._config.enabled

    @property
    def replica_id(self) -> str:
        return self._replica_id

    # ------------------------------------------------------------- context
    def set_context(
        self,
        quorum_id: Optional[int] = None,
        step: Optional[int] = None,
    ) -> None:
        """Update the ``(quorum_id, step)`` stamped into subsequent spans."""
        with self._lock:
            if quorum_id is not None:
                self._quorum_id = quorum_id
            if step is not None:
                self._step = step

    def set_skew(
        self, skew_ms: float, rtt_ms: float = 0.0, samples: int = 0
    ) -> None:
        """Feed the latest heartbeat-derived skew estimate
        (``ManagerServer.clock_skew()``). An injected test clock offset
        shifts the estimate too — a host whose clock runs fast is fast in
        both its span stamps and its measured skew."""
        with self._lock:
            self._skew_ms = float(skew_ms)
            self._rtt_ms = float(rtt_ms)
            self._skew_samples = int(samples)

    # ----------------------------------------------------------- recording
    def _now_us(self) -> int:
        off = _offset_ms_for(self._replica_id)
        return time.time_ns() // 1000 + int(off * 1000)

    def new_id(self) -> int:
        """A span id of this recorder, for a span that will be recorded
        after the fact (``record`` / ``record_rel`` with ``id=``) and whose
        children need it as ``parent=`` before then."""
        return next(self._ids)

    def current(self) -> Optional[int]:
        """Id of the innermost span open on THIS thread, or None: the
        parent of whatever this thread is about to cause on another."""
        stack = self._stack()
        return stack[-1] if stack else None

    def _stack(self) -> List[int]:
        try:
            return self._tls.stack
        except AttributeError:
            self._tls.stack = []
            return self._tls.stack

    def _append(
        self, name: str, cat: str, ts_us: int, dur_us: int, args: dict,
        span_id: Optional[int] = None, parent: Optional[int] = None,
        step: Any = _CURRENT,
    ) -> None:
        if not self._config.enabled:
            return
        with self._lock:
            if len(self._spans) == self._spans.maxlen:
                self._dropped += 1
            self._recorded += 1
            self._spans.append((
                name, cat, ts_us, dur_us, self._quorum_id,
                self._step if step is _CURRENT else step,
                span_id if span_id is not None else next(self._ids),
                parent, args or None,
            ))

    def span(
        self,
        name: str,
        cat: str = "step",
        parent: Optional[int] = None,
        **args: Any,
    ) -> "_SpanHandle | _NullSpan":
        """``with tracer.span("quorum_rpc", cat="quorum"): ...`` — a ring
        span and, where jax is loaded, a profiler annotation
        ``manager.<cat>.<name>`` around the same statements. ``parent``:
        the id of the span that caused this one when that span is open on
        ANOTHER thread (same thread: the innermost open span, by itself)."""
        if not self._config.enabled:
            return _NullSpan()
        return _SpanHandle(self, name, cat, args, parent)

    def record(
        self,
        name: str,
        cat: str,
        t0_us: int,
        t1_us: int,
        id: Optional[int] = None,  # noqa: A002 — the span's key in the dump
        parent: Optional[int] = None,
        **args: Any,
    ) -> None:
        """Record a completed interval given absolute epoch-us endpoints
        (ring only: the profiler takes no span after the fact)."""
        self._append(
            name, cat, int(t0_us), max(int(t1_us - t0_us), 1), args, id, parent
        )

    def record_rel(
        self,
        name: str,
        cat: str,
        t0_pc: float,
        t1_pc: float,
        id: Optional[int] = None,  # noqa: A002
        parent: Optional[int] = None,
        **args: Any,
    ) -> None:
        """Record a completed interval given ``time.perf_counter()``
        endpoints (the pipeline marks' native form): anchored to the wall
        clock at call time, so recently-finished intervals land within
        scheduler noise of their true wall positions."""
        anchor_us = self._now_us()
        anchor_pc = time.perf_counter()
        t0_us = anchor_us + int((t0_pc - anchor_pc) * 1e6)
        t1_us = anchor_us + int((t1_pc - anchor_pc) * 1e6)
        self._append(
            name, cat, t0_us, max(t1_us - t0_us, 1), args, id, parent
        )

    def instant(self, name: str, cat: str, **args: Any) -> None:
        """Zero-duration marker (RPC retry, reroute, heal chunk events)."""
        self._append(name, cat, self._now_us(), 1, args)

    def when_ready(
        self,
        name: str,
        cat: str,
        handle: Any,
        span: bool = True,
        parent: Optional[int] = None,
        **args: Any,
    ) -> None:
        """A milestone of the DEVICE: record ``cat/name`` once ``handle``
        (anything with ``block_until_ready()``, e.g. a jax.Array; its
        ``is_ready()`` is asked too where it has one) is ready. Returns at
        once: one daemon thread a recorder, started here at first use, takes
        the handles in the order they were registered and blocks on each, so
        register them in the order the device reaches them. The handles make
        a chain. ``span=True``: a ring span from the moment the handle
        registered BEFORE this one became ready, or from this registration
        if that was earlier still (nothing was pending: the device had
        nothing of ours to do), to this one's readiness: what the device did
        between the two. ``span=False``: an instant at readiness, a link of
        the chain all the same. Both carry the step and the span open on
        this thread (``parent`` overrides it) at REGISTRATION, ``waited_us``
        (how long the watcher blocked) and ``late`` (1: the handle was ready
        when the watcher reached it, so the stamp is an upper bound: the
        order of registration was not the device's, or the watcher was
        behind). A handle that raises (a donated array, a failed program) is
        dropped and the chain starts anew. The watcher lets go of a handle
        the moment it is ready. A disabled recorder keeps no reference and
        starts no thread."""
        if not self._config.enabled:
            return
        if parent is None:
            parent = self.current()
        with self._lock:
            if self._closed:
                return
            if self._watcher is None:
                self._watcher = threading.Thread(
                    target=self._watch, name="torchft_trace_watch", daemon=True
                )
                self._watcher.start()
            step = self._step
        self._watched.put(
            (name, cat, handle, span, parent, args, step, self._now_us())
        )

    def _watch(self) -> None:
        """The watcher thread: see :meth:`when_ready`."""
        ready_us: Optional[int] = None  # when the chain's last link was ready
        while True:
            item = self._watched.get()
            if item is None:
                return
            name, cat, handle, span, parent, args, step, registered_us = item
            del item
            try:
                late = int(getattr(handle, "is_ready", bool)())
                t0 = time.perf_counter()
                handle.block_until_ready()
                waited_us = int((time.perf_counter() - t0) * 1e6)
            except Exception:  # noqa: BLE001 — whatever the handle raises
                ready_us = None
                continue
            finally:
                del handle
            now_us = self._now_us()
            args.update(waited_us=waited_us, late=late)
            if not span:
                start_us = now_us
            elif ready_us is None or ready_us < registered_us:
                start_us = registered_us
            else:
                start_us = ready_us
            self._append(
                name, cat, start_us, max(now_us - start_us, 1), args, None,
                parent, step,
            )
            ready_us = now_us

    def close(self) -> None:
        """End the watcher thread (``Manager.shutdown``). Handles it has
        not taken are dropped unrecorded, and :meth:`when_ready` registers
        nothing from here on; the ring and every other method stay."""
        with self._lock:
            self._closed = True
            watcher, self._watcher = self._watcher, None
        if watcher is None:
            return
        try:
            while True:
                self._watched.get_nowait()
        except queue.Empty:
            pass
        self._watched.put(None)
        # the handle it may be blocked on is the device's to finish: a
        # daemon thread, not waited for beyond a moment
        watcher.join(timeout=1.0)

    def _on_compile(self, name: str, dur_s: float, fun: Any) -> None:
        """One backend compile or cache retrieval the process just ended
        (the ``jax.monitoring`` listener's thread: whichever dispatched the
        jitted call)."""
        if name == "backend_compile":
            with self._lock:
                self._compile_s += dur_s
        t1_us = self._now_us()
        args = {"fun": str(fun)} if fun is not None else {}
        # the listener runs on the thread that dispatched the jitted call:
        # the span open there (trainer/grad_dispatch, ...) is the cause
        self.record(
            name, "compile", t1_us - int(dur_s * 1e6), t1_us,
            parent=self.current(), **args,
        )

    def compile_total_s(self) -> float:
        """Seconds this process spent in backend compiles since this
        recorder was built. Each includes its persistent-cache lookup: the
        ``compile/cache_retrieval`` spans say how much of it was loading."""
        with self._lock:
            return self._compile_s

    # ------------------------------------------------------------- exports
    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "spans": float(len(self._spans)),
                "recorded": float(self._recorded),
                "dropped": float(self._dropped),
            }

    def export(self) -> Dict[str, Any]:
        """One replica's span dump: merge-ready, skew-stamped."""
        with self._lock:
            out = {
                "replica_id": self._replica_id,
                "clock": "epoch_us",
                "skew_ms": self._skew_ms + _offset_ms_for(self._replica_id),
                "rtt_ms": self._rtt_ms,
                "skew_samples": self._skew_samples,
                "dropped": self._dropped,
            }
            spans = list(self._spans)
        # the dump's format, outside the lock: recording goes on meanwhile
        out["spans"] = [
            dict(zip(_SPAN_KEYS, s), args=s[-1]) if s[-1]
            else dict(zip(_SPAN_KEYS, s))
            for s in spans
        ]
        return out

    def dump(self, path: "str | Path | None" = None) -> Optional[Path]:
        """Write :meth:`export` as JSON; never raises (dumps run on
        failure paths). Default location: ``TORCHFT_TRACE_DIR``, else next
        to the flight-recorder base path, else None (disabled)."""
        try:
            if path is None:
                base = self._config.dump_dir or os.environ.get(
                    "TORCHFT_FR_BASE_PATH", ""
                )
                if not base:
                    return None
                d = Path(base) if self._config.dump_dir else Path(
                    str(base) + "_traces"
                )
                d.mkdir(parents=True, exist_ok=True)
                path = d / f"trace_{self._replica_id}_{time.time_ns()}.json"
            path = Path(path)
            path.parent.mkdir(parents=True, exist_ok=True)
            with open(path, "w") as f:
                json.dump(self.export(), f)
            return path
        except Exception:  # noqa: BLE001 — observability must not raise
            return None


# -------------------------------------------------------------------- merge
def merge_traces(dumps: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge N replicas' span dumps into one Chrome-trace JSON dict.

    Each replica becomes a trace process (pid ordered by replica_id) and
    each span category a thread within it; every timestamp is shifted by
    ``-skew_ms`` onto the lighthouse's clock, so the same step's spans
    from different replicas line up within the skew-estimate error.
    Load the result in Perfetto / chrome://tracing.
    """
    events: List[Dict[str, Any]] = []
    ordered = sorted(dumps, key=lambda d: str(d.get("replica_id", "")))
    for pid, dump in enumerate(ordered):
        rid = str(dump.get("replica_id", f"replica_{pid}"))
        skew_us = float(dump.get("skew_ms", 0.0)) * 1000.0
        events.append({
            "name": "process_name",
            "ph": "M",
            "pid": pid,
            "tid": 0,
            "args": {
                "name": f"{rid} (skew {dump.get('skew_ms', 0.0):+.3f}ms)"
            },
        })
        tids: Dict[str, int] = {}
        for span in dump.get("spans", []):
            cat = str(span.get("cat", "step"))
            tid = tids.setdefault(cat, len(tids))
            args = dict(span.get("args", {}))
            args["quorum_id"] = span.get("quorum_id")
            args["step"] = span.get("step")
            args["replica_id"] = rid
            events.append({
                "name": str(span.get("name", "?")),
                "cat": cat,
                "ph": "X",
                "ts": float(span.get("ts_us", 0)) - skew_us,
                "dur": max(float(span.get("dur_us", 1)), 1.0),
                "pid": pid,
                "tid": tid,
                "args": args,
            })
        for cat, tid in tids.items():
            events.append({
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": cat},
            })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# ------------------------------------------------------------------ history
def parse_history(text: str) -> List[Dict[str, Any]]:
    """Parse recorded-history JSONL content into an event list (blank
    lines skipped) — the Python twin of the native read path's parser."""
    events: List[Dict[str, Any]] = []
    for line in text.splitlines():
        if not line.strip():
            continue
        events.append(json.loads(line))
    return events


def load_history(source: str) -> List[Dict[str, Any]]:
    """THE history loader: accepts a path to a ``--history`` JSONL file
    (plain or gzip'd, sniffed by magic bytes — fleets routinely gzip
    rotated histories) or raw JSONL content, and returns the event list.

    Every consumer funnels through here — the ``trace history`` CLI, the
    policy replay CLI, and ``coordination.history_replay`` (which keeps
    its content-only signature but shares this parser) — so path
    vs. content can never diverge again between entry points.
    """
    import gzip
    import os

    if "\n" not in source and os.path.exists(source):
        with open(source, "rb") as f:
            blob = f.read()
        if blob[:2] == b"\x1f\x8b":
            blob = gzip.decompress(blob)
        return parse_history(blob.decode("utf-8"))
    return parse_history(source)


def history_fold(events: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Canonical fold over history events -> summary.

    MUST stay field-for-field identical to ``history_fold`` in
    native/history.cc (the ``tft_history_replay`` summary); the parity
    test drives the same JSONL through both.
    """
    kinds: Dict[str, int] = {}
    replicas = set()
    count = 0
    last_quorum_id = -1
    max_step = -1
    first_ts = -1
    last_ts = -1
    for e in events:
        count += 1
        kind = str(e.get("kind", "unknown"))
        kinds[kind] = kinds.get(kind, 0) + 1
        if "replica_id" in e:
            replicas.add(str(e["replica_id"]))
        for rid in e.get("participants", []):
            replicas.add(str(rid))
        if "quorum_id" in e:
            last_quorum_id = int(e["quorum_id"])
        if "step" in e:
            max_step = max(max_step, int(e["step"]))
        if "to_step" in e:
            max_step = max(max_step, int(e["to_step"]))
        if "ts_ms" in e:
            ts = int(e["ts_ms"])
            if first_ts < 0:
                first_ts = ts
            last_ts = ts
    return {
        "count": count,
        "kinds": kinds,
        "replicas": sorted(replicas),
        "quorum_transitions": kinds.get("quorum", 0),
        "last_quorum_id": last_quorum_id,
        "heals": kinds.get("heal", 0),
        "ejections": kinds.get("eject", 0),
        "readmissions": kinds.get("readmit", 0),
        "warns": kinds.get("straggler_warn", 0),
        "max_step": max_step,
        "first_ts_ms": first_ts,
        "last_ts_ms": last_ts,
    }
