"""HTTP checkpoint transport: the default live-recovery path.

Design mirror of the reference HTTPTransport
(torchft/checkpointing/http_transport.py:38-266): a threaded HTTP server
serving ``/checkpoint/{step}/{metadata|chunk_{i}}``, gated by an RWLock so
serving can be disallowed while the optimizer mutates state; receivers fetch
chunks in parallel and reassemble the pytree.

Both directions stream (reference `_streaming_save/_load`,
http_transport.py:219-266): the sender serves leaf payloads straight from
the staged host arrays — one [leaf_idx, offset, nbytes] frame header then
the raw byte range, no pre-pickled chunk bodies — and the receiver reads
each frame directly into the leaf's final preallocated array
(``readinto``). Peak host overhead is O(stream buffer), not O(payload),
which is what makes 12GB-class state dicts transferable at 8B scale.

Wire chunks are BYTE ranges (``plan_wire_ranges``), not whole leaves: a
single multi-GB fused parameter buffer splits across chunks, so parallel
chunk fetches overlap its network transfer with the device placement of
already-complete leaves instead of store-and-forwarding one blob.

Wire version 3 adds receiver-opt-in integrity + resume to the chunk wire:
a ``crc=1`` query appends a 4-byte crc32 trailer over the canonical chunk
body, and ``offset=N`` resumes the body mid-stream from byte ``N`` — the
receiver keeps a running crc across reconnects, so a stalled transfer
resumes from the last received byte and a corrupt chunk is detected and
re-fetched instead of silently loaded into params. Both features ride
query params the v2 server never saw, and a v3 receiver only sends them
to peers whose metadata advertises v3, so v2<->v3 interop in either
direction is byte-identical to v2. v1 senders (whole-leaf
``[leaf_idx, nbytes]`` frames) are still understood on receive.

``recv_checkpoint_multi`` layers mid-heal failover on top: an ordered list
of candidate sources is tried under one deadline, and because
``plan_wire_ranges`` is deterministic and every max-step peer stages the
same state, a chunk half-fetched from a dying source resumes at the same
byte offset on the next peer.
"""

from __future__ import annotations

import logging
import pickle
import socket
import struct
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
import zlib
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple

from torchft_tpu.retry import RetryPolicy

from torchft_tpu.checkpointing._rwlock import RWLock
from torchft_tpu.checkpointing._serialization import (
    TreeSpecPayload,
    alloc_leaf,
    can_absorb,
    flatten_state,
    payload_memoryview,
    place_leaf_like,
    template_leaves_for,
    unflatten_state,
)
from torchft_tpu.checkpointing.transport import (
    CheckpointTransport,
    ChunkStat,
    StreamTimings,
    plan_wire_ranges,
    stream_chunk_bytes,
)

logger = logging.getLogger(__name__)

__all__ = ["HTTPTransport"]

_FRAME = struct.Struct("<qq")  # v1: leaf_idx, nbytes (whole leaf)
_FRAME_V2 = struct.Struct("<qqq")  # leaf_idx, offset, nbytes (byte range)
_CRC = struct.Struct("<I")  # v3 opt-in chunk trailer: crc32 of the body
_WIRE_VERSION = 3
# cap on auto-planned chunks (num_chunks=0): bounds fetch parallelism and
# the per-chunk frame overhead on huge states
_AUTO_MAX_CHUNKS = 8


def _to_seconds(timeout: "float | timedelta") -> float:
    return timeout.total_seconds() if isinstance(timeout, timedelta) else float(timeout)


class HTTPTransport(CheckpointTransport[Any]):
    """Serve checkpoints over HTTP; receive with parallel chunk fetch.

    ``num_chunks=0`` auto-plans byte-range chunks of roughly
    ``TORCHFT_STREAM_CHUNK_BYTES`` (default 32 MiB, at most 8 chunks), so
    the default transport pipelines large heals; ``num_chunks>0`` forces
    that many chunks. Chunk boundaries are byte offsets, not leaf
    boundaries — one huge leaf still streams as multiple chunks.

    ``state_dict_template`` (zero-arg callable returning a pytree, same
    contract as PGTransport's) enables in-place receive: a matching host
    ndarray leaf streams from the socket DIRECTLY into the template's
    buffer (no wire allocation), a jax.Array leaf lands via ``device_put``
    on the template's sharding. Leaves are written AS THEY ARRIVE, so a
    mid-stream failure leaves the template torn — even mid-leaf on this
    direct-stream path. That is safe only under the Manager's
    discard-and-retry heal protocol (a failed recv is reported, the step
    discarded, the heal retried); do not hand live state to a template
    outside that protocol. Structural drift between sender and template
    degrades the WHOLE receive to wire buffers with one warning (see
    ``template_leaves_for``).
    """

    def __init__(self, timeout: "float | timedelta" = 60.0, num_chunks: int = 0,
                 hostname: str = "",
                 state_dict_template: "Optional[Any]" = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 client_only: bool = False) -> None:
        self._timeout = _to_seconds(timeout)
        # client_only: a pure receiver (serving-plane workers, bootstrap
        # pulls) that never stages state — skip binding a listener so a
        # fleet of pullers doesn't burn a port (and a thread) each
        self._client_only = client_only
        self._num_chunks = num_chunks
        # per-chunk same-source retry budget + backoff for the recv side
        self._retry_policy = (
            retry_policy if retry_policy is not None else RetryPolicy.from_env()
        )
        # test-only serve-side fault injection (see inject_chunk_fault)
        self._fault_lock = threading.Lock()
        self._chunk_faults: List[Dict[str, int]] = []
        if state_dict_template is not None and not callable(state_dict_template):
            # same contract (and failure mode) as PGTransport: fail at
            # construction, not as an endlessly-retried heal error
            raise TypeError(
                "state_dict_template must be a zero-arg callable returning "
                "the template pytree, not the pytree itself "
                f"(got {type(state_dict_template).__name__})"
            )
        self._template_fn = state_dict_template
        # advertised heal address: overridable for fleets where
        # gethostname() is not peer-resolvable (e.g. k8s pods)
        self._hostname = hostname
        # Write-locked whenever there is NO servable checkpoint; readers are
        # in-flight HTTP requests (reference: http_transport.py:181-202).
        self._state_lock = RWLock(timeout=self._timeout)
        self._state_lock.w_acquire()
        self._have_state = False

        # One atomic snapshot per staging: (step, spec, payloads,
        # assignments). Handlers capture the reference ONCE per request, so
        # a restage mid-stream keeps serving the old snapshot consistently
        # instead of mixing two steps' leaves into one body (restaging swaps
        # a single attribute; the old snapshot's references stay alive for
        # in-flight readers).
        self._staged: Optional[tuple] = None

        # Delivery tracking: how many chunk fetches we expect for the staged
        # step vs. how many were served. disallow_checkpoint() grants a grace
        # window for lagging receivers before closing the window — without
        # this, a fast sender can reach should_commit and re-lock before a
        # healing peer started its fetch, failing the peer's recovery for a
        # full extra step.
        self._fetch_cond = threading.Condition()
        self._expected_fetches = 0
        self._served_fetches = 0

        transport = self

        class _Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # quiet
                logger.debug("http_transport: " + fmt, *args)

            def do_GET(self) -> None:
                try:
                    # bound the streamed write: the chunk body is written
                    # while holding the state read lock, so a stalled
                    # receiver must time out rather than wedge
                    # disallow_checkpoint's write-acquire forever
                    self.connection.settimeout(transport._timeout)
                    raw_path, _, raw_query = self.path.partition("?")
                    parts = raw_path.strip("/").split("/")
                    # /checkpoint/{step}/{what}[?crc=1&offset=N]
                    if len(parts) != 3 or parts[0] != "checkpoint":
                        self.send_error(404, "unknown path")
                        return
                    step = int(parts[1])
                    what = parts[2]
                    query = urllib.parse.parse_qs(raw_query)
                    # Acquire the read lock OUTSIDE the streaming block:
                    # socket.timeout IS TimeoutError (py>=3.10), so a
                    # mid-stream write timeout must never reach a handler
                    # that answers with send_error — a 503 page injected
                    # into the middle of the frame stream would parse as
                    # leaf payload on the receiver.
                    if not transport._state_lock.r_acquire(
                        timeout=transport._timeout
                    ):
                        self.send_error(503, "checkpoint not available (locked)")
                        return
                    try:
                        # the read lock is held across the whole streamed
                        # write: disallow_checkpoint cannot yank the staged
                        # arrays out from under an in-flight response. The
                        # snapshot is captured once — restaging swaps the
                        # attribute atomically and cannot tear this body.
                        staged = transport._staged
                        if staged is None or staged[0] != step:
                            have = staged[0] if staged else None
                            self.send_error(
                                400,
                                f"serving step {have}, asked {step}",
                            )
                            return
                        if not transport._stream_response(
                            self, staged, what, query
                        ):
                            self.send_error(404, f"unknown resource {what}")
                            return
                    except (BrokenPipeError, TimeoutError, OSError):
                        # receiver gone or stalled past the socket timeout:
                        # drop the connection; never write an error page
                        # into a partially-streamed body
                        self.close_connection = True
                        return
                    finally:
                        transport._state_lock.r_release()
                except (BrokenPipeError, socket.timeout):
                    pass  # receiver gone or stalled past the timeout
                except Exception as e:  # noqa: BLE001
                    logger.exception("http_transport handler failed")
                    try:
                        self.send_error(500, str(e))
                    except Exception:  # noqa: BLE001
                        pass

        if client_only:
            self._server = None
            self._serve_thread = None
        else:
            self._server = ThreadingHTTPServer(("0.0.0.0", 0), _Handler)
            self._server.daemon_threads = True
            self._serve_thread = threading.Thread(
                target=self._server.serve_forever, daemon=True,
                name="torchft_http_ckpt",
            )
            self._serve_thread.start()

    # -- serving side -----------------------------------------------------
    def inject_chunk_fault(self, chunk: int, mode: str, times: int = 1) -> None:
        """Test-only: make the next ``times`` serves of ``chunk`` fail.

        ``mode="corrupt"``: one payload byte of the served body is flipped
        while the crc32 trailer stays canonical — the receiver detects the
        mismatch and re-fetches. ``mode="die"``: the connection drops
        roughly halfway through the requested span — models the source
        dying mid-heal. ``times=-1`` faults every serve (a permanently-dead
        source, forcing receiver failover)."""
        if mode not in ("corrupt", "die"):
            raise ValueError(f"unknown fault mode {mode!r}")
        with self._fault_lock:
            self._chunk_faults.append(
                {"chunk": chunk, "mode": mode, "times": times}  # type: ignore[dict-item]
            )

    def _take_fault(self, chunk: int) -> Optional[str]:
        with self._fault_lock:
            for f in self._chunk_faults:
                if f["chunk"] == chunk and f["times"] != 0:
                    if f["times"] > 0:
                        f["times"] -= 1
                    return f["mode"]  # type: ignore[return-value]
        return None

    def _stream_response(
        self, handler: Any, staged: tuple, what: str, query: dict
    ) -> bool:
        """Write the response for ``what`` (True if the resource exists)
        from the captured ``staged`` snapshot.

        Chunk bodies stream straight from the staged arrays: per range a
        24-byte [leaf_idx, offset, nbytes] frame then the raw byte range —
        never assembled in memory. ``offset=N`` serves the body from byte
        ``N`` (resume); ``crc=1`` appends a 4-byte crc32 trailer over the
        CANONICAL full body, so a resuming receiver's running crc still
        verifies end to end."""
        _step, spec, payloads, assignments = staged
        if what == "metadata":
            body = pickle.dumps((spec, len(assignments), _WIRE_VERSION))
            handler.send_response(200)
            handler.send_header("Content-Type", "application/octet-stream")
            handler.send_header("Content-Length", str(len(body)))
            handler.end_headers()
            handler.wfile.write(body)
            return True
        if what.startswith("chunk_"):
            i = int(what[len("chunk_"):])
            if not (0 <= i < len(assignments)):
                return False
            want_crc = query.get("crc", ["0"])[0] == "1"
            start = int(query.get("offset", ["0"])[0])
            ranges = assignments[i]
            body_len = sum(_FRAME_V2.size + ln for (_j, _off, ln) in ranges)
            if start < 0 or start > body_len:
                return False
            fault = self._take_fault(i)
            die_after: Optional[int] = None
            if fault == "die":
                # drop the connection roughly halfway through the span
                die_after = max((body_len - start) // 2, 1)
            total = body_len - start + (_CRC.size if want_crc else 0)
            handler.send_response(200)
            handler.send_header("Content-Type", "application/octet-stream")
            handler.send_header("Content-Length", str(total))
            handler.end_headers()
            crc = 0
            pos = 0  # canonical body cursor
            written = 0
            corrupt_pending = fault == "corrupt"
            for j, off, ln in ranges:
                mv = payload_memoryview(payloads[j])
                for is_payload, seg in (
                    (False, _FRAME_V2.pack(j, off, ln)),
                    (True, mv[off : off + ln]),
                ):
                    seg_len = len(seg)
                    if want_crc:
                        crc = zlib.crc32(seg, crc)
                    if pos + seg_len > start:
                        lo = max(0, start - pos)
                        out = seg[lo:]
                        if corrupt_pending and is_payload and len(out):
                            out = bytearray(out)
                            out[0] ^= 0xFF
                            corrupt_pending = False
                        if die_after is not None and written + len(out) >= die_after:
                            handler.wfile.write(out[: max(die_after - written, 0)])
                            handler.close_connection = True
                            return True
                        handler.wfile.write(out)
                        written += len(out)
                    pos += seg_len
            if want_crc:
                handler.wfile.write(_CRC.pack(crc & 0xFFFFFFFF))
            with self._fetch_cond:
                # only count serves of the CURRENT staging: a stale-snapshot
                # serve completing after a restage must not satisfy the new
                # staging's grace window before its receivers have fetched
                current = self._staged
                if current is not None and current[0] == _step:
                    self._served_fetches += 1
                    self._fetch_cond.notify_all()
            return True
        return False

    def metadata(self) -> str:
        if self._server is None:
            raise RuntimeError(
                "client_only transport has no serve address (metadata())"
            )
        host = self._hostname or socket.gethostname()
        port = self._server.server_address[1]
        return f"http://{host}:{port}"

    def staged_step(self) -> "Optional[int]":
        """Step currently staged for serving, or None when the window is
        closed (serving-plane introspection; reads one attribute)."""
        staged = self._staged
        return staged[0] if staged is not None else None

    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: Any, timeout
    ) -> None:
        """Stage the state (host copy) and open the serving window.

        HTTP is pull-based: "send" = make available to ``dst_ranks`` until
        ``disallow_checkpoint`` re-locks (reference: http_transport.py:219-241).
        """
        if self._server is None:
            raise RuntimeError("client_only transport cannot stage checkpoints")
        spec, payloads = flatten_state(state_dict)
        leaf_nbytes = [m.nbytes for m in spec.leaves]
        total = sum(leaf_nbytes)
        if self._num_chunks > 0:
            chunk_bytes = max(1, -(-total // self._num_chunks))
        else:
            chunk_bytes = stream_chunk_bytes()
            if total > chunk_bytes * _AUTO_MAX_CHUNKS:
                chunk_bytes = -(-total // _AUTO_MAX_CHUNKS)
        assignments = plan_wire_ranges(leaf_nbytes, chunk_bytes)
        # single atomic swap: in-flight readers keep the old snapshot
        self._staged = (step, spec, payloads, assignments)
        with self._fetch_cond:
            self._expected_fetches = len(assignments) * max(len(dst_ranks), 0)
            self._served_fetches = 0
        if not self._have_state:
            self._have_state = True
            self._state_lock.w_release()

    def disallow_checkpoint(self, grace: Optional[float] = None) -> None:
        if self._have_state:
            # Grace window: give expected receivers a chance to fetch before
            # closing. Bounded so a crashed receiver can't stall the sender.
            grace = min(self._timeout, 10.0) if grace is None else grace
            with self._fetch_cond:
                self._fetch_cond.wait_for(
                    lambda: self._served_fetches >= self._expected_fetches,
                    timeout=grace,
                )
            if not self._state_lock.w_acquire(timeout=self._timeout):
                # A straggling receiver still streaming must NOT kill the
                # healthy donor (this raises out of should_commit). The
                # staged snapshot owns independent copies, so the in-flight
                # stream stays consistent even while training mutates live
                # state; just close the window for new requests and let the
                # next disallow re-attempt the lock.
                logger.warning(
                    "slow checkpoint receiver still streaming; closing the "
                    "serving window without re-locking"
                )
                self._staged = None
                return
            self._have_state = False
            self._staged = None

    # -- receiving side ---------------------------------------------------
    supports_multi_source = True

    def recv_checkpoint(self, src_rank: int, metadata: str, step: int, timeout) -> Any:
        return self.recv_checkpoint_multi(
            [(f"replica_rank_{src_rank}", lambda: metadata)], step, timeout
        )

    def recv_checkpoint_multi(
        self,
        sources: List[Tuple[str, Callable[[], str]]],
        step: int,
        timeout,
        on_event: Optional[Callable[..., None]] = None,
    ) -> Any:
        """Fetch ``step`` from an ordered list of candidate sources under
        one deadline, resuming and failing over mid-transfer.

        Chunk progress (byte offset, running crc, pending credits) survives
        a source switch: same-step peers stage identical states and
        ``plan_wire_ranges`` is deterministic, so as long as the next peer's
        metadata matches the plan signature, a half-fetched chunk continues
        at its last received byte on the new peer. A signature mismatch
        (different chunking config) restarts the receive from scratch."""
        timeout_s = _to_seconds(timeout)
        deadline = time.monotonic() + timeout_s
        emit = on_event if on_event is not None else (lambda kind, **f: None)
        timings = StreamTimings()
        t_all = time.perf_counter()
        rs: Optional[_RecvState] = None
        last_exc: Optional[Exception] = None
        tried = 0
        for src_i, (label, metadata_fn) in enumerate(sources):
            if time.monotonic() >= deadline:
                break
            if src_i > 0:
                timings.failovers += 1
                emit("heal_failover", source=label, prior_error=repr(last_exc))
            tried += 1
            try:
                base = f"{metadata_fn()}/checkpoint/{step}"
                meta_timeout = min(
                    timeout_s, max(deadline - time.monotonic(), 0.001)
                )
                with urllib.request.urlopen(
                    f"{base}/metadata", timeout=meta_timeout
                ) as r:
                    raw_meta = r.read()
            except Exception as e:  # noqa: BLE001 — any peer error -> next peer
                last_exc = e
                continue
            # tolerant unpack: v1 senders ship (spec, num_chunks), v2+
            # appends the wire version — unknown trailing fields ignored
            spec, num_chunks, *meta_rest = pickle.loads(raw_meta)
            version = meta_rest[0] if meta_rest else 1
            sig = (num_chunks, tuple(m.nbytes for m in spec.leaves))
            if rs is None or rs.sig != sig:
                if rs is not None:
                    logger.warning(
                        "heal source %s plans %s, prior source planned %s; "
                        "restarting the receive from scratch", label, sig, rs.sig
                    )
                rs = _RecvState(spec, num_chunks, self._template_fn, emit)
            try:
                self._fetch_all(
                    rs, base, version, deadline, timeout_s, timings, emit, label
                )
            except Exception as e:  # noqa: BLE001 — exhausted on this peer
                last_exc = e
                continue
            # success: finalize zero-byte leaves (no range bytes on the
            # wire), check completeness, reassemble
            for i, rem in enumerate(rs.remaining):
                if rem == 0 and rs.payloads[i] is None:
                    rs.buffer_for(i)
                    rs.finish_leaf(i)
            missing = [i for i, p in enumerate(rs.payloads) if p is None]
            if missing:
                raise RuntimeError(f"checkpoint chunks missing leaves {missing}")
            timings.total_s = time.perf_counter() - t_all
            self._last_recv_timings = timings
            return unflatten_state(rs.spec, rs.payloads)  # type: ignore[arg-type]
        timings.total_s = time.perf_counter() - t_all
        self._last_recv_timings = timings
        raise RuntimeError(
            f"heal failed: all {tried}/{len(sources)} source(s) exhausted "
            f"within {timeout_s:.1f}s (last error: {last_exc!r})"
        ) from last_exc

    def _fetch_all(
        self,
        rs: "_RecvState",
        base: str,
        version: int,
        deadline: float,
        timeout_s: float,
        timings: StreamTimings,
        emit: Callable[..., None],
        label: str,
    ) -> None:
        """Fetch every unfinished chunk from one source in parallel, with a
        per-chunk same-source retry loop (resume on stall when the source
        speaks v3, full chunk refetch on crc mismatch)."""
        todo = [st for st in rs.chunk_states if not st.done]
        if not todo:
            return
        policy = self._retry_policy

        def run(st: "_ChunkFetch") -> None:
            attempts = 0
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"heal deadline exhausted before chunk {st.i}"
                    )
                try:
                    self._fetch_chunk_once(
                        rs, st, base, version, min(timeout_s, remaining),
                        timings, emit,
                    )
                    return
                except _ChunkCrcError as e:
                    # corrupt bytes are never credited/finalized: throw away
                    # the chunk's progress and re-fetch it from byte 0
                    st.reset()
                    with rs.stats_lock:
                        timings.crc_failures += 1
                    emit("chunk_crc_failure", chunk=st.i, source=label)
                    err: Exception = e
                except (ConnectionError, TimeoutError, OSError) as e:
                    if version < 3:
                        # v2 peers can't serve a body suffix: restart chunk
                        st.reset()
                    err = e
                attempts += 1
                if attempts >= policy.max_attempts:
                    raise err
                with rs.stats_lock:
                    timings.retries += 1
                emit(
                    "heal_retry",
                    chunk=st.i,
                    source=label,
                    attempt=attempts,
                    resume_offset=st.body_off,
                    error=repr(err),
                )
                pause = policy.backoff_s(attempts + 1)
                time.sleep(min(pause, max(deadline - time.monotonic(), 0)))

        with ThreadPoolExecutor(max_workers=max(1, min(len(todo), 8))) as ex:
            futs = [ex.submit(run, st) for st in todo]
            errs = [f.exception() for f in futs]
        for e in errs:
            if e is not None:
                raise e  # type: ignore[misc]

    def _fetch_chunk_once(
        self,
        rs: "_RecvState",
        st: "_ChunkFetch",
        base: str,
        version: int,
        timeout_s: float,
        timings: StreamTimings,
        emit: Callable[..., None],
    ) -> None:
        """One streaming attempt at chunk ``st.i``: read range frames and
        stream payloads straight into the leaf recv buffers, resuming from
        ``st.body_off`` when the source speaks v3.

        Leaf byte credits are DEFERRED to the chunk's pending list and only
        applied after the whole chunk verifies (v3: crc trailer matches;
        v1/v2: clean EOF), so a corrupt chunk can be re-fetched with the
        buffer rewrites staying idempotent and no leaf is ever finalized
        from unverified bytes."""
        frame = _FRAME_V2 if version >= 2 else _FRAME
        want_crc = version >= 3
        url = f"{base}/chunk_{st.i}"
        if want_crc:
            params = ["crc=1"]
            if st.body_off:
                params.append(f"offset={st.body_off}")
            url += "?" + "&".join(params)
        t0 = time.perf_counter()
        attempt_bytes = 0
        with urllib.request.urlopen(url, timeout=timeout_s) as r:
            while True:
                if st.cur is None:
                    hdr = _read_upto(r, frame.size)
                    if not hdr:
                        if want_crc:
                            raise ConnectionError(
                                f"chunk {st.i}: stream ended before crc trailer"
                            )
                        break  # v1/v2: clean end of chunk
                    if want_crc and len(hdr) == _CRC.size:
                        expected = _CRC.unpack(hdr)[0]
                        if st.crc & 0xFFFFFFFF != expected:
                            raise _ChunkCrcError(
                                f"chunk {st.i}: crc32 mismatch "
                                f"(got {st.crc & 0xFFFFFFFF:#010x}, "
                                f"trailer {expected:#010x})"
                            )
                        break  # verified
                    if len(hdr) < frame.size:
                        # partial header bytes are NOT counted in body_off,
                        # so a resume re-reads the whole header
                        raise ConnectionError(
                            f"chunk {st.i}: truncated frame header"
                        )
                    if version >= 2:
                        leaf_idx, off, nbytes = frame.unpack(hdr)
                    else:
                        leaf_idx, nbytes = frame.unpack(hdr)
                        off = 0
                    if not (0 <= leaf_idx < len(rs.spec.leaves)):
                        raise ConnectionError(
                            f"chunk {st.i}: frame names leaf {leaf_idx} of "
                            f"{len(rs.spec.leaves)}"
                        )
                    meta = rs.spec.leaves[leaf_idx]
                    if version < 2 and nbytes != meta.nbytes:
                        # a short v1 frame would exit the read loop cleanly
                        # and leave the leaf — possibly a live template
                        # buffer — half-written with no error
                        raise ConnectionError(
                            f"chunk {st.i} leaf {leaf_idx}: frame carries "
                            f"{nbytes} bytes but the leaf spec says "
                            f"{meta.nbytes}"
                        )
                    if off < 0 or nbytes < 0 or off + nbytes > meta.nbytes:
                        raise ConnectionError(
                            f"chunk {st.i} leaf {leaf_idx}: range "
                            f"[{off}, {off + nbytes}) outside the leaf's "
                            f"{meta.nbytes} bytes"
                        )
                    if want_crc:
                        st.crc = zlib.crc32(hdr, st.crc)
                    st.body_off += frame.size
                    st.cur = (leaf_idx, off, nbytes, 0)
                leaf_idx, off, nbytes, got = st.cur
                buf = rs.buffer_for(leaf_idx)
                if isinstance(buf, bytearray):
                    span = memoryview(buf)[off : off + nbytes]
                else:
                    span = memoryview(buf.reshape(-1).view("u1"))[
                        off : off + nbytes
                    ]
                while got < nbytes:
                    n = r.readinto(span[got:])
                    if not n:
                        raise ConnectionError(
                            f"chunk {st.i} truncated at leaf {leaf_idx} "
                            f"({got}/{nbytes} bytes of range)"
                        )
                    if want_crc:
                        st.crc = zlib.crc32(span[got : got + n], st.crc)
                    st.body_off += n
                    got += n
                    st.cur = (leaf_idx, off, nbytes, got)
                    attempt_bytes += n
                st.pending.append((leaf_idx, nbytes))
                st.cur = None
        # socket -> recv buffers, this attempt (placement comes after)
        emit("heal_fetch", chunk=st.i, bytes=attempt_bytes,
             t0_pc=t0, t1_pc=time.perf_counter())
        # chunk verified (or v1/v2-complete): apply the deferred credits,
        # finalizing any leaves this chunk completed
        for leaf_idx, n in st.pending:
            if rs.mark_written(leaf_idx, n):
                rs.finish_leaf(leaf_idx)
        st.pending = []
        st.done = True
        with rs.stats_lock:
            timings.chunks.append(
                ChunkStat(
                    nbytes=attempt_bytes,
                    transfer_s=time.perf_counter() - t0,
                )
            )
            timings.total_bytes += attempt_bytes

    def shutdown(self, wait: bool = True) -> None:
        if self._server is None:
            return
        self._server.shutdown()
        self._server.server_close()
        if wait:
            self._serve_thread.join(timeout=5)


class _ChunkCrcError(ConnectionError):
    """The chunk's crc32 trailer did not match the received body."""


def _read_upto(r: Any, n: int) -> bytes:
    """Read up to ``n`` bytes, short only at EOF (loops over short reads)."""
    buf = b""
    while len(buf) < n:
        got = r.read(n - len(buf))
        if not got:
            break
        buf += got
    return buf


class _ChunkFetch:
    """Resumable per-chunk fetch state, surviving reconnects and source
    failovers: ``body_off`` is the canonical-body byte to resume from,
    ``crc`` the running crc32 of everything consumed so far, ``cur`` a
    partially-read range ``(leaf_idx, off, nbytes, got)``, and ``pending``
    the leaf byte credits deferred until the chunk verifies."""

    __slots__ = ("i", "body_off", "crc", "cur", "pending", "done")

    def __init__(self, i: int) -> None:
        self.i = i
        self.reset()

    def reset(self) -> None:
        self.body_off = 0
        self.crc = 0
        self.cur: Optional[Tuple[int, int, int, int]] = None
        self.pending: List[Tuple[int, int]] = []
        self.done = False


class _RecvState:
    """Shared reassembly state of one multi-source receive: recv buffers,
    per-leaf byte accounting, and the per-chunk fetch states.

    Per-leaf reassembly: ranges of one leaf may arrive on different
    chunk-fetch threads, so the recv buffer is allocated once under a lock
    and a bytes-remaining counter triggers finalization (device placement /
    bytes conversion) exactly once, on the thread whose chunk lands the
    leaf's last verified range — placement of completed leaves overlaps the
    wire transfer of the chunks still streaming."""

    def __init__(
        self,
        spec: Any,
        num_chunks: int,
        template_fn: Any,
        emit: Callable[..., None],
    ) -> None:
        self.emit = emit
        self.spec = spec
        self.num_chunks = num_chunks
        self.sig = (num_chunks, tuple(m.nbytes for m in spec.leaves))
        self.payloads: List[Optional[Any]] = [None] * len(spec.leaves)
        self.template_leaves: Optional[List[Any]] = None
        if template_fn is not None:
            # returns None (one warning) when the sender's tree STRUCTURE
            # differs from the template's — index-aligned placement would
            # risk streaming leaves into the wrong buffers
            self.template_leaves = template_leaves_for(spec, template_fn(), logger)
        self.buf_lock = threading.Lock()
        self.stats_lock = threading.Lock()
        self.buffers: List[Optional[Any]] = [None] * len(spec.leaves)
        self.direct: List[bool] = [False] * len(spec.leaves)
        self.remaining: List[int] = [m.nbytes for m in spec.leaves]
        self.chunk_states = [_ChunkFetch(i) for i in range(num_chunks)]

    def _host_target(self, meta: Any, leaf_idx: int) -> Optional[Any]:
        """A host ndarray template leaf that can absorb this wire leaf
        lets the socket stream DIRECTLY into the resident buffer —
        zero wire-buffer alloc, the strongest in-place path."""
        if self.template_leaves is None or meta.kind != "array":
            return None
        t = self.template_leaves[leaf_idx]
        if can_absorb(t, meta.shape, meta.dtype, require_contiguous=True):
            return t
        return None

    def buffer_for(self, leaf_idx: int) -> Any:
        with self.buf_lock:
            if self.buffers[leaf_idx] is None:
                meta = self.spec.leaves[leaf_idx]
                if meta.kind == "array":
                    target = self._host_target(meta, leaf_idx)
                    if target is not None:
                        self.buffers[leaf_idx] = target
                        self.direct[leaf_idx] = True
                    else:
                        self.buffers[leaf_idx] = alloc_leaf(meta)
                else:
                    self.buffers[leaf_idx] = bytearray(meta.nbytes)
            return self.buffers[leaf_idx]

    def mark_written(self, leaf_idx: int, n: int) -> bool:
        """Credit ``n`` verified bytes; True when the leaf is complete
        (finalize on the calling thread, outside the lock)."""
        with self.buf_lock:
            self.remaining[leaf_idx] -= n
            if self.remaining[leaf_idx] < 0:
                raise ConnectionError(
                    f"leaf {leaf_idx}: overlapping/duplicate wire ranges"
                )
            return self.remaining[leaf_idx] == 0 and self.payloads[leaf_idx] is None

    def finish_leaf(self, leaf_idx: int) -> None:
        meta = self.spec.leaves[leaf_idx]
        arr = self.buffers[leaf_idx]
        if meta.kind == "array":
            if not self.direct[leaf_idx] and self.template_leaves is not None:
                # device template (device_put) or a mismatch
                # (warns "in-place receive degraded")
                t0 = time.perf_counter()
                arr = place_leaf_like(arr, self.template_leaves[leaf_idx], logger)
                self.emit("heal_place", leaf=leaf_idx, bytes=meta.nbytes,
                          t0_pc=t0, t1_pc=time.perf_counter())
            self.payloads[leaf_idx] = arr
        else:
            self.payloads[leaf_idx] = bytes(arr)
