"""Reconfigurable process groups: the fault-tolerant communication backend.

Role-equivalent of the reference's torchft/process_group.py (the shim over
NCCL/Gloo/XCCL that can be torn down and rebuilt per quorum without
restarting the process). The TPU-native design differs deliberately:

- **Immutable arrays.** JAX arrays cannot be mutated in place, so collectives
  return their results through the Work's future instead of writing into the
  input buffers. ``allreduce([x])`` yields a Work whose future resolves to the
  reduced arrays.
- **Two planes, like the reference.** ``ProcessGroupHost`` is the Gloo
  equivalent: CPU collectives over a full TCP mesh between replica groups,
  used for control data, tests, and as the DCN bridge for cross-replica-group
  traffic. Device arrays are staged host-side (device_get/device_put). The
  intra-replica-group plane (FSDP/TP shard dims) is *not* a process group at
  all on TPU — it is XLA SPMD over a jax.sharding.Mesh (see
  torchft_tpu/parallel/), exactly as the reference delegates intra-group
  parallelism to torchtitan (reference README.md:40).
- **Abort-based timeouts.** Collectives are issued on a dedicated dispatch
  thread per PG; timeouts arm a watchdog that calls ``abort()`` (closing the
  sockets), mirroring the reference's NCCL abort recovery
  (process_group.py:780-891).

Reconfiguration handshake matches the reference: ``configure(store_addr,
replica_rank, replica_world_size, ...)`` tears down the old communicator and
rendezvouses a new one via the KV store under a per-quorum prefix
(reference: manager.py:692-737).
"""

from __future__ import annotations

import enum
import itertools
import logging
import os
import pickle
import queue
import socket
import struct
import threading
import time
from abc import ABC, abstractmethod
from contextlib import contextmanager
from datetime import timedelta
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

import torchft_tpu.flight_recorder as _fr
from torchft_tpu.coordination import KvClient
from torchft_tpu.futures import context_timeout
from torchft_tpu.work import DummyWork, Future, FutureWork, Work

logger = logging.getLogger(__name__)

__all__ = [
    "ReduceOp",
    "ProcessGroup",
    "ProcessGroupDummy",
    "ProcessGroupHost",
    "ErrorSwallowingProcessGroupWrapper",
    "FakeProcessGroupWrapper",
    "ManagedProcessGroup",
]


class ReduceOp(enum.Enum):
    SUM = "sum"
    AVG = "avg"
    MAX = "max"
    MIN = "min"
    PRODUCT = "product"


def _accum(op: ReduceOp, dst: np.ndarray, src: np.ndarray) -> None:
    """In-place elementwise accumulate — the one dispatch table shared by the
    full-mesh exchange (_reduce_np) and the ring (_ring_allreduce)."""
    if op in (ReduceOp.SUM, ReduceOp.AVG):
        dst += src
    elif op == ReduceOp.MAX:
        np.maximum(dst, src, out=dst)
    elif op == ReduceOp.MIN:
        np.minimum(dst, src, out=dst)
    elif op == ReduceOp.PRODUCT:
        dst *= src
    else:
        raise ValueError(f"unsupported reduce op: {op}")


def _reduce_np(op: ReduceOp, bufs: List[np.ndarray]) -> np.ndarray:
    out = bufs[0].copy()
    for b in bufs[1:]:
        _accum(op, out, b)
    if op == ReduceOp.AVG:
        out = out / len(bufs)
    return out


def _copy_payload(h: Any) -> Any:
    """Independent copy of a wire payload: ndarray, or tuple containing
    ndarrays (the quantized (q, scales, n) format)."""
    if isinstance(h, np.ndarray):
        return h.copy()
    if isinstance(h, tuple):
        return tuple(
            x.copy() if isinstance(x, np.ndarray) else x for x in h
        )
    return h


def _to_host(x: Any) -> Any:
    """Stage a jax.Array (or array-like) to host memory.

    Tuples pass through untouched (the quantized collectives ship
    (payload, scales, n) tuples); everything else — including plain Python
    lists — is coerced to ndarray so the reduce math is well-defined.
    """
    if isinstance(x, np.ndarray):
        return x
    if isinstance(x, tuple):
        return x
    return np.asarray(x)


class ProcessGroup(ABC):
    """Abstract reconfigurable process group.

    API mirror of the reference ProcessGroup ABC (process_group.py:131-399)
    with JAX-style value-returning collectives.
    """

    def __init__(self) -> None:
        self._timeout: float = 60.0

    # -- lifecycle --------------------------------------------------------
    @abstractmethod
    def configure(
        self,
        store_addr: str,
        replica_rank: int,
        replica_world_size: int,
        quorum_id: int = 0,
    ) -> None:
        """(Re)initialize the communicator for a new quorum.

        ``store_addr`` is ``"host:port/prefix"`` into the rendezvous KV store;
        the prefix embeds the quorum id so concurrent reconfigurations never
        collide (reference: manager.py:703-705).
        """

    def prepare_configure(
        self,
        store_addr: str,
        replica_rank: int,
        replica_world_size: int,
        quorum_id: int = 0,
    ) -> Optional[Callable[[], None]]:
        """Two-phase configure: run everything that is safe off the main
        thread NOW and return the main-thread commit, or None when nothing
        needs the main thread.

        The Manager calls this from its quorum executor thread so the
        control-plane round-trip (rendezvous, membership barriers) overlaps
        the trainer's compute; whatever the returned callable does (e.g. a
        live jax-backend swap in ProcessGroupXLA's distributed mode) is
        applied by the Manager from the main thread at the next safe point.

        Default: the whole configure is prepare — host-plane PGs touch no
        global device runtime, so running configure on the quorum thread is
        already safe. Routed through ``self.configure`` (not a base
        implementation) so instance-attribute shadowing of ``configure``
        (timing wrappers, test mocks) keeps seeing every reconfigure.
        """
        self.configure(
            store_addr, replica_rank, replica_world_size, quorum_id=quorum_id
        )
        return None

    @abstractmethod
    def abort(self) -> None:
        """Hard-kill in-flight collectives; the PG stays errored until
        reconfigured."""

    @abstractmethod
    def shutdown(self) -> None:
        """Tear down cleanly (terminal)."""

    @abstractmethod
    def errored(self) -> Optional[Exception]:
        """Error state since last configure, if any."""

    @abstractmethod
    def size(self) -> int: ...

    @abstractmethod
    def rank(self) -> int: ...

    def set_timeout(self, timeout: "float | timedelta") -> None:
        self._timeout = (
            timeout.total_seconds() if isinstance(timeout, timedelta) else timeout
        )

    def getBackendName(self) -> str:
        return type(self).__name__

    # -- collectives ------------------------------------------------------
    @abstractmethod
    def allreduce(
        self,
        arrays: Sequence[Any],
        op: ReduceOp = ReduceOp.SUM,
        donate: bool = False,
    ) -> Work:
        """Future resolves to the reduced arrays (same structure as input).

        The results are the caller's own: they share no memory with
        ``arrays``, unless the call ``donate``s them. ``donate=True`` is the
        caller's word that the arrays are its own and that it will not touch
        them again: a group may then reduce in them and hand them back as
        the result (:class:`ProcessGroupHost` does at every world size, for
        plain ndarrays: at a world of one untouched, behind the ring reduced
        in place; a ring that fails leaves them half-reduced). A group may
        ignore it."""

    @abstractmethod
    def allgather(self, arrays: Sequence[Any]) -> Work:
        """Future resolves to a list (one per rank) of lists of arrays."""

    @abstractmethod
    def broadcast(self, arrays: Sequence[Any], root: int = 0) -> Work:
        """Future resolves to root's arrays on every rank."""

    @abstractmethod
    def reduce_scatter(
        self, input_chunks: Sequence[Sequence[Any]], op: ReduceOp = ReduceOp.SUM
    ) -> Work:
        """``input_chunks[r]`` is this rank's contribution destined for rank r;
        future resolves to the reduced chunk owned by this rank."""

    @abstractmethod
    def alltoall(self, input_chunks: Sequence[Any]) -> Work:
        """Future resolves to [chunk from rank 0, chunk from rank 1, ...]."""

    @abstractmethod
    def send(self, arrays: Sequence[Any], dst: int, tag: int = 0) -> Work: ...

    @abstractmethod
    def recv(self, src: int, tag: int = 0) -> Work:
        """Future resolves to the received arrays."""

    def barrier(self) -> Work:
        return self.allreduce([np.zeros((1,), dtype=np.float32)])


class ProcessGroupDummy(ProcessGroup):
    """World-size-1 no-op PG: collectives return their inputs.

    Reference: process_group.py:1005-1134 (used to soak up init broadcasts
    and in tests).
    """

    def __init__(self, rank: int = 0, world: int = 1) -> None:
        super().__init__()
        self._rank = rank
        self._world = world
        self.configure_count = 0

    def configure(self, store_addr, replica_rank, replica_world_size, quorum_id=0):
        self.configure_count += 1

    def abort(self) -> None:
        pass

    def shutdown(self) -> None:
        pass

    def errored(self) -> Optional[Exception]:
        return None

    def size(self) -> int:
        return self._world

    def rank(self) -> int:
        return self._rank

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        return DummyWork(list(arrays))

    def allgather(self, arrays):
        return DummyWork([list(arrays)])

    def broadcast(self, arrays, root=0):
        return DummyWork(list(arrays))

    def reduce_scatter(self, input_chunks, op=ReduceOp.SUM):
        return DummyWork(list(input_chunks[0]))

    def alltoall(self, input_chunks):
        return DummyWork(list(input_chunks))

    def send(self, arrays, dst, tag=0):
        return DummyWork(None)

    def recv(self, src, tag=0):
        return DummyWork(None)


# ---------------------------------------------------------------------------
# Host TCP mesh process group
# ---------------------------------------------------------------------------

_HDR = struct.Struct("!Q")


def _send_msg(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(_HDR.pack(len(payload)) + payload)


def _recv_msg(sock: socket.socket) -> bytes:
    hdr = _recv_exact(sock, _HDR.size)
    (length,) = _HDR.unpack(hdr)
    return _recv_exact(sock, length)


_NATIVE: Any = ()  # not asked yet; then coordination.native_ring()'s, or None


def _native_ring() -> Optional[Any]:
    """The native library's part of the ring (``bf16_add``, ``fd_send_all``,
    ``fd_recv_all``: coordination.native_ring), or None where the library
    or a symbol is missing, which is said once. ml_dtypes adds bfloat16 an
    element at a time, and a frame through ``sendall`` / ``recv_into`` takes
    the interpreter's lock again after every piece the kernel hands over:
    with several lanes' threads in one process that hand-over, not the
    copy, sets the pace."""
    global _NATIVE
    if _NATIVE == ():
        from torchft_tpu.coordination import native_ring

        _NATIVE = native_ring()
        if _NATIVE is None:
            logger.warning(
                "native tft_bf16_add / tft_fd_send_all / tft_fd_recv_all are "
                "missing: the host ring falls back to numpy's accumulate "
                "and Python's socket calls on one lane (ring_lanes 1)"
            )
    return _NATIVE


def _raise_fd(rc: int, what: str) -> None:
    """A native socket call's status (coordination.native_ring) as the
    exception Python's own call would have raised."""
    if rc == 1:
        raise socket.timeout(f"{what}: timed out")
    if rc == 2:
        raise ConnectionError(f"{what}: peer closed connection")
    if rc:
        raise OSError(-rc, f"{what}: {os.strerror(-rc)}")


def _idle_ms(sock: socket.socket) -> int:
    """The socket's timeout for a native call: how long its peer may make
    no progress, in ms; negative for none."""
    timeout = sock.gettimeout()
    return -1 if timeout is None else int(timeout * 1000)


def _send_all(sock: socket.socket, a: np.ndarray, more: bool = False) -> None:
    """All of a flat uint8 array down a socket: one native call where the
    library has it (``more``: the rest of the message follows at once),
    else ``sendall``."""
    native = _native_ring()
    if native is None:
        sock.sendall(memoryview(a))
    else:
        _raise_fd(native.fd_send_all(
            sock.fileno(), a.ctypes.data, a.size, _idle_ms(sock), more), "send")


def _recv_all(sock: socket.socket, a: np.ndarray) -> None:
    """Fill a flat uint8 array from a socket: one native call where the
    library has it, else ``recv_into`` until it is full."""
    native = _native_ring()
    if native is not None:
        if not a.flags.writeable:  # as recv_into would refuse it
            raise ValueError("cannot receive into a read-only buffer")
        _raise_fd(native.fd_recv_all(
            sock.fileno(), a.ctypes.data, a.size, _idle_ms(sock)), "recv")
        return
    mv, got = memoryview(a), 0
    while got < len(mv):
        n = sock.recv_into(mv[got:], min(len(mv) - got, 1 << 20))
        if n == 0:
            raise ConnectionError("peer closed connection")
        got += n


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    got = 0
    while got < n:
        chunk = sock.recv(min(n - got, 1 << 20))
        if not chunk:
            raise ConnectionError("peer closed connection")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


class _Comm:
    """One generation of the TCP full mesh. Abort closes every socket, which
    makes all in-flight ops fail fast; a new generation is built on the next
    configure()."""

    def __init__(
        self,
        rank: int,
        world: int,
        store_addr: str,
        quorum_id: int,
        timeout: float,
    ) -> None:
        self.rank = rank
        self.world = world
        self.aborted = False
        self._lock = threading.Lock()
        self.peers: Dict[int, socket.socket] = {}
        # the connections by lane, then by rank: lane 0 is ``peers``, the
        # mesh; a further lane of the plain ring holds one to the right and
        # one from the left ring neighbour (one socket for both at a world
        # of two)
        self.lanes = 1
        self.lane_socks: List[Dict[int, socket.socket]] = [self.peers]
        # per-socket write serialization, by (lane, peer): collective
        # writers (dispatch/ring threads) and async p2p writers must never
        # interleave frames on one socket
        self._send_locks: Dict[Tuple[int, int], threading.Lock] = {}
        self._p2p_queues: Dict[int, "queue.Queue"] = {}
        # persistent collective workers (lazily started), one queue and one
        # thread a lane. "collwr": ring hops and full-mesh exchanges need a
        # concurrent writer so symmetric send/send never deadlocks on full
        # TCP buffers, but spawning a thread PER HOP charges every
        # collective ~2 thread creations — ruinous for the per-bucket
        # streaming pipeline where a 16-bucket plan is 16 ops instead of
        # one. One long-lived worker fed by a queue keeps the same
        # concurrency at a queue-handoff price. "fold": the plain ring's
        # accumulate, beside the dispatch thread's receive. "collwr<l>",
        # "fold<l>", "recv<l>": the ring's lane l >= 1 (_ring_pass).
        self._coll_qs: Dict[str, "queue.Queue"] = {}
        # traffic accounting (benchmarks/transport_bench.py asserts the ring
        # path's world-size-independent per-rank bytes from these)
        self.bytes_sent = 0
        self.bytes_recv = 0
        self._count_lock = threading.Lock()
        # send-side wire occupancy: seconds spent inside sendall pushing
        # frames into the link. Receive waits are deliberately NOT counted —
        # a recv blocked on a peer that is still computing would charge
        # compute time to the wire. bytes_sent / wire_busy_s is the
        # transport's delivered bandwidth (benchmarks use it for the
        # compressed-vs-raw effective-bandwidth comparison)
        self.wire_busy_s = 0.0
        # injected link faults: {frozenset({a, b}): fire_at_hop}. Shared by
        # reference with the owning ProcessGroupHost (configure() points this
        # at the PG-level dict) so tests can arm a fault before OR after the
        # generation exists. Checked only by the compressed ring's hop loop.
        self.link_faults: Dict[frozenset, int] = {}
        # per-comm compressed-collective sequence number: ops dispatch in the
        # same order on every rank (SPMD contract), so tagging hop frames
        # with (seq, attempt) lets a re-routed ring tell a stale frame from
        # a live one without a coordination round
        self.cring_seq = 0
        # links this comm has already seen die: later collectives start from
        # a topology that avoids them instead of re-discovering the failure
        # (a dead link stays avoided for the life of the generation)
        self.cring_dead: set = set()
        # the plain ring's receive scratch (ring_scratch): two chunks a
        # lane, made by the first ring and warm for every one after it
        self._ring_scratch: Optional[np.ndarray] = None

        # store_addr is "host:port/prefix"; the prefix (set per-quorum and
        # per-group-rank by the Manager, reference manager.py:703-705) plus the
        # quorum id namespaces this generation's rendezvous keys.
        host_port, _, path = store_addr.partition("/")
        prefix = f"{path or 'pg'}/{quorum_id}"
        kv = KvClient(host_port, connect_timeout=timeout)

        listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        listener.bind(("0.0.0.0", 0))
        listener.listen(world + _RING_LANES)
        port = listener.getsockname()[1]
        self._listener = listener

        # what this rank can run: lanes pay only over a fold that lets go
        # of the interpreter's lock (_native_ring says why, once)
        mine = _RING_LANES if world > 1 and _native_ring() else 1
        my_host = socket.gethostname()
        kv.set(f"{prefix}/addr_{rank}", f"{my_host}:{port}/{mine}",
               timeout=timeout)
        addrs: Dict[int, Tuple[str, int]] = {}
        for j in range(world):
            if j == rank:
                continue
            addr = kv.get(f"{prefix}/addr_{j}", timeout=timeout).decode()
            addr, _, theirs = addr.partition("/")
            host, _, p = addr.rpartition(":")
            addrs[j] = (host, int(p))
            # every rank rides the lanes the poorest of them has
            mine = min(mine, int(theirs or 1))
        self.lanes = mine
        self.lane_socks += [{} for _ in range(1, self.lanes)]
        right, left = (rank + 1) % world, (rank - 1) % world

        def dial(j: int, lane: int) -> socket.socket:
            s = socket.create_connection(addrs[j], timeout=timeout)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            _send_msg(s, pickle.dumps(("hello", rank, lane)))
            return s

        # Deterministic connection pattern: rank i dials every j < i and
        # accepts from every j > i (with a hello carrying the dialer's rank
        # and lane so accepts can arrive in any order). That mesh is lane
        # 0. For every further lane each rank dials its right ring
        # neighbour and accepts from its left one; at a world of two rank
        # 0 alone dials, and both directions share the socket as they
        # share lane 0's.
        for j in range(rank):
            self.peers[j] = dial(j, 0)
        if world > 2 or rank == 0:
            for lane in range(1, self.lanes):
                self.lane_socks[lane][right] = dial(right, lane)
        accepts_lanes = world > 2 or rank == 1
        listener.settimeout(timeout)
        for _ in range(world - 1 - rank
                       + (self.lanes - 1 if accepts_lanes else 0)):
            s, _ = listener.accept()
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            # accepted sockets need the op timeout too — dialed ones carry
            # it from create_connection; without this, waits on accepted
            # sockets are unbounded and set_timeout has nothing to update
            s.settimeout(timeout)
            tag, peer_rank, lane = pickle.loads(_recv_msg(s))
            assert tag == "hello"
            assert lane == 0 or peer_rank == left, (lane, peer_rank, left)
            self.lane_socks[lane][peer_rank] = s
        for lane, socks in enumerate(self.lane_socks):
            for j in socks:
                self._send_locks[lane, j] = threading.Lock()

    def _all_socks(self) -> List[socket.socket]:
        return [s for socks in self.lane_socks for s in socks.values()]

    def settimeout(self, timeout: float) -> None:
        with self._lock:
            for s in self._all_socks():
                try:
                    s.settimeout(timeout)
                except OSError:
                    pass

    def send_to(self, peer: int, obj: Any) -> None:
        payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
        with self._send_locks[0, peer]:
            t0 = time.perf_counter()
            _send_msg(self.peers[peer], payload)
            busy = time.perf_counter() - t0
        self._count(sent=len(payload) + _HDR.size, busy_s=busy)

    def recv_from(self, peer: int) -> Any:
        payload = _recv_msg(self.peers[peer])
        self._count(recv=len(payload) + _HDR.size)
        return pickle.loads(payload)

    def _count(self, sent: int = 0, recv: int = 0, busy_s: float = 0.0) -> None:
        """The traffic counters have writers on several threads (dispatch,
        ring lanes, p2p): one lock, or a read-modify-write is lost."""
        with self._count_lock:
            self.bytes_sent += sent
            self.bytes_recv += recv
            self.wire_busy_s += busy_s

    @staticmethod
    def _frame_bytes(buf: Any) -> List[np.ndarray]:
        """One frame's payload as flat uint8 arrays: a buffer, or a list of
        them that travel end to end under one header. (Typed ndarrays go
        through a uint8 view: memoryview can't export extended dtypes like
        ml_dtypes.bfloat16, the dominant TPU gradient dtype.)"""
        return [
            b.reshape(-1).view(np.uint8)  # reshape first: 0-d safe
            if isinstance(b, np.ndarray) else np.frombuffer(b, np.uint8)
            for b in (buf if isinstance(buf, list) else [buf])
        ]

    def send_raw(self, peer: int, buf: Any, lane: int = 0) -> float:
        """Frame a raw buffer (no pickle, no concat copy): length header,
        then the bytes straight from the caller's memory. ``lane``: which
        of the connections to a ring neighbour. Returns the seconds the
        frame spent going into the link, the same it adds to
        ``wire_busy_s`` (the ring's ``send``: one interval, one clock)."""
        runs = self._frame_bytes(buf)
        length = sum(a.size for a in runs)
        sock = self.lane_socks[lane][peer]
        with self._send_locks[lane, peer]:
            t0 = time.perf_counter()
            _send_all(sock, np.frombuffer(_HDR.pack(length), np.uint8),
                      more=length > 0)
            for a in runs:
                _send_all(sock, a)
            busy = time.perf_counter() - t0
        self._count(sent=length + _HDR.size, busy_s=busy)
        return busy

    def recv_raw_into(self, peer: int, out: Any, lane: int = 0) -> float:
        """Receive one frame directly into a writable buffer, or into a
        list of them in turn (zero staging copies on the receive side).
        Returns the ``time.perf_counter()`` at which the frame's header
        was in: before it the peer had nothing to send, after it the
        payload is being copied (the ring tells the two apart)."""
        sock = self.lane_socks[lane][peer]
        (length,) = _HDR.unpack(_recv_exact(sock, _HDR.size))
        t_header = time.perf_counter()
        runs = self._frame_bytes(out)
        if length != sum(a.size for a in runs):
            raise ValueError(
                f"frame size {length} != buffer size "
                f"{sum(a.size for a in runs)}"
            )
        for a in runs:
            _recv_all(sock, a)
        self._count(recv=length + _HDR.size)
        return t_header

    def ring_scratch(self, lanes: int, nbytes: int) -> np.ndarray:
        """This comm's receive scratch, ``[>= lanes, 2, >= nbytes]`` bytes:
        two chunks a lane, where a ring hop's incoming frame waits to be
        accumulated while the lane's next arrives in the other. Asked once
        a ring pass, for its largest frame; one op at a time."""
        have = self._ring_scratch
        if have is None or have.shape[0] < lanes or have.shape[2] < nbytes:
            self._ring_scratch = have = np.empty((lanes, 2, nbytes), np.uint8)
        return have

    def check_link_fault(self, a: int, b: int, hop: int) -> None:
        """Raise ConnectionError if an injected fault covers link (a, b) at
        this hop. A fired fault stays armed — a dead link stays dead for the
        generation, which is exactly what forces the ring to re-form around
        it rather than retry through it."""
        at_hop = self.link_faults.get(frozenset((a, b)))
        if at_hop is not None and hop >= at_hop:
            raise ConnectionError(
                f"injected link failure {a}<->{b} at hop {hop}"
            )

    def recv_raw_discard(self, peer: int) -> int:
        """Read one raw frame from ``peer`` and throw the bytes away.

        Used by the compressed ring's re-route path to drain segment frames
        that belong to an aborted attempt (their pickled header was read,
        the raw payload behind it must not be left to corrupt the next
        attempt's frame stream). Returns the discarded byte count."""
        sock = self.peers[peer]
        (length,) = _HDR.unpack(_recv_exact(sock, _HDR.size))
        got = 0
        scratch = bytearray(min(length, 1 << 20) or 1)
        mv = memoryview(scratch)
        while got < length:
            n = sock.recv_into(mv, min(length - got, len(scratch)))
            if n == 0:
                raise ConnectionError("peer closed connection")
            got += n
        self._count(recv=length + _HDR.size)
        return length

    def _coll_writer_loop(self, q: "queue.Queue") -> None:
        while True:
            item = q.get()
            if item is None:
                return
            job, done, err = item
            try:
                job()
            except BaseException as e:  # noqa: BLE001
                err.append(e)
            finally:
                done.set()

    def submit_write(self, job: Callable[[], None], lane: str = "collwr"):
        """Run ``job`` on the persistent collective-writer thread (or on
        another ``lane``'s); returns ``(done_event, err_list)``.
        Sentinel-safe vs abort: the aborted check and the enqueue share
        ``_lock`` with ``abort``'s sentinel post, so a job can never land
        behind the shutdown sentinel and leave its waiter blocked
        forever."""
        done = threading.Event()
        err: List[BaseException] = []
        with self._lock:
            if self.aborted:
                raise RuntimeError("communicator aborted")
            q = self._coll_qs.get(lane)
            if q is None:
                q = self._coll_qs[lane] = queue.Queue()
                threading.Thread(
                    target=self._coll_writer_loop, args=(q,),
                    daemon=True, name=f"pg_host_{lane}_r{self.rank}",
                ).start()
            q.put((job, done, err))
        return done, err

    def exchange(self, payloads: Dict[int, Any]) -> Dict[int, Any]:
        """Send payloads[r] to each rank r and receive one object from every
        peer. Deadlock-free: the collective-writer worker streams our sends
        while the caller thread drains receives."""

        def _writes() -> None:
            for peer in sorted(payloads):
                if peer != self.rank:
                    self.send_to(peer, payloads[peer])

        done, err = self.submit_write(_writes)
        out: Dict[int, Any] = {}
        if self.rank in payloads:
            out[self.rank] = payloads[self.rank]
        for peer in range(self.world):
            if peer == self.rank:
                continue
            out[peer] = self.recv_from(peer)
        done.wait()
        if err:
            raise err[0]
        return out

    def p2p_send_async(self, peer: int, job, fut, fail) -> None:
        """Run a p2p write job on the per-peer writer thread (strict FIFO
        per peer) instead of the dispatch thread. Rationale: symmetric
        send/send between two ranks would block both dispatch threads in
        sendall on full TCP buffers, and the matching recvs — queued behind
        them — could never drain (the deadlock the exchange/ring writer
        threads already guard against)."""
        import queue as _q

        def _writer(wq: "_q.Queue") -> None:
            while True:
                item = wq.get()
                if item is None:
                    return
                jb, ft, fl = item
                try:
                    jb()
                    ft.set_result(None)
                except BaseException as e:  # noqa: BLE001
                    err = e if isinstance(e, Exception) else RuntimeError(str(e))
                    fl(err)
                    try:
                        ft.set_exception(err)
                    except RuntimeError:
                        pass

        with self._lock:
            if self.aborted:
                raise RuntimeError("communicator aborted")
            q = self._p2p_queues.get(peer)
            if q is None:
                q = _q.Queue()
                self._p2p_queues[peer] = q
                threading.Thread(
                    target=_writer, args=(q,), daemon=True,
                    name=f"pg_host_p2p_r{self.rank}_to{peer}",
                ).start()
            # enqueue under the lock: abort() posts its shutdown sentinel
            # under the same lock, so a job can never land behind the
            # sentinel and leave its future unresolved
            q.put((job, fut, fail))

    def abort(self) -> None:
        with self._lock:
            self.aborted = True
            for q in self._p2p_queues.values():
                q.put(None)
            for q in self._coll_qs.values():
                q.put(None)
            for s in self._all_socks():
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
                try:
                    s.close()
                except OSError:
                    pass
            try:
                self._listener.close()
            except OSError:
                pass


# Payloads at or above this take the bandwidth-optimal ring; below it the
# full-mesh exchange wins on latency (one round-trip vs 2*(world-1)).
_RING_MIN_BYTES = 64 * 1024
# A segment crosses a ring hop as a train of frames of at most this many
# bytes: the accumulate of one overlaps the wire of the next, and a hop can
# forward a frame while the rest of its segment is still arriving.
_RING_CHUNK_BYTES = 4 * 1024 * 1024
# The frames of a segment travel on this many lanes: connections to each
# ring neighbour, each with a receiver, a fold and a writer thread of its
# own. One loopback stream carries what one thread pair's copies carry, and
# streams add up (PERF.md section 6, PR 45: the sweep that set this).
_RING_LANES = 4
# Lanes pay where every lane gets at least two frames of a segment; a
# smaller segment rides lane 0 alone.
_RING_LANE_FLOOR_BYTES = 2 * _RING_LANES * _RING_CHUNK_BYTES

def _fold(op: ReduceOp, dst: np.ndarray, src: np.ndarray) -> None:
    """The ring's accumulate of one received run into its place:
    :func:`_accum`, but for bfloat16 sums the native loop (same bits)."""
    native = _native_ring()
    if (native is not None and dst.dtype.name == "bfloat16"
            and op in (ReduceOp.SUM, ReduceOp.AVG) and dst.shape == src.shape
            and dst.dtype == src.dtype and dst.flags.writeable
            and dst.flags.c_contiguous and src.flags.c_contiguous):
        native.bf16_add(dst.ctypes.data, src.ctypes.data, dst.size)
    else:
        _accum(op, dst, src)


class _RingFailed(ConnectionError):
    """Raised to a ring thread that waited on a count another failed."""


class _RingCount:
    """A count of the frames of a ring lane that one of its threads is done
    with, in receive order, for another to wait on: the moment each was
    done, as the thread that did it stamped it."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._at: List[float] = []
        self._failed = False

    def advance(self, at: float) -> None:
        """One more frame is done, as of ``at``: the ``perf_counter()`` its
        thread read when the work ended (no clock is read here)."""
        with self._cond:
            self._at.append(at)
            self._cond.notify_all()

    def fail(self) -> None:
        with self._cond:
            self._failed = True
            self._cond.notify_all()

    def wait_past(self, frame: int) -> float:
        """Until frame number ``frame`` is done. A receiving thread always
        ends, by finishing or by its socket's timeout or the op's watchdog
        closing the socket, and fails every count when it fails: this needs
        no clock. Returns the hand-off: where it had to block, the seconds
        from that frame's ``advance`` stamp until this thread ran again (the
        lock, the notify, the wake-up and the interpreter's lock between two
        threads of a lane); 0.0 where the frame was done already."""
        with self._cond:
            blocked = False
            while len(self._at) <= frame and not self._failed:
                blocked = True
                self._cond.wait()
            if self._failed:
                raise _RingFailed("the ring failed on another thread")
            return time.perf_counter() - self._at[frame] if blocked else 0.0


def _ring_pass(
    comm: "_Comm", parts: List[np.ndarray], op: ReduceOp
) -> Tuple[int, int, List[Dict[str, float]]]:
    """Ring reduce-scatter + allgather over ``parts``, 1-D arrays of one
    dtype that are reduced where they lie, as if laid end to end. Returns
    the frames of a hop, the lanes they rode, and each lane's clock.

    The ``world`` segments are ranges of that concatenation at multiples of
    ``ceil(len / world)`` (the last ones shorter, or empty). Hop ``h`` of
    ``2 * (world - 1)`` sends segment ``rank - h`` and receives segment
    ``rank - h - 1``, a frame of ``_RING_CHUNK_BYTES`` at a time. Frame
    ``k`` of every segment belongs to lane ``k % lanes``: all the comm's
    lanes where a segment holds ``_RING_LANE_FLOOR_BYTES``, else lane 0
    alone. Three threads run a lane. Its receiver (lane 0's is this one,
    the PG's dispatch thread) takes the lane's frames off its socket: for
    the first ``world - 1`` hops into the lane's two scratch chunks in
    turn, after that straight into the segment. Its fold accumulates a
    scratch chunk into its segment while the next frame arrives in the
    other. Its writer sends every hop's frames (both sides send first:
    with synchronous sockets that would deadlock on full TCP buffers), each
    as soon as the hop before is done with it: the hops overlap each other,
    the accumulate the wire, and the lanes one another. Lanes share nothing
    but failure: the thread that fails fails every lane's counts, and this
    thread raises once every other has ended.

    A lane's clock is where its three threads spent the pass, in seconds,
    each a plain float that the one thread which owns it adds up around
    what it does anyway and leaves in the lane's dict as it ends. The
    receiver: ``hdr_wait`` blocked for a frame's header (the left neighbour
    had nothing to send), of which ``first_wait`` for the lane's first,
    which was in at ``first_at`` (``perf_counter()``; absent: the lane had
    no frame) as its last payload was at ``last_at``; ``recv`` copying the
    payload; ``slot_wait`` until its scratch chunk was folded. The fold:
    ``arrive_wait`` with nothing arrived, ``fold`` inside :func:`_fold`.
    The writer: ``ready_wait`` until the frame was reduced or received
    here, ``send`` inside ``send_raw``. ``handoff_recv`` / ``_fold`` /
    ``_send``: of each thread's waits on a count, the part after the frame
    WAS done (:meth:`_RingCount.wait_past`): what a lane pays for being
    three threads. Read only after a pass that ended well: a failed one
    raises.
    """
    world, rank = comm.world, comm.rank
    right, left = (rank + 1) % world, (rank - 1) % world
    dtype = parts[0].dtype
    ends = np.cumsum([p.size for p in parts])
    total = int(ends[-1])
    seg_len = -(-total // world)
    step = max(1, _RING_CHUNK_BYTES // dtype.itemsize)
    lanes = (comm.lanes
             if seg_len * dtype.itemsize >= _RING_LANE_FLOOR_BYTES else 1)

    def frames(seg: int) -> List[Tuple[int, int]]:
        lo, hi = min(seg * seg_len, total), min((seg + 1) * seg_len, total)
        return [(a, min(a + step, hi)) for a in range(lo, hi, step)]

    seg_frames = [frames(seg) for seg in range(world)]

    def views(a: int, b: int) -> List[np.ndarray]:
        out = []
        i = int(np.searchsorted(ends, a, side="right"))
        while a < b:
            start, stop = int(ends[i]) - parts[i].size, min(b, int(ends[i]))
            if stop > a:  # a leaf of no elements has no bytes to frame
                out.append(parts[i][a - start:stop - start])
            a, i = stop, i + 1
        return out

    scratch = comm.ring_scratch(lanes, step * dtype.itemsize)
    hops = 2 * (world - 1)
    counts: List[_RingCount] = []
    clocks: List[Dict[str, float]] = []

    def fail_all() -> None:
        for count in counts:  # whoever waits, on whichever lane
            count.fail()

    def failing(job: Callable[[], None]) -> Callable[[], None]:
        def run() -> None:
            try:
                job()
            except BaseException:
                fail_all()
                raise
        return run

    def lane_jobs(lane: int) -> Tuple[Callable[[], None], ...]:
        """Lane ``lane``'s receiver, fold and writer."""
        mine = [f[lane::lanes] for f in seg_frames]
        recv_frames = [mine[(rank - h - 1) % world] for h in range(hops)]
        # the number, in the lane's receive order, of each hop's first frame
        first = [0, *itertools.accumulate(len(f) for f in recv_frames)]
        to_fold = [ab for f in recv_frames[:world - 1] for ab in f]
        # frames in receive order: those that have arrived in the scratch,
        # and those that are where they belong (folded, or received in place)
        arrived, done = _RingCount(), _RingCount()
        counts.extend((arrived, done))
        # lane 0 is the comm's mesh socket and today's call
        on = {"lane": lane} if lane else {}

        def waiting(k: int, a: int, b: int) -> np.ndarray:
            """Where frame ``k`` of the reduce-scatter waits to be folded."""
            return scratch[lane, k % 2, :(b - a) * dtype.itemsize].view(dtype)

        clock: Dict[str, float] = {}
        clocks.append(clock)
        now = time.perf_counter

        def receives() -> None:
            hdr_wait = recv = slot_wait = handoff = 0.0
            header0: Optional[Tuple[float, float]] = None  # (wait, in at)

            def frame_into(out: Any, since: float) -> float:
                """One frame off the socket; when it was all here."""
                nonlocal hdr_wait, recv, header0
                t_header = comm.recv_raw_into(left, out, **on)
                t = now()
                hdr_wait += t_header - since
                recv += t - t_header
                if header0 is None:
                    header0 = (t_header - since, t_header)
                return t

            for k, (a, b) in enumerate(to_fold):
                t0 = t = now()
                if k >= 2:  # what waited in this chunk is folded
                    handoff += done.wait_past(k - 2)
                    t = now()
                    slot_wait += t - t0
                arrived.advance(frame_into(waiting(k, a, b), t))
            t0 = now()
            handoff += done.wait_past(len(to_fold) - 1)
            slot_wait += now() - t0
            for h in range(world - 1, hops):
                for a, b in recv_frames[h]:
                    done.advance(frame_into(views(a, b), now()))
            clock.update(hdr_wait=hdr_wait, recv=recv, slot_wait=slot_wait,
                         handoff_recv=handoff)
            if header0 is not None:
                clock["first_wait"], clock["first_at"] = header0
                clock["last_at"] = now()

        def folds() -> None:
            arrive_wait = fold = handoff = 0.0
            for k, (a, b) in enumerate(to_fold):
                got, into = waiting(k, a, b), views(a, b)
                t0 = now()
                handoff += arrived.wait_past(k)
                t1 = now()
                for v in into:
                    _fold(op, v, got[:v.size])
                    got = got[v.size:]
                t2 = now()
                arrive_wait += t1 - t0
                fold += t2 - t1
                done.advance(t2)
            clock.update(arrive_wait=arrive_wait, fold=fold,
                         handoff_fold=handoff)

        def writes() -> None:
            ready_wait = send = handoff = 0.0
            for h in range(hops):
                for k, (a, b) in enumerate(mine[(rank - h) % world]):
                    if h:
                        t0 = now()
                        handoff += done.wait_past(first[h - 1] + k)
                        ready_wait += now() - t0
                    send += comm.send_raw(right, views(a, b), **on)
            clock.update(ready_wait=ready_wait, send=send,
                         handoff_send=handoff)

        return failing(receives), failing(folds), failing(writes)

    jobs = [lane_jobs(lane) for lane in range(lanes)]
    pending: List[Tuple[threading.Event, List[BaseException]]] = []
    errors: List[BaseException] = []
    try:
        for lane, (receives, folds, writes) in enumerate(jobs):
            tag = str(lane) if lane else ""
            pending.append(comm.submit_write(writes, lane="collwr" + tag))
            pending.append(comm.submit_write(folds, lane="fold" + tag))
            if lane:
                pending.append(comm.submit_write(receives, lane="recv" + tag))
        jobs[0][0]()
    except BaseException as e:  # noqa: BLE001
        fail_all()  # submit_write's own failure (aborted) had not yet
        errors.append(e)
    # no thread is left in the buffers when the op resolves: a failed count
    # wakes who waits on one, the socket's timeout or the watchdog's abort
    # ends who waits on a peer
    for ended, err in pending:
        ended.wait()
        errors.extend(err)
    if errors:
        # the cause, not a thread that was only told of it
        raise next((e for e in errors if not isinstance(e, _RingFailed)),
                   errors[0])
    return len(seg_frames[(rank - 1) % world]), lanes, clocks


# What a lane's threads do with a pass, by thread: the receiver (the header
# waits after the pass's first header, the payloads, the waits for a scratch
# chunk, and the interval these three lie in, from the first moment it
# counts to its last payload: what they fall short of it is Python between
# frames), the fold (nothing arrived, the add), the writer (nothing ready,
# the link), and the hand-offs between the three.
_RING_TERMS = ("recv_wait", "recv", "slot_wait", "recv_span", "arrive_wait",
               "fold", "ready_wait", "send", "handoff")


def _ring_terms(
    clock: Dict[str, float], t_first: Optional[float]
) -> Dict[str, float]:
    """One lane's clock of one pass (:func:`_ring_pass`) as the seconds of
    ``_RING_TERMS``. Of the lane's first header wait only what came after
    ``t_first`` is a ``recv_wait``: before that moment nothing had reached
    this rank on any lane, and the wait is the op's entry wait, which
    ``t_first`` itself tells."""
    terms = {term: clock.get(term, 0.0) for term in _RING_TERMS}
    terms["recv_wait"] = clock["hdr_wait"]
    if "first_at" in clock:
        began = clock["first_at"] - clock["first_wait"]
        entry = max(0.0, min(t_first, clock["first_at"]) - began)
        terms["recv_wait"] -= entry
        terms["recv_span"] = clock["last_at"] - began - entry
    terms["handoff"] = (clock["handoff_recv"] + clock["handoff_fold"]
                        + clock["handoff_send"])
    return terms


def _ring_allreduce(
    comm: "_Comm",
    leaves: List[np.ndarray],
    op: ReduceOp,
    donate: bool = False,
    info: Optional[Dict[str, Any]] = None,
) -> List[np.ndarray]:
    """Bandwidth-optimal allreduce: ring reduce-scatter + ring allgather
    (:func:`_ring_pass`), in the leaves' own memory where the caller gave
    it up.

    Per-rank traffic is 2*(world-1)/world * payload — independent of world
    size — versus the full-mesh exchange's (world-1) * payload (the
    round-1 data plane's O(world x bytes) weakness). Frames are raw bytes
    straight out of the arrays: no pickling, and nothing is packed.

    A leaf is reduced in place, and is its own result, when the call
    ``donate``s it and it is a writable C-contiguous array whose reduction
    keeps its dtype (everything but AVG of integers). Any other leaf is
    left as it was: the ring works in one private copy of it, which is the
    result. Leaves of one dtype ride one ring (gradients are almost always
    a single dtype, so this is one ring in practice). Matches
    ``_reduce_np``'s semantics: accumulate in the input dtype, AVG divides
    by world at the end. ``info`` receives ``inplace`` (1 when no leaf was
    copied), ``chunks`` (frames a hop) and ``lanes`` (the connections to a
    neighbour that the last dtype's frames rode), and once every pass has
    ended well the ring's own account of its time (:func:`_ring_terms`):
    ``t_first``, the ``perf_counter()`` at which the first header of the
    first pass was in on any lane (before it no peer byte had reached this
    rank), and for each of ``_RING_TERMS`` the mean over the lanes
    (``<term>_us``) and the largest lane's (``<term>_us_max``), whole
    microseconds, summed over the passes.
    """
    out: List[np.ndarray] = []
    for a in leaves:
        keeps_dtype = not (
            op == ReduceOp.AVG and np.issubdtype(a.dtype, np.integer)
        )
        if (donate and keeps_dtype and a.flags.c_contiguous
                and a.flags.writeable):
            out.append(a)
        else:
            out.append(np.array(a, order="C", copy=True))
    info = {} if info is None else info
    info["inplace"] = int(all(o is a for o, a in zip(out, leaves)))
    info["chunks"], info["lanes"] = 0, 1

    groups: Dict[Any, List[int]] = {}
    for i, a in enumerate(out):
        groups.setdefault(a.dtype, []).append(i)
    t_first: Optional[float] = None
    spent = {term: [0.0, 0.0] for term in _RING_TERMS}  # lanes' mean, max
    for _dtype, idxs in sorted(groups.items(), key=lambda kv: str(kv[0])):
        chunks, info["lanes"], clocks = _ring_pass(
            comm, [out[i].reshape(-1) for i in idxs], op)
        info["chunks"] += chunks
        if t_first is None:  # a later pass's first wait is a recv_wait
            t_first = min((c["first_at"] for c in clocks if "first_at" in c),
                          default=None)
        by_lane = [_ring_terms(c, t_first) for c in clocks]
        for term, sums in spent.items():
            sums[0] += sum(lane[term] for lane in by_lane) / len(by_lane)
            sums[1] += max(lane[term] for lane in by_lane)
        if op == ReduceOp.AVG:
            for i in idxs:
                if np.issubdtype(out[i].dtype, np.integer):
                    out[i] = out[i] / comm.world  # float, as _reduce_np
                else:
                    out[i] /= comm.world
    if t_first is not None:
        info["t_first"] = t_first
        for term, (mean, most) in spent.items():
            info[term + "_us"] = int(mean * 1e6)
            info[term + "_us_max"] = int(most * 1e6)
    return out


class _LinkFailure(Exception):
    """One ring hop's link is dead; carries the (lo, hi) rank pair."""

    def __init__(self, a: int, b: int) -> None:
        self.pair = (min(a, b), max(a, b))
        super().__init__(
            f"ring link {self.pair[0]}<->{self.pair[1]} failed"
        )


def _ring_order(world: int, dead: "set") -> Optional[List[int]]:
    """Deterministic rank ordering whose ring adjacencies (wraparound
    included) avoid every dead link. Every rank computes this from the same
    dead set, so the re-formed ring needs no extra coordination round.
    Returns None when no such ordering exists (e.g. world=2 with its only
    link dead)."""
    if not dead:
        return list(range(world))

    def _ok(order: List[int]) -> bool:
        return all(
            frozenset((order[i], order[(i + 1) % world])) not in dead
            for i in range(world)
        )

    base = list(range(world))
    if _ok(base):
        return base
    if world <= 8:
        import itertools

        # rotations of a valid cycle are the same ring, so pinning rank 0
        # first loses nothing and caps the search at (world-1)!
        for perm in itertools.permutations(range(1, world)):
            cand = [0, *perm]
            if _ok(cand):
                return cand
        return None
    # large worlds: greedy chain extension — dead links are few in practice,
    # and a miss here degrades to the pre-existing swallowed-step behavior
    order = [0]
    rest = list(range(1, world))
    while rest:
        nxt = next(
            (r for r in rest if frozenset((order[-1], r)) not in dead), None
        )
        if nxt is None:
            return None
        order.append(nxt)
        rest.remove(nxt)
    return order if _ok(order) else None


def _chain_order(world: int, dead: "set") -> Optional[List[int]]:
    """Hamiltonian path over healthy links — the fallback for dead-link
    sets that break every cycle but not every path. Any single dead link at
    world<=3 is in this class (a 3-cycle needs all three edges), so this is
    what makes small-world failover possible at all. Deterministic for the
    same reason as _ring_order."""
    def _ok(order) -> bool:
        return all(
            frozenset((order[i], order[i + 1])) not in dead
            for i in range(world - 1)
        )

    base = list(range(world))
    if _ok(base):
        return base
    if world <= 8:
        import itertools

        for perm in itertools.permutations(range(world)):
            if perm[0] > perm[-1]:
                continue  # a path equals its reverse; keep one canonical form
            if _ok(perm):
                return list(perm)
        return None
    order = [0]
    rest = list(range(1, world))
    while rest:
        nxt = next(
            (r for r in rest if frozenset((order[-1], r)) not in dead), None
        )
        if nxt is None:
            return None
        order.append(nxt)
        rest.remove(nxt)
    return order


def _flood_reroute(
    comm: "_Comm", left: int, right: int, seq: int, attempt: int, pair
) -> None:
    """Best-effort broadcast of a dead link to both ring neighbours.

    Each rank that learns of the failure forwards before restarting, so the
    signal chains rightward around the ring (every rank's blocking recv is
    from its left) and unblocks everyone. Sends are small pickled frames on
    otherwise-healthy sockets; failures (e.g. the dead link itself) are
    swallowed — the flood only needs one surviving direction."""
    msg = ("creroute", seq, attempt, (min(pair), max(pair)))
    for nb in {left, right}:
        if nb == comm.rank:
            continue
        try:
            comm.send_to(nb, msg)
        except Exception:  # noqa: BLE001 - best-effort by design
            pass


def _drain_stale_frames(
    comm: "_Comm", skip_peer: int, seq: int, attempt: int,
    quiet_s: float = 0.05,
) -> None:
    """Best-effort sweep of every peer socket (except the new left, whose
    stale frames the hop recv loop handles in-line) at the start of a
    re-routed attempt. The aborted attempt may have left one hop's frames
    queued on a socket the new ring never reads — and a peer's sendall can
    be blocked mid-frame on it, so draining here is also what unblocks that
    peer's collective writer. A current-attempt re-route signal found while
    draining propagates as _LinkFailure."""
    for peer in sorted(comm.peers):
        if peer == skip_peer or peer == comm.rank:
            continue
        sock = comm.peers[peer]
        try:
            old = sock.gettimeout()
        except OSError:
            continue
        try:
            while True:
                sock.settimeout(quiet_s)
                try:
                    hdr = comm.recv_from(peer)
                except OSError:
                    break  # quiet (or dead) socket — nothing to drain
                if not (isinstance(hdr, tuple) and len(hdr) == 4):
                    raise RuntimeError(
                        f"compressed ring desync draining rank {peer}: "
                        f"{hdr!r}"
                    )
                tag, h_seq, h_attempt, rest = hdr
                stale = h_seq < seq or (
                    h_seq == seq and h_attempt < attempt
                )
                if tag == "cseg" and stale:
                    # body frames follow; read them under the op timeout
                    sock.settimeout(old)
                    comm.recv_raw_discard(peer)
                    comm.recv_raw_discard(peer)
                    continue
                if tag == "creroute":
                    if stale:
                        continue
                    raise _LinkFailure(*rest)
                raise RuntimeError(
                    f"compressed ring desync draining rank {peer}: "
                    f"tag={tag!r} seq={h_seq} attempt={h_attempt}"
                )
        finally:
            try:
                sock.settimeout(old)
            except OSError:
                pass


def _recv_compressed_hop(
    comm: "_Comm", left: int, seq: int, attempt: int, hop: int,
    out_q: np.ndarray, out_s: np.ndarray,
) -> None:
    """Receive one compressed-ring hop (header + payload + scales frames),
    draining stale frames from aborted attempts / earlier collectives and
    converting re-route signals into _LinkFailure."""
    while True:
        hdr = comm.recv_from(left)
        if not (isinstance(hdr, tuple) and len(hdr) == 4):
            raise RuntimeError(
                f"unexpected frame on compressed ring: {hdr!r}"
            )
        tag, h_seq, h_attempt, rest = hdr
        stale = h_seq < seq or (h_seq == seq and h_attempt < attempt)
        if tag == "cseg":
            if stale:
                # the aborted attempt's segment bytes follow the header;
                # drain both frames or they corrupt this attempt's stream
                comm.recv_raw_discard(left)
                comm.recv_raw_discard(left)
                continue
            if h_seq != seq or h_attempt != attempt or rest != hop:
                raise RuntimeError(
                    "compressed ring desync: got "
                    f"seq={h_seq} attempt={h_attempt} hop={rest}, expected "
                    f"seq={seq} attempt={attempt} hop={hop}"
                )
            comm.recv_raw_into(left, out_q)
            comm.recv_raw_into(left, out_s)
            return
        if tag == "creroute":
            if stale:
                continue  # duplicate from an already-handled flood
            raise _LinkFailure(*rest)
        raise RuntimeError(f"unexpected compressed ring tag {tag!r}")


def _compressed_ring_pass(
    comm: "_Comm",
    wire,
    quantize,
    dequantize,
    Q: np.ndarray,
    S: np.ndarray,
    rows: int,
    seg_rows: int,
    op: ReduceOp,
    order: List[int],
    seq: int,
    attempt: int,
):
    """One attempt of the compressed ring over ``order``.

    Reduce-scatter hops carry compressed segments; each hop dequantizes the
    incoming segment, accumulates in f32, and requantizes the accumulated
    segment for the next hop (hop 0 forwards the original codes — no extra
    rounding). The allgather phase circulates the reduced compressed
    segments verbatim. Restart-safe: all state derives from the immutable
    (Q, S) input codes, so a _LinkFailure anywhere re-runs cleanly."""
    world = len(order)
    pos = order.index(comm.rank)
    right = order[(pos + 1) % world]
    left = order[(pos - 1) % world]
    row = int(wire.row)
    seg_elems = seg_rows * row

    if attempt > 0:
        _drain_stale_frames(comm, left, seq, attempt)

    # f32 working accumulation, one slab per chunk (chunk j = rows
    # [j*seg_rows, (j+1)*seg_rows) of the padded code matrix). Slabs are
    # decoded lazily at their first accumulate — the chunk this rank sends
    # at hop 0 leaves as the original codes and never needs an f32 copy
    acc = np.empty((world, seg_elems), np.float32)

    def _own_slab(j: int) -> np.ndarray:
        return dequantize(
            Q[j * seg_rows:(j + 1) * seg_rows],
            S[j * seg_rows:(j + 1) * seg_rows],
            seg_elems,
            np.float32,
        )
    recv_q = np.empty((seg_rows, row), np.uint8)
    recv_s = np.empty(seg_rows, np.float32)
    hop = 0

    def _send_recv(send_q: np.ndarray, send_s: np.ndarray) -> None:
        nonlocal hop
        this_hop = hop
        try:
            comm.check_link_fault(comm.rank, right, this_hop)
        except ConnectionError as e:
            _flood_reroute(comm, left, right, seq, attempt,
                           (comm.rank, right))
            raise _LinkFailure(comm.rank, right) from e
        try:
            comm.check_link_fault(left, comm.rank, this_hop)
        except ConnectionError as e:
            _flood_reroute(comm, left, right, seq, attempt,
                           (left, comm.rank))
            raise _LinkFailure(left, comm.rank) from e
        hdr = ("cseg", seq, attempt, this_hop)

        def _writes() -> None:
            comm.send_to(right, hdr)
            comm.send_raw(right, send_q)
            comm.send_raw(right, send_s)

        done, err = comm.submit_write(_writes)
        try:
            _recv_compressed_hop(
                comm, left, seq, attempt, this_hop, recv_q, recv_s
            )
        except _LinkFailure as lf:
            # forward the flood before restarting so the signal keeps
            # chaining rightward past us
            _flood_reroute(comm, left, right, seq, attempt, lf.pair)
            raise
        except (ConnectionError, OSError, ValueError) as e:
            _flood_reroute(comm, left, right, seq, attempt,
                           (left, comm.rank))
            raise _LinkFailure(left, comm.rank) from e
        finally:
            done.wait()
        if err:
            e = err[0]
            _flood_reroute(comm, left, right, seq, attempt,
                           (comm.rank, right))
            raise _LinkFailure(comm.rank, right) from e
        hop += 1

    # reduce-scatter: after world-1 hops this rank holds the fully reduced
    # chunk (pos+1) % world in f32
    for step in range(world - 1):
        s_idx = (pos - step) % world
        r_idx = (pos - step - 1) % world
        if step == 0:
            sq = Q[s_idx * seg_rows:(s_idx + 1) * seg_rows]
            ss = S[s_idx * seg_rows:(s_idx + 1) * seg_rows]
        else:
            sq, ss, _ = quantize(acc[s_idx], row=row)
            ss = np.ascontiguousarray(ss, dtype=np.float32)
        _send_recv(sq, ss)
        # each r_idx is distinct across the sweep, so first touch decodes
        # this rank's own contribution and the hop's payload lands on top
        acc[r_idx] = _own_slab(r_idx)
        acc[r_idx] += dequantize(recv_q, recv_s, seg_elems, np.float32)

    own = (pos + 1) % world
    if op == ReduceOp.AVG:
        acc[own] /= world
    q_own, s_own, _ = quantize(acc[own], row=row)

    Qr = np.empty((world, seg_rows, row), np.uint8)
    Sr = np.empty((world, seg_rows), np.float32)
    Qr[own] = q_own
    Sr[own] = np.ascontiguousarray(s_own, dtype=np.float32)

    # allgather: circulate the reduced compressed segments verbatim
    for step in range(world - 1):
        s_idx = (pos + 1 - step) % world
        r_idx = (pos - step) % world
        _send_recv(Qr[s_idx], Sr[s_idx])
        Qr[r_idx] = recv_q
        Sr[r_idx] = recv_s

    from torchft_tpu.ops.quantization import CompressedWire

    return CompressedWire(
        mode=wire.mode,
        payload=Qr.reshape(world * seg_rows, row)[:rows].copy(),
        scales=Sr.reshape(-1)[:rows].copy(),
        n=wire.n,
        dtype=wire.dtype,
        row=row,
    )


def _compressed_chain_pass(
    comm: "_Comm",
    wire,
    quantize,
    dequantize,
    Q: np.ndarray,
    S: np.ndarray,
    rows: int,
    op: ReduceOp,
    order: List[int],
    seq: int,
    attempt: int,
):
    """Degraded open-chain attempt used when the dead-link set leaves no
    ring but still admits a Hamiltonian path. The reduce sweeps head→tail
    (each hop dequantizes, accumulates in f32, requantizes the full
    buffer), the tail finishes the op (AVG divide) and the reduced codes
    ride back tail→head verbatim. Each rank moves 2 full-buffer hops of
    wire instead of the ring's 2×(1/world) segments — correctness over
    bandwidth, which is the right trade for a re-routed slow step.

    Hop labels are global chain positions (reduce hop i = order[i]→
    order[i+1], broadcast hop (w-1)+(w-1-i) = order[i+1]→order[i]) so both
    endpoints of a hop agree without per-rank counters."""
    world = len(order)
    pos = order.index(comm.rank)
    # comm.rank as a sentinel "no neighbour": _flood_reroute skips self
    left = order[pos - 1] if pos > 0 else comm.rank
    right = order[pos + 1] if pos < world - 1 else comm.rank
    row = int(wire.row)
    pad_rows = Q.shape[0]

    if attempt > 0:
        _drain_stale_frames(comm, left if pos > 0 else right, seq, attempt)

    recv_q = np.empty((pad_rows, row), np.uint8)
    recv_s = np.empty(pad_rows, np.float32)

    def _checked(a: int, b: int, hop: int) -> None:
        try:
            comm.check_link_fault(a, b, hop)
        except ConnectionError as e:
            _flood_reroute(comm, left, right, seq, attempt, (a, b))
            raise _LinkFailure(a, b) from e

    def _send(peer: int, hop: int, sq: np.ndarray, ss: np.ndarray) -> None:
        _checked(comm.rank, peer, hop)
        hdr = ("cseg", seq, attempt, hop)

        def _writes() -> None:
            comm.send_to(peer, hdr)
            comm.send_raw(peer, sq)
            comm.send_raw(peer, ss)

        done, err = comm.submit_write(_writes)
        done.wait()
        if err:
            _flood_reroute(comm, left, right, seq, attempt,
                           (comm.rank, peer))
            raise _LinkFailure(comm.rank, peer) from err[0]

    def _recv(peer: int, hop: int) -> None:
        _checked(peer, comm.rank, hop)
        try:
            _recv_compressed_hop(
                comm, peer, seq, attempt, hop, recv_q, recv_s
            )
        except _LinkFailure as lf:
            _flood_reroute(comm, left, right, seq, attempt, lf.pair)
            raise
        except (ConnectionError, OSError, ValueError) as e:
            _flood_reroute(comm, left, right, seq, attempt,
                           (peer, comm.rank))
            raise _LinkFailure(peer, comm.rank) from e

    # reduce sweep head → tail
    acc = None
    if pos > 0:
        _recv(left, pos - 1)
        acc = dequantize(Q, S, Q.size, np.float32)
        acc += dequantize(recv_q, recv_s, Q.size, np.float32)
    if pos < world - 1:
        if acc is None:  # chain head forwards its original codes unrounded
            sq, ss = Q, S
        else:
            sq, ss, _ = quantize(acc, row=row)
            ss = np.ascontiguousarray(ss, dtype=np.float32)
        _send(right, pos, sq, ss)
        # broadcast sweep tail → head
        _recv(right, (world - 1) + (world - 1 - pos))
        out_q = recv_q.copy()
        out_s = recv_s.copy()
    else:
        if op == ReduceOp.AVG:
            acc /= world
        oq, os_, _ = quantize(acc, row=row)
        out_q = np.asarray(oq)
        out_s = np.ascontiguousarray(os_, dtype=np.float32)
    if pos > 0:
        _send(left, (world - 1) + (world - 1 - (pos - 1)), out_q, out_s)

    from torchft_tpu.ops.quantization import CompressedWire

    return CompressedWire(
        mode=wire.mode,
        payload=out_q.reshape(pad_rows, row)[:rows].copy(),
        scales=out_s.reshape(-1)[:rows].copy(),
        n=wire.n,
        dtype=wire.dtype,
        row=row,
    )


def _ring_allreduce_compressed(
    comm: "_Comm",
    wire,
    op: ReduceOp,
    timeout: float = 60.0,
    on_reroute=None,
):
    """Compressed ring allreduce with mid-collective link failover.

    The FT layer lives *inside* the collective (R2CCL, PAPERS.md): a hop
    failure — socket error or injected ``link_faults`` entry — floods a
    re-route signal around the ring, every rank restarts under the shared
    ``retry.py`` policy (TORCHFT_RETRY_*), and the ring re-forms over a
    deterministic ordering that avoids every known-dead link. The step
    finishes as a re-routed slow step instead of a swallowed one.
    ``on_reroute(pair, attempt)`` fires once per re-route on the rank(s)
    that initiated or learned of it, before the restart."""
    from torchft_tpu.ops.quantization import codec
    from torchft_tpu.retry import RetryPolicy, retry_call

    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        raise ValueError(
            f"compressed allreduce supports SUM and AVG, got {op}"
        )
    quantize, dequantize = codec(wire.mode)
    world = comm.world
    seq = comm.cring_seq
    comm.cring_seq = seq + 1

    scales = np.asarray(wire.scales, dtype=np.float32).reshape(-1)
    rows = int(scales.size)
    row = int(wire.row)
    seg_rows = max(1, -(-rows // world))
    pad_rows = seg_rows * world
    Q = np.zeros((pad_rows, row), np.uint8)
    Q[:rows] = np.asarray(wire.payload).reshape(rows, row)
    S = np.ones(pad_rows, np.float32)
    S[:rows] = scales

    # seed from the comm's known-dead set: once a link has killed one
    # collective, later collectives on this generation route around it from
    # attempt 0 instead of re-discovering the failure every step
    dead: set = set(comm.cring_dead)
    state = {"attempt": 0}

    def _attempt(_remaining: float):
        order = _ring_order(world, dead)
        chain = None
        if order is None:
            # no surviving cycle — fall back to an open chain (any single
            # dead link at world<=3 lands here: a 3-cycle needs all edges)
            chain = _chain_order(world, dead)
            if chain is None:
                raise RuntimeError(
                    f"compressed ring cannot re-form at world={world}: "
                    f"dead links "
                    f"{sorted(tuple(sorted(d)) for d in dead)} leave no "
                    "valid ring or chain ordering"
                )
        try:
            if order is not None:
                return _compressed_ring_pass(
                    comm, wire, quantize, dequantize, Q, S, rows, seg_rows,
                    op, order, seq, state["attempt"],
                )
            return _compressed_chain_pass(
                comm, wire, quantize, dequantize, Q, S, rows,
                op, chain, seq, state["attempt"],
            )
        except _LinkFailure as lf:
            dead.add(frozenset(lf.pair))
            comm.cring_dead.add(frozenset(lf.pair))
            state["attempt"] += 1
            if on_reroute is not None:
                try:
                    on_reroute(lf.pair, state["attempt"])
                except Exception:  # noqa: BLE001 - observer must not kill op
                    pass
            raise

    return retry_call(
        _attempt,
        RetryPolicy.from_env(),
        timeout=timeout,
        retryable=(_LinkFailure,),
    )


class ProcessGroupHost(ProcessGroup):
    """CPU collectives over a TCP full mesh between replica groups.

    The Gloo-equivalent data plane (reference ProcessGroupGloo,
    process_group.py:643-711): used for the fault-tolerant replicated-dim
    traffic, tests, and control data. JAX arrays are staged through host
    memory; outputs are plain numpy (callers ``device_put`` as needed).

    Collectives are dispatched on a single background thread (preserving
    issue order, like a communication stream); each op arms an abort watchdog
    for ``timeout`` seconds (reference abort-based recovery,
    process_group.py:739-763).
    """

    class _Generation:
        """One configure() generation: its mesh, dispatch queue, and error
        state. Ops are bound to the generation they were submitted under, so
        a late failure from a torn-down mesh can never poison (or abort) the
        fresh one."""

        def __init__(self, comm: "_Comm") -> None:
            self.comm = comm
            self.queue: queue.Queue = queue.Queue()
            self.error: Optional[Exception] = None
            # "p2p" | "collective" | None — fixed by the first op. p2p
            # sends ride per-peer writer threads while collectives write
            # from the dispatch/ring threads; mixing the two on one
            # generation could reorder frames on a shared socket, so it is
            # rejected (in-tree usage already splits them: the Manager's PG
            # does collectives, the recovery PGTransport's PG does p2p).
            self.mode: Optional[str] = None
            self.mode_lock = threading.Lock()

        def claim_mode(self, mode: str) -> None:
            with self.mode_lock:
                if self.mode is None:
                    self.mode = mode
                elif self.mode != mode:
                    raise RuntimeError(
                        f"ProcessGroupHost generation already used for "
                        f"{self.mode} ops; p2p and collective ops cannot "
                        "mix on one generation (frame ordering) — use a "
                        "separate PG (the reference uses a dedicated "
                        "recovery PG for checkpoints too)"
                    )

        def abort(self) -> None:
            if self.error is None:
                self.error = RuntimeError("process group aborted")
            self.comm.abort()

    def __init__(self, timeout: "float | timedelta" = 60.0) -> None:
        super().__init__()
        self.set_timeout(timeout)
        self._gen: Optional[ProcessGroupHost._Generation] = None
        self._rank = 0
        self._world = 1
        self._lock = threading.Lock()
        # injected link faults (tests / chaos): shared by reference with
        # every generation's _Comm so arming works before or after configure
        self._link_faults: Dict[frozenset, int] = {}
        self._reroute_observer: Optional[Callable[[tuple, int], None]] = None
        # wire counters folded in from retired generations so wire_stats()
        # stays monotonic across reconfigures
        self._wire_totals = {"bytes_sent": 0, "bytes_recv": 0, "busy_s": 0.0}

    # -- fault injection & failover observability -------------------------
    def inject_link_fault(self, src: int, dst: int, at_hop: int = 0) -> None:
        """Sever ring link (src, dst) from hop ``at_hop`` of every
        compressed collective on this PG — the network-fault analog of
        FakeProcessGroupWrapper.report_future_error, but *inside* the
        collective so the ring's re-route path is what recovers. The link
        stays dead until :meth:`clear_link_faults`."""
        self._link_faults[frozenset((int(src), int(dst)))] = int(at_hop)

    def clear_link_faults(self) -> None:
        self._link_faults.clear()

    def set_reroute_observer(self, fn) -> None:
        """``fn(dead_pair, attempt)`` fires on every mid-collective
        re-route (Manager wires this into the ``collective_reroute``
        counter and a flight-recorder breadcrumb)."""
        self._reroute_observer = fn

    def wire_stats(self) -> Dict[str, float]:
        """Cumulative transport counters across every generation this PG
        has run: frame bytes sent/received and ``wire_busy_s`` — seconds
        the sender spent inside sendall actually pushing those bytes
        (receive waits excluded; see _Comm.wire_busy_s).
        ``bytes_sent / wire_busy_s`` is the delivered wire bandwidth the
        compressed-allreduce bench compares across compress modes."""
        with self._lock:
            out = dict(self._wire_totals)
            gen = self._gen
        if gen is not None:
            out["bytes_sent"] += gen.comm.bytes_sent
            out["bytes_recv"] += gen.comm.bytes_recv
            out["busy_s"] += gen.comm.wire_busy_s
        return out

    # -- lifecycle --------------------------------------------------------
    def configure(self, store_addr, replica_rank, replica_world_size, quorum_id=0):
        comm = _Comm(
            rank=replica_rank,
            world=replica_world_size,
            store_addr=store_addr,
            quorum_id=quorum_id,
            timeout=self._timeout,
        )
        # share (not copy) the fault registry: arming after configure must
        # reach the live generation
        comm.link_faults = self._link_faults
        gen = ProcessGroupHost._Generation(comm)
        with self._lock:
            old, self._gen = self._gen, gen
            self._rank = replica_rank
            self._world = replica_world_size
            if old is not None:
                self._wire_totals["bytes_sent"] += old.comm.bytes_sent
                self._wire_totals["bytes_recv"] += old.comm.bytes_recv
                self._wire_totals["busy_s"] += old.comm.wire_busy_s
        if old is not None:
            old.abort()
            old.queue.put(None)
        threading.Thread(
            target=self._dispatch_loop,
            args=(gen,),
            daemon=True,
            name=f"pg_host_dispatch_r{replica_rank}",
        ).start()

    def set_timeout(self, timeout) -> None:
        super().set_timeout(timeout)
        # reaches the wire: without this only the abort watchdog moves and
        # the sockets keep their configure-time timeouts (asymmetric
        # failures: dialed sockets time out, accepted ones never would).
        # Guarded: the constructor calls set_timeout before _lock exists.
        lock = getattr(self, "_lock", None)
        if lock is None:
            return
        with lock:
            gen = self._gen
        if gen is not None:
            gen.comm.settimeout(self._timeout)

    def abort(self) -> None:
        with self._lock:
            gen = self._gen
        if gen is not None:
            gen.abort()
            from torchft_tpu.observability import log_error_event

            log_error_event(
                source="process_group",
                event="abort",
                replica_rank=self._rank,
                replica_world_size=self._world,
            )
            # abort-triggered postmortem dump (reference: abort→FR named-pipe
            # trigger, process_group.py:875-883)
            _fr.recorder.record("pg_abort", rank=self._rank, world=self._world)
            _fr.recorder.dump(reason="pg_abort")

    def shutdown(self) -> None:
        with self._lock:
            gen, self._gen = self._gen, None
        if gen is not None:
            gen.abort()
            gen.queue.put(None)

    def errored(self) -> Optional[Exception]:
        with self._lock:
            return self._gen.error if self._gen is not None else None

    def size(self) -> int:
        return self._world

    def rank(self) -> int:
        return self._rank

    # -- dispatch ---------------------------------------------------------
    def _dispatch_loop(self, gen: "ProcessGroupHost._Generation") -> None:
        while True:
            item = gen.queue.get()
            if item is None:
                return
            fn, fut, t_enqueued = item
            t_run0 = time.perf_counter()
            try:
                # the watchdog aborts THIS generation's mesh only
                with context_timeout(gen.abort, self._timeout):
                    result = fn(gen.comm)
                # (enqueued, fn started, fn ended) on the op's future, set
                # before it resolves: what the Manager records as
                # allreduce/wire_run and its queued_us (no recorder here)
                fut.stamps = (t_enqueued, t_run0, time.perf_counter())
            except BaseException as e:  # noqa: BLE001
                gen.error = e if isinstance(e, Exception) else RuntimeError(str(e))
                try:
                    fut.set_exception(e)
                except RuntimeError:
                    pass
            else:
                # set_result runs chained done-callbacks synchronously;
                # they must not be charged against the collective's
                # watchdog (a slow callback would abort a healthy mesh)
                try:
                    fut.set_result(result)
                except RuntimeError:
                    pass

    def _submit(self, fn: Callable[["_Comm"], Any], name: str = "op",
                mode: str = "collective",
                ring: Optional[Dict[str, Any]] = None) -> Work:
        _fr.recorder.record(
            "collective", op=name, rank=self._rank, world=self._world
        )
        with self._lock:
            gen = self._gen
            if gen is None:
                raise RuntimeError("process group is not configured")
            if gen.error is not None:
                raise gen.error
            gen.claim_mode(mode)
            fut: Future[Any] = Future()
            if ring is not None:
                # what the op's ring will say of itself (inplace, chunks),
                # beside the dispatch thread's stamps: allreduce/wire_run
                fut.ring = ring
            gen.queue.put((fn, fut, time.perf_counter()))
            return FutureWork(fut)

    # -- collectives ------------------------------------------------------
    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        from torchft_tpu.ops.quantization import CompressedWire

        host = [_to_host(a) for a in arrays]
        # filled by the plain ring, if one runs (_ring_allreduce: inplace,
        # chunks, lanes, and its account of its own time). A world of one
        # says ``lanes`` alone; the mesh exchange under _RING_MIN_BYTES and
        # the compressed ring say nothing
        info: Dict[str, Any] = {}

        def _run(comm):
            # compressed buckets always ride the self-healing ring: it is
            # the only path whose reduce step can dequantize→accumulate→
            # requantize per hop, and the only one that can re-route around
            # a dead link mid-collective
            if len(host) == 1 and isinstance(host[0], CompressedWire):
                wire = host[0]
                if comm.world == 1:
                    return [
                        CompressedWire(
                            wire.mode, wire.payload.copy(),
                            wire.scales.copy(), wire.n, wire.dtype,
                            wire.row,
                        )
                    ]
                return [
                    _ring_allreduce_compressed(
                        comm, wire, op, timeout=self._timeout,
                        on_reroute=self._reroute_observer,
                    )
                ]
            if comm.world == 1:
                # nothing to reduce. A donated ndarray is its own result
                # (the caller gave it up: no copy, no fresh pages), as it
                # is behind the ring. Anything else gets an independent
                # copy: at world >= 2 the results of what was not donated
                # never alias the inputs (the ring copies, the exchange
                # allocates), and the degraded single-replica fleet honors
                # the same contract. _copy_payload is tuple-safe (quantized
                # wire), and tuples are always copied.
                info["lanes"] = 1  # no neighbour: wire_run says so too
                return [
                    h if donate and isinstance(h, np.ndarray)
                    else _copy_payload(h)
                    for h in host
                ]
            # Large ndarray payloads ride the ring (per-rank traffic ~2x
            # payload, world-size-independent); small or non-ndarray ones
            # (quantized tuples) use the one-round full-mesh exchange.
            if all(isinstance(h, np.ndarray) for h in host) and (
                sum(h.nbytes for h in host) >= _RING_MIN_BYTES
            ):
                return _ring_allreduce(comm, host, op, donate, info)
            payload = {r: host for r in range(comm.world) if r != comm.rank}
            gathered = comm.exchange({**payload, comm.rank: host})
            return [
                _reduce_np(op, [gathered[r][i] for r in range(comm.world)])
                for i in range(len(host))
            ]

        return self._submit(_run, "allreduce", ring=info)

    def allgather(self, arrays):
        host = [_to_host(a) for a in arrays]

        def _run(comm):
            if comm.world == 1:
                return [[_copy_payload(h) for h in host]]
            gathered = comm.exchange(
                {r: host for r in range(comm.world)}
            )
            return [gathered[r] for r in range(comm.world)]

        return self._submit(_run, "allgather")

    def broadcast(self, arrays, root=0):
        host = [_to_host(a) for a in arrays]

        def _run(comm):
            if comm.world == 1:
                return [_copy_payload(h) for h in host]
            if comm.rank == root:
                for peer in range(comm.world):
                    if peer != comm.rank:
                        comm.send_to(peer, host)
                # ack round-trip makes broadcast a real collective: a small
                # payload to a dead peer can land in the kernel buffer and
                # "succeed", leaving the root blind to the failure — NCCL-
                # class broadcasts are communicator-wide and error on a dead
                # rank, and the resiliency matrix relies on that contract
                for peer in range(comm.world):
                    if peer != comm.rank:
                        ack = comm.recv_from(peer)
                        if ack != ("bcast_ack", peer):
                            raise RuntimeError(f"bad broadcast ack: {ack!r}")
                return host
            out = comm.recv_from(root)
            comm.send_to(root, ("bcast_ack", comm.rank))
            return out

        return self._submit(_run, "broadcast")

    def reduce_scatter(self, input_chunks, op=ReduceOp.SUM):
        host = [[_to_host(a) for a in chunk] for chunk in input_chunks]

        def _run(comm):
            if comm.world == 1:
                return [_copy_payload(h) for h in host[0]]
            assert len(host) == comm.world, "need one chunk per rank"
            gathered = comm.exchange({r: host[r] for r in range(comm.world)})
            mine = [gathered[r] for r in range(comm.world)]
            return [
                _reduce_np(op, [mine[r][i] for r in range(comm.world)])
                for i in range(len(host[0]))
            ]

        return self._submit(_run, "reduce_scatter")

    def alltoall(self, input_chunks):
        host = [_to_host(a) for a in input_chunks]

        def _run(comm):
            if comm.world == 1:
                return [_copy_payload(h) for h in host]
            assert len(host) == comm.world, "need one chunk per rank"
            gathered = comm.exchange({r: host[r] for r in range(comm.world)})
            return [gathered[r] for r in range(comm.world)]

        return self._submit(_run, "alltoall")

    def send(self, arrays, dst, tag=0):
        host = [_to_host(a) for a in arrays]
        _fr.recorder.record(
            "collective", op="send", rank=self._rank, world=self._world
        )
        with self._lock:
            gen = self._gen
            if gen is None:
                raise RuntimeError("process group is not configured")
            if gen.error is not None:
                raise gen.error
            gen.claim_mode("p2p")
        fut: Future[Any] = Future()
        timeout = self._timeout

        def job() -> None:
            # own watchdog: the job runs on the per-peer writer thread, not
            # the dispatch thread (see _Comm.p2p_send_async — symmetric
            # send/send would deadlock both dispatch threads otherwise)
            with context_timeout(gen.abort, timeout):
                comm = gen.comm
                if all(isinstance(h, np.ndarray) for h in host) and (
                    sum(h.nbytes for h in host) >= _RING_MIN_BYTES
                ):
                    # raw-frame p2p: a small pickled header with dtype/shape
                    # metas, then each leaf's bytes straight from memory —
                    # no pickling copy of multi-GB checkpoint leaves
                    metas = [(str(h.dtype), h.shape) for h in host]
                    comm.send_to(dst, ("p2p_raw", tag, metas))
                    for h in host:
                        comm.send_raw(dst, np.ascontiguousarray(h))
                else:
                    comm.send_to(dst, ("p2p", tag, host))

        def fail(e: Exception) -> None:
            gen.error = gen.error or e

        gen.comm.p2p_send_async(dst, job, fut, fail)
        return FutureWork(fut)

    def recv(self, src, tag=0):
        return self.recv_into([], src, tag)

    def recv_into(self, buffers, src, tag=0):
        """Like :meth:`recv` (which delegates here with no buffers), but
        raw-frame payloads land DIRECTLY in the caller's preallocated
        ``buffers`` — no wire allocation and no copy (the in-place
        checkpoint receive's hot path; beyond the torch PG surface, and
        what ``PGTransport`` requires of its recovery PG).

        The returned Work's value is the list of received arrays: entry i
        IS ``buffers[i]`` when the wire used a raw frame and the buffer
        can absorb it (the shared ``can_absorb`` predicate, contiguity
        required); otherwise a freshly allocated array (small pickled
        messages, mismatched buffers, or more leaves than buffers).
        """
        def _run(comm):
            kind, got_tag, payload = comm.recv_from(src)
            assert got_tag == tag, (kind, got_tag, tag)
            if kind == "p2p":
                return payload  # pickled small-message path: no raw frames
            assert kind == "p2p_raw", kind
            # one absorb predicate across every in-place path (no import
            # cycle: _serialization depends only on numpy/utils)
            from torchft_tpu.checkpointing._serialization import can_absorb
            from torchft_tpu.utils import np_dtype_from_str

            out = []
            for i, (dtype_str, shape) in enumerate(payload):
                target = buffers[i] if i < len(buffers) else None
                if not can_absorb(target, shape, dtype_str,
                                  require_contiguous=True):
                    target = np.empty(shape, np_dtype_from_str(dtype_str))
                comm.recv_raw_into(src, target)
                out.append(target)
            return out

        return self._submit(_run, "recv", mode="p2p")


# ---------------------------------------------------------------------------
# Wrappers
# ---------------------------------------------------------------------------


class _ErrorSwallowingWork(Work):
    """Work whose future errors resolve to a default value instead of raising
    (reference: process_group.py:1137-1173)."""

    def __init__(self, pg: "ErrorSwallowingProcessGroupWrapper", work: Work,
                 default_fn: Callable[[], Any]):
        self._pg = pg
        self._work = work
        self._future: Future[Any] = Future()

        def _transfer(f: Future[Any]) -> None:
            exc = f.exception()
            if exc is not None:
                self._pg.report_error(
                    exc if isinstance(exc, Exception) else RuntimeError(str(exc))
                )
                # default built lazily, only on the error path — and a
                # default_fn that itself raises (e.g. non-addressable
                # sharded arrays) must fail the future, not strand it
                # (Future._invoke swallows callback exceptions)
                try:
                    self._future.set_result(default_fn())
                except Exception as e:  # noqa: BLE001
                    try:
                        self._future.set_exception(e)
                    except RuntimeError:
                        pass
            else:
                self._future.set_result(f.value())

        work.get_future().add_done_callback(_transfer)

    def wait(self, timeout=None):
        self._future.wait(timeout)
        return True

    def get_future(self):
        return self._future


class ErrorSwallowingProcessGroupWrapper(ProcessGroup):
    """Swallows collective errors: after the first error every op returns its
    input unchanged (identity for the train loop) until reconfigured.

    Reference: process_group.py:1176-1249. This is what lets a replica keep
    stepping through a dead communicator — the Manager discards the step at
    should_commit time.
    """

    def __init__(self, pg: ProcessGroup) -> None:
        super().__init__()
        self._pg = pg
        self._error: Optional[Exception] = None

    @property
    def device_native(self) -> bool:
        # forward the inner PG's data-plane capability so wrapping a
        # ProcessGroupXLA doesn't silently re-enable host staging in the
        # Manager (it reads this attribute off the outermost PG)
        return getattr(self._pg, "device_native", False)

    def parent(self) -> ProcessGroup:
        return self._pg

    def error(self) -> Optional[Exception]:
        return self._error

    def report_error(self, e: Exception) -> None:
        self._error = e

    def configure(self, store_addr, replica_rank, replica_world_size, quorum_id=0):
        self._error = None
        self._pg.configure(store_addr, replica_rank, replica_world_size, quorum_id)

    def prepare_configure(
        self, store_addr, replica_rank, replica_world_size, quorum_id=0
    ) -> Optional[Callable[[], None]]:
        # forward the split so wrapping a prepare/commit PG keeps the commit
        # on the main thread; the swallowed-error state clears when the new
        # communicator is actually LIVE (commit time for split PGs)
        inner = self._pg.prepare_configure(
            store_addr, replica_rank, replica_world_size, quorum_id=quorum_id
        )
        if inner is None:
            self._error = None
            return None

        def commit() -> None:
            inner()
            self._error = None

        return commit

    def abort(self) -> None:
        self._pg.abort()

    def shutdown(self) -> None:
        self._pg.shutdown()

    def errored(self) -> Optional[Exception]:
        return self._error or self._pg.errored()

    def size(self) -> int:
        return self._pg.size()

    def rank(self) -> int:
        return self._pg.rank()

    def set_timeout(self, timeout) -> None:
        self._pg.set_timeout(timeout)

    def _guard(self, fn: Callable[[], Work], default_fn: Callable[[], Any]) -> Work:
        """``default_fn`` is LAZY: building a swallow default stages the
        whole payload to host (blocking D2H for device-native trees, and an
        outright error for non-addressable sharded arrays), so it must only
        run on the error path — never per healthy op."""
        if self._error is not None:
            return DummyWork(default_fn())
        try:
            return _ErrorSwallowingWork(self, fn(), default_fn)
        except Exception as e:  # noqa: BLE001
            self.report_error(e)
            return DummyWork(default_fn())

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        return self._guard(
            lambda: self._pg.allreduce(arrays, op, donate=donate),
            lambda: [_to_host(a) for a in arrays],
        )

    def allgather(self, arrays):
        # contract: one entry per rank (identity rows for every rank)
        return self._guard(
            lambda: self._pg.allgather(arrays),
            lambda: [
                [_to_host(a) for a in arrays] for _ in range(self._pg.size())
            ],
        )

    def broadcast(self, arrays, root=0):
        return self._guard(
            lambda: self._pg.broadcast(arrays, root),
            lambda: [_to_host(a) for a in arrays],
        )

    def reduce_scatter(self, input_chunks, op=ReduceOp.SUM):
        # identity default = the chunk THIS rank owns, not rank 0's
        return self._guard(
            lambda: self._pg.reduce_scatter(input_chunks, op),
            lambda: [_to_host(a) for a in input_chunks[self._pg.rank()]],
        )

    def alltoall(self, input_chunks):
        return self._guard(
            lambda: self._pg.alltoall(input_chunks),
            lambda: [_to_host(a) for a in input_chunks],
        )

    def send(self, arrays, dst, tag=0):
        return self._guard(lambda: self._pg.send(arrays, dst, tag), lambda: None)

    def recv(self, src, tag=0):
        return self._guard(lambda: self._pg.recv(src, tag), lambda: None)


class FakeProcessGroupWrapper(ProcessGroup):
    """Test-only fault injection: ``report_future_error`` makes the next
    op's future raise (reference: process_group.py:1252-1317), and the
    network-shaped knobs (``times`` for a flaky-link burst, ``delay_ops``
    for a stalled-but-alive wire) let tests reproduce degraded transports
    rather than only clean crashes."""

    def __init__(self, pg: ProcessGroup) -> None:
        super().__init__()
        self._pg = pg
        self._next_error: Optional[Exception] = None
        self._next_error_skip = 0
        self._next_error_times = 0
        self._next_configure_error: Optional[Exception] = None
        # network-stall shape: the next N ops sleep before dispatch
        self._delay_ops_s = 0.0
        self._delay_ops_count = 0
        # test hook: called at the START of prepare_configure (on the
        # quorum thread) — EventInjector uses it to stall the prepare
        # phase past a step boundary deterministically
        self._on_prepare: Optional[Callable[[], None]] = None
        # intra-group member death (degrade plane): the Manager registers
        # a callback here when TORCHFT_DEGRADE=on; dead members accumulate
        # so a test can assert which chips a scenario lost
        self._member_death_cb: Optional[Callable[[int], None]] = None
        self._dead_members: List[int] = []

    @property
    def device_native(self) -> bool:
        return getattr(self._pg, "device_native", False)

    def report_future_error(
        self, e: Exception, skip_ops: int = 0, times: int = 1
    ) -> None:
        """Fail upcoming ops' futures with ``e``. ``skip_ops=k`` lets the
        next k ops through untouched and fails the (k+1)-th — with the
        per-bucket streaming pipeline, that targets bucket k of a plan
        mid-stream instead of only ever the first collective. ``times=n``
        fails n consecutive ops (a flaky link rather than a single drop)."""
        self._next_error = e
        self._next_error_skip = int(skip_ops)
        self._next_error_times = max(1, int(times))

    def delay_ops(self, seconds: float, count: int = 1) -> None:
        """Stall the next ``count`` ops by ``seconds`` before their work
        handle is returned — a slow-but-alive wire, the shape that
        exercises timeout/retry budgets without tripping the error path."""
        self._delay_ops_s = float(seconds)
        self._delay_ops_count = int(count)

    def report_configure_error(self, e: Exception) -> None:
        self._next_configure_error = e

    def set_prepare_hook(self, fn: Optional[Callable[[], None]]) -> None:
        self._on_prepare = fn

    # -- intra-group member death (degrade plane) -------------------------
    def set_member_death_callback(
        self, fn: Optional[Callable[[int], None]]
    ) -> None:
        """Degrade-plane detection hook: the Manager registers its
        report_member_death here (only when TORCHFT_DEGRADE=on), matching
        the abort-watchdog shape a device PG would use on real hardware.
        Also forwarded to the wrapped PG when it has its own support."""
        self._member_death_cb = fn
        setter = getattr(self._pg, "set_member_death_callback", None)
        if setter is not None:
            setter(fn)

    def inject_group_member_death(self, group_rank: int) -> None:
        """Kill chip ``group_rank`` INSIDE this replica's group: the
        intra-group fault the degrade plane survives by resharding onto
        the survivors (EventInjector.kill_chip routes here). Fires the
        registered member-death callback between steps — the
        abort-watchdog detection shape — rather than failing the in-flight
        collective, so the step is re-planned, not discarded."""
        self._dead_members.append(int(group_rank))
        fwd = getattr(self._pg, "inject_group_member_death", None)
        if fwd is not None:
            fwd(group_rank)
        cb = self._member_death_cb
        if cb is not None:
            cb(int(group_rank))

    @property
    def dead_members(self) -> List[int]:
        """Group ranks this wrapper has killed (test assertions)."""
        return list(self._dead_members)

    # -- compressed-ring failover passthroughs ----------------------------
    # (EventInjector.kill_link and the Manager's reroute counter reach the
    # wrapped host PG through these; non-host PGs silently no-op)
    def inject_link_fault(self, src: int, dst: int, at_hop: int = 0) -> None:
        fn = getattr(self._pg, "inject_link_fault", None)
        if fn is not None:
            fn(src, dst, at_hop)

    def clear_link_faults(self) -> None:
        fn = getattr(self._pg, "clear_link_faults", None)
        if fn is not None:
            fn()

    def set_reroute_observer(self, fn) -> None:
        setter = getattr(self._pg, "set_reroute_observer", None)
        if setter is not None:
            setter(fn)

    def configure(self, store_addr, replica_rank, replica_world_size, quorum_id=0):
        if self._next_configure_error is not None:
            e, self._next_configure_error = self._next_configure_error, None
            raise e
        self._pg.configure(store_addr, replica_rank, replica_world_size, quorum_id)

    def prepare_configure(
        self, store_addr, replica_rank, replica_world_size, quorum_id=0
    ) -> Optional[Callable[[], None]]:
        # injection parity with configure(): a staged configure error fires
        # during PREPARE (that is where the real failures live — rendezvous,
        # membership barriers), and the prepare hook runs before it
        if self._on_prepare is not None:
            self._on_prepare()
        if self._next_configure_error is not None:
            e, self._next_configure_error = self._next_configure_error, None
            raise e
        return self._pg.prepare_configure(
            store_addr, replica_rank, replica_world_size, quorum_id=quorum_id
        )

    def abort(self) -> None:
        self._pg.abort()

    def shutdown(self) -> None:
        self._pg.shutdown()

    def errored(self) -> Optional[Exception]:
        return self._pg.errored()

    def size(self) -> int:
        return self._pg.size()

    def rank(self) -> int:
        return self._pg.rank()

    def set_timeout(self, timeout) -> None:
        self._pg.set_timeout(timeout)

    def _maybe_fail(self, work: Work) -> Work:
        if self._delay_ops_count > 0:
            self._delay_ops_count -= 1
            time.sleep(self._delay_ops_s)
        if self._next_error is not None:
            if self._next_error_skip > 0:
                self._next_error_skip -= 1
                return work
            e = self._next_error
            self._next_error_times -= 1
            if self._next_error_times <= 0:
                self._next_error = None
            fut: Future[Any] = Future()

            def _fail(_f: Future[Any]) -> None:
                try:
                    fut.set_exception(e)
                except RuntimeError:
                    pass

            work.get_future().add_done_callback(_fail)
            return FutureWork(fut)
        return work

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        return self._maybe_fail(self._pg.allreduce(arrays, op, donate=donate))

    def allgather(self, arrays):
        return self._maybe_fail(self._pg.allgather(arrays))

    def broadcast(self, arrays, root=0):
        return self._maybe_fail(self._pg.broadcast(arrays, root))

    def reduce_scatter(self, input_chunks, op=ReduceOp.SUM):
        return self._maybe_fail(self._pg.reduce_scatter(input_chunks, op))

    def alltoall(self, input_chunks):
        return self._maybe_fail(self._pg.alltoall(input_chunks))

    def send(self, arrays, dst, tag=0):
        return self._maybe_fail(self._pg.send(arrays, dst, tag))

    def recv(self, src, tag=0):
        return self._maybe_fail(self._pg.recv(src, tag))


class ManagedProcessGroup(ProcessGroup):
    """PG adapter whose allreduce routes through a Manager, so unmodified
    data-parallel code picks up quorum participation + error swallowing
    (reference: process_group.py:1320-1353)."""

    def __init__(self, manager: "Any") -> None:  # Manager (avoid cycle)
        super().__init__()
        self._manager = manager

    def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
        return self._manager.allreduce(list(arrays), reduce_op=op)

    def size(self) -> int:
        return self._manager.num_participants()

    def rank(self) -> int:
        # replica_rank() is Optional (None before the first quorum); the PG
        # contract is int — report rank 0 until a quorum assigns one.
        r = self._manager.replica_rank()
        return 0 if r is None else r

    def configure(self, store_addr, replica_rank, replica_world_size, quorum_id=0):
        raise RuntimeError("ManagedProcessGroup is configured by its Manager")

    def abort(self) -> None:
        self._manager._pg.abort()

    def shutdown(self) -> None:
        self._manager._pg.shutdown()

    def errored(self) -> Optional[Exception]:
        return self._manager._pg.errored()

    def allgather(self, arrays):
        raise NotImplementedError("managed PG only routes allreduce")

    def broadcast(self, arrays, root=0):
        raise NotImplementedError("managed PG only routes allreduce")

    def reduce_scatter(self, input_chunks, op=ReduceOp.SUM):
        raise NotImplementedError("managed PG only routes allreduce")

    def alltoall(self, input_chunks):
        raise NotImplementedError("managed PG only routes allreduce")

    def send(self, arrays, dst, tag=0):
        raise NotImplementedError("managed PG only routes allreduce")

    def recv(self, src, tag=0):
        raise NotImplementedError("managed PG only routes allreduce")
