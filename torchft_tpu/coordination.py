"""Python bindings for the native C++ control plane.

Role-equivalent of the reference's pyo3 extension ``torchft._torchft``
(reference: src/lib.rs:80-761, torchft/_torchft.pyi, torchft/coordination.py):
``LighthouseServer``/``LighthouseClient``, ``ManagerServer``/``ManagerClient``,
``QuorumResult``, plus the rendezvous ``KvStoreServer``/``KvClient`` (the
TPU-native replacement for torch's TCPStore). The native side is C++
(``native/`` -> ``torchft_tpu/_native/libtorchft_tpu.so``) speaking
length-framed JSON over TCP; ctypes releases the GIL around every blocking
RPC, matching the reference's ``py.allow_threads`` behavior.
"""

from __future__ import annotations

import ctypes
import json
import os
import subprocess
import types
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Dict, List, Optional, Tuple

from .retry import RetryPolicy, retry_call

__all__ = [
    "QuorumMember",
    "Quorum",
    "QuorumResult",
    "FallbackPeer",
    "LighthouseServer",
    "LighthouseClient",
    "AggregatorServer",
    "ManagerServer",
    "ManagerClient",
    "KvStoreServer",
    "KvClient",
]

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "_native")
_SO_PATH = os.path.join(_NATIVE_DIR, "libtorchft_tpu.so")

# /metrics per-replica series cap (see LighthouseServer / docs/operations.md).
METRICS_PER_REPLICA_LIMIT_ENV = "TORCHFT_METRICS_PER_REPLICA_LIMIT"

# status codes from native/capi.cc
_OK, _TIMEOUT, _ERROR, _NOT_FOUND, _INVALID, _UNAVAILABLE = range(6)


def ensure_native_built() -> str:
    """Bring the native library up to date with ``native/*.cc`` (requires
    g++ + make) and return its path.

    Always runs ``make -C native``: a no-op when the library is current
    (the Makefile tracks sources and headers), a rebuild when a copied or
    stale ``.so`` is older than the sources it claims to come from.
    Serialized across processes with a file lock so a multi-process launch
    on a fresh checkout doesn't race the build.
    """
    native_src = os.path.abspath(
        os.path.join(os.path.dirname(_NATIVE_DIR), "..", "native")
    )
    if not os.path.isdir(native_src):
        if os.path.exists(_SO_PATH):
            return _SO_PATH  # installed without the source tree
        raise RuntimeError(
            f"native library missing at {_SO_PATH} and no source tree found"
        )
    import fcntl

    os.makedirs(_NATIVE_DIR, exist_ok=True)
    lock_path = os.path.join(_NATIVE_DIR, ".build.lock")
    with open(lock_path, "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            subprocess.run(
                ["make", "-C", native_src, "-j", "-s"], check=True,
                stdout=subprocess.DEVNULL,
            )
        finally:
            fcntl.flock(lock, fcntl.LOCK_UN)
    return _SO_PATH


_lib: Optional[ctypes.CDLL] = None


def _load() -> ctypes.CDLL:
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(ensure_native_built())
        lib.tft_free.argtypes = [ctypes.c_char_p]
        lib.tft_free.restype = None
        lib.tft_lighthouse_new.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_lighthouse_new_v2.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_lighthouse_address.argtypes = [ctypes.c_void_p]
        lib.tft_lighthouse_address.restype = ctypes.c_void_p
        lib.tft_lighthouse_port.argtypes = [ctypes.c_void_p]
        lib.tft_lighthouse_shutdown.argtypes = [ctypes.c_void_p]
        lib.tft_lighthouse_free.argtypes = [ctypes.c_void_p]
        # policy plane: in-process control surface (NOT wire RPCs)
        lib.tft_lighthouse_set_policy.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_lighthouse_policy.argtypes = [ctypes.c_void_p]
        lib.tft_lighthouse_policy.restype = ctypes.c_void_p
        lib.tft_lighthouse_drain_events.argtypes = [ctypes.c_void_p]
        lib.tft_lighthouse_drain_events.restype = ctypes.c_void_p
        lib.tft_lighthouse_retune_health.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_aggregator_new.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_aggregator_address.argtypes = [ctypes.c_void_p]
        lib.tft_aggregator_address.restype = ctypes.c_void_p
        lib.tft_aggregator_status.argtypes = [ctypes.c_void_p]
        lib.tft_aggregator_status.restype = ctypes.c_void_p
        lib.tft_aggregator_port.argtypes = [ctypes.c_void_p]
        lib.tft_aggregator_shutdown.argtypes = [ctypes.c_void_p]
        lib.tft_aggregator_free.argtypes = [ctypes.c_void_p]
        lib.tft_manager_new.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_manager_control_status.argtypes = [ctypes.c_void_p]
        lib.tft_manager_control_status.restype = ctypes.c_void_p
        lib.tft_manager_address.argtypes = [ctypes.c_void_p]
        lib.tft_manager_address.restype = ctypes.c_void_p
        lib.tft_manager_publish_telemetry.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_manager_health.argtypes = [ctypes.c_void_p]
        lib.tft_manager_health.restype = ctypes.c_void_p
        lib.tft_manager_policy.argtypes = [ctypes.c_void_p]
        lib.tft_manager_policy.restype = ctypes.c_void_p
        lib.tft_manager_clock_skew.argtypes = [ctypes.c_void_p]
        lib.tft_manager_clock_skew.restype = ctypes.c_void_p
        lib.tft_manager_port.argtypes = [ctypes.c_void_p]
        lib.tft_manager_shutdown.argtypes = [ctypes.c_void_p]
        lib.tft_manager_free.argtypes = [ctypes.c_void_p]
        lib.tft_client_new.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_client_free.argtypes = [ctypes.c_void_p]
        lib.tft_client_call.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_kvstore_new.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_kvstore_port.argtypes = [ctypes.c_void_p]
        lib.tft_kvstore_shutdown.argtypes = [ctypes.c_void_p]
        lib.tft_kvstore_free.argtypes = [ctypes.c_void_p]
        lib.tft_quorum_compute.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_compute_quorum_results.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_health_scores.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_health_replay.argtypes = [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        lib.tft_history_replay.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_char_p),
        ]
        _lib = lib
    return _lib


def native_ring() -> Optional[Any]:
    """What the host ring runs outside the interpreter (ctypes lets go of
    its lock for a call), as attributes, or None where the library cannot
    be had or does not export them (one built before they existed,
    installed without its sources):

    - ``bf16_add(dst, src, n)``: native/reduce.cc, two addresses and an
      element count;
    - ``fd_send_all(fd, data, n, idle_ms, more)`` and ``fd_recv_all(fd,
      data, n, idle_ms)``: native/net.cc, a whole buffer over a Python
      socket's fd; 0 done, 1 no progress within ``idle_ms`` (negative: no
      limit), 2 closed, else ``-errno``.
    """
    try:
        lib = _load()
        fns = types.SimpleNamespace(
            bf16_add=lib.tft_bf16_add, fd_send_all=lib.tft_fd_send_all,
            fd_recv_all=lib.tft_fd_recv_all)
    except (AttributeError, OSError, RuntimeError,
            subprocess.CalledProcessError):
        return None
    fns.bf16_add.argtypes = [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
    fns.bf16_add.restype = None
    fns.fd_send_all.argtypes = [ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t,
                                ctypes.c_int64, ctypes.c_int]
    fns.fd_recv_all.argtypes = [ctypes.c_int, ctypes.c_void_p, ctypes.c_size_t,
                                ctypes.c_int64]
    fns.fd_send_all.restype = fns.fd_recv_all.restype = ctypes.c_int
    return fns


def _take_str(lib: ctypes.CDLL, ptr: "ctypes.c_char_p | int | None") -> str:
    if not ptr:
        return ""
    try:
        raw = ctypes.cast(ptr, ctypes.c_char_p).value or b""
        return raw.decode("utf-8", errors="replace")
    finally:
        lib.tft_free(ctypes.cast(ptr, ctypes.c_char_p))


def _raise_for_status(status: int, err: str, what: str) -> None:
    if status == _OK:
        return
    msg = f"{what}: {err}" if err else what
    if status == _TIMEOUT:
        raise TimeoutError(msg)
    if status == _NOT_FOUND:
        raise LookupError(msg)
    if status == _INVALID:
        raise ValueError(msg)
    raise RuntimeError(msg)


def _ms(timeout: "float | timedelta") -> int:
    if isinstance(timeout, timedelta):
        return int(timeout.total_seconds() * 1000)
    return int(timeout * 1000)


# --------------------------------------------------------------------- types
@dataclass
class QuorumMember:
    """Mirror of the wire QuorumMember (reference: proto/torchft.proto:37-47)."""

    replica_id: str
    address: str = ""
    store_address: str = ""
    step: int = 0
    world_size: int = 1
    shrink_only: bool = False
    commit_failures: int = 0
    data: str = ""

    @staticmethod
    def _from_json(d: dict) -> "QuorumMember":
        return QuorumMember(
            replica_id=d["replica_id"],
            address=d.get("address", ""),
            store_address=d.get("store_address", ""),
            step=d.get("step", 0),
            world_size=d.get("world_size", 1),
            shrink_only=d.get("shrink_only", False),
            commit_failures=d.get("commit_failures", 0),
            data=d.get("data", ""),
        )

    def _to_json(self) -> dict:
        return {
            "replica_id": self.replica_id,
            "address": self.address,
            "store_address": self.store_address,
            "step": self.step,
            "world_size": self.world_size,
            "shrink_only": self.shrink_only,
            "commit_failures": self.commit_failures,
            "data": self.data,
        }


@dataclass
class Quorum:
    quorum_id: int
    participants: List[QuorumMember]
    created_ms: int = 0

    @staticmethod
    def _from_json(d: dict) -> "Quorum":
        return Quorum(
            quorum_id=d["quorum_id"],
            participants=[QuorumMember._from_json(p) for p in d["participants"]],
            created_ms=d.get("created_ms", 0),
        )


@dataclass
class FallbackPeer:
    """An up-to-date peer a healing replica can fail over to if its assigned
    recovery source dies mid-transfer."""

    replica_rank: int
    address: str  # manager RPC address (host:port)

    @staticmethod
    def _from_json(d: dict) -> "FallbackPeer":
        return FallbackPeer(
            replica_rank=d.get("replica_rank", 0), address=d.get("address", "")
        )


@dataclass
class QuorumResult:
    """Per-rank manager quorum response (reference: proto ManagerQuorumResponse
    + src/lib.rs:284-319)."""

    quorum_id: int
    replica_rank: int
    replica_world_size: int
    recover_src_manager_address: str
    recover_src_replica_rank: Optional[int]
    recover_dst_replica_ranks: List[int]
    store_address: str
    max_step: int
    max_replica_rank: Optional[int]
    max_world_size: int
    heal: bool
    commit_failures: int = 0
    replica_ids: List[str] = field(default_factory=list)
    # remaining max_step peers in round-robin order after the assigned
    # source; empty when not healing or from a pre-fallback native build
    recover_src_fallbacks: List[FallbackPeer] = field(default_factory=list)

    @staticmethod
    def _from_json(d: dict) -> "QuorumResult":
        return QuorumResult(
            quorum_id=d["quorum_id"],
            replica_rank=d["replica_rank"],
            replica_world_size=d["replica_world_size"],
            recover_src_manager_address=d.get("recover_src_manager_address", ""),
            recover_src_replica_rank=d.get("recover_src_replica_rank"),
            recover_dst_replica_ranks=list(d.get("recover_dst_replica_ranks", [])),
            store_address=d.get("store_address", ""),
            max_step=d.get("max_step", 0),
            max_replica_rank=d.get("max_replica_rank"),
            max_world_size=d.get("max_world_size", 0),
            heal=d.get("heal", False),
            commit_failures=d.get("commit_failures", 0),
            replica_ids=list(d.get("replica_ids", [])),
            recover_src_fallbacks=[
                FallbackPeer._from_json(f)
                for f in d.get("recover_src_fallbacks", [])
            ],
        )


# ------------------------------------------------------------------- servers
class LighthouseServer:
    """In-process lighthouse quorum server (native C++).

    Reference equivalent: ``LighthouseServer`` in src/lib.rs:609-671 backed by
    src/lighthouse.rs. Also serves the HTML dashboard + ``/status`` JSON +
    ``POST /replica/{id}/kill`` on the same port.
    """

    def __init__(
        self,
        bind: str = "0.0.0.0:0",
        min_replicas: int = 1,
        join_timeout_ms: int = 60000,
        quorum_tick_ms: int = 100,
        heartbeat_timeout_ms: int = 5000,
        health: "Optional[dict]" = None,
        history_path: str = "",
        metrics_per_replica_limit: "Optional[int]" = None,
        serve_registry: bool = False,
        serve_drain_on: "Optional[str]" = None,
        redundancy_directory: bool = False,
        policy: "Optional[str]" = None,
    ) -> None:
        """``health`` configures the healthwatch ledger (HealthOpts fields,
        see torchft_tpu/healthwatch.py); None reads ``TORCHFT_HEALTH_*``
        from the environment (default: observe mode). ``history_path``
        enables the recorded-history store: append-only JSONL of quorum
        transitions / heals / health events / telemetry snapshots, readable
        via :func:`history_replay` (empty = disabled).
        ``metrics_per_replica_limit`` caps per-replica /metrics series (the
        tail collapses into min/median/max aggregates); None reads
        ``TORCHFT_METRICS_PER_REPLICA_LIMIT`` (default 64).
        ``serve_registry=True`` co-hosts a serving-plane SnapshotRegistry
        that polls this lighthouse's /health summary to drain unhealthy
        sources (``serve_drain_on``: "warn"/"eject", None reads
        ``TORCHFT_SERVE_DRAIN_ON``); see docs/serving.md.
        ``redundancy_directory=True`` co-hosts a redundancy-plane
        ShardDirectory that tracks erasure-coded shard placements, polls
        this lighthouse's /health ledger for owner deaths, and promotes
        hot spares into the next quorum (docs/operations.md).
        ``policy`` attaches the adaptive policy engine: ``"builtin"`` or a
        PolicySpec JSON path (None reads ``TORCHFT_POLICY_SPEC`` when
        ``TORCHFT_POLICY`` != off). The engine folds this lighthouse's
        live event ring into fleet signals every
        ``TORCHFT_POLICY_INTERVAL_S`` and publishes versioned knob-
        override frames on existing heartbeat/agg_tick replies; see
        docs/operations.md#adaptive-policies."""
        from torchft_tpu import knobs

        lib = _load()
        policy_mode = knobs.env_str("TORCHFT_POLICY", "off").strip() or "off"
        if policy is None and policy_mode != "off":
            policy = knobs.env_str("TORCHFT_POLICY_SPEC", "builtin") or "builtin"
        policy_ring = (
            knobs.env_int("TORCHFT_POLICY_RING", 4096)
            if policy is not None and policy_mode != "off"
            else 0
        )
        if health is None:
            from torchft_tpu.healthwatch import HealthConfig

            health = HealthConfig.from_env().to_json()
        if metrics_per_replica_limit is None:
            metrics_per_replica_limit = int(
                os.environ.get(METRICS_PER_REPLICA_LIMIT_ENV, "") or 64
            )
        handle = ctypes.c_void_p()
        err = ctypes.c_char_p()
        opts = {
            "bind": bind,
            "min_replicas": min_replicas,
            "join_timeout_ms": join_timeout_ms,
            "quorum_tick_ms": quorum_tick_ms,
            "heartbeat_timeout_ms": heartbeat_timeout_ms,
            "health": health,
            "history_path": history_path,
            "policy_ring": policy_ring,
            "metrics_per_replica_limit": metrics_per_replica_limit,
        }
        status = lib.tft_lighthouse_new_v2(
            json.dumps(opts).encode(), ctypes.byref(handle), ctypes.byref(err)
        )
        _raise_for_status(status, _take_str(lib, err), "lighthouse start failed")
        self._lib = lib
        self._handle = handle
        self.serve_registry = None
        if serve_registry:
            # lazy import: the serving plane is optional and serving.py
            # imports back into this module for its health poll client
            from torchft_tpu.serving import SERVE_DRAIN_ON_ENV, SnapshotRegistry

            drain_on = (
                serve_drain_on
                if serve_drain_on is not None
                else os.environ.get(SERVE_DRAIN_ON_ENV, "warn").strip() or "warn"
            )
            self.serve_registry = SnapshotRegistry(
                lighthouse_addr=self.address(), drain_on=drain_on
            )
        self.redundancy_directory = None
        if redundancy_directory:
            # lazy import, same reason as the serving registry above:
            # redundancy.py imports LighthouseClient back from here for
            # the directory's health poll
            from torchft_tpu.redundancy import ShardDirectory

            self.redundancy_directory = ShardDirectory(
                lighthouse_addr=self.address()
            )
        self.policy_controller = None
        self.policy_mode = policy_mode
        self._policy_thread = None
        self._policy_stop = None
        if policy is not None and policy_mode != "off":
            self._attach_policy(policy, policy_mode)

    def _attach_policy(self, policy: str, mode: str) -> None:
        """Python-side lazy attach (same pattern as serve_registry /
        redundancy_directory): a PolicyController polling the native
        handle's event ring on a daemon thread."""
        import threading

        from torchft_tpu import knobs
        from torchft_tpu.policy import (
            PolicyController,
            PolicyEngine,
            PolicySpec,
        )

        spec = PolicySpec.load(policy)
        engine = PolicyEngine(
            spec,
            mode=mode,
            window_s=knobs.env_float("TORCHFT_POLICY_WINDOW_S", 300.0),
        )
        self.policy_controller = PolicyController(
            engine,
            drain_fn=self._policy_drain,
            set_policy_fn=self.set_policy,
            retune_health_fn=self.retune_health,
        )
        interval_s = max(knobs.env_float("TORCHFT_POLICY_INTERVAL_S", 5.0), 0.05)
        stop = threading.Event()

        def _loop() -> None:
            while not stop.wait(interval_s):
                try:
                    self.policy_controller.step()
                except Exception:  # noqa: BLE001 — the policy plane must
                    pass  # never take down the quorum coordinator

        self._policy_stop = stop
        self._policy_thread = threading.Thread(
            target=_loop, name="torchft-policy", daemon=True
        )
        self._policy_thread.start()

    def _policy_drain(self) -> "List[dict]":
        raw = _take_str(
            self._lib, self._lib.tft_lighthouse_drain_events(self._handle)
        )
        return json.loads(raw or "[]")

    def set_policy(self, frame: dict) -> None:
        """Publish a policy frame onto heartbeat/agg_tick replies (``{}``
        clears it — the kill switch)."""
        err = ctypes.c_char_p()
        status = self._lib.tft_lighthouse_set_policy(
            self._handle, json.dumps(frame).encode(), ctypes.byref(err)
        )
        _raise_for_status(
            status, _take_str(self._lib, err), "set_policy failed"
        )

    def policy(self) -> dict:
        """The currently published policy frame (``{}`` when none)."""
        return json.loads(
            _take_str(self._lib, self._lib.tft_lighthouse_policy(self._handle))
            or "{}"
        )

    def retune_health(self, partial: dict) -> dict:
        """Live-merge partial HealthOpts over the running ledger (policy
        enforce mode tightening/widening eject thresholds). Returns the
        resulting opts."""
        out = ctypes.c_char_p()
        err = ctypes.c_char_p()
        status = self._lib.tft_lighthouse_retune_health(
            self._handle, json.dumps(partial).encode(),
            ctypes.byref(out), ctypes.byref(err),
        )
        out_s = _take_str(self._lib, out)
        _raise_for_status(
            status, _take_str(self._lib, err), "retune_health failed"
        )
        return json.loads(out_s or "{}")

    def address(self) -> str:
        return _take_str(self._lib, self._lib.tft_lighthouse_address(self._handle))

    @property
    def port(self) -> int:
        return self._lib.tft_lighthouse_port(self._handle)

    def serve_registry_url(self) -> "Optional[str]":
        return self.serve_registry.url if self.serve_registry is not None else None

    def redundancy_directory_url(self) -> "Optional[str]":
        return (
            self.redundancy_directory.url
            if self.redundancy_directory is not None
            else None
        )

    def shutdown(self) -> None:
        if self._policy_stop is not None:
            self._policy_stop.set()
            if self._policy_thread is not None:
                self._policy_thread.join(timeout=5.0)
            self._policy_stop = None
            self._policy_thread = None
            self.policy_controller = None
        if self.serve_registry is not None:
            self.serve_registry.shutdown()
            self.serve_registry = None
        if self.redundancy_directory is not None:
            self.redundancy_directory.shutdown()
            self.redundancy_directory = None
        if self._handle:
            self._lib.tft_lighthouse_shutdown(self._handle)

    def __del__(self) -> None:
        try:
            if getattr(self, "_handle", None):
                self._lib.tft_lighthouse_free(self._handle)
                self._handle = None
        except Exception:
            pass


class AggregatorServer:
    """Pod-level lighthouse aggregator (native C++, ``native/aggregator.cc``).

    Fronts a pod of replica-group managers: speaks the lighthouse wire
    protocol downstream (``heartbeat`` / ``quorum`` / ``GET /status``) so a
    manager points at it via ``TORCHFT_LIGHTHOUSE_AGGREGATOR`` with zero API
    changes, and batches the pod into one delta-encoded ``agg_tick`` RPC per
    tick upstream to the root lighthouse.
    """

    def __init__(
        self,
        root_addr: str,
        bind: str = "0.0.0.0:0",
        agg_id: str = "",
        tick_ms: int = 100,
        heartbeat_timeout_ms: int = 5000,
        connect_timeout: "float | timedelta" = 10.0,
    ) -> None:
        lib = _load()
        handle = ctypes.c_void_p()
        err = ctypes.c_char_p()
        opts = {
            "bind": bind,
            "root_addr": root_addr,
            "agg_id": agg_id,
            "tick_ms": tick_ms,
            "heartbeat_timeout_ms": heartbeat_timeout_ms,
            "connect_timeout_ms": _ms(connect_timeout),
        }
        status = lib.tft_aggregator_new(
            json.dumps(opts).encode(), ctypes.byref(handle), ctypes.byref(err)
        )
        _raise_for_status(status, _take_str(lib, err), "aggregator start failed")
        self._lib = lib
        self._handle = handle

    def address(self) -> str:
        return _take_str(self._lib, self._lib.tft_aggregator_address(self._handle))

    def status(self) -> dict:
        """Pod + upstream view: pod_size/pod_live, joiners_pending,
        ticks_ok/ticks_failed, upstream_bytes, last_tick_ok, last_error."""
        return json.loads(
            _take_str(self._lib, self._lib.tft_aggregator_status(self._handle))
            or "{}"
        )

    @property
    def port(self) -> int:
        return self._lib.tft_aggregator_port(self._handle)

    def shutdown(self) -> None:
        if self._handle:
            self._lib.tft_aggregator_shutdown(self._handle)

    def __del__(self) -> None:
        try:
            if getattr(self, "_handle", None):
                self._lib.tft_aggregator_free(self._handle)
                self._handle = None
        except Exception:
            pass


class ManagerServer:
    """Per-replica-group manager server (native C++).

    Reference equivalent: ``ManagerServer`` in src/lib.rs:80-144 backed by
    src/manager.rs.
    """

    def __init__(
        self,
        replica_id: str,
        lighthouse_addr: str,
        hostname: str = "",
        bind: str = "0.0.0.0:0",
        store_addr: str = "",
        world_size: int = 1,
        heartbeat_interval: "float | timedelta" = 0.1,
        connect_timeout: "float | timedelta" = 10.0,
        quorum_retries: int = 0,
        aggregator_addr: str = "",
    ) -> None:
        """``aggregator_addr`` points control RPCs at a pod aggregator
        (:class:`AggregatorServer`); empty = flat fleet, direct to the
        lighthouse. The manager fails over to direct-to-root on its own if
        the aggregator dies and re-points when the root names a
        replacement."""
        lib = _load()
        handle = ctypes.c_void_p()
        err = ctypes.c_char_p()
        opts = {
            "replica_id": replica_id,
            "lighthouse_addr": lighthouse_addr,
            "hostname": hostname,
            "bind": bind,
            "store_addr": store_addr,
            "world_size": world_size,
            "heartbeat_interval_ms": _ms(heartbeat_interval),
            "connect_timeout_ms": _ms(connect_timeout),
            "quorum_retries": quorum_retries,
            "aggregator_addr": aggregator_addr,
        }
        status = lib.tft_manager_new(
            json.dumps(opts).encode(), ctypes.byref(handle), ctypes.byref(err)
        )
        _raise_for_status(status, _take_str(lib, err), "manager start failed")
        self._lib = lib
        self._handle = handle

    def address(self) -> str:
        return _take_str(self._lib, self._lib.tft_manager_address(self._handle))

    def publish_telemetry(self, telemetry: dict) -> None:
        """Set the per-step telemetry payload the background heartbeat
        thread piggybacks on every beat (healthwatch plane). Keys the
        lighthouse ledger reads: ``step``, ``step_s``, ``wire_s``; anything
        else rides along for the /health dashboard."""
        err = ctypes.c_char_p()
        status = self._lib.tft_manager_publish_telemetry(
            self._handle, json.dumps(telemetry).encode(), ctypes.byref(err)
        )
        _raise_for_status(
            status, _take_str(self._lib, err), "publish_telemetry failed"
        )

    def health(self) -> dict:
        """This replica's health summary from the last heartbeat response
        (state / state_code / score / ejections / readmissions); ``{}``
        until the first beat round-trips."""
        return json.loads(
            _take_str(self._lib, self._lib.tft_manager_health(self._handle))
            or "{}"
        )

    def policy(self) -> dict:
        """The latest adaptive-policy frame carried on a heartbeat reply
        (directly from the root, or fanned out by the pod aggregator):
        ``{"policy_seq", "mode", "knob_overrides", "active_rules"}``.
        ``{}`` until a frame arrives. The Manager polls this at its
        quorum safe point; the beat loop never interprets it."""
        return json.loads(
            _take_str(self._lib, self._lib.tft_manager_policy(self._handle))
            or "{}"
        )

    def clock_skew(self) -> dict:
        """Clock-skew estimate vs the lighthouse from heartbeat round-trips,
        replica-minus-lighthouse: positive when this host's clock runs
        ahead (``skew_ms``/``rtt_ms`` from the minimum-RTT beat, plus
        ``last_skew_ms``/``last_rtt_ms``/``samples``). ``samples`` is 0
        until the first beat round-trips; the tracing plane stamps
        ``skew_ms`` into every span export so the trace merger can place N
        replicas on one corrected timeline."""
        return json.loads(
            _take_str(
                self._lib, self._lib.tft_manager_clock_skew(self._handle)
            )
            or "{}"
        )

    def control_status(self) -> dict:
        """Two-level control plane view: ``aggregator_addr`` /
        ``via_aggregator`` / ``direct_mode`` / ``failovers`` — which
        upstream the heartbeat/quorum RPCs currently use."""
        return json.loads(
            _take_str(
                self._lib, self._lib.tft_manager_control_status(self._handle)
            )
            or "{}"
        )

    @property
    def port(self) -> int:
        return self._lib.tft_manager_port(self._handle)

    def shutdown(self) -> None:
        if self._handle:
            self._lib.tft_manager_shutdown(self._handle)

    def __del__(self) -> None:
        try:
            if getattr(self, "_handle", None):
                self._lib.tft_manager_free(self._handle)
                self._handle = None
        except Exception:
            pass


class KvStoreServer:
    """Rendezvous key-value store server (native C++; TCPStore equivalent)."""

    def __init__(self, bind: str = "0.0.0.0:0") -> None:
        lib = _load()
        handle = ctypes.c_void_p()
        err = ctypes.c_char_p()
        status = lib.tft_kvstore_new(
            bind.encode(), ctypes.byref(handle), ctypes.byref(err)
        )
        _raise_for_status(status, _take_str(lib, err), "kvstore start failed")
        self._lib = lib
        self._handle = handle

    @property
    def port(self) -> int:
        return self._lib.tft_kvstore_port(self._handle)

    def address(self) -> str:
        import socket

        return f"{socket.gethostname()}:{self.port}"

    def shutdown(self) -> None:
        if self._handle:
            self._lib.tft_kvstore_shutdown(self._handle)

    def __del__(self) -> None:
        try:
            if getattr(self, "_handle", None):
                self._lib.tft_kvstore_free(self._handle)
                self._handle = None
        except Exception:
            pass


# ------------------------------------------------------------------- clients
# Test-only fault injection: called before every RPC attempt with
# (method, addr); may sleep (to model a slow link) and/or return an exception
# to raise in place of the real call (to model a flaky/partitioned server).
# Lets tests exercise the retry paths deterministically without real outages.
_rpc_fault_hook: Optional[Callable[[str, str], Optional[Exception]]] = None


def set_rpc_fault_hook(
    hook: Optional[Callable[[str, str], Optional[Exception]]],
) -> None:
    """Install (or clear, with None) the process-wide RPC fault hook."""
    global _rpc_fault_hook
    _rpc_fault_hook = hook


# Exceptions worth retrying: connection-class failures (_UNAVAILABLE/_ERROR
# map to RuntimeError, stalls to TimeoutError). _NOT_FOUND/_INVALID are
# semantic errors — retrying cannot change the answer.
_RETRYABLE_RPC_ERRORS = (TimeoutError, RuntimeError, ConnectionError)

# Connection-loss classes retry with FULL jitter (uniform [0, ceiling]): a
# restarted lighthouse drops every replica at the same instant, and bounded
# jitter would wake the whole herd inside the top half of each backoff
# window (retry.RetryPolicy.backoff_s). Timeouts keep bounded jitter — they
# are not herd-synchronized and bounded jitter preserves deadline pacing.
_FULL_JITTER_RPC_ERRORS = (ConnectionError, RuntimeError)


def _seconds(timeout: "float | timedelta") -> float:
    if isinstance(timeout, timedelta):
        return timeout.total_seconds()
    return float(timeout)


class _RawClient:
    """Generic framed-JSON RPC client over the native transport.

    Every call runs under the shared jittered-backoff retry policy
    (``TORCHFT_RETRY_*`` env knobs; ``TORCHFT_RETRY_MAX_ATTEMPTS=1``
    disables) with the caller's timeout as the hard deadline budget — the
    native ``RpcClient`` re-dials a stale cached connection per attempt, so
    a server blip shorter than the budget degrades to a slower call rather
    than an errored one. On exhaustion the *last underlying* exception is
    re-raised so callers keep their exact pre-retry exception taxonomy.
    """

    def __init__(
        self,
        addr: str,
        connect_timeout: "float | timedelta" = 10.0,
        retry_policy: Optional[RetryPolicy] = None,
    ):
        self._lib = _load()
        handle = ctypes.c_void_p()
        err = ctypes.c_char_p()
        status = self._lib.tft_client_new(
            addr.encode(), _ms(connect_timeout), ctypes.byref(handle),
            ctypes.byref(err),
        )
        _raise_for_status(status, _take_str(self._lib, err), "client create failed")
        self._handle = handle
        self.addr = addr
        self._retry_policy = (
            retry_policy if retry_policy is not None else RetryPolicy.from_env()
        )
        # observer: (method, attempt, prior_exception) on every retry attempt
        self.on_retry: Optional[Callable[[str, int, BaseException], None]] = None

    def call(
        self,
        method: str,
        params: dict,
        timeout: "float | timedelta",
        retry: bool = True,
    ) -> dict:
        return self.call_raw(method, json.dumps(params).encode(), timeout, retry)

    def _call_once(
        self, method: str, params_json: bytes, timeout: "float | timedelta"
    ) -> dict:
        hook = _rpc_fault_hook
        if hook is not None:
            injected = hook(method, self.addr)
            if injected is not None:
                raise injected
        result = ctypes.c_char_p()
        err = ctypes.c_char_p()
        status = self._lib.tft_client_call(
            self._handle, method.encode(), params_json,
            _ms(timeout), ctypes.byref(result), ctypes.byref(err),
        )
        err_s = _take_str(self._lib, err)
        result_s = _take_str(self._lib, result)
        _raise_for_status(status, err_s, f"{method} to {self.addr} failed")
        return json.loads(result_s) if result_s else {}

    def call_raw(
        self,
        method: str,
        params_json: bytes,
        timeout: "float | timedelta",
        retry: bool = True,
    ) -> dict:
        """Like :meth:`call` but takes the params frame pre-encoded —
        per-step callers (the commit vote) build their frame once and
        splice in what changes, skipping json.dumps on the hot path.

        ``retry=False`` opts a call out of the retry policy — required for
        non-idempotent RPCs (``add``) and fire-and-forget ones (``kill``)."""
        policy = self._retry_policy
        if not retry or not policy.enabled:
            return self._call_once(method, params_json, timeout)

        def _on_attempt(attempt: int, prior: Optional[BaseException]) -> None:
            if attempt > 1 and prior is not None and self.on_retry is not None:
                self.on_retry(method, attempt, prior)

        from .retry import RetryBudgetExhausted

        try:
            return retry_call(
                lambda remaining: self._call_once(method, params_json, remaining),
                policy,
                timeout=_seconds(timeout),
                retryable=_RETRYABLE_RPC_ERRORS,
                full_jitter_on=_FULL_JITTER_RPC_ERRORS,
                on_attempt=_on_attempt,
            )
        except RetryBudgetExhausted as e:
            # preserve the pre-retry exception taxonomy for callers
            # (RuntimeError stays RuntimeError, TimeoutError TimeoutError)
            assert e.last_exception is not None
            raise e.last_exception from e

    def __del__(self) -> None:
        try:
            if getattr(self, "_handle", None):
                self._lib.tft_client_free(self._handle)
                self._handle = None
        except Exception:
            pass


class LighthouseClient:
    """Client for the lighthouse service (reference: src/lib.rs:486-594)."""

    def __init__(
        self,
        addr: str,
        connect_timeout: "float | timedelta" = 10.0,
        retry_policy: Optional[RetryPolicy] = None,
    ):
        self._client = _RawClient(addr, connect_timeout, retry_policy)

    def set_retry_observer(
        self, fn: Optional[Callable[[str, int, BaseException], None]]
    ) -> None:
        """Observer called as ``fn(method, attempt, prior_exc)`` on each RPC
        retry attempt (never on the first attempt)."""
        self._client.on_retry = fn

    def quorum(
        self,
        replica_id: str,
        timeout: "float | timedelta",
        address: str = "",
        store_address: str = "",
        step: int = 0,
        world_size: int = 1,
        shrink_only: bool = False,
        data: Optional[Dict] = None,
        commit_failures: int = 0,
    ) -> Quorum:
        member = QuorumMember(
            replica_id=replica_id,
            address=address,
            store_address=store_address,
            step=step,
            world_size=world_size,
            shrink_only=shrink_only,
            commit_failures=commit_failures,
            data=json.dumps(data) if data is not None else "",
        )
        resp = self._client.call("quorum", {"requester": member._to_json()}, timeout)
        return Quorum._from_json(resp["quorum"])

    def heartbeat(
        self,
        replica_id: str,
        timeout: "float | timedelta" = 5.0,
        telemetry: Optional[dict] = None,
    ) -> dict:
        """Beat once; optionally carries a healthwatch telemetry payload.
        Returns the lighthouse's response (``health`` key: this replica's
        health summary)."""
        params: Dict = {"replica_id": replica_id}
        if telemetry is not None:
            params["telemetry"] = telemetry
        return self._client.call("heartbeat", params, timeout)

    def status(self, timeout: "float | timedelta" = 5.0) -> dict:
        return self._client.call("status", {}, timeout)

    def health(self, timeout: "float | timedelta" = 5.0) -> dict:
        """Full healthwatch ledger dump (same payload as GET /health)."""
        return self._client.call("health", {}, timeout)


class ManagerClient:
    """Client for a replica group's manager service (reference: src/lib.rs:153-282)."""

    def __init__(
        self,
        addr: str,
        connect_timeout: "float | timedelta" = 10.0,
        retry_policy: Optional[RetryPolicy] = None,
    ):
        self._client = _RawClient(addr, connect_timeout, retry_policy)
        # pre-built vote frames keyed by (group_rank, vote): everything but
        # the step number is invariant across a training run, so the
        # per-step should_commit only splices the step into a cached prefix
        # instead of re-serializing the params dict (see should_commit)
        self._vote_frames: Dict[Tuple[int, bool], bytes] = {}

    def set_retry_observer(
        self, fn: Optional[Callable[[str, int, BaseException], None]]
    ) -> None:
        """Observer called as ``fn(method, attempt, prior_exc)`` on each RPC
        retry attempt (never on the first attempt)."""
        self._client.on_retry = fn

    def _quorum(
        self,
        group_rank: int,
        step: int,
        checkpoint_metadata: str,
        shrink_only: bool,
        timeout: "float | timedelta",
        init_sync: bool = True,
        commit_failures: int = 0,
    ) -> QuorumResult:
        resp = self._client.call(
            "quorum",
            {
                "group_rank": group_rank,
                "step": step,
                "checkpoint_metadata": checkpoint_metadata,
                "shrink_only": shrink_only,
                "init_sync": init_sync,
                "commit_failures": commit_failures,
            },
            timeout,
        )
        return QuorumResult._from_json(resp)

    def _checkpoint_metadata(self, rank: int, timeout: "float | timedelta") -> str:
        resp = self._client.call("checkpoint_metadata", {"rank": rank}, timeout)
        return resp["checkpoint_metadata"]

    def should_commit(
        self,
        group_rank: int,
        step: int,
        should_commit: bool,
        timeout: "float | timedelta",
    ) -> bool:
        key = (group_rank, should_commit)
        prefix = self._vote_frames.get(key)
        if prefix is None:
            # '{"group_rank": N, "should_commit": B}' -> strip the closing
            # brace, leave a slot for the step: '...,"step":'
            head = json.dumps(
                {"group_rank": group_rank, "should_commit": should_commit}
            ).encode()
            prefix = head[:-1] + b', "step": '
            self._vote_frames[key] = prefix
        resp = self._client.call_raw(
            "should_commit", prefix + str(step).encode() + b"}", timeout
        )
        return resp["should_commit"]

    def kill(self, msg: str = "", timeout: "float | timedelta" = 5.0) -> None:
        try:
            # fire-and-forget: never retried (the target exits mid-reply)
            self._client.call("kill", {"msg": msg}, timeout, retry=False)
        except (RuntimeError, TimeoutError):
            pass  # the target exits without replying


class KvClient:
    """Client for the rendezvous KV store.

    ``set`` values are arbitrary bytes ("b64:"-prefixed base64 on the wire);
    ``add`` counters are stored by the server as plain decimal text — ``get``
    handles both transparently.
    """

    def __init__(
        self,
        addr: str,
        connect_timeout: "float | timedelta" = 10.0,
        retry_policy: Optional[RetryPolicy] = None,
    ):
        self._client = _RawClient(addr, connect_timeout, retry_policy)

    def set_retry_observer(
        self, fn: Optional[Callable[[str, int, BaseException], None]]
    ) -> None:
        self._client.on_retry = fn

    def set(self, key: str, value: "bytes | str", timeout: "float | timedelta" = 10.0) -> None:
        import base64

        if isinstance(value, str):
            value = value.encode()
        self._client.call(
            "set",
            {"key": key, "value": "b64:" + base64.b64encode(value).decode()},
            timeout,
        )

    def get(
        self, key: str, timeout: "float | timedelta" = 10.0, wait: bool = True
    ) -> bytes:
        import base64

        resp = self._client.call("get", {"key": key, "wait": wait}, timeout)
        value = resp["value"]
        if value.startswith("b64:"):
            return base64.b64decode(value[4:])
        return value.encode()  # add() counter or other plain-text value

    def add(self, key: str, amount: int, timeout: "float | timedelta" = 10.0) -> int:
        # non-idempotent: a retry after a lost reply would double-count
        return self._client.call(
            "add", {"key": key, "amount": amount}, timeout, retry=False
        )["value"]

    def check(self, keys: List[str], timeout: "float | timedelta" = 10.0) -> bool:
        return self._client.call("check", {"keys": keys}, timeout)["exists"]

    def delete(self, key: str, timeout: "float | timedelta" = 10.0) -> bool:
        return self._client.call("delete", {"key": key}, timeout)["deleted"]

    def num_keys(self, timeout: "float | timedelta" = 10.0) -> int:
        return self._client.call("num_keys", {}, timeout)["count"]


# ----------------------------------------------------- pure logic (testing)
def quorum_compute(state: dict, opts: dict) -> dict:
    """Run the native lighthouse quorum computation on a synthetic state.

    For unit tests (reference pattern: src/lighthouse.rs:627-1071).
    """
    lib = _load()
    result = ctypes.c_char_p()
    err = ctypes.c_char_p()
    status = lib.tft_quorum_compute(
        json.dumps(state).encode(), json.dumps(opts).encode(),
        ctypes.byref(result), ctypes.byref(err),
    )
    err_s = _take_str(lib, err)
    result_s = _take_str(lib, result)
    _raise_for_status(status, err_s, "quorum_compute failed")
    return json.loads(result_s)


def compute_quorum_results(
    replica_id: str, group_rank: int, quorum: dict, init_sync: bool = True
) -> QuorumResult:
    """Run the native per-rank recovery-assignment computation.

    For unit tests (reference pattern: src/manager.rs:881-1108).
    """
    lib = _load()
    result = ctypes.c_char_p()
    err = ctypes.c_char_p()
    status = lib.tft_compute_quorum_results(
        replica_id.encode(), group_rank, json.dumps(quorum).encode(),
        1 if init_sync else 0, ctypes.byref(result), ctypes.byref(err),
    )
    err_s = _take_str(lib, err)
    result_s = _take_str(lib, result)
    _raise_for_status(status, err_s, "compute_quorum_results failed")
    return QuorumResult._from_json(json.loads(result_s))


def health_scores(windows: "Dict[str, list]", opts: dict) -> "Dict[str, float]":
    """Run the NATIVE straggler scoring on synthetic windows.

    Parity hook for tests: torchft_tpu/healthwatch.py carries the canonical
    Python implementation and tests pin the native one to it.
    """
    lib = _load()
    result = ctypes.c_char_p()
    err = ctypes.c_char_p()
    status = lib.tft_health_scores(
        json.dumps(windows).encode(), json.dumps(opts).encode(),
        ctypes.byref(result), ctypes.byref(err),
    )
    err_s = _take_str(lib, err)
    result_s = _take_str(lib, result)
    _raise_for_status(status, err_s, "health_scores failed")
    return json.loads(result_s)


def health_replay(script: list, opts: dict) -> dict:
    """Replay a scripted beat/tick sequence through the NATIVE health
    ledger on a synthetic clock; returns ``{"events", "ledger", "excluded"}``.

    ``script`` entries: ``{"t_ms": N, "replica_id": ..., "telemetry":
    {...}?}`` for beats, ``{"t_ms": N, "tick": true}`` for ticks. ``opts``
    is HealthOpts fields plus ``heartbeat_timeout_ms`` / ``min_replicas``.
    Parity hook for tests against the Python :class:`HealthLedger`.
    """
    lib = _load()
    result = ctypes.c_char_p()
    err = ctypes.c_char_p()
    status = lib.tft_health_replay(
        json.dumps(script).encode(), json.dumps(opts).encode(),
        ctypes.byref(result), ctypes.byref(err),
    )
    err_s = _take_str(lib, err)
    result_s = _take_str(lib, result)
    _raise_for_status(status, err_s, "health_replay failed")
    return json.loads(result_s)


def history_replay(jsonl_text: str) -> dict:
    """Parse a recorded-history JSONL through the NATIVE read path;
    returns ``{"events": [...], "summary": {...}}``.

    Accepts content or a path (plain or gzip'd) — both are funnelled
    through :func:`torchft_tpu.tracing.load_history`, the single loader
    shared with the ``trace history`` and ``policy replay`` CLIs, so the
    entry points can't drift apart again.

    Parity hook for tests: torchft_tpu.tracing.history_fold carries the
    canonical Python fold and tests pin the native summary to it (same
    convention as :func:`health_replay`).
    """
    from torchft_tpu.tracing import load_history

    events = load_history(jsonl_text)
    normalized = "\n".join(json.dumps(e) for e in events)
    lib = _load()
    result = ctypes.c_char_p()
    err = ctypes.c_char_p()
    status = lib.tft_history_replay(
        normalized.encode(), ctypes.byref(result), ctypes.byref(err)
    )
    err_s = _take_str(lib, err)
    result_s = _take_str(lib, result)
    _raise_for_status(status, err_s, "history_replay failed")
    return json.loads(result_s)
