"""Manager: the per-worker fault-tolerance state machine.

Role-equivalent of the reference Manager (torchft/manager.py:148-1046). Owns
the quorum lifecycle (async on a one-thread executor), process-group
reconfiguration per quorum, live healing (send/recv checkpoint between
replica groups), error capture with swallow-to-default semantics, the
two-phase commit protocol, and step/batches accounting.

JAX-flavored deviations from the reference, by design:

- **State is a pytree.** Registered state-dict functions return/accept JAX
  pytrees; "zero the tensor on error" becomes *returning a zeros pytree*
  (arrays are immutable, so corrupt in-flight buffers can simply be dropped).
- **No stream plumbing.** JAX has no user streams; the recovery "stream" is
  the quorum executor thread, and ``should_commit`` joins it instead of
  synchronizing a CUDA event (reference manager.py:873-885).
- **Eager future chains.** The reference's lazy ``_ManagedWork`` machinery
  exists to avoid blocking CUDA streams from Python; with host-side
  collectives + async dispatch there is nothing to block, so futures chain
  eagerly.
"""

from __future__ import annotations

import logging
import os
import socket as _socket
import threading
import time
import traceback
import uuid
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from pathlib import Path
from enum import Enum
from typing import Any, Callable, Dict, List, Optional, TypeVar, cast

import numpy as np

from torchft_tpu import bucketing, knobs
from torchft_tpu.checkpointing import CheckpointTransport, HTTPTransport, RWLock
from torchft_tpu.coordination import (
    KvClient,
    KvStoreServer,
    ManagerClient,
    ManagerServer,
)
from torchft_tpu.futures import future_timeout
from torchft_tpu.observability import (
    ALLREDUCE_PIPELINE_PHASE,
    COMMIT_EVENTS,
    HEALTH_EVENTS,
    METRICS_PORT_ENV,
    POLICY_EVENTS,
    TIMING_EVENTS,
    MetricsRegistry,
    MetricsServer,
    emit_event_async,
    get_event_drain,
    log_error_event,
    log_quorum_event,
)
from torchft_tpu.ops.quantization import resolve_compress_mode
from torchft_tpu.process_group import ProcessGroup, ReduceOp
from torchft_tpu.tracing import TRACE_BUFFER_ENV, SpanRecorder, TraceConfig
from torchft_tpu.work import (
    DummyWork,
    Future,
    FutureWork,
    GradStream,
    Work,
)

T = TypeVar("T")

logger = logging.getLogger(__name__)

__all__ = ["Manager", "WorldSizeMode", "ExceptionWithTraceback"]

# env-var config knobs (reference: manager.py:74-89)
MANAGER_PORT_ENV = "TORCHFT_MANAGER_PORT"
LIGHTHOUSE_ENV = "TORCHFT_LIGHTHOUSE"
# optional pod-level lighthouse aggregator (two-level control plane); the
# manager prefers it for heartbeat/quorum and fails over to the root
# lighthouse on its own if it dies (coordination.AggregatorServer)
AGGREGATOR_ENV = "TORCHFT_LIGHTHOUSE_AGGREGATOR"
TIMEOUT_SEC_ENV = "TORCHFT_TIMEOUT_SEC"
QUORUM_TIMEOUT_SEC_ENV = "TORCHFT_QUORUM_TIMEOUT_SEC"
CONNECT_TIMEOUT_SEC_ENV = "TORCHFT_CONNECT_TIMEOUT_SEC"
QUORUM_RETRIES_ENV = "TORCHFT_QUORUM_RETRIES"
# bucket cap for the managed allreduce's bucketed path, in MiB; 0 disables
# bucketing entirely (per-leaf collectives, the pre-bucketing behavior)
BUCKET_CAP_MB_ENV = "TORCHFT_BUCKET_CAP_MB"
# wire compression for the pipeline's buckets ("off" | "fp8" | "int8"): resolved
# in ops/quantization.resolve_compress_mode (env TORCHFT_COMPRESS >
# constructor > "off") so doctor.py validates the same way the Manager does

# timings() keys that are cumulative counters (rendered as Prometheus
# `_total` counters by _refresh_metrics); every other numeric key is a
# last-value gauge
_COUNTER_TIMINGS = frozenset(
    {
        "heal_attempts",
        "heal_failovers",
        "rpc_retries",
        "chunk_crc_failures",
        "collective_reroute",
        "ejections",
        "readmissions",
        "dropped_events",
        "trace_dropped",
        # standby snapshot refused because this replica is itself mid-heal
        # (see _async_quorum_body): a fallback peer asking us for state
        # would get the stale pre-heal copy, so we decline loudly
        "standby_skipped",
        # redundancy plane (redundancy.py): shard staging + reconstruct
        "shards_staged",
        "shard_stage_skipped",
        "shard_stage_dropped",
        "shard_stage_failed",
        "shard_put_failed",
        "shard_announce_rejected",
        "reconstructs",
        "reconstruct_failures",
        "shard_corrupt",
        "shard_fetch_failed",
        # degrade plane (parallel/degrade.py): in-place group shrinks and
        # full-degree restores
        "degrade_events",
        "restored_events",
        # policy plane (_poll_policy_safe_point): frames enforced /
        # observed at the quorum safe point (policy_seq stays a gauge —
        # it is the latest frame version, not a count)
        "policy_applies",
        "policy_intents",
    }
)


def _to_seconds(t: "float | timedelta") -> float:
    return t.total_seconds() if isinstance(t, timedelta) else float(t)


class WorldSizeMode(Enum):
    """Gradient semantics under a changing world size
    (reference: manager.py:123-139).

    DYNAMIC: quorum can be any size >= min_replica_size; batch size varies.
    FIXED_WITH_SPARES: at most min_replica_size replicas contribute; extras
    are hot spares with zeroed contributions, keeping gradient scale fixed.
    """

    DYNAMIC = "dynamic"
    FIXED_WITH_SPARES = "fixed_with_spares"


class ExceptionWithTraceback(Exception):
    def __init__(self, e: Exception) -> None:
        self.original_exception = e
        self.tb = traceback.format_exception(type(e), e, e.__traceback__)
        super().__init__("".join(self.tb))


class _ManagerLogger:
    def __init__(self, manager: "Manager", replica_id: str, group_rank: int):
        self._logger = logger
        self._replica_id = replica_id
        self._group_rank = group_rank
        self._manager = manager

    def _prefix(self) -> str:
        return f"[{self._replica_id}/{self._group_rank} - step {self._manager._step}]"

    def debug(self, msg: str) -> None:
        logger.debug(f"{self._prefix()} {msg}")

    def info(self, msg: str) -> None:
        self._logger.info(f"{self._prefix()} {msg}")

    def warning(self, msg: str) -> None:
        self._logger.warning(f"{self._prefix()} {msg}")

    def exception(self, msg: str) -> None:
        self._logger.exception(f"{self._prefix()} {msg}")


class Manager:
    """Fault-tolerance manager for one worker of one replica group.

    Typical single-process-per-replica-group usage::

        manager = Manager(
            pg=ProcessGroupHost(),
            load_state_dict=load_fn,     # applied on live recovery
            state_dict=state_fn,         # served to healing peers
            min_replica_size=2,
        )
        for batch in data:
            manager.start_quorum()
            grads = grad_fn(params, batch)
            reduced = manager.allreduce(grads).get_future().wait()
            if manager.should_commit():
                params = apply(params, reduced)
    """

    def __init__(
        self,
        pg: ProcessGroup,
        load_state_dict: Optional[Callable[[Any], None]],
        state_dict: Optional[Callable[[], Any]],
        min_replica_size: int,
        use_async_quorum: bool = True,
        timeout: "float | timedelta" = 60.0,
        quorum_timeout: "float | timedelta | None" = None,
        connect_timeout: "float | timedelta | None" = None,
        replica_id: Optional[str] = None,
        lighthouse_addr: Optional[str] = None,
        store_addr: Optional[str] = None,
        group_rank: int = 0,
        group_world_size: int = 1,
        checkpoint_transport: Optional[CheckpointTransport] = None,
        init_sync: bool = True,
        world_size_mode: WorldSizeMode = WorldSizeMode.DYNAMIC,
        max_retries: Optional[int] = None,
        quorum_retries: Optional[int] = None,
        heartbeat_interval: "float | timedelta" = 0.1,
        hostname: str = "",
        bucket_cap_bytes: Optional[int] = None,
        compress: Optional[str] = None,
        tracing: Optional[bool] = None,
        metrics_port: Optional[int] = None,
        spare: bool = False,
    ) -> None:
        self._pg = pg
        self._min_replica_size = min_replica_size
        self._use_async_quorum = use_async_quorum
        # the mode the CALLER asked for: the requires_sync_quorum override
        # below is re-evaluated per step (start_quorum) so an auto-mode PG
        # that stops requiring sync quorum once configure resolves its mode
        # gets async quorum back — but never a caller who chose sync
        self._requested_async_quorum = use_async_quorum
        if use_async_quorum and getattr(pg, "requires_sync_quorum", False):
            # Safety valve for PGs WITHOUT a prepare/commit configure
            # split that still rebuild global device state inside
            # configure: running that concurrently with the trainer's own
            # jax computations would race backend init mid-rebuild.
            # ProcessGroupXLA no longer sets this — its prepare_configure
            # stages the control plane on the quorum thread and hands the
            # backend swap back as a commit this Manager applies from the
            # main thread (_commit_pending_configure), so async quorum is
            # safe on the device plane.
            logger.info(
                "pg %s requires sync quorum; overriding use_async_quorum",
                type(pg).__name__,
            )
            self._use_async_quorum = False
        self._timeout = float(os.environ.get(TIMEOUT_SEC_ENV, _to_seconds(timeout)))
        self._quorum_timeout = float(
            os.environ.get(
                QUORUM_TIMEOUT_SEC_ENV,
                _to_seconds(quorum_timeout) if quorum_timeout is not None else self._timeout,
            )
        )
        self._connect_timeout = float(
            os.environ.get(
                CONNECT_TIMEOUT_SEC_ENV,
                _to_seconds(connect_timeout) if connect_timeout is not None else 10.0,
            )
        )
        self._replica_world_size_mode = world_size_mode
        self._init_sync = init_sync
        self._max_retries = max_retries
        self._group_rank = group_rank
        self._group_world_size = group_world_size
        quorum_retries = (
            int(os.environ.get(QUORUM_RETRIES_ENV, 0))
            if quorum_retries is None
            else quorum_retries
        )

        # (transport constructed after the hostname default below)

        # user state-dict functions, guarded against concurrent mutation
        # during checkpoint serving (reference: manager.py:243, 366-391)
        self._state_dict_lock = RWLock(timeout=self._timeout)
        self._load_state_dict_fns: Dict[str, Callable[[Any], None]] = {}
        self._user_state_dicts: Dict[str, Callable[[], Any]] = {}
        if state_dict is not None and load_state_dict is not None:
            self.register_state_dict_fn("default", load_state_dict, state_dict)

        self._store: Optional[KvStoreServer] = None
        self._manager: Optional[ManagerServer] = None
        hostname = hostname or _socket.gethostname()

        if checkpoint_transport is None:
            # the heal URL must use the same peer-resolvable hostname the
            # store/manager addresses use, or healing alone breaks on
            # fleets where gethostname() doesn't resolve (k8s pods)
            checkpoint_transport = HTTPTransport(
                timeout=self._timeout, hostname=hostname
            )
        self._checkpoint_transport: CheckpointTransport = checkpoint_transport

        # Hot-spare role (redundancy.py, docs/operations.md): a spare
        # shadows the fleet WITHOUT joining the quorum — no ManagerServer,
        # no lighthouse heartbeat — so the quorum never counts or waits on
        # it. The control-plane join is deferred into promote(), which
        # fires when the shard directory promotes this spare to replace a
        # dead member; until then the quorum-facing methods (start_quorum,
        # should_commit, allreduce) must not be called.
        self._spare = spare
        self._spare_join_args: Optional[Dict[str, Any]] = None
        self._spare_promotion: Optional[Dict[str, Any]] = None
        manager_addr: Optional[str] = None
        if spare:
            if group_rank != 0:
                raise ValueError(
                    "Manager(spare=True) is a whole-replica role: only "
                    "group_rank 0 may construct it"
                )
            replica_name = replica_id if replica_id is not None else "spare"
            self._replica_id = f"{replica_name}:{uuid.uuid4()}"
            self._spare_join_args = {
                "hostname": hostname,
                "store_addr": store_addr,
                "lighthouse_addr": (
                    lighthouse_addr
                    if lighthouse_addr is not None
                    else os.environ.get(LIGHTHOUSE_ENV)
                ),
                "group_world_size": group_world_size,
                "heartbeat_interval": heartbeat_interval,
                "quorum_retries": quorum_retries,
            }
        elif group_rank == 0:
            # Group leader: owns the rendezvous store and the manager server.
            if store_addr is None:
                bind_port = int(os.environ.get(MANAGER_PORT_ENV, 0))
                self._store = KvStoreServer("0.0.0.0:0")
                store_addr = f"{hostname}:{self._store.port}"
            else:
                bind_port = int(os.environ.get(MANAGER_PORT_ENV, 0))

            if lighthouse_addr is None:
                lighthouse_addr = os.environ[LIGHTHOUSE_ENV]

            replica_name = replica_id if replica_id is not None else "replica"
            full_replica_id = f"{replica_name}:{uuid.uuid4()}"
            self._manager = ManagerServer(
                replica_id=full_replica_id,
                lighthouse_addr=lighthouse_addr,
                hostname=hostname,
                bind=f"0.0.0.0:{bind_port}",
                store_addr=store_addr,
                world_size=group_world_size,
                heartbeat_interval=heartbeat_interval,
                connect_timeout=self._connect_timeout,
                quorum_retries=quorum_retries,
                aggregator_addr=os.environ.get(AGGREGATOR_ENV, ""),
            )
            self._replica_id = full_replica_id
            manager_addr = self._manager.address()
            # publish for the other group ranks (reference: manager.py:333-337)
            KvClient(store_addr, connect_timeout=self._connect_timeout).set(
                "manager_addr", manager_addr, timeout=self._timeout
            )
        else:
            assert store_addr is not None, "non-leader ranks need store_addr"
            manager_addr = (
                KvClient(store_addr, connect_timeout=self._connect_timeout)
                .get("manager_addr", timeout=self._timeout)
                .decode()
            )
            self._replica_id = replica_id if replica_id is not None else "replica"

        self._store_addr = store_addr
        self._client: Optional[ManagerClient] = None
        self._vote_client: Optional[ManagerClient] = None
        if manager_addr is not None:
            self._client = ManagerClient(
                manager_addr, connect_timeout=self._connect_timeout
            )
            # Dedicated client for the per-step commit vote: the native RPC
            # client keeps ONE cached keep-alive connection per handle, and a
            # call that arrives while another thread holds it falls back to a
            # one-shot connect. The quorum thread's RPC is in flight exactly
            # when the main thread votes (async quorum), so sharing a handle
            # would put a TCP connect on the hot path every overlapped step.
            self._vote_client = ManagerClient(
                manager_addr, connect_timeout=self._connect_timeout
            )

        # bucketed managed allreduce: cap resolution order is env var >
        # constructor > default; 0 disables (per-leaf collectives)
        env_cap = os.environ.get(BUCKET_CAP_MB_ENV)
        if env_cap is not None:
            self._bucket_cap_bytes = int(float(env_cap) * 1024 * 1024)
        elif bucket_cap_bytes is not None:
            self._bucket_cap_bytes = int(bucket_cap_bytes)
        else:
            self._bucket_cap_bytes = bucketing.DEFAULT_BUCKET_CAP_BYTES
        self._buffer_pool = bucketing.BufferPool()
        # wire compression for the pipeline's buckets: TORCHFT_COMPRESS env >
        # constructor > "off". Raises on a bad value (same message the
        # doctor check surfaces) rather than training uncompressed silently.
        self._compress = resolve_compress_mode(compress)

        self._step = 0
        self._quorum_id = -1
        self._batches_committed = 0
        self._commit_failures = 0
        self._errored: Optional[ExceptionWithTraceback] = None
        # lifetime counters for metrics() — monotonic, never reset (unlike
        # _commit_failures, which is the protocol's CONSECUTIVE counter)
        self._metrics_lock = threading.Lock()
        self._metrics: Dict[str, int] = {
            "quorums": 0,
            "reconfigures": 0,
            "heals": 0,
            "commits": 0,
            "commit_failures": 0,
            "allreduces": 0,
            "errors": 0,
        }
        self._healing = False
        self._last_quorum_healed = False
        # True while this replica holds a standby failover snapshot open
        # for a heal in progress elsewhere in the quorum (see
        # _async_quorum_body); should_commit defers disallow_checkpoint
        # until the episode ends
        self._standby_source = False
        self._pending_state_dict: Optional[Dict[str, Any]] = None
        # prepare/commit configure split: the quorum thread stages the
        # reconfigure (prepare_configure) and stashes the returned commit
        # here; the main thread applies it at the next safe point via
        # _commit_pending_configure. Guarded by its own lock so a late
        # quorum-thread stash can't race the main-thread take.
        self._pending_pg_commit: Optional[Callable[[], None]] = None
        self._pending_commit_lock = threading.Lock()
        # per-phase wall-clock timings for the most recent quorum cycle
        # (quorum_overlap_s, configure_prepare_s, configure_commit_s,
        # heal_recv_s, ...) — shares _metrics_lock
        self._timings: Dict[str, float] = {}
        # resilience counters ride the same dict so they flow through
        # timings() and the torchft_timings stream without a second
        # plumbing path. Unlike the phase timings these are CUMULATIVE:
        # a blip that cost two RPC retries three steps ago stays visible.
        for _counter in (
            "heal_attempts",
            "heal_failovers",
            "rpc_retries",
            "chunk_crc_failures",
            "collective_reroute",
            "standby_skipped",
        ):
            self._timings[_counter] = 0.0
        # rpc_retries: every retried control-plane call on either manager
        # client bumps the counter and leaves a flight-recorder breadcrumb,
        # so "the step got slower" is attributable to a named RPC.
        # (A spare has no clients until promote() joins the control plane.)
        if self._client is not None:
            self._client.set_retry_observer(self._on_rpc_retry)
        if self._vote_client is not None:
            self._vote_client.set_retry_observer(self._on_rpc_retry)
        # collective_reroute: the compressed ring re-formed around a dead
        # link mid-collective. Same pattern as rpc_retries — counter plus a
        # flight-recorder breadcrumb naming the link.
        _set_reroute = getattr(pg, "set_reroute_observer", None)
        if _set_reroute is not None:
            _set_reroute(self._on_collective_reroute)
        # healthwatch: the group leader piggybacks per-step telemetry on
        # its heartbeat thread (publish_telemetry) and reads the
        # lighthouse's health summary back off the same round-trip. The
        # summary's cumulative counters and latest state ride timings();
        # state TRANSITIONS additionally emit torchft_health events and
        # flight-recorder breadcrumbs (_publish_step_telemetry).
        for _counter in ("health_state", "straggler_score", "ejections", "readmissions"):
            self._timings[_counter] = 0.0
        # degrade plane: in-place group shrinks / full-degree restores
        # (docs/operations.md#degraded-replicas)
        for _counter in ("degrade_events", "restored_events"):
            self._timings[_counter] = 0.0
        # policy plane (docs/operations.md#adaptive-policies): frames are
        # polled off the heartbeat mirror at the start_quorum safe point.
        # policy_seq = last frame version seen; policy_intents counts
        # observe-mode would-be applications, policy_applies enforce-mode
        # real ones. TORCHFT_POLICY=off skips the poll entirely (the
        # byte-identical contract pinned by test_policy_off_byte_identical).
        for _counter in ("policy_seq", "policy_applies", "policy_intents"):
            self._timings[_counter] = 0.0
        self._policy_mode = knobs.env_str("TORCHFT_POLICY", "off").strip() or "off"
        self._policy_seq_seen = -1
        # live knob adjusters: knob name -> setter, registered by the
        # planes that can retarget without a restart (LocalSGD/DiLoCo
        # sync_every, redundancy staging interval). Applied in enforce
        # mode at the safe point, after knobs.set_override.
        self._policy_adjusters: Dict[str, Callable[[str], None]] = {}
        # the override set THIS manager last applied in enforce mode —
        # the diff base for reverts (knobs' global layer is shared
        # across managers in-process, so it can't be the baseline)
        self._policy_overrides_applied: Dict[str, str] = {}
        self._telemetry_transform: Optional[
            Callable[[Dict[str, Any]], Dict[str, Any]]
        ] = None
        self._last_health_state: Optional[str] = None
        self._last_commit_t: Optional[float] = None
        # serving plane (attach_serve_publisher): committed snapshots are
        # published as (quorum_id, step) versions; None = plane disabled
        self._serve_publisher: Optional[Any] = None
        self._serve_params_fn: Optional[Callable[[], Any]] = None
        self._last_vote_committed = False
        self._telemetry_quorum_id: Optional[int] = None
        self._participating_replica_rank: Optional[int] = None
        # last seen PG backend generation (see _sync_device_world)
        self._device_world_epoch = getattr(pg, "device_world_epoch", None)
        self._participating_replica_world_size: int = 0
        self._num_replicas: int = 0

        self._executor = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="torchft_quorum"
        )
        self._quorum_future: Optional[Any] = None

        self._logger = _ManagerLogger(self, self._replica_id, group_rank)

        # fleet tracing plane: per-replica span recorder (tracing.py).
        # Constructor arg > TORCHFT_TRACE env (default on); spans are O(1)
        # dict appends behind one lock (what default-on costs on the chip:
        # PERF.md section 6, PR 24).
        trace_cfg = TraceConfig.from_env()
        if tracing is not None:
            trace_cfg.enabled = bool(tracing)
        self._tracer = SpanRecorder(self._replica_id, trace_cfg)
        # what is recorded before the first quorum (the trainer's start-up,
        # the head of its first iteration) belongs to the step it leads to
        self._tracer.set_context(step=self._step)
        # the data plane of allreduce(): capture, staging, wire, landing and
        # the threads they run on; host staging buffers recycle through the
        # pool instead of allocating a gradient-sized buffer per step
        self._pipeline = bucketing.BucketPipeline(
            pg, self._tracer, self._buffer_pool, self._update_timings
        )
        # heal_fetch / heal_place seconds of the receive in flight, summed
        # over the transport's fetch threads (_on_heal_event), and the
        # heal_recv span they are children of
        self._heal_lock = threading.Lock()
        self._heal_sums: Dict[str, float] = {}
        self._heal_recv_span: Optional[int] = None
        self._first_commit_seen = False
        # one-shot latch for the dropped_events warning (satellite: the
        # drain's drop count used to be silently discarded)
        self._dropped_events_warned = False

        # manager-side /metrics: constructor arg > TORCHFT_METRICS_PORT
        # env; absent/empty = no server. Histograms are fed at record time
        # (_record_timing); gauges/counters sync from timings() and
        # wire_stats() only when a scrape actually arrives (refresh hook).
        self._metrics_registry: Optional[MetricsRegistry] = None
        self._metrics_server: Optional[MetricsServer] = None
        env_metrics = os.environ.get(METRICS_PORT_ENV, "")
        if metrics_port is None and env_metrics != "":
            try:
                metrics_port = int(env_metrics)
            except ValueError:
                self._logger.warning(
                    f"ignoring invalid {METRICS_PORT_ENV}={env_metrics!r}"
                )
        if metrics_port is not None:
            # Never let the observability knob take down training: with a
            # fixed TORCHFT_METRICS_PORT and >1 Manager per host (multiple
            # group ranks, or a restart racing TIME_WAIT) the bind raises
            # EADDRINUSE — run without metrics instead of crashing.
            try:
                registry = MetricsRegistry()
                self._metrics_server = MetricsServer(
                    registry,
                    port=metrics_port,
                    refresh=self._refresh_metrics,
                )
                self._metrics_registry = registry
            except OSError as e:
                self._logger.warning(
                    f"metrics server failed to bind port {metrics_port} "
                    f"({e}); continuing without /metrics"
                )

        # redundancy plane (redundancy.py, docs/operations.md): when
        # TORCHFT_REDUNDANCY_K >= 1 and a shard directory is configured,
        # the group leader erasure-codes every committed generation and
        # stages the shards across peers off the hot path, and the heal
        # path tries a parallel reconstruct before the serial peer pull.
        # k=0 (the default) leaves every existing path byte-identical —
        # pinned by tests/test_redundancy.py.
        self._redundancy_cfg: Optional[Any] = None
        self._shard_stager: Optional[Any] = None
        self._hot_spare: Optional[Any] = None
        self._redundancy_stage_pending = False
        try:
            from torchft_tpu import redundancy as _redundancy

            _red_cfg = _redundancy.RedundancyConfig.from_env()
            if spare:
                if not _red_cfg.directory:
                    raise ValueError(
                        "Manager(spare=True) requires a shard directory "
                        f"(${_redundancy.REDUNDANCY_DIRECTORY_ENV})"
                    )
                self._redundancy_cfg = _red_cfg
                self._hot_spare = _redundancy.HotSpare(
                    _red_cfg,
                    spare_id=self._replica_id,
                    # shadow the serving-plane delta chain too when the
                    # registry is configured (serving.SERVE_REGISTRY_ENV)
                    serve_registry=os.environ.get(
                        "TORCHFT_SERVE_REGISTRY", ""
                    )
                    or None,
                    on_metric=self._on_redundancy_metric,
                )
            elif _red_cfg.enabled:
                _red_cfg.validate()
                self._redundancy_cfg = _red_cfg
                if group_rank == 0:
                    self._shard_stager = _redundancy.ShardStager(
                        _red_cfg,
                        self._replica_id,
                        on_metric=self._on_redundancy_metric,
                    )
        except ValueError:
            raise
        except Exception:  # noqa: BLE001 — the plane is advisory
            self._logger.exception(
                "redundancy plane failed to attach; continuing without it"
            )
            self._redundancy_cfg = None
            self._shard_stager = None
        if self._shard_stager is not None:
            # policy plane can retune staging cadence / parity count live;
            # both take effect at the next maybe_stage (per-commit gate)
            self._policy_red_defaults = (
                self._redundancy_cfg.interval,
                self._redundancy_cfg.m,
            )
            self.register_policy_adjuster(
                "TORCHFT_REDUNDANCY_INTERVAL", self._policy_set_red_interval
            )
            self.register_policy_adjuster(
                "TORCHFT_REDUNDANCY_M", self._policy_set_red_m
            )

        # degrade plane (parallel/degrade.py, docs/operations.md
        # #degraded-replicas): with TORCHFT_DEGRADE=on a dead chip inside
        # the replica group shrinks the group's own TP/PP degree in place
        # — a re-planned slow step — instead of costing the whole group a
        # leave-heal-rejoin cycle. Off (the default) registers nothing and
        # leaves every code path byte-identical, pinned by
        # tests/test_degrade.py.
        self._degrade_cfg: Optional[Any] = None
        self._degrade_lock = threading.Lock()
        # the group's parallel degree: in single-controller SPMD jobs the
        # mesh spans chips the Manager's group_world_size never sees, so
        # the degree is declared via set_group_degree()
        self._full_group_degree: int = group_world_size
        self._group_degree: int = group_world_size
        self._degrade_pending: Optional[int] = None  # dead group_rank
        self._reshard_fn: Optional[Callable[[int, int], Any]] = None
        try:
            from torchft_tpu.parallel.degrade import DegradeConfig

            _deg_cfg = DegradeConfig.from_env()
            if _deg_cfg.enabled:
                self._degrade_cfg = _deg_cfg
                # member-death detection: the abort watchdog / fault
                # injection path on PGs that track intra-group members.
                # Registered ONLY when the plane is on.
                _set_death = getattr(pg, "set_member_death_callback", None)
                if _set_death is not None:
                    _set_death(self.report_member_death)
        except ValueError:
            raise
        except Exception:  # noqa: BLE001 — the plane is advisory
            self._logger.exception(
                "degrade plane failed to attach; continuing without it"
            )
            self._degrade_cfg = None

    # ------------------------------------------------------------- state fns
    def register_state_dict_fn(
        self,
        key: str,
        load_fn: Callable[[Any], None],
        value_fn: Callable[[], Any],
    ) -> None:
        """Register a named (load, save) pair included in live recovery
        (reference: manager.py:380-391)."""
        with self._state_dict_lock.w_lock():
            self._load_state_dict_fns[key] = load_fn
            self._user_state_dicts[key] = value_fn

    def set_state_dict_fns(
        self,
        load_state_dict: Callable[[Any], None],
        state_dict: Callable[[], Any],
    ) -> None:
        """Deprecated alias kept for reference API parity
        (manager.py set_state_dict_fns); use register_state_dict_fn."""
        self._logger.warning(
            "set_state_dict_fns is deprecated, use register_state_dict_fn"
        )
        # Register under "default" (the constructor's slot) so a replica using
        # this legacy setter stays checkpoint-compatible when healing from a
        # replica that registered via the constructor, and vice versa.
        self.register_state_dict_fn("default", load_state_dict, state_dict)

    def allow_state_dict_read(self) -> None:
        if self._state_dict_lock.w_locked():
            self._state_dict_lock.w_release()

    def disallow_state_dict_read(self) -> None:
        if not self._state_dict_lock.w_locked():
            self._state_dict_lock.w_acquire()

    # --------------------------------------------------------------- quorum
    def start_quorum(
        self,
        allow_heal: bool = True,
        shrink_only: bool = False,
        timeout: "float | timedelta | None" = None,
    ) -> None:
        """Compute a new quorum (async by default) and ready the manager for a
        new step. Call before the forward pass (reference: manager.py:560-615)."""
        if self._quorum_future is not None:
            self._quorum_future.result()
            # a commit left over from the previous quorum (e.g. the caller
            # skipped should_commit after an error) must land before the
            # next prepare runs against the old world
            self._commit_pending_configure()

        # Re-evaluate the construction-time sync-quorum override: an
        # auto-mode PG can't know whether it needs sync quorum until its
        # first configure resolves the mode, so sampling the property once
        # at construction would tax every later step with a synchronous
        # quorum RPC. Only the caller's requested mode is ever restored.
        if (
            self._requested_async_quorum
            and not self._use_async_quorum
            and not getattr(self._pg, "requires_sync_quorum", False)
        ):
            self._logger.info(
                "pg no longer requires sync quorum; restoring async quorum"
            )
            self._use_async_quorum = True

        if self._shard_stager is not None and self._redundancy_stage_pending:
            # redundancy plane: the previous round committed and the
            # caller has applied its update — the user state is now the
            # exact post-commit generation a healer joining THIS round
            # needs, and announcing before the quorum/allreduce barrier
            # means that healer can reconstruct it instead of deadlocking
            # on a commit it is itself blocking
            self._redundancy_stage_pending = False
            self._stage_redundancy_committed()

        self._errored = None
        self._healing = False
        self._last_quorum_healed = False
        # the allreduces from here to the next start_quorum are one step's:
        # segment 0, 1, ..., and timings() adds them up
        self._pipeline.begin_step()

        # a degrade staged since the last safe point lands here, AFTER the
        # per-step error reset and BEFORE the new prepare is submitted: the
        # reshard must replace the dead member before the next quorum's
        # world is staged, and a fallback's report_error must survive into
        # this step so its vote fails (placing this above the reset
        # silently swallowed the fallback)
        if self._degrade_cfg is not None:
            self._commit_pending_degrade()

        # adaptive policy plane: a frame that arrived on the heartbeat
        # mirror lands here — the quorum safe point — never mid-step.
        # TORCHFT_POLICY=off skips the poll entirely (byte-identical).
        if self._policy_mode != "off":
            self._poll_policy_safe_point()

        self._quorum_future = self._executor.submit(
            self._async_quorum,
            allow_heal=allow_heal,
            shrink_only=shrink_only,
            quorum_timeout=_to_seconds(timeout) if timeout is not None else self._quorum_timeout,
        )
        if not self._use_async_quorum:
            self.wait_quorum()
            self._commit_pending_configure()
            self._sync_device_world()
            if self._healing and self._pending_state_dict is not None:
                # apply eagerly so the forward pass runs on recovered state
                self._apply_pending_state_dict()
                self._healing = False
            elif self._healing:
                # recovery failed (error already reported); retry next quorum
                self._healing = False

    def wait_quorum(
        self, cat: str = "quorum", parent: Optional[int] = None,
        **span_args: Any,
    ) -> None:
        # one span per wait that waits, under the category of the phase
        # that does (``allreduce/wait_quorum`` is the one at the head of
        # _allreduce); a quorum that is already there records nothing —
        # num_participants() and its kin come through here several times
        # a step
        assert self._quorum_future is not None, "must call start_quorum first"
        if self._quorum_future.done():
            self._quorum_future.result()
            return
        with self._tracer.span(
            "wait_quorum", cat=cat, parent=parent, **span_args
        ):
            self._quorum_future.result()

    # ------------------------------------------------------------- policy
    def register_policy_adjuster(
        self, knob: str, fn: "Callable[[Optional[str]], None]"
    ) -> None:
        """Register a live setter for one knob (LocalSGD/DiLoCo register
        their ``sync_every`` here, redundancy its staging interval). In
        enforce mode the setter runs at the quorum safe point with the
        frame's string value, or ``None`` when the override is released
        (hysteresis relaxed) — the plane restores its construction-time
        value. Last registration per knob wins."""
        self._policy_adjusters[knob] = fn

    def policy_status(self) -> Dict[str, Any]:
        """Operator view of the policy plane on this replica: mode, last
        frame seq applied/observed, and the override set in force."""
        with self._metrics_lock:
            seq = int(self._timings.get("policy_seq", 0.0))
        return {
            "mode": self._policy_mode,
            "policy_seq": seq,
            "overrides": knobs.get_overrides(),
            "adjusters": sorted(self._policy_adjusters),
        }

    def _policy_set_red_interval(self, value: Optional[str]) -> None:
        cfg = self._redundancy_cfg
        if cfg is None:
            return
        if value is None:
            cfg.interval = self._policy_red_defaults[0]
        else:
            cfg.interval = max(1, int(value))

    def _policy_set_red_m(self, value: Optional[str]) -> None:
        cfg = self._redundancy_cfg
        if cfg is None:
            return
        if value is None:
            m = self._policy_red_defaults[1]
        else:
            # keep within the GF(256) shard limit the constructor enforces
            m = min(max(1, int(value)), 255 - cfg.k)
        cfg.m = m

    def _poll_policy_safe_point(self) -> None:
        """Poll the heartbeat mirror for a new policy frame and act on it.

        Runs only from start_quorum (the safe point: no collective in
        flight, the previous configure committed) and only when
        TORCHFT_POLICY != off. Observe mode records the would-be action
        everywhere an operator looks (timings, torchft_policy stream,
        flight recorder, trace instant) without touching a knob; enforce
        additionally installs the overrides through the central registry
        layer and runs the registered live adjusters. Must never raise —
        a malformed frame degrades to a logged warning, not a lost step."""
        try:
            frame = self._manager.policy() if self._manager is not None else {}
        except Exception:  # noqa: BLE001 — mirror read must not cost a step
            return
        if not frame:
            return
        try:
            seq = int(frame.get("policy_seq", 0))
            if seq <= self._policy_seq_seen:
                return
            self._policy_seq_seen = seq
            overrides = {
                str(k): str(v)
                for k, v in (frame.get("knob_overrides") or {}).items()
                if knobs.is_registered(str(k))
            }
            enforce = (
                self._policy_mode == "enforce"
                and str(frame.get("mode", "")) == "enforce"
            )
            with self._metrics_lock:
                self._timings["policy_seq"] = float(seq)
                if enforce:
                    self._timings["policy_applies"] += 1.0
                else:
                    self._timings["policy_intents"] += 1.0
            action = "apply" if enforce else "intent"
            self._logger.info(
                f"policy: {action} seq={seq} overrides={overrides} "
                f"rules={frame.get('active_rules', [])}"
            )
            emit_event_async(
                POLICY_EVENTS,
                replica_id=self._replica_id,
                group_rank=self._group_rank,
                step=self._step,
                quorum_id=self._quorum_id,
                policy_seq=seq,
                action=action,
                overrides=overrides,
                active_rules=list(frame.get("active_rules", [])),
            )
            from torchft_tpu.flight_recorder import recorder

            recorder.record(
                "policy_" + action,
                policy_seq=seq,
                overrides=overrides,
                step=self._step,
                replica=self._replica_id,
            )
            self._tracer.instant(
                "policy_" + action, cat="policy", policy_seq=seq
            )
            if not enforce:
                return
            # Enforce: diff against what THIS manager applied from the
            # predecessor frame so a released rule's knob reverts
            # (hysteresis relaxation must undo, not just stop
            # re-applying). The diff base is per-manager, not the global
            # override layer: with several managers in one process (test
            # fleets) whichever polls a frame first mutates the shared
            # layer, and diffing against it would skip the others'
            # adjuster restore calls.
            previous = self._policy_overrides_applied
            for name in previous:
                if name not in overrides:
                    knobs.set_override(name, None)
                    adjuster = self._policy_adjusters.get(name)
                    if adjuster is not None:
                        adjuster(None)
            for name, value in overrides.items():
                knobs.set_override(name, value)
                adjuster = self._policy_adjusters.get(name)
                if adjuster is not None:
                    adjuster(value)
            # Manager-owned knob: the wire codec retargets in place (the
            # next streamed allreduce picks it up; error-feedback
            # residuals are keyed per plan and survive the switch).
            if "TORCHFT_COMPRESS" in overrides:
                self._compress = resolve_compress_mode(
                    overrides["TORCHFT_COMPRESS"]
                )
            elif "TORCHFT_COMPRESS" in previous:
                self._compress = resolve_compress_mode(None)
            self._policy_overrides_applied = dict(overrides)
        except Exception:  # noqa: BLE001
            self._logger.exception("policy frame handling failed (ignored)")

    def _sync_device_world(self) -> None:
        """Re-land registered user state on the live jax backend after the
        PG rebuilt the device world (ProcessGroupXLA's per-quorum
        distributed worlds tear down + rejoin `jax.distributed`). Arrays
        created on the OLD backend stay readable but cannot mix with
        new-world arrays inside one jitted computation — without this, the
        first post-reconfigure optimizer update dies with "incompatible
        devices for jitted computation". Called from the main thread at
        the should_commit / start_quorum sync points (the same places a
        pending heal is applied). No-op for PGs without a
        ``device_world_epoch`` (host PGs, local mode) and when a pending
        heal is about to overwrite user state anyway."""
        epoch = getattr(self._pg, "device_world_epoch", None)
        if epoch is None or epoch == self._device_world_epoch:
            return
        self._device_world_epoch = epoch
        if self._healing and self._pending_state_dict is not None:
            return  # the heal lands on the live backend and wins
        if not self._user_state_dicts:
            return
        import jax

        self._logger.info(
            f"device world rebuilt (epoch {epoch}); re-landing user state "
            "on the live backend"
        )
        host = jax.tree_util.tree_map(
            lambda l: np.asarray(l) if isinstance(l, jax.Array) else l,
            self.user_state_dict(),
        )
        self.load_user_state_dict(host)

    def _async_quorum(
        self, allow_heal: bool, shrink_only: bool, quorum_timeout: float
    ) -> None:
        # quorum_overlap_s is the wall-clock the whole control-plane cycle
        # spent on the quorum thread — with async quorum this is the work
        # the train step no longer waits for (minus configure_commit_s,
        # the only piece that still serializes with the trainer)
        t0 = time.perf_counter()
        try:
            with self._tracer.span("async_quorum", cat="quorum"):
                self._async_quorum_body(
                    allow_heal, shrink_only, quorum_timeout
                )
        finally:
            self._record_timing("quorum_overlap_s", time.perf_counter() - t0)

    def _async_quorum_body(
        self, allow_heal: bool, shrink_only: bool, quorum_timeout: float
    ) -> None:
        try:
            with self._tracer.span("quorum_rpc", cat="quorum"):
                quorum = self._client._quorum(
                    group_rank=self._group_rank,
                    step=self._step,
                    checkpoint_metadata=self._checkpoint_transport.metadata(),
                    shrink_only=shrink_only,
                    timeout=quorum_timeout,
                    init_sync=self._init_sync,
                    commit_failures=self._commit_failures,
                )
        except Exception as e:  # noqa: BLE001
            self._logger.exception(f"quorum RPC failed: {e}")
            self.report_error(e)
            return

        self._num_replicas = quorum.replica_world_size
        self._bump_metric("quorums")
        self._tracer.set_context(quorum_id=quorum.quorum_id, step=self._step)

        # Participation (reference: manager.py:671-690): async quorum means
        # healing replicas sit this step out, so the participating world is
        # the max-step cohort; sync quorum heals first, so everyone counts.
        if self._use_async_quorum or not allow_heal:
            self._participating_replica_rank = quorum.max_replica_rank
            self._participating_replica_world_size = quorum.max_world_size
        else:
            self._participating_replica_rank = quorum.replica_rank
            self._participating_replica_world_size = quorum.replica_world_size

        if self._replica_world_size_mode == WorldSizeMode.FIXED_WITH_SPARES:
            # Spares beyond min_replica_size contribute zeros so gradient
            # scale stays constant.
            self._participating_replica_world_size = min(
                self._participating_replica_world_size, self._min_replica_size
            )
            if (
                self._participating_replica_rank is not None
                and self._participating_replica_rank >= self._min_replica_size
            ):
                self._participating_replica_rank = None

        if quorum.quorum_id != self._quorum_id:
            store_prefixed_addr = (
                f"{quorum.store_address}/torchft/{quorum.quorum_id}/{self._group_rank}"
            )
            self._logger.info(
                f"reconfiguring for quorum_id={quorum.quorum_id} store={store_prefixed_addr}"
            )
            log_quorum_event(
                replica_id=self._replica_id,
                group_rank=self._group_rank,
                step=self._step,
                quorum_id=quorum.quorum_id,
                replica_rank=quorum.replica_rank,
                replica_world_size=quorum.replica_world_size,
                heal=quorum.heal,
                recover_dst_replica_ranks=quorum.recover_dst_replica_ranks,
            )
            try:
                self._bump_metric("reconfigures")
                # prepare/commit split: everything control-plane runs HERE
                # on the quorum thread; a PG that must swap live backend
                # state returns that swap as a commit callable which the
                # main thread applies at the next safe point
                t_prep = time.perf_counter()
                with self._tracer.span("configure_prepare", cat="quorum"):
                    pg_commit = self._pg.prepare_configure(
                        store_prefixed_addr,
                        quorum.replica_rank,
                        quorum.replica_world_size,
                        quorum_id=quorum.quorum_id,
                    )
                self._record_timing(
                    "configure_prepare_s", time.perf_counter() - t_prep
                )
                with self._pending_commit_lock:
                    self._pending_pg_commit = pg_commit
                if pg_commit is None:
                    # fully committed in prepare (host PGs, local mode):
                    # report an explicit zero so BENCH rows always carry
                    # the key and overlap math stays artifact-derivable
                    self._record_timing("configure_commit_s", 0.0)
                # keep the checkpoint transport in lockstep with the quorum
                # (no-op for address-based transports; PGTransport
                # rendezvouses its recovery PG here). Distinct /recovery
                # store namespace so the two meshes can't cross-wire.
                with self._tracer.span("transport_configure", cat="quorum"):
                    self._checkpoint_transport.configure(
                        f"{quorum.store_address}/torchft/{quorum.quorum_id}"
                        f"/recovery/{self._group_rank}",
                        quorum.replica_rank,
                        quorum.replica_world_size,
                        quorum_id=quorum.quorum_id,
                    )
                # recorded only after BOTH configures succeed. On failure
                # _quorum_id stays stale and the step's commit vote fails,
                # so the next quorum request carries commit_failures>0 and
                # the lighthouse bumps quorum_id (native/lighthouse.cc) —
                # EVERY replica then re-rendezvouses under the new id.
                # That bump, not a one-sided same-id retry, is what makes
                # the retry collective (a lone replica re-running a
                # blocking mesh rendezvous its peers skipped would just
                # time out); tests/test_manager_integ.py pins the loop.
                self._quorum_id = quorum.quorum_id
                # flight-recorder reconfiguration boundary marker
                # (reference: manager.py:729-733, 808-817)
                from torchft_tpu.flight_recorder import recorder

                recorder.record(
                    "quorum_reconfigure",
                    quorum_id=quorum.quorum_id,
                    replica=self._replica_id,
                    group_rank=self._group_rank,
                )
                if pg_commit is None:
                    # split PGs log theirs from _commit_pending_configure,
                    # after the commit half has a measured duration
                    self._log_timing_snapshot("configure_prepare")
            except Exception as e:  # noqa: BLE001
                self._logger.exception(f"got exception in pg configure: {e}")
                self.report_error(e)
                return

        if allow_heal:
            try:
                if quorum.recover_dst_replica_ranks:
                    self._logger.info(
                        f"peers need recovery from us {quorum.recover_dst_replica_ranks}"
                    )
                    t_send = time.perf_counter()
                    with self._tracer.span(
                        "heal_send",
                        cat="heal",
                        dst_ranks=list(quorum.recover_dst_replica_ranks),
                    ):
                        self._checkpoint_transport.send_checkpoint(
                            dst_ranks=quorum.recover_dst_replica_ranks,
                            step=quorum.max_step,
                            state_dict=self._manager_state_dict(),
                            timeout=self._timeout,
                        )
                    self._record_timing(
                        "heal_send_s", time.perf_counter() - t_send
                    )

                # Standby failover source: someone in the quorum is behind
                # but WE got no dst assignment. A healing replica whose
                # assigned source dies mid-transfer fails over to the
                # fallback peers the quorum computed — which only works if
                # those peers actually have the step staged. Stage once per
                # heal episode (rising edge; the snapshot owns host copies,
                # so serving stays consistent while training mutates live
                # state) and hold the window open across commits until the
                # quorum shows nobody behind (should_commit skips
                # disallow_checkpoint while _standby_source is set).
                # Pull-based transports only: a PGTransport standby would
                # just rendezvous a transfer no one initiates.
                standby_wanted = (
                    not quorum.recover_dst_replica_ranks
                    and quorum.max_world_size < quorum.replica_world_size
                    and self._checkpoint_transport.supports_multi_source
                )
                standby = standby_wanted and not quorum.heal
                if standby_wanted and quorum.heal:
                    # We are a fallback candidate AND behind ourselves: the
                    # quorum listed us as a standby source, but our local
                    # state is the pre-heal copy — serving it would hand a
                    # failing-over peer stale state. Refuse loudly instead
                    # of silently staging nothing (the old behavior left
                    # fallback peers shardless with no audit trail).
                    self._logger.warning(
                        "refusing to stage standby failover snapshot for "
                        f"step {quorum.max_step}: this replica is itself "
                        "mid-heal and holds stale state"
                    )
                    self._bump_counter("standby_skipped")
                if standby and not self._standby_source:
                    self._logger.info(
                        "staging standby failover snapshot for "
                        f"step {quorum.max_step}"
                    )
                    self._checkpoint_transport.send_checkpoint(
                        dst_ranks=[],
                        step=quorum.max_step,
                        state_dict=self._manager_state_dict(),
                        timeout=self._timeout,
                    )
                self._standby_source = standby

                if quorum.heal:
                    self._healing = True
                    assert quorum.recover_src_replica_rank is not None
                    self._bump_counter("heal_attempts")
                    t_recv = time.perf_counter()
                    with self._heal_lock:
                        self._heal_sums = {
                            "heal_fetch_s": 0.0, "heal_place_s": 0.0,
                        }
                    with self._tracer.span("heal_recv", cat="heal") as recv:
                        self._heal_recv_span = recv.id
                        self._pending_state_dict = self._recv_checkpoint(quorum)
                    self._record_timing(
                        "heal_recv_s", time.perf_counter() - t_recv
                    )
                    # the parts of heal_recv the transport timed (the HTTP
                    # transport: per chunk and per leaf). SUMS over its
                    # fetch threads (up to 8 run at once), so the two can
                    # add up to more than heal_recv_s
                    with self._heal_lock:
                        sums = dict(self._heal_sums)
                    for key, total in sums.items():
                        if total:
                            self._record_timing(key, total)
                    stream = self._checkpoint_transport.last_recv_timings()
                    if stream is not None:
                        self._record_timing("heal_chunks", float(stream.num_chunks))
                        self._record_timing("heal_mb_per_s", stream.mb_per_s)
                    # restore ft step/batches immediately; user state is
                    # applied from the main thread when safe
                    self.load_state_dict(self._pending_state_dict["torchft"])
                    self._step = quorum.max_step
            except Exception as e:  # noqa: BLE001
                self._logger.exception(f"got exception in recovery: {e}")
                self.report_error(e)

    # ------------------------------------------------------------- healing
    def _heal_sources(
        self, quorum: Any
    ) -> List[Any]:
        """Ordered candidate sources for a multi-peer heal: the assigned
        recovery source first, then every other up-to-date peer in the
        round-robin order the native quorum computed
        (``recover_src_fallbacks``). Each entry is ``(label, metadata_fn)``
        with the metadata RPC resolved LAZILY — an unreachable fallback
        costs nothing unless the transport actually fails over to it."""

        def _metadata_fn(addr: str) -> Callable[[], str]:
            def fetch() -> str:
                client = ManagerClient(
                    addr, connect_timeout=self._connect_timeout
                )
                # the metadata RPC itself rides the bounded-retry layer,
                # feeding the same rpc_retries counter as the main clients
                client.set_retry_observer(self._on_rpc_retry)
                return client._checkpoint_metadata(
                    self._group_rank, timeout=self._timeout
                )

            return fetch

        sources = [
            (
                f"replica_rank_{quorum.recover_src_replica_rank}"
                f"@{quorum.recover_src_manager_address}",
                _metadata_fn(quorum.recover_src_manager_address),
            )
        ]
        for peer in quorum.recover_src_fallbacks:
            sources.append(
                (
                    f"replica_rank_{peer.replica_rank}@{peer.address}",
                    _metadata_fn(peer.address),
                )
            )
        return sources

    def _on_heal_event(self, kind: str, **fields: Any) -> None:
        """Transport → Manager bridge for resilient-heal notifications:
        bump the matching cumulative counter and leave a flight-recorder
        breadcrumb so a postmortem can reconstruct the heal's retry/
        failover sequence."""
        if kind in ("heal_fetch", "heal_place"):
            # a timed piece of the receive (one chunk off the socket, one
            # leaf placed), on the transport's fetch thread
            t0_pc, t1_pc = fields.pop("t0_pc"), fields.pop("t1_pc")
            with self._heal_lock:
                key = kind + "_s"
                self._heal_sums[key] = (
                    self._heal_sums.get(key, 0.0) + t1_pc - t0_pc
                )
            self._tracer.record_rel(
                kind, "heal", t0_pc, t1_pc,
                parent=self._heal_recv_span, **fields,
            )
            return
        counter = {
            "heal_retry": "heal_attempts",
            "heal_failover": "heal_failovers",
            "chunk_crc_failure": "chunk_crc_failures",
        }.get(kind)
        if counter is not None:
            self._bump_counter(counter)
        self._tracer.instant(kind, cat="heal", **fields)
        from torchft_tpu.flight_recorder import recorder

        recorder.record(
            kind,
            step=self._step,
            replica=self._replica_id,
            group_rank=self._group_rank,
            **fields,
        )

    def _on_redundancy_metric(self, name: str, value: float) -> None:
        """ShardStager/HotSpare → Manager metrics bridge: counters (named
        in _COUNTER_TIMINGS) accumulate, everything else is a last-value
        gauge riding timings() like any phase timing."""
        if name in _COUNTER_TIMINGS:
            self._bump_counter(name, value)
        else:
            self._record_timing(name, value)

    def _on_redundancy_event(self, kind: str, info: Dict[str, Any]) -> None:
        """reconstruct_state → Manager bridge: per-shard faults become
        cumulative counters + tracer instants so a heal postmortem can say
        WHICH shard failed or arrived corrupt."""
        counter = {
            "shard_corrupt": "shard_corrupt",
            "shard_fetch_failed": "shard_fetch_failed",
        }.get(kind)
        if counter is not None:
            self._bump_counter(counter)
        self._tracer.instant(kind, cat="redundancy", **info)

    def _reconstruct_checkpoint(self, quorum: Any) -> Optional[Dict[str, Any]]:
        """Attempt the parallel shard reconstruct for this heal. Returns
        the state dict on success, None to fall back to the peer pull
        (never raises — the redundancy plane is an accelerator, not a
        dependency, of healing)."""
        from torchft_tpu import redundancy as _redundancy

        cfg = self._redundancy_cfg
        assert cfg is not None
        t0 = time.perf_counter()
        try:
            with self._tracer.span(
                "reconstruct", cat="redundancy", step=quorum.max_step
            ):
                step, state, stats = _redundancy.reconstruct_state(
                    cfg.directory,
                    step=quorum.max_step,
                    timeout=self._timeout,
                    on_event=self._on_redundancy_event,
                )
        except Exception as e:  # noqa: BLE001 — fall back to peer pull
            self._logger.warning(
                f"shard reconstruct unavailable ({e!r}); falling back to "
                "peer heal"
            )
            self._bump_counter("reconstruct_failures")
            return None
        if step != quorum.max_step:
            self._logger.warning(
                f"shard directory generation is step {step}, quorum wants "
                f"{quorum.max_step}; falling back to peer heal"
            )
            self._bump_counter("reconstruct_failures")
            return None
        self._bump_counter("reconstructs")
        self._record_timing(
            "reconstruct_s", stats.get("reconstruct_s", time.perf_counter() - t0)
        )
        self._record_timing(
            "reconstruct_mb_per_s", float(stats.get("mb_per_s", 0.0))
        )
        self._logger.info(
            f"healed step {step} by parallel reconstruct: "
            f"{stats['shards_ok']} shards ok, "
            f"{stats['shards_failed']} failed, "
            f"{stats['shards_corrupt']} corrupt, "
            f"{stats.get('mb_per_s', 0.0):.1f} MB/s"
        )
        return state

    def _recv_checkpoint(self, quorum: Any) -> Dict[str, Any]:
        """Fetch the healing checkpoint, failing over across up-to-date
        peers when the transport supports it (pull-based HTTP). Push-based
        transports (PGTransport) stay on the single assigned source — a
        fallback peer there would never send, so failing over to it could
        only hang (see ``CheckpointTransport.supports_multi_source``)."""
        transport = self._checkpoint_transport
        # Reconstruct mode (redundancy.py): with the plane enabled, try to
        # rebuild the generation from erasure shards pulled in PARALLEL
        # from distinct peers before falling back to the serial pull. Any
        # failure — directory empty, stale generation, fewer than k shards
        # surviving — degrades to the existing heal path, so k=0 and a
        # broken plane behave identically (byte-identical path pinned by
        # tests/test_redundancy.py).
        if self._redundancy_cfg is not None and self._redundancy_cfg.enabled:
            state = self._reconstruct_checkpoint(quorum)
            if state is not None:
                return state
        if transport.supports_multi_source:
            sources = self._heal_sources(quorum)
            self._logger.info(
                f"healing required, {len(sources)} candidate source(s): "
                f"{[label for label, _ in sources]}"
            )
            try:
                return transport.recv_checkpoint_multi(
                    sources,
                    step=quorum.max_step,
                    timeout=self._timeout,
                    on_event=self._on_heal_event,
                )
            except Exception:
                # every candidate peer exhausted within the heal budget:
                # dump the ring buffer NOW, while the heal_retry/
                # heal_failover breadcrumbs are still in it. The tag
                # carries (replica, step, reason) so a same-second eject
                # dump can never overwrite this one, and the span ring
                # dumps beside it for the fleet-timeline view.
                from torchft_tpu.flight_recorder import recorder

                fr_path = recorder.dump(
                    reason="heal_exhausted",
                    quorum_id=quorum.quorum_id,
                    tag=f"{self._replica_id}_{self._group_rank}"
                    f"_s{quorum.max_step}_heal_exhausted",
                )
                self._auto_dump_trace("heal_exhausted", fr_path)
                raise
        self._logger.info(
            f"healing required, fetching metadata from "
            f"{quorum.recover_src_manager_address}"
        )
        primary_client = ManagerClient(
            quorum.recover_src_manager_address,
            connect_timeout=self._connect_timeout,
        )
        primary_client.set_retry_observer(self._on_rpc_retry)
        checkpoint_metadata = primary_client._checkpoint_metadata(
            self._group_rank, timeout=self._timeout
        )
        return transport.recv_checkpoint(
            src_rank=quorum.recover_src_replica_rank,
            metadata=checkpoint_metadata,
            step=quorum.max_step,
            timeout=self._timeout,
        )

    def _apply_pending_state_dict(self) -> None:
        assert self._healing, "must be in healing state"
        self.wait_quorum()
        pending = self._pending_state_dict
        assert pending is not None, "checkpoint was not staged"
        self._logger.info("applying pending state dict")
        t0 = time.perf_counter()
        with self._tracer.span("heal_apply", cat="heal"), \
                self._state_dict_lock.w_lock():
            user = pending["user"]
            for key, load_fn in self._load_state_dict_fns.items():
                if key in user:
                    load_fn(user[key])
            self._pending_state_dict = None
        self._record_timing("heal_apply_s", time.perf_counter() - t0)
        self._last_quorum_healed = True
        self._bump_metric("heals")

    def _commit_pending_configure(self) -> None:
        """Apply the backend-swap half of a split reconfigure. MUST run on
        the main thread (the commit swaps live jax backend state that the
        trainer's own computations touch); called at every sync point —
        start_quorum, allreduce-after-wait, should_commit. No-op when the
        last prepare had nothing to commit."""
        with self._pending_commit_lock:
            commit, self._pending_pg_commit = self._pending_pg_commit, None
        if commit is None:
            return
        t0 = time.perf_counter()
        try:
            with self._tracer.span("configure_commit", cat="quorum"):
                commit()
        except Exception as e:  # noqa: BLE001
            # force the next quorum cycle to re-run prepare+commit even if
            # the lighthouse hands back the same quorum_id: _quorum_id was
            # already recorded after prepare succeeded, so without this the
            # reconfigure would be skipped and the PG left half-configured
            self._quorum_id = -1
            self._logger.exception(f"got exception in pg configure commit: {e}")
            self.report_error(e)
        finally:
            self._record_timing("configure_commit_s", time.perf_counter() - t0)
            self._log_timing_snapshot("configure_commit")

    # -------------------------------------------------------- degrade plane
    def set_group_degree(self, full_degree: int) -> None:
        """Declare the group's intra-replica parallel degree (chips in its
        TP/PP mesh). Single-controller SPMD jobs own chips the Manager's
        ``group_world_size`` never sees, so the degrade plane scores and
        reports against this declared degree. Resets any in-progress
        degrade bookkeeping to full capacity."""
        if full_degree < 1:
            raise ValueError(f"full_degree must be >= 1, got {full_degree}")
        with self._degrade_lock:
            self._full_group_degree = full_degree
            self._group_degree = full_degree
            self._degrade_pending = None

    def set_reshard_fn(
        self, fn: Optional[Callable[[int, int], Any]]
    ) -> None:
        """Register the trainer's reshard hook, called at the commit point
        of a staged degrade as ``fn(dead_group_rank, new_degree)``. The
        hook owns the actual param movement (parallel/degrade.py reshard +
        mesh.shrink_mesh device_put); the Manager stays model-agnostic. A
        raise inside the hook falls back to the classic whole-group error
        path. May return a stats dict (e.g. DegradeStats.to_json()) that
        rides the flight-recorder breadcrumb."""
        self._reshard_fn = fn

    @property
    def group_degree(self) -> int:
        """Current intra-group parallel degree (< full while degraded)."""
        return self._group_degree

    @property
    def full_group_degree(self) -> int:
        return self._full_group_degree

    def report_member_death(self, group_rank: int) -> None:
        """Stage a degrade: chip ``group_rank`` of this group's mesh died.
        Called by the PG's abort watchdog / fault injection (via
        ``set_member_death_callback``) or directly by a trainer that
        detected the loss. Thread-safe; the shrink itself is applied at
        the next safe point (_commit_pending_degrade), making the step a
        re-planned slow step rather than a discarded one."""
        if self._degrade_cfg is None:
            return
        with self._degrade_lock:
            if self._degrade_pending is not None:
                return  # one shrink at a time; next death re-stages after
            self._degrade_pending = int(group_rank)
        self._logger.warning(
            f"group member {group_rank} died; degrade staged "
            f"(degree {self._group_degree} -> {self._group_degree - 1})"
        )

    def _commit_pending_degrade(self) -> None:
        """Apply a staged intra-group degrade at a safe point (main
        thread, same sync points as _commit_pending_configure). Shrinks
        the declared group degree, runs the registered reshard hook, and
        surfaces the event; if the surviving degree would fall below
        min_degree or the reshard fails, falls back to the classic
        whole-group error path (report_error -> this step's vote is False
        and the group leaves to heal)."""
        if self._degrade_cfg is None:
            return
        with self._degrade_lock:
            dead_rank, self._degrade_pending = self._degrade_pending, None
            degree = self._group_degree
            full = self._full_group_degree
        if dead_rank is None:
            return
        new_degree = degree - 1
        if new_degree < self._degrade_cfg.min_degree:
            self.report_error(
                RuntimeError(
                    f"group member {dead_rank} died and surviving degree "
                    f"{new_degree} is below TORCHFT_DEGRADE_MIN_DEGREE="
                    f"{self._degrade_cfg.min_degree}; falling back to "
                    "leave-heal-rejoin"
                )
            )
            return
        t0 = time.perf_counter()
        stats: Any = None
        try:
            with self._tracer.span(
                "degraded_reshard", cat="degrade", dead_rank=dead_rank
            ):
                if self._reshard_fn is not None:
                    stats = self._reshard_fn(dead_rank, new_degree)
                shrink = getattr(self._pg, "prepare_shrink", None)
                if shrink is not None:
                    commit = shrink(dead_rank)
                    if commit is not None:
                        commit()  # already at a safe point
        except Exception as e:  # noqa: BLE001
            self._logger.exception(
                f"in-place reshard after member {dead_rank} death failed; "
                "falling back to leave-heal-rejoin"
            )
            self.report_error(e)
            return
        reshard_s = time.perf_counter() - t0
        with self._degrade_lock:
            self._group_degree = new_degree
        self._record_timing("degraded_reshard_s", reshard_s)
        self._bump_counter("degrade_events")
        self._logger.warning(
            f"degraded in place: member {dead_rank} lost, group degree "
            f"{degree} -> {new_degree} (full {full}), reshard took "
            f"{reshard_s:.3f}s"
        )
        emit_event_async(
            HEALTH_EVENTS,
            replica_id=self._replica_id,
            group_rank=self._group_rank,
            step=self._step,
            quorum_id=self._quorum_id,
            kind="degrade",
            dead_group_rank=dead_rank,
            group_world_size=new_degree,
            full_group_world_size=full,
            reshard_s=reshard_s,
        )
        from torchft_tpu.flight_recorder import recorder

        recorder.record(
            "degrade",
            dead_group_rank=dead_rank,
            group_world_size=new_degree,
            full_group_world_size=full,
            reshard_s=reshard_s,
            stats=stats,
            step=self._step,
            replica=self._replica_id,
            group_rank=self._group_rank,
        )

    def restore_full_degree(self) -> None:
        """Re-promote a degraded group to full degree (a spare/repaired
        chip came back). Telemetry returns to full capacity on the next
        beat, which walks the lighthouse ledger DEGRADED -> OK."""
        if self._degrade_cfg is None:
            return
        with self._degrade_lock:
            restored = self._group_degree < self._full_group_degree
            degree = self._full_group_degree
            self._group_degree = degree
            self._degrade_pending = None
        if not restored:
            return
        self._bump_counter("restored_events")
        self._logger.warning(
            f"restored to full group degree {degree}"
        )
        emit_event_async(
            HEALTH_EVENTS,
            replica_id=self._replica_id,
            group_rank=self._group_rank,
            step=self._step,
            quorum_id=self._quorum_id,
            kind="restore",
            group_world_size=degree,
        )
        from torchft_tpu.flight_recorder import recorder

        recorder.record(
            "restore",
            group_world_size=degree,
            step=self._step,
            replica=self._replica_id,
            group_rank=self._group_rank,
        )

    # ------------------------------------------------------------ allreduce
    def allreduce(
        self,
        values: Any,
        should_quantize: bool = False,
        reduce_op: ReduceOp = ReduceOp.AVG,
    ) -> Work:
        """Fault-tolerant allreduce over a pytree of arrays.

        Returns a Work whose future resolves to the reduced pytree (with
        device placement matching the inputs). On error, the future resolves
        to a zeros pytree and the error is tracked for ``should_commit``
        (reference: manager.py:410-493).
        """
        work, _stream = self._allreduce(values, should_quantize, reduce_op)
        return work

    def allreduce_streamed(
        self,
        values: Any,
        reduce_op: ReduceOp = ReduceOp.AVG,
        bucket_cap_bytes: Optional[int] = None,
        should_quantize: bool = False,
    ) -> GradStream:
        """Streaming variant: per-bucket completion through a GradStream.

        Same numerics, error swallowing (zeros + ``should_commit`` False),
        and ordering contract as :meth:`allreduce`, but the returned handle
        exposes ``ready(i)`` per bucket so a gradient-accumulation loop can
        watch buckets land while later microbatches still compute, and
        ``wait()`` returns the reduced pytree directly.
        ``should_quantize=True`` streams the buckets COMPRESSED on a
        host-plane PG (fp8 unless ``TORCHFT_COMPRESS`` picks int8), with
        per-bucket error feedback — quantization does not force the
        monolithic exchange. When the tree has no plan (single leaf,
        bucketing disabled, device-native quantized), the handle
        degenerates to one bucket covering the whole op.
        ``bucket_cap_bytes`` overrides the manager's cap for this call
        (``PureDistributedDataParallel`` routes its own cap through here).
        """
        work, stream = self._allreduce(
            values,
            should_quantize,
            reduce_op,
            bucket_cap_bytes=bucket_cap_bytes,
        )
        if stream is None:
            fut = work.get_future()
            stream = GradStream([fut], fut)
        return stream

    def _allreduce(
        self,
        values: Any,
        should_quantize: bool = False,
        reduce_op: ReduceOp = ReduceOp.AVG,
        bucket_cap_bytes: Optional[int] = None,
    ) -> "tuple[Work, Optional[GradStream]]":
        """Shared engine behind allreduce / allreduce_streamed: the state
        machine's half of it (quorum, participants, the error policy, the
        choice of path); the data plane is ``self._pipeline``
        (:class:`bucketing.BucketPipeline`).

        Returns ``(work, stream)``; ``stream`` is a GradStream when the
        tree had a bucket plan and went through the per-bucket pipeline,
        else None (the no-plan path: one collective for the whole tree).
        """
        import jax

        t_allreduce0 = time.perf_counter()
        self._bump_metric("allreduces")
        leaves, treedef = jax.tree_util.tree_flatten(values)
        tracer = self._tracer
        pipeline = self._pipeline
        # allreduce/allreduce runs from here to the resolve of the returned
        # work, on another thread: recorded there (_time_allreduce), its
        # id known now so the spans below can name it as their parent
        ar_id = tracer.new_id()
        ar_parent = tracer.current()  # the caller's span (trainer/step)
        # which of the step's allreduces this is: a trainer that hands the
        # gradients over segment by segment makes several (models/staged.py)
        segment = pipeline.next_segment()
        ar_args: Dict[str, Any] = {"segment": segment}

        # Bucketed path: pack a multi-leaf tree into a handful of flat
        # same-dtype buffers (shared bucketing.py; plan cached by tree
        # identity + leaf geometry) so the wire carries ceil(bytes/cap)
        # collectives instead of one per leaf. On a host-plane PG a
        # quantized tree has a plan too: it rides the pipeline as compressed
        # buckets with error feedback (one codec boundary per bucket,
        # carried per-bucket residuals). A device-native PG's quantized
        # exchange is the monolithic one, which is never pre-bucketed
        # (BucketPipeline.allreduce_leaves says why).
        cap = (
            self._bucket_cap_bytes
            if bucket_cap_bytes is None
            else int(bucket_cap_bytes)
        )
        device_native = pipeline.device_native
        # wire compression: TORCHFT_COMPRESS / compress= knob, plus
        # should_quantize callers who land on the host plane defaulting
        # to fp8
        compress = self._compress
        if should_quantize and compress == "off":
            compress = "fp8"
        if (
            bucket_cap_bytes is None
            and not device_native
            and compress == "off"
        ):
            # a host-plane op over device leaves moves in RUNS of whole
            # leaves, so that a run lands while the next is fetched: its
            # plan is cut at bucketing.RUN_BYTES (never above the cap).
            # Every group sees the same tree and cuts the same runs. A
            # caller's own cap for the call (ddp.py), the compressed wire
            # (its residuals are a bucket's), a device-native PG and a
            # host tree keep the plan of the cap alone
            cap = bucketing.run_cap(leaves, cap)
        plan: Optional[bucketing.BucketPlan] = None
        if (
            not (should_quantize and device_native)
            and len(leaves) > 1
            and cap > 0
        ):
            try:
                plan = bucketing.plan_for(leaves, cap, treedef=treedef)
            except Exception:  # noqa: BLE001 — exotic leaves fall back per-leaf
                plan = None

        place = bucketing.leaf_placer()

        def unflatten(f: Future) -> Any:
            return jax.tree_util.tree_unflatten(treedef, f.value())

        def zeros() -> Any:
            return jax.tree_util.tree_unflatten(treedef, [
                place(l, np.zeros(np.shape(l), bucketing.leaf_dtype(l)))
                for l in leaves
            ])

        if self.errored():
            return DummyWork(zeros()), None

        self.wait_quorum(cat="allreduce", parent=ar_id, segment=segment)
        # a reconfigure that landed during the forward pass commits its
        # backend swap here, before the collective touches the PG — this
        # is the "next safe point" for steps that skip should_commit
        with tracer.span(
            "configure_commit_wait", cat="allreduce", parent=ar_id,
            segment=segment,
        ):
            self._commit_pending_configure()
        if self.errored():
            return DummyWork(zeros()), None
        num_participants = self.num_participants()

        pg_reduce_op = reduce_op
        if reduce_op == ReduceOp.AVG:
            if not all(
                bucketing.is_float_dtype(bucketing.leaf_dtype(l))
                for l in leaves
            ):
                raise ValueError("AVG allreduce requires floating point arrays")
            pg_reduce_op = ReduceOp.SUM
        # the reduced SUM is divided by this where it lands; None: no AVG
        divisor = (
            num_participants
            if reduce_op == ReduceOp.AVG and num_participants > 0
            else None
        )

        def _time_allreduce(_f: Future) -> None:
            # submission → resolve wall clock of the most recent
            # collective, for the steady-state budget split
            # (ft_overhead harness; see timings())
            t1 = time.perf_counter()
            self._record_timing("allreduce_s", t1 - t_allreduce0)
            tracer.record_rel(
                "allreduce", "allreduce", t_allreduce0, t1, id=ar_id,
                parent=ar_parent, **ar_args,
            )

        try:
            op = None
            if plan is not None:
                op = pipeline.allreduce_buckets(
                    leaves, plan, pg_reduce_op,
                    participating=self.is_participating(),
                    divisor=divisor, place=place, timeout=self._timeout,
                    compress=compress, parent=ar_id, segment=segment,
                )
                # the op holds stand-ins for what it captured: so do the
                # zeros of the error path, and the caller alone the leaves
                leaves = op.leaves
                ar_args["buckets"] = len(plan)
                ar_args["bytes"] = sum(op.bucket_bytes)
                landed = op.final
            else:
                landed = pipeline.allreduce_leaves(
                    leaves, pg_reduce_op, quantize=should_quantize,
                    participating=self.is_participating(),
                    divisor=divisor, place=place, timeout=self._timeout,
                    parent=ar_id, segment=segment,
                )
            # device plane: submission-time timer (the op starts at once).
            # host plane: the pipeline's stage-start deadline owns the
            # bound — a submission timer would charge queue time behind an
            # in-flight quantized sync against this op.
            fut = self.wrap_future(
                landed.then(unflatten), zeros, arm_timeout=device_native
            )
            fut.add_done_callback(_time_allreduce)
            if op is None:
                return FutureWork(fut), None

            def _finalize_pipeline(_f: Future) -> None:
                try:
                    pipeline.record_timings(op)
                    self._log_timing_snapshot(ALLREDUCE_PIPELINE_PHASE)
                except Exception:  # noqa: BLE001
                    self._logger.exception(
                        "failed to record pipeline timings"
                    )

            fut.add_done_callback(_finalize_pipeline)
            return FutureWork(fut), GradStream(op.bucket_futs, fut)
        except Exception as e:  # noqa: BLE001
            self._logger.exception(f"got exception in allreduce -- skipping remaining: {e}")
            self.report_error(e)
            return DummyWork(zeros()), None


    # ------------------------------------------------------------ metrics
    def _bump_metric(self, name: str) -> None:
        with self._metrics_lock:
            self._metrics[name] += 1

    def metrics(self) -> Dict[str, int]:
        """Lifetime counters for operators/tests: quorums completed,
        PG reconfigures, live heals applied, commits, commit failures
        (monotonic total, unlike the protocol's consecutive
        ``_commit_failures``), allreduce calls, and errors reported. The
        structured event streams (observability.py) log the same moments
        as events; this is the cheap queryable aggregate."""
        with self._metrics_lock:
            return dict(self._metrics)

    # ------------------------------------------------------------ tracing
    @property
    def tracer(self) -> SpanRecorder:
        """This replica's span recorder (see :mod:`torchft_tpu.tracing`)."""
        return self._tracer

    def record_phase(self, cat: str, name: str, t0_us: int, t1_us: int) -> None:
        """A one-off phase the CALLER timed (epoch microseconds), e.g. the
        trainer's start-up before this Manager existed: a ring span
        ``cat/name`` and ``timings()["<cat>_<name>_s"]``, so it reaches
        both a span dump and whatever prints the timings."""
        self._tracer.record(name, cat, t0_us, t1_us)
        self._record_timing(f"{cat}_{name}_s", (t1_us - t0_us) / 1e6)

    def dump_trace(self, path: "str | Path | None" = None) -> Optional[Path]:
        """Write the span ring as a merge-ready JSON dump and return its
        path (None when no destination is configured — set
        ``TORCHFT_TRACE_DIR`` or pass a path). Feed one dump per replica
        to ``python -m torchft_tpu.trace merge`` for the fleet timeline."""
        return self._tracer.dump(path)

    def _auto_dump_trace(self, reason: str, fr_path: Optional[Path]) -> None:
        """Drop the span ring next to a flight-recorder dump so the two
        postmortem artifacts travel together (same directory, matching
        reason suffix); falls back to the default trace destination when
        the FR dump itself was disabled. Never raises."""
        try:
            path = None
            if fr_path is not None:
                path = Path(fr_path).parent / (
                    f"trace_{self._replica_id}_{self._group_rank}"
                    f"_s{self._step}_{reason}.json"
                )
            out = self._tracer.dump(path)
            if out is not None:
                self._logger.warning(f"span ring dumped to {out} ({reason})")
        except Exception:  # noqa: BLE001 — postmortem path must not raise
            self._logger.exception("trace auto-dump failed")

    @property
    def metrics_port(self) -> Optional[int]:
        """Bound TCP port of the Prometheus ``/metrics`` endpoint (None
        when not serving; enable via ``metrics_port=`` or
        ``TORCHFT_METRICS_PORT``)."""
        return (
            self._metrics_server.port
            if self._metrics_server is not None
            else None
        )

    def _refresh_metrics(self) -> None:
        """Scrape-time sync of gauges/counters into the Prometheus
        registry (the MetricsServer calls this before each render).
        Histograms fill at :meth:`_record_timing` write time; everything
        here is a last-value gauge or an absolute cumulative counter, so
        re-rendering per scrape can't double-book."""
        reg = self._metrics_registry
        if reg is None:
            return
        for name, value in self.timings().items():
            if not isinstance(value, (int, float)):
                continue
            if name in _COUNTER_TIMINGS:
                reg.counter_set(
                    f"torchft_manager_{name}_total",
                    float(value),
                    f"Cumulative {name} (Manager.timings()).",
                )
            else:
                reg.gauge_set(
                    f"torchft_manager_{name}",
                    float(value),
                    f"Last-value {name} (Manager.timings()).",
                )
        for name, value in self.metrics().items():
            reg.counter_set(
                f"torchft_manager_{name}_total",
                float(value),
                f"Lifetime {name} (Manager.metrics()).",
            )
        reg.gauge_set(
            "torchft_manager_step", float(self._step), "Current manager step."
        )
        reg.gauge_set(
            "torchft_manager_quorum_id",
            float(self._quorum_id),
            "Quorum id of the current process-group generation.",
        )
        tstats = self._tracer.stats()
        reg.counter_set(
            "torchft_manager_trace_spans_total",
            tstats["recorded"],
            "Spans recorded into the trace ring since construction.",
        )
        try:
            wire_fn = getattr(self._pg, "wire_stats", None)
            wire = wire_fn() if wire_fn is not None else {}
        except Exception:  # noqa: BLE001
            wire = {}
        for name, value in (wire or {}).items():
            if not isinstance(value, (int, float)):
                continue
            if name.startswith("bytes_"):
                reg.counter_set(
                    f"torchft_manager_wire_{name}_total",
                    float(value),
                    f"Cumulative transport {name} across PG generations.",
                )
            else:
                reg.gauge_set(
                    f"torchft_manager_wire_{name}",
                    float(value),
                    f"Transport {name} (ProcessGroup.wire_stats()).",
                )
        if self._manager is not None:
            try:
                skew_fn = getattr(self._manager, "clock_skew", None)
                skew = skew_fn() if skew_fn is not None else {}
            except Exception:  # noqa: BLE001
                skew = {}
            if skew:
                reg.gauge_set(
                    "torchft_manager_clock_skew_ms",
                    float(skew.get("skew_ms", 0.0)),
                    "Estimated clock skew vs the lighthouse "
                    "(best = minimum-RTT heartbeat sample).",
                )
                reg.gauge_set(
                    "torchft_manager_clock_skew_rtt_ms",
                    float(skew.get("rtt_ms", 0.0)),
                    "Heartbeat RTT of the best skew sample.",
                )

    def _record_timing(self, name: str, value: float) -> None:
        with self._metrics_lock:
            self._timings[name] = value
        # histograms accumulate at write time (the scrape-time refresh only
        # syncs last-value gauges and cumulative counters — re-observing a
        # last-value snapshot per scrape would double-book the same phase)
        if self._metrics_registry is not None and name.endswith("_s"):
            self._metrics_registry.observe(
                f"torchft_manager_{name[:-2]}_seconds",
                value,
                f"Manager {name[:-2]} phase wall-clock (seconds).",
            )

    def _bump_counter(self, name: str, n: float = 1.0) -> None:
        """Increment a cumulative resilience counter in timings()."""
        with self._metrics_lock:
            self._timings[name] = self._timings.get(name, 0.0) + n

    def _on_rpc_retry(self, method: str, attempt: int, exc: BaseException) -> None:
        """Retry observer installed on both manager RPC clients: a
        control-plane blip shorter than the quorum timeout degrades to a
        slower step, and this is the audit trail that says so."""
        self._bump_counter("rpc_retries")
        self._tracer.instant(
            "rpc_retry", cat="rpc", method=method, attempt=attempt
        )
        self._logger.warning(
            f"RPC {method} retrying (attempt {attempt}) after {exc!r}"
        )
        from torchft_tpu.flight_recorder import recorder

        recorder.record(
            "rpc_retry",
            method=method,
            attempt=attempt,
            error=repr(exc),
            step=self._step,
            replica=self._replica_id,
            group_rank=self._group_rank,
        )

    def _on_collective_reroute(self, pair, attempt: int) -> None:
        """Re-route observer installed on PGs that support the compressed
        ring: a mid-collective link failure degraded to a re-routed slow
        step instead of a swallowed one, and this is the audit trail."""
        self._bump_counter("collective_reroute")
        self._tracer.instant(
            "reroute", cat="rpc", link=list(pair), attempt=attempt
        )
        self._logger.warning(
            f"collective re-routed around dead link {pair} "
            f"(attempt {attempt})"
        )
        from torchft_tpu.flight_recorder import recorder

        recorder.record(
            "collective_reroute",
            link=tuple(pair),
            attempt=attempt,
            step=self._step,
            replica=self._replica_id,
            group_rank=self._group_rank,
        )

    def _update_timings(self, stats: Dict[str, Optional[float]]) -> None:
        """What the bucket pipeline reports of an allreduce (last values,
        see :meth:`timings`; None: the key is gone until it is said again);
        its thread, not the caller's."""
        with self._metrics_lock:
            for key, value in stats.items():
                if value is None:
                    self._timings.pop(key, None)
                else:
                    self._timings[key] = value

    def timings(self) -> Dict[str, float]:
        """Per-phase wall-clock of the most recent quorum cycle:
        ``quorum_overlap_s`` (control-plane time on the quorum thread —
        hidden from the train step under async quorum),
        ``configure_prepare_s`` / ``configure_commit_s`` (the split
        reconfigure; commit is the only part that serializes with the
        trainer), and ``heal_send_s`` / ``heal_recv_s`` plus
        ``heal_chunks`` / ``heal_mb_per_s`` when the checkpoint transport
        reports chunk-stream stats. The allreduces of a step
        (:meth:`start_quorum` to the next) are described TOGETHER:
        ``allreduce_ops`` says how many there were (a trainer that hands
        the gradients over segment by segment makes several), and bucketed
        ones add ``allreduce_pack_s`` / ``allreduce_wire_s`` /
        ``allreduce_unpack_s`` / ``allreduce_buckets`` /
        ``overlap_efficiency`` (see
        ``bucketing.BucketPipeline.record_timings``) and, on the host plane,
        ``allreduce_runs``: the runs the step's ops were cut into (an op
        over device leaves of more than ``bucketing.RUN_BYTES`` goes as
        several: each is a bucket of its plan, fetched, reduced and landed
        by itself), ``land_under_fetch_share``: of the seconds the step's
        landings spent in their ``h2d`` and ``divide`` spans, the part
        before the same op's last fetch had ended (0.0 where every op is
        one run: a landing then follows the whole fetch);
        ``stage_pool_hit_share``: of the device buckets the step's
        allreduces fetched, the share that went into a recycled buffer of
        the pool (pages mapped) rather than a new allocation; 1.0 from a
        plan's second step on; and ``d2h_under_backward_share``: of the
        seconds those fetches took, the part that ran before the staging
        thread's last wait for gradients still being computed returned,
        i.e. under the backward pass (0.0 where one op carries the whole
        tree: its fetch follows its wait); ``d2h_concurrency``: over those
        same seconds, the seconds the pipeline's fetcher threads spent
        inside a piece (1.0: one copy at a time; towards
        ``bucketing.FETCH_WIDTH`` when the buckets have pieces enough and
        the process cores enough), and ``d2h_gb_s``: the bytes fetched over
        them, in GB/s. Behind the plain host ring (a world of two or more,
        buckets over ``process_group._RING_MIN_BYTES``, no compression)
        the step also carries the ring's own account of its time, each key
        summed over the step's runs and gone again when the next step
        begins (``bucketing.RING_KEYS``, with ``ring_lanes``: the fewest
        lanes any of the step's rings rode): ``ring_entry_wait_s``, from a
        run's start on the PG's dispatch thread until the first peer byte
        (the left ring neighbour had not entered), and the lanes' mean
        seconds in ``ring_recv_wait_s`` (blocked for a frame's header after
        that), ``ring_recv_s``, ``ring_fold_s``, ``ring_send_s`` and
        ``ring_handoff_s`` (``docs/observability.md``, span
        ``allreduce/ring_stream``). Keys appear once the phase has run.

        Also carries the CUMULATIVE resilience counters (present from
        construction, never reset): ``heal_attempts`` (initial heal tries
        plus same-source retries), ``heal_failovers`` (mid-heal switches to
        a fallback peer), ``rpc_retries`` (retried control-plane calls),
        and ``chunk_crc_failures`` (chunks refetched after an integrity
        mismatch).

        When healthwatch telemetry is enabled (group leader talking to a
        lighthouse with ``TORCHFT_HEALTH_MODE`` != ``off``) it also
        mirrors the lighthouse's latest health summary for THIS replica:
        ``health_state`` (0=ok 1=warn 2=ejected 3=probation),
        ``straggler_score`` (quorum-relative modified z-score), and the
        cumulative ``ejections`` / ``readmissions`` counts. All four are
        seeded to 0.0 at construction.

        ``dropped_events`` / ``trace_dropped`` count observability losses:
        telemetry events shed by the bounded async drain under
        saturation, and spans overwritten in the trace ring. Both planes
        are deliberately lossy (they must never stall the step), so these
        are the honesty counters — nonzero means the record is
        incomplete, warned once per Manager."""
        with self._metrics_lock:
            out = dict(self._timings)
        # Two-level control plane: when this replica is configured for a
        # lighthouse aggregator (TORCHFT_LIGHTHOUSE_AGGREGATOR), mirror
        # which upstream the control RPCs use (``via_aggregator``) and the
        # cumulative aggregator->root ``aggregator_failovers``.
        cs_fn = getattr(getattr(self, "_manager", None), "control_status", None)
        if cs_fn is not None:
            try:
                cs = cs_fn() or {}
                if cs.get("aggregator_addr"):
                    out["via_aggregator"] = 1.0 if cs.get("via_aggregator") else 0.0
                    out["aggregator_failovers"] = float(cs.get("failovers", 0))
            except Exception:  # noqa: BLE001 — advisory plane
                pass
        out["dropped_events"] = float(get_event_drain().dropped)
        out["trace_dropped"] = self._tracer.stats()["dropped"]
        if (
            out["dropped_events"] + out["trace_dropped"] > 0
            and not self._dropped_events_warned
        ):
            self._dropped_events_warned = True
            self._logger.warning(
                f"observability queues saturated: "
                f"{int(out['dropped_events'])} telemetry event(s) and "
                f"{int(out['trace_dropped'])} span(s) dropped so far — "
                f"timings/trace records are incomplete (raise "
                f"{TRACE_BUFFER_ENV} or reduce scrape/step rate)"
            )
        return out

    # ------------------------------------------------------ serving plane
    def attach_serve_publisher(
        self,
        publisher: Any,
        params_fn: Optional[Callable[[], Any]] = None,
    ) -> None:
        """Attach a serving-plane SnapshotPublisher: every committed step
        is published as a versioned snapshot stamped ``(quorum_id, step)``
        (docs/serving.md).  ``params_fn`` selects what to publish (default:
        the registered user state dict).  Group leader only — follower
        ranks ignore the attach so a replica announces exactly once.
        Publishing is advisory: failures log, they never fail a commit."""
        if self._group_rank != 0:
            return
        self._serve_publisher = publisher
        self._serve_params_fn = (
            params_fn if params_fn is not None else self.user_state_dict
        )

    def _serve_publish_committed(self) -> None:
        """Commit-path hook: hand the just-committed params to the
        publisher.  The host copy happens here (so the next step cannot
        tear the snapshot); encoding and announcing ride the publisher's
        own thread.  Never raises — the serving plane is advisory."""
        t0 = time.perf_counter()
        try:
            self._serve_publisher.publish_async(
                self._quorum_id, self._step, self._serve_params_fn()
            )
            self._bump_counter("serve_published_total")
        except Exception:  # noqa: BLE001 — advisory plane
            self._bump_counter("serve_publish_errors_total")
            self._logger.exception("serve snapshot publish failed")
        self._record_timing("serve_publish_s", time.perf_counter() - t0)

    def _stage_redundancy_committed(self) -> None:
        """Round-start hook for the redundancy plane: hand the committed
        composite state (the update the caller just applied, labeled with
        the step about to run — exactly what a healer joining this round
        must load) to the ShardStager. The hot path pays one host
        snapshot copy + a queue put; encode/PUT/announce are the worker's.
        Never raises — staging is advisory."""
        t0 = time.perf_counter()
        try:
            with self._tracer.span(
                "shard_stage", cat="redundancy", step=self._step
            ):
                self._shard_stager.stage(self._step, self._manager_state_dict())
        except Exception:  # noqa: BLE001 — advisory plane
            self._bump_counter("shard_stage_failed")
            self._logger.exception("redundancy shard staging failed")
        self._record_timing("shard_stage_hot_s", time.perf_counter() - t0)

    # ---------------------------------------------------------- hot spare
    def promote(
        self, timeout: "float | timedelta | None" = None
    ) -> Dict[str, Any]:
        """Hot-spare promotion: block until the shard directory promotes
        this spare into the fleet (a member died), load the freshest
        prefetched state, and ONLY THEN join the control plane — create
        the rendezvous store, the ManagerServer (which heartbeats the
        lighthouse and so enters the next quorum), and the RPC clients.
        Returns the directory's promotion record. After this returns the
        Manager behaves exactly like one constructed with spare=False: the
        next start_quorum()/should_commit() cycle converges it bitwise
        (the prefetched generation IS a committed generation, so at worst
        one incremental heal covers the steps staged since)."""
        if not self._spare or self._hot_spare is None:
            raise RuntimeError("promote() requires Manager(spare=True)")
        budget = _to_seconds(timeout) if timeout is not None else None
        result = self._hot_spare.wait_promoted(timeout=budget)
        if result is None:
            raise TimeoutError(
                f"spare {self._replica_id} not promoted within {budget}s"
            )
        state_step, state, promotion = result
        self._spare_promotion = promotion
        if state is not None:
            with self._state_dict_lock.w_lock():
                user = state.get("user", {})
                for key, load_fn in self._load_state_dict_fns.items():
                    if key in user:
                        load_fn(user[key])
            self.load_state_dict(state["torchft"])
            self._logger.info(
                f"spare promoted at prefetched step {state_step} "
                f"(replacing {promotion.get('replaces')!r})"
            )
        else:
            self._logger.warning(
                "spare promoted with no prefetched generation — joining "
                "cold; the first quorum will heal it like any rejoiner"
            )
        self._hot_spare.shutdown()
        self._join_control_plane()
        # a promoted spare is a full member: it starts staging its own
        # shard generations like any group leader with the plane enabled
        if self._redundancy_cfg is not None and self._redundancy_cfg.enabled:
            try:
                from torchft_tpu import redundancy as _redundancy

                self._shard_stager = _redundancy.ShardStager(
                    self._redundancy_cfg,
                    self._replica_id,
                    on_metric=self._on_redundancy_metric,
                )
            except Exception:  # noqa: BLE001 — advisory plane
                self._logger.exception(
                    "promoted spare could not start its shard stager"
                )
        self._record_timing("spare_promote_step", float(state_step))
        return promotion

    def _join_control_plane(self) -> None:
        """The deferred half of __init__ for a spare: identical wiring to
        the group-leader branch, run at promotion time so the lighthouse
        only ever sees the spare once it is a real member."""
        args = self._spare_join_args
        assert args is not None, "control plane already joined"
        self._spare_join_args = None
        hostname = args["hostname"]
        store_addr = args["store_addr"]
        if store_addr is None:
            self._store = KvStoreServer("0.0.0.0:0")
            store_addr = f"{hostname}:{self._store.port}"
        lighthouse_addr = args["lighthouse_addr"]
        if lighthouse_addr is None:
            lighthouse_addr = os.environ[LIGHTHOUSE_ENV]
        bind_port = int(os.environ.get(MANAGER_PORT_ENV, 0))
        self._manager = ManagerServer(
            replica_id=self._replica_id,
            lighthouse_addr=lighthouse_addr,
            hostname=hostname,
            bind=f"0.0.0.0:{bind_port}",
            store_addr=store_addr,
            world_size=args["group_world_size"],
            heartbeat_interval=args["heartbeat_interval"],
            connect_timeout=self._connect_timeout,
            quorum_retries=args["quorum_retries"],
            aggregator_addr=os.environ.get(AGGREGATOR_ENV, ""),
        )
        manager_addr = self._manager.address()
        KvClient(store_addr, connect_timeout=self._connect_timeout).set(
            "manager_addr", manager_addr, timeout=self._timeout
        )
        self._store_addr = store_addr
        self._client = ManagerClient(
            manager_addr, connect_timeout=self._connect_timeout
        )
        self._vote_client = ManagerClient(
            manager_addr, connect_timeout=self._connect_timeout
        )
        self._client.set_retry_observer(self._on_rpc_retry)
        self._vote_client.set_retry_observer(self._on_rpc_retry)

    # -------------------------------------------------------- healthwatch
    def set_telemetry_transform(
        self, fn: Optional[Callable[[Dict[str, Any]], Dict[str, Any]]]
    ) -> None:
        """Install a hook applied to the per-step telemetry dict right
        before it is published to the lighthouse (None to clear). Exists
        for fault injection: ``EventInjector.slow_replica`` dilates the
        reported ``step_s`` so straggler ejection can be exercised without
        actually slowing a test replica down."""
        self._telemetry_transform = fn

    def _publish_step_telemetry(self, committed: bool = True) -> None:
        """Group leader only: stage this step's telemetry for the C++
        heartbeat thread (the lighthouse ingests it into the health
        ledger) and fold the summary the previous heartbeat brought back
        into timings() / the ``torchft_health`` stream.

        ``step_s`` is the wall clock between consecutive commit votes —
        the only boundary every replica crosses exactly once per step.
        ``wire_s`` is the most recent allreduce wire time, so the
        lighthouse can score on COMPUTE time (step minus wire): wall time
        equalizes across a quorum because the allreduce is a barrier, and
        the straggler is the replica whose compute share grew.

        A sample is published only when THIS vote and the PREVIOUS vote
        both committed AND both ran under the same quorum_id: a span
        touching a failed vote measures quorum retries, healing, or a
        discarded step, and a span crossing a reconfiguration measures the
        reconfiguration itself — neither is training pace. The quorum_id
        leg is what makes probationary readmission survivable: an excluded
        replica casts no votes at all while its quorum thread spins in the
        re-subscribe loop, so its first post-readmit interval bridges two
        committed votes that straddle the whole exclusion, and scoring
        that one multi-second sample would re-eject it on the spot.

        Must never raise — telemetry is advisory and this sits on the
        commit path."""
        self._tracer.set_context(step=self._step)
        if self._manager is None:
            return
        # fold the beat loop's latest skew estimate into the tracer so the
        # next export/auto-dump is merge-ready; pure local state, no RPC
        try:
            skew_fn = getattr(self._manager, "clock_skew", None)
            if skew_fn is not None:
                skew = skew_fn() or {}
                self._tracer.set_skew(
                    skew.get("skew_ms", 0.0),
                    skew.get("rtt_ms", 0.0),
                    skew.get("samples", 0),
                )
        except Exception:  # noqa: BLE001 — advisory plane, commit path
            pass
        now = time.perf_counter()
        last, self._last_commit_t = self._last_commit_t, now
        prev_committed = self._last_vote_committed
        self._last_vote_committed = committed
        same_quorum = self._quorum_id == self._telemetry_quorum_id
        self._telemetry_quorum_id = self._quorum_id
        try:
            if last is not None and committed and prev_committed and same_quorum:
                with self._metrics_lock:
                    wire_s = self._timings.get(
                        "allreduce_wire_s", self._timings.get("allreduce_s", 0.0)
                    )
                    heal_attempts = self._timings.get("heal_attempts", 0.0)
                    rpc_retries = self._timings.get("rpc_retries", 0.0)
                    reroutes = self._timings.get("collective_reroute", 0.0)
                    crc_fails = self._timings.get("chunk_crc_failures", 0.0)
                telemetry: Dict[str, Any] = {
                    "step": self._step,
                    "step_s": now - last,
                    "wire_s": wire_s,
                    "heal_attempts": heal_attempts,
                    "rpc_retries": rpc_retries,
                    # cumulative link-fault counters: the policy plane's
                    # link_quality signal differences these per replica
                    "collective_reroute": reroutes,
                    "chunk_crc_failures": crc_fails,
                }
                if self._degrade_cfg is not None:
                    # degrade plane: self-report capacity so the ledger
                    # scores this replica against what a step SHOULD cost
                    # at its current degree (healthwatch DEGRADED state)
                    with self._degrade_lock:
                        telemetry["group_world_size"] = self._group_degree
                        telemetry["full_group_world_size"] = (
                            self._full_group_degree
                        )
                if self._telemetry_transform is not None:
                    telemetry = self._telemetry_transform(telemetry)
                self._manager.publish_telemetry(telemetry)
            self._observe_health(self._manager.health())
        except Exception:  # noqa: BLE001 — advisory plane, commit path
            self._logger.exception("failed to publish step telemetry")

    def _observe_health(self, summary: Dict[str, Any]) -> None:
        """Fold a heartbeat health summary into timings() and emit a
        ``torchft_health`` event (plus a flight-recorder breadcrumb) on
        every state TRANSITION: ``straggler_warn`` on entering warn,
        ``eject`` on entering ejected, ``readmit`` on entering probation
        (the lighthouse lifts the exclusion at that edge), ``recovered``
        on returning to ok."""
        state = summary.get("state")
        if not state:
            return
        with self._metrics_lock:
            self._timings["health_state"] = float(summary.get("state_code", 0))
            self._timings["straggler_score"] = float(summary.get("score", 0.0))
            self._timings["ejections"] = float(summary.get("ejections", 0))
            self._timings["readmissions"] = float(summary.get("readmissions", 0))
        prev, self._last_health_state = self._last_health_state, state
        if prev == state or prev is None and state == "ok":
            return
        kind = {
            "warn": "straggler_warn",
            "ejected": "eject",
            "probation": "readmit",
            "ok": "recovered",
            # ledger acknowledged this replica's reduced group degree
            "degraded": "degrade_acked",
        }.get(state, state)
        emit_event_async(
            HEALTH_EVENTS,
            replica_id=self._replica_id,
            group_rank=self._group_rank,
            step=self._step,
            quorum_id=self._quorum_id,
            kind=kind,
            state=state,
            prev_state=prev,
            score=summary.get("score", 0.0),
            ejections=summary.get("ejections", 0),
            readmissions=summary.get("readmissions", 0),
        )
        self._logger.warning(
            f"healthwatch: {kind} (state {prev} -> {state}, "
            f"score={summary.get('score', 0.0)})"
        )
        from torchft_tpu.flight_recorder import recorder

        recorder.record(
            kind,
            state=state,
            prev_state=prev,
            score=summary.get("score", 0.0),
            step=self._step,
            replica=self._replica_id,
            group_rank=self._group_rank,
        )
        self._tracer.instant(
            kind,
            cat="health",
            state=state,
            prev_state=prev,
            score=summary.get("score", 0.0),
        )
        if kind == "eject":
            # the lighthouse just cut this replica out of the quorum: dump
            # both postmortem artifacts NOW, while the straggler evidence
            # (slow buckets, retried RPCs) is still in the rings
            fr_path = recorder.dump(
                reason="eject",
                quorum_id=self._quorum_id,
                tag=f"{self._replica_id}_{self._group_rank}"
                f"_s{self._step}_eject",
            )
            self._auto_dump_trace("eject", fr_path)

    def _log_timing_snapshot(self, phase: str) -> None:
        try:
            # through the bounded async drain: snapshots fire from the
            # commit path (which serializes with the trainer), so the JSON
            # encode + logging I/O must not ride the critical path
            emit_event_async(
                TIMING_EVENTS,
                replica_id=self._replica_id,
                group_rank=self._group_rank,
                step=self._step,
                quorum_id=self._quorum_id,
                phase=phase,
                **self.timings(),
            )
        except Exception:  # noqa: BLE001
            self._logger.exception("failed to log timing snapshot")

    # ------------------------------------------------------------- errors
    def report_error(self, e: Exception) -> None:
        """Mark the step as corrupt; it will be discarded at should_commit
        and the PG reconfigured on the next quorum."""
        # count error EPISODES, not report_error calls: one wire fault fans
        # out into a report per in-flight allreduce plus one per commit vote
        # while the PG stays errored — operators comparing this against
        # commit_failures need fault frequency, not callback fan-out. The
        # None-check and the assignment must be one atomic step: reports
        # arrive concurrently from allreduce done-callbacks and the timeout
        # loop, and two threads both observing None would double-count.
        with self._metrics_lock:
            if self._errored is None:
                self._metrics["errors"] += 1
            self._errored = ExceptionWithTraceback(e)
        from torchft_tpu.flight_recorder import recorder

        recorder.record(
            "manager_error",
            error=str(e),
            step=self._step,
            replica=self._replica_id,
            group_rank=self._group_rank,
        )
        recorder.dump(
            reason="manager_error",
            quorum_id=self._quorum_id,
            tag=f"{self._replica_id}_{self._group_rank}"
            f"_s{self._step}_manager_error",
        )
        log_error_event(
            replica_id=self._replica_id,
            group_rank=self._group_rank,
            step=self._step,
            quorum_id=self._quorum_id,
            error=str(e),
        )

    def errored(self) -> Optional[ExceptionWithTraceback]:
        return self._errored

    def wrap_future(
        self,
        fut: Future[T],
        default: Any,
        timeout: "float | timedelta | None" = None,
        arm_timeout: bool = True,
    ) -> Future[T]:
        """Timeout + swallow errors into ``default``, reporting them
        (reference: manager.py:516-558). ``default`` may be a zero-arg
        factory — then the fallback value is only built on the error path,
        not eagerly per call (a zeros pytree of a multi-GB gradient tree
        would otherwise cost host alloc + H2D on every healthy step).

        ``arm_timeout=False`` skips the submission-time timer for callers
        that arm their own deadline when work actually STARTS (the staged
        host path: a timer started at submission would charge queue time
        behind an in-flight quantized sync against this op)."""
        if arm_timeout:
            timed = future_timeout(
                fut,
                _to_seconds(timeout) if timeout is not None else self._timeout,
            )
        else:
            timed = fut

        def callback(f: Future[T]) -> T:
            try:
                return f.value()
            except Exception as e:  # noqa: BLE001
                self._logger.exception(f"got exception in future -- skipping remaining: {e}")
                self.report_error(e)
                return default() if callable(default) else default

        return timed.then(callback)

    # ------------------------------------------------------------- commit
    def should_commit(self, timeout: "float | timedelta | None" = None) -> bool:
        """Two-phase commit vote across the replica group; True iff every
        rank of this group is healthy and enough replicas participate
        (reference: manager.py:848-936)."""
        with self._tracer.span("should_commit", cat="commit"):
            return self._should_commit(timeout)

    def _should_commit(self, timeout: "float | timedelta | None") -> bool:
        t_begin = time.perf_counter()
        # recovery (on the quorum thread) must finish before we decide
        if self._quorum_future is not None:
            try:
                self._quorum_future.result()
            except Exception as e:  # noqa: BLE001
                self.report_error(e)
        # time spent joining the quorum thread is overlap shortfall, not
        # bookkeeping — split it out so the steady-state budget is honest
        join_s = time.perf_counter() - t_begin

        # apply a pending backend swap BEFORE sampling pg.errored(): after
        # a membership change the OLD world is typically errored (the abort
        # that triggered the change); the sync flow cleared that state
        # inside configure, the split flow clears it at commit
        self._commit_pending_configure()
        # a staged intra-group degrade also lands here, BEFORE the errored
        # sample: the reshard replaces the dead member, so the step votes
        # as a re-planned slow step instead of a discarded one
        if self._degrade_cfg is not None:
            self._commit_pending_degrade()

        if (err := self._pg.errored()) is not None:
            self.report_error(err)

        self._sync_device_world()
        if self._healing and self._pending_state_dict is not None:
            self._apply_pending_state_dict()
        elif self._healing:
            # recovery failed mid-flight; the error is already reported and
            # this step will not commit — retry healing on the next quorum
            self._healing = False

        enough_replicas = self.num_participants() >= self._min_replica_size
        local_should_commit = enough_replicas and self._errored is None
        if not local_should_commit:
            # a false local vote silently discards the whole group's step —
            # at WARNING so the reason is visible under default logging
            # (INFO-only reasons made a spurious device-plane error during
            # a quiet chaos soak undiagnosable from its console log)
            self._logger.warning(
                f"voting False for step {self._step}: "
                f"enough_replicas={enough_replicas} "
                f"(participants={self.num_participants()} "
                f"min={self._min_replica_size}) "
                f"errored={self._errored!r}"
            )
        # the vote rides its own warm client (see __init__) and a pre-built
        # frame (coordination.py): the steady-state step is this one RPC
        # round-trip plus the collective
        t_rpc = time.perf_counter()
        with self._tracer.span(
            "commit_vote", cat="commit", local=local_should_commit
        ):
            should_commit = self._vote_client.should_commit(
                self._group_rank,
                self._step,
                local_should_commit,
                timeout=_to_seconds(timeout) if timeout is not None else self._timeout,
            )
        rpc_s = time.perf_counter() - t_rpc
        # per-step outcome at DEBUG: the False cases already warn above /
        # in the retry path, and the commit event below carries the full
        # record — an INFO line per healthy step is pure hot-loop cost
        self._logger.debug(
            f"should_commit={should_commit} enough_replicas={enough_replicas} errored={self._errored is not None}"
        )
        emit_event_async(
            COMMIT_EVENTS,
            replica_id=self._replica_id,
            group_rank=self._group_rank,
            step=self._step,
            quorum_id=self._quorum_id,
            committed=should_commit,
            enough_replicas=enough_replicas,
            errored=self._errored is not None,
            num_participants=self.num_participants(),
        )

        if not self._standby_source:
            self._checkpoint_transport.disallow_checkpoint()

        if should_commit:
            if self._serve_publisher is not None:
                # publish the committed snapshot BEFORE the step advances:
                # the serving version is stamped with the step that voted
                self._serve_publish_committed()
            if self._shard_stager is not None:
                # redundancy plane: arm staging for the NEXT round start.
                # Staging here would label the generation with the step
                # that just voted, but a healer joining round M needs the
                # post-commit state labeled M — which only exists once the
                # caller applies this round's update. Deferring to
                # start_quorum also lands the announce BEFORE the round's
                # allreduce barrier, so a healer blocking that barrier can
                # still reconstruct the generation it needs.
                self._redundancy_stage_pending = True
            self._step += 1
            self._batches_committed += self.num_participants()
            self._commit_failures = 0
            self._bump_metric("commits")
            if not self._first_commit_seen:
                # what this process compiled, or loaded from the persistent
                # cache, on the way to its first committed step (a
                # rejoiner: its heal step); the spans are compile/*
                self._first_commit_seen = True
                self._record_timing(
                    "first_step_compile_s", self._tracer.compile_total_s()
                )
        else:
            self._commit_failures += 1
            self._bump_metric("commit_failures")
            if (
                self._max_retries is not None
                and self._commit_failures > self._max_retries
            ):
                msg = (
                    f"should_commit failed {self._commit_failures} times "
                    f"consecutively, exceeding max_retries={self._max_retries}"
                )
                self._logger.exception(msg)
                raise RuntimeError(msg)

        self._record_timing("should_commit_rpc_s", rpc_s)
        self._record_timing(
            "bookkeeping_s",
            max(0.0, time.perf_counter() - t_begin - rpc_s - join_s),
        )
        # stage telemetry for the heartbeat thread + fold back the health
        # summary it last brought home; pure bookkeeping (one dict build
        # and two lock hops), no RPC on this path
        self._publish_step_telemetry(committed=should_commit)
        return should_commit

    # -------------------------------------------------------- introspection
    def load_state_dict(self, state_dict: Dict[str, int]) -> None:
        self._step = state_dict["step"]
        self._batches_committed = state_dict["batches_committed"]

    def _manager_state_dict(self) -> Dict[str, Any]:
        assert len(self._user_state_dicts) > 0, "user state_dict is not initialized"
        # one source of truth for the user-state composition: live healing
        # and durable checkpoints must capture the same composite
        return {
            "user": self.user_state_dict(),
            "torchft": self.state_dict(),
        }

    def state_dict(self) -> Dict[str, int]:
        """Manager state for durable checkpoints: include this in your own
        periodic checkpoints (reference: manager.py:938-958)."""
        return {"step": self._step, "batches_committed": self._batches_committed}

    def state_dict_template(self) -> Dict[str, Any]:
        """The LIVE healing composite, for use as a PGTransport in-place
        template: ``PGTransport(pg, state_dict_template=lambda:
        manager.state_dict_template())`` (late-bound — construct the
        transport first, the Manager after). Because sender and receiver
        both build this exact tree from their registered state-dict fns,
        the transport's index-based leaf alignment holds by construction —
        including algorithm state like DiLoCo fragments, whose keys sort
        BEFORE "default" in the flattened composite (hand-rolled templates
        that guess the shape silently lose the in-place property when any
        extra state fn is registered)."""
        return self._manager_state_dict()

    def user_state_dict(self) -> Dict[str, Any]:
        """Every registered user state (trainer state, DiLoCo fragment
        globals + outer optimizer, LocalSGD backups, data position, ...)
        under the read lock — the same composite live healing transfers.
        Durable (tier-2) checkpoints should save THIS, not just the
        trainer's own state, or algorithm state silently resets on a cold
        restart."""
        with self._state_dict_lock.r_lock():
            return {key: fn() for key, fn in self._user_state_dicts.items()}

    def load_user_state_dict(self, user_state: Dict[str, Any]) -> None:
        """Feed a ``user_state_dict()`` composite back through every
        registered load fn (the cold-restart counterpart of healing's
        ``_apply_pending_state_dict``)."""
        with self._state_dict_lock.w_lock():
            for key, load_fn in self._load_state_dict_fns.items():
                if key in user_state:
                    load_fn(user_state[key])

    def current_quorum_id(self) -> int:
        """The id of the last quorum this manager joined (-1 before the
        first). Bumps exactly when the lighthouse changes membership (or
        after commit failures) — operators and benchmarks use the bump as
        the observable 'membership changed' edge."""
        return self._quorum_id

    def current_step(self) -> int:
        return self._step

    def batches_committed(self) -> int:
        return self._batches_committed

    def participating_rank(self) -> Optional[int]:
        if self._quorum_future is None:
            return None
        self.wait_quorum()
        return self._participating_replica_rank

    # aliases used by wrappers
    def replica_rank(self) -> Optional[int]:
        return self.participating_rank()

    def num_participants(self) -> int:
        if self._quorum_future is None:
            return 0
        self.wait_quorum()
        assert self._participating_replica_world_size >= 0
        return self._participating_replica_world_size

    def num_replicas(self) -> int:
        """Total replicas in the current quorum, including non-participants."""
        return self._num_replicas

    def is_participating(self) -> bool:
        if self._participating_replica_rank is None:
            return False
        if self._healing:
            assert self._use_async_quorum
            return False
        return True

    def last_quorum_healed(self) -> bool:
        """True iff the most recent quorum live-healed this replica (its
        registered state-dict fns were fed recovered state). Functional
        training loops use this to re-read state that the quorum rebound —
        values captured before ``start_quorum`` are stale after a heal."""
        return self._last_quorum_healed

    # ------------------------------------------------------------ lifecycle
    def shutdown(self, wait: bool = True) -> None:
        if self._metrics_server is not None:
            self._metrics_server.shutdown()
            self._metrics_server = None
        # redundancy plane first: its worker threads hold no locks the
        # teardown below needs, and a late shard PUT against a dying peer
        # is harmless but noisy
        if self._shard_stager is not None:
            try:
                self._shard_stager.shutdown()
            except Exception:  # noqa: BLE001 — teardown must never raise
                pass
            self._shard_stager = None
        if self._hot_spare is not None:
            try:
                self._hot_spare.shutdown()
            except Exception:  # noqa: BLE001 — teardown must never raise
                pass
            self._hot_spare = None
        self._checkpoint_transport.shutdown(wait=wait)
        if self._manager is not None:
            self._manager.shutdown()
        if self._store is not None:
            self._store.shutdown()
        self._executor.shutdown(wait=wait)
        # never apply a backend swap during teardown — drop it
        with self._pending_commit_lock:
            self._pending_pg_commit = None
        # before the PG goes: queued staging tasks must not dispatch against
        # a PG that is shut down
        self._pipeline.shutdown(wait=wait)
        self._pg.shutdown()
        self._tracer.close()  # the watcher thread; the ring stays readable
        # best-effort: land any commit/timing events still queued in the
        # async drain before the process (and its log handlers) go away
        try:
            from torchft_tpu.observability import get_event_drain

            get_event_drain().flush(timeout=2.0)
        except Exception:  # noqa: BLE001 — teardown must never raise
            pass

    @property
    def store_addr(self) -> str:
        """Rendezvous store address of this replica group (leader's store)."""
        assert self._store_addr is not None
        return self._store_addr
