"""Device-plane reconfigurable collectives: the NCCL-role component.

The reference's core data plane is abort/reconfigure-capable *device*
collectives (reference: torchft/process_group.py:780-891, ProcessGroupNCCL).
This module is the TPU-native equivalent: a :class:`ProcessGroupXLA` whose
cross-replica-group collectives execute **as XLA collectives on device** —
``lax.psum``-class reductions over a ``jax.sharding.Mesh`` with a
``"replica"`` axis — instead of host pickle-over-TCP
(:class:`torchft_tpu.process_group.ProcessGroupHost`, the Gloo-role host
plane).

Two operating modes, selected automatically at ``configure()``:

- **local**: one Python process owns every device of the quorum (a
  single-host multi-chip slice, the driver's virtual-CPU-device dryrun, the
  thread-per-replica test harness). Replica ``r``'s payload lives on lead
  device ``r``; an op rendezvouses all replicas' contributions — zero-copy,
  ``jax.make_array_from_single_device_arrays`` wraps the already-placed
  per-device shards — and one jitted reduction runs over the mesh. XLA
  lowers the reduction over the sharded axis to a cross-device all-reduce
  that rides ICI on real hardware.

- **distributed**: each replica group's lead process joins a
  ``jax.distributed`` world spanning the quorum (collectives ride ICI/DCN
  on TPU pods; the CPU test fabric uses XLA's Gloo-backed cross-host
  collectives). The coordinator address is rendezvoused through the same KV
  store the host plane uses, under a quorum-scoped prefix, so concurrent
  reconfigurations never collide. Reconfiguring tears the old world down
  (``jax.distributed.shutdown`` + backend clear) and initializes the new
  membership keyed by ``quorum_id``.

Reconfiguration semantics and their cost:

- The reference aborts and rebuilds one NCCL communicator while the rest of
  the process (CUDA context, model tensors) survives. XLA has no
  per-communicator world: in distributed mode the runtime world is global
  to the process, so ``configure()`` after a membership change
  **invalidates live device arrays** in that process. That is acceptable
  exactly where this PG sits: on a membership change the Manager re-stages
  state anyway (healing receives a checkpoint; survivors re-``device_put``
  onto the new mesh), and ``WorldSizeMode.FIXED_WITH_SPARES``
  (manager.py:364-374) keeps the world constant so steady-state failures
  need no re-init at all — dead spares contribute zeros, matching the
  reference's no-recompile design.
- In local mode reconfiguration is cheap: a new mesh over the surviving
  lead devices plus fresh jitted reductions.

Timeout→abort dispatch, error swallowing, and fault injection come from the
existing wrappers (ProcessGroupWrapper and friends, process_group.py) —
this class plugs into them unchanged. ``device_native = True`` tells the
Manager to keep payloads on device instead of staging to numpy.
"""

from __future__ import annotations

import logging
import os
import socket
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from torchft_tpu.coordination import KvClient
from torchft_tpu.process_group import ProcessGroup, ReduceOp
from torchft_tpu.work import DummyWork, Future, FutureWork, Work

logger = logging.getLogger(__name__)

__all__ = ["ProcessGroupXLA"]


_REDUCERS = {
    ReduceOp.SUM: lambda a: a.sum(axis=0),
    ReduceOp.AVG: lambda a: a.mean(axis=0).astype(a.dtype),
    ReduceOp.MAX: lambda a: a.max(axis=0),
    ReduceOp.MIN: lambda a: a.min(axis=0),
    ReduceOp.PRODUCT: lambda a: a.prod(axis=0),
}

# Peer-failure detection latency for the per-quorum jax.distributed world.
# jax.distributed.initialize's default is 100s — useless for per-step fault
# tolerance; the reference's NCCL plane detects via op timeout in seconds.
_HEARTBEAT_TIMEOUT_S = float(os.environ.get("TORCHFT_XLA_HEARTBEAT_SEC", 10.0))


def _join_distributed_world(
    coord: str,
    rank: int,
    world_size: int,
    timeout: float,
) -> None:
    """Join a per-quorum ``jax.distributed`` world with FT-grade options.

    Vanilla ``jax.distributed.initialize`` is unusable as a reconfigurable
    communicator on this toolchain (jax 0.9.0, measured in
    docs/operations.md):

    - ``shutdown()`` on a degraded world blocks in the cooperative shutdown
      barrier and then ``LOG(FATAL)``s the process;
    - the default 100s heartbeat hides peer death from the quorum layer;
    - overriding ``missed_heartbeat_callback`` is not viable: jaxlib's
      binding cannot convert the ``absl::Status`` argument (``std::bad_cast``
      → ``std::terminate``).

    Nor can a degraded world be abandoned silently: a released client's
    heartbeat/error-poll threads hold it alive internally, and the
    coordination service pushes a task-death error to every live poller
    ~heartbeat_timeout after a peer dies (measured: 11.0s at the 10s
    default; ``recoverable=True`` merely stretches it to ~25s). The
    consequence is a hard toolchain invariant this module is designed
    around (docs/operations.md): **membership can only shrink by process
    restart** — a member of a degraded distributed world always dies; the
    short heartbeat bounds *when*, and the supervising launcher restarting
    it into the next quorum is the recovery path (the reference's
    Baby-subprocess isolation inverted: the trainer process is the
    expendable child, the launcher is the parent). Healthy transitions
    (same membership re-keyed, grows, graceful leaves) reconfigure
    IN-PROCESS via the cooperative shutdown barrier, which succeeds
    precisely when everyone is alive to vote.

    The same ``jax._src.distributed.global_state`` fields are populated as
    ``initialize`` would, so backend creation picks up the world normally.
    """
    import jax
    from jax._src import distributed as _dist
    from jax._src.lib import _jax as _jaxlib

    state = _dist.global_state
    if state.client is not None:
        raise RuntimeError(
            "a jax.distributed world is already initialized; tear it down "
            "before joining a new quorum"
        )

    hb = max(1, int(_HEARTBEAT_TIMEOUT_S))
    # the cooperative-shutdown barrier wait: short, because on a degraded
    # world the barrier CANNOT succeed and its failure is process-fatal —
    # a small bound turns "die eventually" into "die promptly, restart"
    shutdown_to = min(max(1, int(timeout)), 10)
    if rank == 0:
        bind = "[::]:" + coord.rsplit(":", 1)[1]
        state.service = _jaxlib.get_distributed_runtime_service(
            bind, world_size, heartbeat_timeout=hb,
            shutdown_timeout=shutdown_to,
        )

    try:
        client = _jaxlib.get_distributed_runtime_client(
            coord, rank,
            init_timeout=max(1, int(timeout)),
            heartbeat_timeout=hb,
            shutdown_timeout=shutdown_to,
            shutdown_on_destruction=False,
            use_compression=True,
        )
        logger.info(
            "joining distributed world %s as %d/%d", coord, rank, world_size
        )
        client.connect()
    except Exception:
        # symmetric cleanup: a failed join must not strand rank 0's live
        # service in jax global state — the next configure() would skip
        # teardown (no world was built) and rebind over a service still
        # holding the port and its threads. NOTE: on this toolchain the
        # world-never-filled case is usually process-FATAL (client.h
        # terminates on the registration deadline) rather than a Python
        # exception — that death is the documented restart-on-shrink path;
        # this cleanup covers the join failures that do raise in-process
        # (client construction errors, toolchains where connect raises).
        if rank == 0 and state.service is not None:
            service, state.service = state.service, None
            t = threading.Thread(
                target=service.shutdown,
                daemon=True,
                name="pgxla_service_shutdown",
            )
            t.start()
            t.join(5.0)  # bounded, like _teardown_distributed_world's
        raise
    state.client = client
    state.process_id = rank
    state.num_processes = world_size
    state.coordinator_address = coord


def _lead_devices_local(world: int) -> List[Any]:
    """One lead device per replica from the local device pool."""
    import jax

    devices = jax.devices()
    if len(devices) < world:
        raise RuntimeError(
            f"ProcessGroupXLA(local) needs >= {world} devices, have "
            f"{len(devices)}; construct ProcessGroupXLA(mode='distributed') "
            "before any other JAX use in the process, or use the host plane"
        )
    per = len(devices) // world
    return [devices[r * per] for r in range(world)]


class _Mailbox:
    """Local-mode p2p handoff (one send/recv pairing)."""

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._payload: Optional[List[Any]] = None
        self._set = False
        self._error: Optional[Exception] = None
        self._closed = False

    def put(self, payload: List[Any]) -> bool:
        """Deposit; returns False when the receiver already gave up
        (closed) — the payload is dropped instead of pinned forever."""
        with self._cond:
            if self._closed:
                return False
            self._payload = payload
            self._set = True
            self._cond.notify_all()
        return True

    def close(self) -> None:
        """Receiver gave up (timeout/abort): a late put must drop its
        payload rather than park device arrays in an orphan mailbox that
        no future recv (the seq counter advanced) will ever read."""
        with self._cond:
            self._closed = True
            self._payload = None
            self._cond.notify_all()

    def fail(self, err: Exception) -> None:
        """abort() path: wake a blocked get() with the abort error instead of
        letting it run out its full timeout."""
        with self._cond:
            self._error = self._error or err
            self._cond.notify_all()

    def get(self, timeout: float) -> List[Any]:
        with self._cond:
            if not self._cond.wait_for(
                lambda: self._set or self._error is not None, timeout
            ):
                raise TimeoutError("p2p recv timed out")
            if self._set:
                return self._payload  # type: ignore[return-value]
            raise self._error  # type: ignore[misc]


class _OpSlot:
    """Local-mode rendezvous for one collective op across replica threads."""

    def __init__(self, world_size: int) -> None:
        self.world_size = world_size
        self.lock = threading.Lock()
        self.contributions: Dict[int, List[Any]] = {}
        self.futures: Dict[int, Future] = {}

    def deposit(self, rank: int, payload: List[Any]) -> Tuple[Future, bool]:
        """Returns (this rank's future, am_i_last)."""
        with self.lock:
            self.contributions[rank] = payload
            fut = self.futures.setdefault(rank, Future())
            last = len(self.contributions) == self.world_size
        return fut, last

    def resolve(self, per_rank: Dict[int, Any]) -> None:
        with self.lock:
            futs = {r: self.futures.setdefault(r, Future()) for r in per_rank}
        for r, fut in futs.items():
            try:
                fut.set_result(per_rank[r])
            except RuntimeError:
                pass

    def fail(self, err: Exception) -> None:
        with self.lock:
            futs = [
                self.futures.setdefault(r, Future())
                for r in range(self.world_size)
            ]
        for fut in futs:
            try:
                fut.set_exception(err)
            except RuntimeError:
                pass


class _XlaWorld:
    """One configure() generation: mesh, jit cache, op rendezvous state.

    In local mode the world is shared by every replica's PG instance (they
    live in one process); ops rendezvous contributions by per-kind sequence
    number — aligned SPMD call order across replicas is the collective
    contract, exactly as with NCCL. In distributed mode each process holds
    its own world object and ops involve only the local shard.
    """

    def __init__(
        self,
        mesh: Any,
        leads: List[Any],
        world_size: int,
        distributed: bool,
        quorum_id: int,
    ) -> None:
        self.mesh = mesh
        self.leads = leads
        self.world_size = world_size
        self.distributed = distributed
        self.quorum_id = quorum_id
        self.lock = threading.Lock()
        self.error: Optional[Exception] = None
        self.slots: Dict[Tuple[str, int], _OpSlot] = {}
        self.mailboxes: Dict[Tuple[str, int], _Mailbox] = {}
        self._jit_cache: Dict[Any, Callable] = {}

    # ---------------------------------------------------------------- jit
    def reduce_fn(self, op: ReduceOp) -> Callable:
        """Jitted leaf-list reduction over the ``replica`` axis, fully
        replicated output. One cache entry per op; XLA re-specializes per
        shape set automatically and lowers the sharded-axis reduction to a
        cross-device all-reduce."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = ("reduce", op)
        if key not in self._jit_cache:
            reducer = _REDUCERS[op]
            self._jit_cache[key] = jax.jit(
                lambda args: [reducer(a) for a in args],
                out_shardings=NamedSharding(self.mesh, P()),
            )
        return self._jit_cache[key]

    def replicate_fn(self) -> Callable:
        """Jitted identity resharding replica-sharded inputs to fully
        replicated — the allgather building block (XLA lowers the reshard to
        an all-gather over the mesh axis)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = ("replicate",)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                lambda args: list(args),
                out_shardings=NamedSharding(self.mesh, P()),
            )
        return self._jit_cache[key]

    # ------------------------------------------------------------- arrays
    def global_array(self, leaf_shards: Dict[int, Any], shape: Tuple[int, ...]):
        """Assemble a replica-sharded global array from per-rank shards
        (each already on its rank's lead device, with a leading length-1
        axis). Local mode supplies every rank; distributed mode only its
        own."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.mesh, P("replica"))
        arrays = [leaf_shards[r] for r in sorted(leaf_shards)]
        return jax.make_array_from_single_device_arrays(
            (self.world_size, *shape), sharding, arrays
        )

    def place(self, rank: int, leaf: Any) -> Any:
        """Put ``leaf`` on rank's lead device with a leading length-1 axis
        (its shard of the global replica-sharded array)."""
        import jax
        import jax.numpy as jnp

        if not isinstance(leaf, jax.Array):
            leaf = jnp.asarray(leaf)
        return jax.device_put(leaf[None], self.leads[rank])

    def result_for(self, out: Any, rank: int) -> Any:
        """The single-device view of a fully-replicated result on rank's
        lead device."""
        dev = self.leads[rank]
        for s in out.addressable_shards:
            if s.device == dev:
                return s.data
        # distributed mode: only the local shard is addressable
        return out.addressable_shards[0].data

    # ----------------------------------------------------------- rendezvous
    def slot(self, kind: str, seq: int) -> _OpSlot:
        with self.lock:
            s = self.slots.get((kind, seq))
            if s is None:
                s = _OpSlot(self.world_size)
                self.slots[(kind, seq)] = s
        return s

    def gc_slot(self, kind: str, seq: int) -> None:
        with self.lock:
            self.slots.pop((kind, seq), None)

    def mailbox(self, kind: str, seq: int) -> _Mailbox:
        with self.lock:
            mb = self.mailboxes.get((kind, seq))
            if mb is None:
                mb = _Mailbox()
                self.mailboxes[(kind, seq)] = mb
        return mb

    def gc_mailbox(self, kind: str, seq: int) -> None:
        with self.lock:
            self.mailboxes.pop((kind, seq), None)


# Process-global local-mode world registry: every replica's PG in this
# process joins the same world per (store key, quorum id, world size).
_local_worlds: Dict[Tuple[str, int, int], _XlaWorld] = {}
_local_worlds_lock = threading.Lock()


class ProcessGroupXLA(ProcessGroup):
    """Reconfigurable device-plane PG (see module docstring).

    ``mode``: "auto" (default; local when this process holds enough devices,
    else distributed), "local", or "distributed".
    """

    device_native = True

    def __init__(self, timeout: "float | Any" = 60.0, mode: str = "auto") -> None:
        super().__init__()
        self.set_timeout(timeout)
        self._mode = mode
        self._world: Optional[_XlaWorld] = None
        self._rank = 0
        self._size = 1
        self._lock = threading.Lock()
        self._seq: Dict[str, int] = {}
        self._error: Optional[Exception] = None
        self._dispatch_q: Optional[Any] = None  # distributed-mode op stream
        self._device_world_epoch = 0
        # last successful configure args, kept for the intra-group degrade
        # path (prepare_shrink re-lands the same world coordinates)
        self._last_configure: Optional[Tuple[str, int, int, int]] = None

    @property
    def requires_sync_quorum(self) -> bool:
        """Always False since the prepare/commit configure split: the
        control-plane part of a reconfigure (quorum-scoped coordinator
        rendezvous through the KV store) runs on the quorum thread via
        ``prepare_configure``, and the only backend-touching piece — the
        jax world swap in distributed mode — is returned as a commit
        callable the Manager applies from the main thread at the next
        safe point. The Manager still honors True from third-party PGs
        without the split (the safety valve this property used to be)."""
        return False

    @property
    def device_world_epoch(self) -> int:
        """Bumped every time this PG rebuilds the jax backend (per-quorum
        distributed worlds tear down + rejoin; the first distributed join
        rebuilds a backend that predates the world). Arrays created before
        a bump stay READABLE (their buffers own a client reference) but
        cannot mix with new-world arrays inside one jitted computation —
        the Manager watches this and re-lands registered user state on the
        live backend at the next main-thread sync point."""
        with self._lock:
            return self._device_world_epoch

    def _distributed_work(self, fn: Callable[[], Any]) -> Work:
        """Distributed-mode op: dispatch + materialization on one worker
        thread per PG (preserving issue order, like a communication stream),
        each op bounded by the configured timeout with ``abort`` as the
        watchdog — the analog of the reference's NCCL
        ``_WorkAcceleratorTimeout`` (process_group.py:714-777). Without
        this, a peer wedged mid-collective would block the caller
        unboundedly at first materialization."""
        import queue as _queue

        fut: Future = Future()
        timeout = self._timeout

        def run() -> None:
            import jax

            from torchft_tpu.futures import context_timeout

            try:
                with context_timeout(self.abort, timeout):
                    out = fn()
                    jax.block_until_ready(out)
                fut.set_result(out)
            except Exception as e:  # noqa: BLE001
                try:
                    fut.set_exception(e)
                except RuntimeError:
                    pass

        # enqueue under the lock: abort() swaps _dispatch_q and posts the
        # shutdown sentinel under the same lock, so an op can never land
        # behind the sentinel and leave its future unresolved
        with self._lock:
            if self._error is not None:
                raise self._error
            if self._dispatch_q is None:
                q: "_queue.Queue" = _queue.Queue()
                self._dispatch_q = q

                def pump() -> None:
                    while True:
                        item = q.get()
                        if item is None:
                            return
                        item()

                threading.Thread(
                    target=pump, daemon=True, name="pgxla_dispatch"
                ).start()
            self._dispatch_q.put(run)
        return FutureWork(fut)

    # ------------------------------------------------------------ lifecycle
    def configure(self, store_addr, replica_rank, replica_world_size, quorum_id=0):
        commit = self.prepare_configure(
            store_addr, replica_rank, replica_world_size, quorum_id=quorum_id
        )
        if commit is not None:
            commit()

    def prepare_configure(
        self, store_addr, replica_rank, replica_world_size, quorum_id=0
    ) -> Optional[Callable[[], None]]:
        """Two-phase configure (see ProcessGroup.prepare_configure).

        Local mode never touches the process-global jax runtime, so the
        whole configure is prepare-safe and there is nothing to commit.
        Distributed mode stages the control plane here — the quorum-scoped
        coordinator rendezvous through the KV store, including the blocking
        wait for rank 0's address — and returns the backend swap (world
        teardown + ``jax.distributed`` rejoin + mesh build) as the commit,
        because ONLY the swap can race the trainer's own jax computations.
        """
        mode = self._mode
        if mode == "auto":
            # "auto" resolves to local: picking distributed here would
            # require counting local devices, and jax.devices() initializes
            # the XLA backend — after which jax.distributed.initialize is
            # forbidden. Distributed mode is therefore an explicit opt-in,
            # made before any other JAX use in the process (the launcher
            # knows the deployment shape; _lead_devices_local raises a
            # pointer here when local mode can't cover the world).
            mode = "local"

        with self._lock:
            self._last_configure = (
                store_addr, replica_rank, replica_world_size, quorum_id
            )

        if mode == "local":
            self._retire_current_world()
            world = self._configure_local(store_addr, replica_world_size, quorum_id)
            self._install_world(world, replica_rank, replica_world_size)
            return None

        coord = self._stage_distributed(store_addr, replica_rank, quorum_id)

        def commit() -> None:
            self._retire_current_world()
            world = self._configure_distributed(
                coord, replica_rank, replica_world_size, quorum_id
            )
            self._install_world(world, replica_rank, replica_world_size)

        return commit

    def prepare_shrink(
        self, dead_group_rank: int
    ) -> Optional[Callable[[], None]]:
        """Intra-group degrade path (docs/operations.md#degraded-replicas):
        a chip INSIDE this replica's group died and the group is shrinking
        its own TP/PP degree in place rather than leaving the quorum.

        The param movement is the reshard engine's job
        (torchft_tpu/parallel/degrade.py); this PG's job is to fence the
        collective generation the dead chip was entangled with. Local mode
        (one process owns the devices) returns a commit callable that
        poisons the current world — failing in-flight ops that could be
        waiting on the dead chip — and re-lands the same world coordinates
        on a fresh generation; co-resident replicas pick the rebuilt world
        up at their next configure, exactly like the poisoned-world rebuild
        on the ordinary reconfigure path. Distributed mode raises: a
        ``jax.distributed`` world's membership can only change by teardown
        + rejoin (a hard toolchain invariant), so an in-place shrink is the
        one reconfiguration this PG cannot stage — the Manager falls back
        to the classic leave-heal-rejoin path.
        """
        with self._lock:
            world = self._world
            args = self._last_configure
        if world is None or args is None:
            return None  # never configured: nothing is entangled yet
        if world.distributed:
            raise RuntimeError(
                "distributed-mode ProcessGroupXLA cannot shrink intra-group "
                "membership in place: jax.distributed world membership only "
                "changes by teardown + rejoin, so a chip loss inside the "
                "group takes the leave-heal-rejoin path"
            )
        store_addr, replica_rank, replica_world_size, quorum_id = args

        def commit() -> None:
            # poison-and-rebuild: retire fails the stale generation's
            # slots/mailboxes (ops entangled with the dead chip can never
            # complete), and _configure_local sees the poisoned registry
            # entry and builds a fresh world under the same key
            self._retire_current_world()
            w = self._configure_local(
                store_addr, replica_world_size, quorum_id
            )
            self._install_world(w, replica_rank, replica_world_size)

        return commit

    def _retire_current_world(self) -> None:
        with self._lock:
            old, self._world = self._world, None
            self._seq = {}  # fresh op ordering per generation
        if old is not None:
            if old.distributed:
                self._teardown_distributed_world()
            else:
                # Ops pending in the abandoned generation can never complete
                # (this member is leaving); fail them promptly instead of
                # letting co-resident replicas wait out their full timeouts
                # (ProcessGroupHost does the same via old.abort()).
                err = RuntimeError("process group torn down for reconfiguration")
                old.error = old.error or err
                with old.lock:
                    stale_slots = list(old.slots.values())
                    stale_mbs = list(old.mailboxes.values())
                for slot in stale_slots:
                    slot.fail(old.error)
                for mb in stale_mbs:
                    mb.fail(old.error)

    def _install_world(self, world: _XlaWorld, replica_rank, replica_world_size) -> None:
        with self._lock:
            self._world = world
            self._rank = replica_rank
            self._size = replica_world_size
            self._error = None  # errored state clears on reconfigure

    def _configure_local(self, store_addr, world_size, quorum_id) -> _XlaWorld:
        from jax.sharding import Mesh

        base = store_addr.split("/", 1)[0]  # the store's host:port
        key = (store_addr, quorum_id, world_size)
        with _local_worlds_lock:
            world = _local_worlds.get(key)
            if world is not None and world.error is not None:
                # a poisoned generation (aborted/torn down) must not be
                # handed back to a reconfiguring replica — build fresh
                world = None
            if world is None:
                leads = _lead_devices_local(world_size)
                mesh = Mesh(np.array(leads), ("replica",))
                world = _XlaWorld(
                    mesh, leads, world_size, distributed=False, quorum_id=quorum_id
                )
                # prune superseded generations of the same store (exact
                # host:port match — a prefix match would reap an unrelated
                # store like :50001 when pruning :5000)
                for k in [
                    k for k, w in _local_worlds.items()
                    if k[0].split("/", 1)[0] == base and k[1] < quorum_id
                ]:
                    del _local_worlds[k]
                _local_worlds[key] = world
        return world

    def _stage_distributed(self, store_addr, rank, quorum_id) -> str:
        """Control-plane half of a distributed reconfigure — safe on the
        quorum thread. Rank 0 publishes a coordinator address under the
        quorum-scoped KV prefix; everyone else blocks on the get until it
        lands. Pure KV RPCs: no jax state is touched."""
        host_port, _, path = store_addr.partition("/")
        prefix = f"{path or 'pgxla'}/{quorum_id}"
        kv = KvClient(host_port, connect_timeout=self._timeout)

        if rank == 0:
            coord = f"{_my_host()}:{_free_port()}"
            kv.set(f"{prefix}/xla_coordinator", coord, timeout=self._timeout)
        else:
            coord = kv.get(f"{prefix}/xla_coordinator", timeout=self._timeout).decode()
        return coord

    def _configure_distributed(
        self, coord, rank, world_size, quorum_id
    ) -> _XlaWorld:
        """Backend half of a distributed reconfigure: join the per-quorum
        ``jax.distributed`` world at the pre-rendezvoused coordinator and
        build the mesh. Runs at COMMIT time, on the Manager's main thread."""
        import jax
        from jax.sharding import Mesh

        _join_distributed_world(coord, rank, world_size, self._timeout)

        devices = jax.devices()
        if any(
            not any(d.process_index == p for d in devices)
            for p in range(world_size)
        ):
            # The local backend predates the distributed world: a trainer
            # whose main thread touched jax before its FIRST distributed
            # configure (computing grads while the async quorum runs) has
            # a cached single-process backend, so device discovery never
            # saw the world we just joined. Rebuild it — per-quorum
            # teardown does the same clear before every REjoin; arrays
            # created on the old backend stay readable (their buffers own
            # a client reference) and collectives device_put onto the new
            # world's mesh.
            jax.clear_caches()
            try:
                import jax.extend

                jax.extend.backend.clear_backends()
            except Exception as e:  # noqa: BLE001
                logger.warning("clear_backends failed: %s", e)
            with self._lock:
                self._device_world_epoch += 1
            devices = jax.devices()
        leads = []
        for p in range(world_size):
            pd = [d for d in devices if d.process_index == p]
            if not pd:
                raise RuntimeError(f"no devices visible for process {p}")
            leads.append(min(pd, key=lambda d: d.id))
        mesh = Mesh(np.array(leads), ("replica",))
        return _XlaWorld(
            mesh, leads, world_size, distributed=True, quorum_id=quorum_id
        )

    def _teardown_distributed_world(self) -> None:
        """Leave the per-quorum world.

        1. ``clear_backends`` first — the backend holds a reference to the
           runtime client; the client cannot be released while a backend
           could still issue RPCs through it.
        2. Cooperative ``client.shutdown()`` on a bounded daemon thread. On
           a HEALTHY transition (same members re-keyed, grow, graceful
           leave) the shutdown barrier completes in milliseconds, the
           client's heartbeat/error-poll threads stop, and the teardown is
           clean. On a DEGRADED world the barrier cannot complete and its
           failure (or the coordinator's task-death error push, whichever
           lands first) is process-fatal by toolchain design — the short
           ``shutdown_timeout``/heartbeat bounds make that death prompt,
           and the supervising launcher restarting this process into the
           next quorum is the recovery path (see _join_distributed_world's
           docstring and docs/operations.md). Merely dropping the reference
           is NOT an escape hatch: the client's own threads keep it alive
           and polling, and the poll fatals within a heartbeat window
           anyway.
        3. Rank 0 shuts the coordination service down after the barrier.
        """
        import jax
        from jax._src import distributed as _dist

        jax.clear_caches()
        try:
            import jax.extend

            jax.extend.backend.clear_backends()
        except Exception as e:  # noqa: BLE001
            logger.warning("clear_backends failed: %s", e)
        # the abort watchdog runs this teardown on a daemon thread while
        # the main thread may be reading device_world_epoch — a bare += 1
        # here can lose a bump and mask a backend rebuild from the Manager
        with self._lock:
            self._device_world_epoch += 1

        state = _dist.global_state
        client, state.client = state.client, None
        service, state.service = state.service, None
        state.process_id = 0
        state.num_processes = None
        state.coordinator_address = None

        if client is not None:
            t = threading.Thread(
                target=lambda: client.shutdown(),
                daemon=True,
                name="pgxla_client_shutdown",
            )
            t.start()
            t.join(12.0)
        del client
        if service is not None:
            t = threading.Thread(
                target=lambda: service.shutdown(),
                daemon=True,
                name="pgxla_service_shutdown",
            )
            t.start()
            t.join(5.0)

    def abort(self) -> None:
        err = RuntimeError("process group aborted")
        with self._lock:
            world, self._world = self._world, None
            self._error = self._error or err
            q, self._dispatch_q = self._dispatch_q, None
        if q is not None:
            q.put(None)  # stop the dispatch pump after draining queued ops
        if world is None:
            return
        world.error = world.error or err
        with world.lock:
            slots = list(world.slots.values())
            mailboxes = list(world.mailboxes.values())
        for slot in slots:
            slot.fail(world.error)
        for mb in mailboxes:
            mb.fail(world.error)
        if world.distributed:
            # The XLA analog of ncclCommAbort — except jax.distributed's
            # shutdown is graceful and can block behind a peer wedged in a
            # collective. abort() must return promptly (the Manager calls it
            # from timeout watchdogs), so the teardown runs on a daemon
            # thread with a bounded grace join. If the runtime stays wedged,
            # the supervising launcher restarts the process — the same
            # escalation path the reference's Baby-NCCL design exists for.
            t = threading.Thread(
                target=self._teardown_distributed_world,
                daemon=True,
                name="pgxla_abort_teardown",
            )
            t.start()
            t.join(5.0)

    def shutdown(self) -> None:
        self.abort()

    def errored(self) -> Optional[Exception]:
        with self._lock:
            if self._error is not None:
                return self._error
            world = self._world
        return None if world is None else world.error

    def size(self) -> int:
        return self._size

    def rank(self) -> int:
        return self._rank

    # ------------------------------------------------------------ internals
    def _require_world(self) -> _XlaWorld:
        with self._lock:
            world = self._world
        if world is None:
            raise RuntimeError("process group is not configured")
        if world.error is not None:
            raise world.error
        return world

    def _bump_seq(self, kind: str) -> int:
        with self._lock:
            n = self._seq.get(kind, 0)
            self._seq[kind] = n + 1
        return n

    def _deposit_checked(
        self,
        world: _XlaWorld,
        slot: _OpSlot,
        kind: str,
        seq: int,
        rank: int,
        leaves: List[Any],
    ) -> Tuple[Future, bool]:
        """Deposit, then close the register/abort race: abort() fails the
        slots it can see under world.lock, so a slot created (or deposited
        into) after that snapshot would hang its future to the wait timeout.
        world.error is set before the snapshot is taken — if it is not
        visible after our deposit, abort() will see our slot (the re-check
        after enqueue that the reference's ProcessGroupBaby._submit makes)."""
        fut, last = slot.deposit(rank, leaves)
        if world.error is not None:
            slot.fail(world.error)
            world.gc_slot(kind, seq)
            return fut, False
        return fut, last

    def _finish_local(
        self,
        world: _XlaWorld,
        slot: _OpSlot,
        kind: str,
        seq: int,
        compute: Callable[[Dict[int, List[Any]]], Dict[int, Any]],
    ) -> None:
        """Run ``compute`` over the full contribution set (last-arriving
        thread), resolving every rank's future."""
        try:
            slot.resolve(compute(slot.contributions))
        except Exception as e:  # noqa: BLE001
            world.error = world.error or e
            slot.fail(e)
        finally:
            world.gc_slot(kind, seq)

    def _run_reduce(
        self,
        world: _XlaWorld,
        op: ReduceOp,
        shards_by_rank: Dict[int, List[Any]],
        shapes: List[Tuple[int, ...]],
    ) -> List[Any]:
        per_leaf = [
            world.global_array(
                {r: shards_by_rank[r][i] for r in shards_by_rank}, shapes[i]
            )
            for i in range(len(shapes))
        ]
        return world.reduce_fn(op)(per_leaf)

    # ----------------------------------------------------------- collectives
    def allreduce(
        self,
        arrays: Sequence[Any],
        op: ReduceOp = ReduceOp.SUM,
        donate: bool = False,
    ) -> Work:
        # ``donate`` is ignored: the collective's result is a new device
        # array whatever the world's size
        world = self._require_world()
        rank = self._rank
        leaves = [world.place(rank, a) for a in arrays]
        shapes = [tuple(np.shape(a)) for a in arrays]

        if world.distributed:
            return self._distributed_work(
                lambda: [
                    world.result_for(o, rank)
                    for o in self._run_reduce(world, op, {rank: leaves}, shapes)
                ]
            )

        def compute(contribs: Dict[int, List[Any]]) -> Dict[int, Any]:
            outs = self._run_reduce(world, op, contribs, shapes)
            return {
                r: [world.result_for(o, r) for o in outs] for r in contribs
            }

        seq = self._bump_seq("allreduce")
        slot = world.slot("allreduce", seq)
        fut, last = self._deposit_checked(world, slot, "allreduce", seq, rank, leaves)
        if last:
            self._finish_local(world, slot, "allreduce", seq, compute)
        return FutureWork(fut)

    def allgather(self, arrays: Sequence[Any]) -> Work:
        """Resolves to ``[rank0's arrays, rank1's arrays, ...]``."""
        world = self._require_world()
        rank = self._rank
        leaves = [world.place(rank, a) for a in arrays]
        shapes = [tuple(np.shape(a)) for a in arrays]

        def rows_for(outs: List[Any], r: int) -> List[List[Any]]:
            mine = [world.result_for(o, r) for o in outs]  # each (W, *shape)
            return [
                [leaf[src] for leaf in mine] for src in range(world.world_size)
            ]

        if world.distributed:
            def gather() -> Any:
                per_leaf = [
                    world.global_array({rank: leaves[i]}, shapes[i])
                    for i in range(len(shapes))
                ]
                return rows_for(world.replicate_fn()(per_leaf), rank)

            return self._distributed_work(gather)

        def compute(contribs: Dict[int, List[Any]]) -> Dict[int, Any]:
            per_leaf = [
                world.global_array(
                    {r: contribs[r][i] for r in contribs}, shapes[i]
                )
                for i in range(len(shapes))
            ]
            outs = world.replicate_fn()(per_leaf)
            return {r: rows_for(outs, r) for r in contribs}

        seq = self._bump_seq("allgather")
        slot = world.slot("allgather", seq)
        fut, last = self._deposit_checked(world, slot, "allgather", seq, rank, leaves)
        if last:
            self._finish_local(world, slot, "allgather", seq, compute)
        return FutureWork(fut)

    def broadcast(self, arrays: Sequence[Any], root: int = 0) -> Work:
        """Root's arrays land on every rank. Moves only root's payload —
        1x N bytes to each receiver — not the W x N an allgather would."""
        world = self._require_world()
        rank = self._rank

        if world.distributed:
            shapes = [tuple(np.shape(a)) for a in arrays]
            leaves = [world.place(rank, a) for a in arrays]

            def bcast() -> Any:
                import jax
                from jax.sharding import NamedSharding, PartitionSpec as P

                per_leaf = [
                    world.global_array({rank: leaves[i]}, shapes[i])
                    for i in range(len(shapes))
                ]
                # a[root] on a replica-sharded array lowers to moving just
                # root's shard to every device
                key = ("bcast", root)
                if key not in world._jit_cache:
                    world._jit_cache[key] = jax.jit(
                        lambda args: [a[root] for a in args],
                        out_shardings=NamedSharding(world.mesh, P()),
                    )
                outs = world._jit_cache[key](per_leaf)
                return [world.result_for(o, rank) for o in outs]

            return self._distributed_work(bcast)

        # local mode: rendezvous (broadcast is still a collective — every
        # rank joins), then copy root's already-placed leaves out
        import jax

        payload = (
            [world.place(rank, a)[0] for a in arrays] if rank == root else []
        )

        def compute(contribs: Dict[int, List[Any]]) -> Dict[int, Any]:
            src = contribs[root]
            return {
                r: [jax.device_put(a, world.leads[r]) for a in src]
                for r in contribs
            }

        seq = self._bump_seq("broadcast")
        slot = world.slot("broadcast", seq)
        fut, last = self._deposit_checked(world, slot, "broadcast", seq, rank, payload)
        if last:
            self._finish_local(world, slot, "broadcast", seq, compute)
        return FutureWork(fut)

    def reduce_scatter(
        self, input_chunks: Sequence[Sequence[Any]], op: ReduceOp = ReduceOp.SUM
    ) -> Work:
        """``input_chunks[r]``: this rank's contribution destined for rank r;
        resolves to the reduced chunk this rank owns. One batched reduction
        over all destination chunks; XLA fuses them into one program."""
        world = self._require_world()
        rank = self._rank
        n_per_dest = len(input_chunks[0]) if input_chunks else 0
        flat_in = [a for chunk in input_chunks for a in chunk]
        leaves = [world.place(rank, a) for a in flat_in]
        shapes = [tuple(np.shape(a)) for a in flat_in]

        def chunk_of(outs: List[Any], r: int) -> List[Any]:
            mine = [world.result_for(o, r) for o in outs]
            return mine[r * n_per_dest:(r + 1) * n_per_dest]

        if world.distributed:
            return self._distributed_work(
                lambda: chunk_of(
                    self._run_reduce(world, op, {rank: leaves}, shapes), rank
                )
            )

        def compute(contribs: Dict[int, List[Any]]) -> Dict[int, Any]:
            outs = self._run_reduce(world, op, contribs, shapes)
            return {r: chunk_of(outs, r) for r in contribs}

        seq = self._bump_seq("reduce_scatter")
        slot = world.slot("reduce_scatter", seq)
        fut, last = self._deposit_checked(world, slot, "reduce_scatter", seq, rank, leaves)
        if last:
            self._finish_local(world, slot, "reduce_scatter", seq, compute)
        return FutureWork(fut)

    def alltoall(self, input_chunks: Sequence[Any]) -> Work:
        """``input_chunks[r]``: chunk destined for rank r; resolves to
        ``[chunk from rank 0, chunk from rank 1, ...]``."""
        world = self._require_world()
        rank = self._rank

        if world.distributed:
            work = self.allgather(input_chunks)
            fut = work.get_future().then(
                lambda f: [row[rank] for row in f.value()]
            )
            return FutureWork(fut)

        import jax

        leaves = [world.place(rank, a) for a in input_chunks]

        def compute(contribs: Dict[int, List[Any]]) -> Dict[int, Any]:
            # pure permutation: move each (1, *s) shard to its destination
            return {
                r: [
                    jax.device_put(contribs[src][r][0], world.leads[r])
                    for src in sorted(contribs)
                ]
                for r in contribs
            }

        seq = self._bump_seq("alltoall")
        slot = world.slot("alltoall", seq)
        fut, last = self._deposit_checked(world, slot, "alltoall", seq, rank, leaves)
        if last:
            self._finish_local(world, slot, "alltoall", seq, compute)
        return FutureWork(fut)

    # ------------------------------------------------------------------ p2p
    def send(self, arrays: Sequence[Any], dst: int, tag: int = 0) -> Work:
        world = self._require_world()
        if world.distributed:
            raise RuntimeError(
                "ProcessGroupXLA p2p send/recv is local-mode only; pairwise "
                "cross-host transfers belong to the checkpoint transports "
                "(HTTP/PG) or the host plane"
            )
        rank = self._rank
        kind = f"p2p_{rank}_{dst}_{tag}"
        seq = self._bump_seq(kind)
        payload = [world.place(rank, a)[0] for a in arrays]
        if not world.mailbox(kind, seq).put(payload):
            # receiver already timed out / aborted this pairing: free the
            # dict entry (payload was dropped by the closed mailbox)
            world.gc_mailbox(kind, seq)
        return DummyWork(None)

    def recv(self, src: int, tag: int = 0) -> Work:
        world = self._require_world()
        if world.distributed:
            raise RuntimeError(
                "ProcessGroupXLA p2p send/recv is local-mode only; pairwise "
                "cross-host transfers belong to the checkpoint transports "
                "(HTTP/PG) or the host plane"
            )
        rank = self._rank
        kind = f"p2p_{src}_{rank}_{tag}"
        seq = self._bump_seq(kind)
        mb = world.mailbox(kind, seq)
        fut: Future = Future()
        timeout = self._timeout

        def do_recv() -> None:
            import jax

            try:
                payload = mb.get(timeout)
                fut.set_result(
                    [jax.device_put(a, world.leads[rank]) for a in payload]
                )
                # consume-once on success: drop the mailbox and its
                # retained device arrays
                world.gc_mailbox(kind, seq)
            except Exception as e:  # noqa: BLE001
                try:
                    fut.set_exception(e)
                except RuntimeError:
                    pass
                # on timeout/abort, CLOSE but keep the dict entry: a late
                # sender must find the closed mailbox and drop its payload
                # (removing it here would let the sender re-create a fresh
                # orphan that pins device arrays until reconfigure)
                mb.close()

        threading.Thread(target=do_recv, daemon=True, name="pgxla_recv").start()
        return FutureWork(fut)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _my_host() -> str:
    return os.environ.get("TORCHFT_HOST", "127.0.0.1")
