"""End-to-end integration: lighthouse + managers + host PGs + HTTP recovery.

Reference pattern (manager_integ_test.py): replica groups run as threads,
restarts are simulated by catching InjectedFailure and re-entering the train
loop with a fresh Manager; final params are asserted bitwise-equal across
replicas (manager_integ_test.py:184-254, 359-367).
"""

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np
import pytest

from torchft_tpu._test.event_injector import EventInjector, InjectedFailure
from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.manager import Manager
from torchft_tpu.process_group import (
    FakeProcessGroupWrapper,
    ProcessGroupHost,
    ReduceOp,
)

NUM_STEPS = 5
LR = 0.1


@dataclass
class Runner:
    replica_id: int
    lighthouse_addr: str
    injector: EventInjector
    min_replica_size: int = 1
    attempts: int = 3
    use_async_quorum: bool = True
    total_steps: int = NUM_STEPS
    # "http" (default) or "pg" — heal over a dedicated recovery
    # ProcessGroupHost via PGTransport, kept in quorum lockstep by the
    # Manager's transport-configure hook; "pg-inplace"/"http-inplace" add
    # the Manager-derived template so received leaves land in place
    transport: str = "http"
    # fail this replica's transport.configure N times (transient recovery-
    # store fault): recovery must come from the commit-failure quorum bump
    # re-rendezvousing EVERY replica, not a one-sided retry
    transport_configure_fails: int = 0
    # override the HTTP transport's own timeout ("http" mode only). Shrinks
    # the serve-side disallow grace window, which otherwise stalls a source
    # whose expected fetch never completes (e.g. the healer failed over to
    # another peer) right up against the 10s allreduce deadline of the rest
    # of the cohort.
    http_timeout: float = 0.0

    def run(self) -> Dict[str, np.ndarray]:
        for attempt in range(self.attempts):
            try:
                return self._train()
            except InjectedFailure:
                continue
        raise RuntimeError(f"replica {self.replica_id} exhausted attempts")

    def _train(self) -> Dict[str, np.ndarray]:
        # Deterministic per-replica init: replicas start DIFFERENT; init_sync
        # must make them identical via recovery from the primary.
        rng = np.random.RandomState(self.replica_id + 1)
        params = {"w": rng.randn(4).astype(np.float32)}

        def load_state(sd):
            params["w"] = np.array(sd["w"], dtype=np.float32)

        def save_state():
            return {"w": params["w"].copy()}

        pg = FakeProcessGroupWrapper(ProcessGroupHost(timeout=10.0))
        transport = None
        if self.transport == "http" and self.http_timeout > 0:
            from torchft_tpu.checkpointing import HTTPTransport

            transport = HTTPTransport(timeout=self.http_timeout)
        elif self.transport == "http-inplace":
            from torchft_tpu.checkpointing import HTTPTransport

            transport = HTTPTransport(
                timeout=10.0,
                state_dict_template=lambda: manager.state_dict_template(),
            )
        elif self.transport.startswith("pg"):
            from torchft_tpu.checkpointing import PGTransport

            template = None
            if self.transport == "pg-inplace":
                # the Manager's own live composite (late-bound: `manager`
                # is assigned below) — alignment with the sender's tree by
                # construction, even when extra state fns register
                def template():
                    return manager.state_dict_template()

            transport = PGTransport(
                ProcessGroupHost(timeout=10.0),  # dedicated recovery PG
                timeout=10.0,
                state_dict_template=template,
            )
            if self.transport_configure_fails:
                real_configure = transport.configure
                remaining = [self.transport_configure_fails]

                def flaky_configure(*a, **k):
                    if remaining[0] > 0:
                        remaining[0] -= 1
                        raise RuntimeError("injected recovery-store fault")
                    return real_configure(*a, **k)

                transport.configure = flaky_configure
        manager = Manager(
            pg=pg,
            load_state_dict=load_state,
            state_dict=save_state,
            min_replica_size=self.min_replica_size,
            use_async_quorum=self.use_async_quorum,
            replica_id=f"replica_{self.replica_id}",
            lighthouse_addr=self.lighthouse_addr,
            timeout=10.0,
            quorum_timeout=10.0,
            checkpoint_transport=transport,
        )
        try:
            while manager.current_step() < self.total_steps:
                # the replica's own serving transport rides along so
                # network-shaped events (kill/corrupt the heal source) can
                # arm serve-side faults on it
                self.injector.check(
                    self.replica_id, manager.current_step(), pg,
                    transport=manager._checkpoint_transport,
                )
                manager.start_quorum()
                # toy "gradient": depends on params so divergence would show
                grads = {"w": (params["w"] * 0.1 + 1.0).astype(np.float32)}
                reduced = manager.allreduce(grads).get_future().wait(timeout=30)
                if manager.should_commit():
                    params["w"] = (params["w"] - LR * reduced["w"]).astype(np.float32)
            return {"w": params["w"].copy(), "steps": manager.current_step(),
                    "batches": manager.batches_committed(),
                    "timings": manager.timings(), "metrics": manager.metrics()}
        finally:
            manager.shutdown(wait=False)
            if transport is not None and hasattr(transport, "_pg"):
                transport._pg.shutdown()  # the recovery PG is caller-owned


def run_replicas(runners: List[Runner]):
    with ThreadPoolExecutor(max_workers=len(runners)) as ex:
        futs = [ex.submit(r.run) for r in runners]
        return [f.result(timeout=120) for f in futs]


@pytest.fixture()
def lighthouse():
    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20, heartbeat_timeout_ms=800,
    )
    yield lh
    lh.shutdown()


def assert_params_equal(results):
    for other in results[1:]:
        np.testing.assert_array_equal(results[0]["w"], other["w"])


class TestHealthyTraining:
    def test_two_replicas_bitwise_equal(self, lighthouse):
        injector = EventInjector()
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [Runner(i, addr, injector, min_replica_size=2) for i in range(2)]
        )
        # init_sync made both replicas start from the primary's params
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)
        assert all(r["batches"] == 2 * NUM_STEPS for r in results)

    def test_sync_quorum_mode(self, lighthouse):
        injector = EventInjector()
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [
                Runner(i, addr, injector, min_replica_size=2, use_async_quorum=False)
                for i in range(2)
            ]
        )
        assert_params_equal(results)


class TestRecovery:
    def test_replica_crash_and_rejoin(self, lighthouse):
        injector = EventInjector().fail_at(replica=1, step=2)
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [Runner(i, addr, injector, min_replica_size=1) for i in range(2)]
        )
        assert injector.count == 1
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)

    def test_crash_and_rejoin_heals_over_http_inplace(self, lighthouse, caplog):
        """The DEFAULT transport with the Manager-derived template: the
        heal streams off the socket into the template's buffers. Zero
        degraded-path records from the transport is the in-place evidence
        — a template misalignment or absorb failure would log per-receive
        fallbacks and this test would still converge but fail here."""
        injector = EventInjector().fail_at(replica=1, step=2)
        addr = f"127.0.0.1:{lighthouse.port}"
        with caplog.at_level(
            "WARNING", logger="torchft_tpu.checkpointing.http_transport"
        ):
            results = run_replicas(
                [Runner(i, addr, injector, min_replica_size=1,
                        transport="http-inplace")
                 for i in range(2)]
            )
        assert injector.count == 1
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)
        degraded = [r for r in caplog.records
                    if r.name == "torchft_tpu.checkpointing.http_transport"]
        assert not degraded, [r.message for r in degraded]

    def test_allreduce_failure_discards_step(self, lighthouse):
        injector = EventInjector().fail_allreduce_at(replica=0, step=1)
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [Runner(i, addr, injector, min_replica_size=1) for i in range(2)]
        )
        assert injector.count == 1
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)

    def test_multiple_failures(self, lighthouse):
        injector = (
            EventInjector().fail_at(replica=0, step=1).fail_at(replica=1, step=3)
        )
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [Runner(i, addr, injector, min_replica_size=1, attempts=4) for i in range(2)]
        )
        assert injector.count == 2
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)


class TestResilientHeal:
    """ISSUE 4 acceptance: multi-peer heal failover, integrity-checked
    chunks, and bounded-retry control-plane RPCs — end to end through real
    Managers, lighthouse, and HTTP transports.

    Source assignment is deterministic: participants sort by replica_id, so
    with replica 2 recovering and group_rank 0 the assigned source is
    replica 0 and the fallback peer replica 1 (native quorum.cc round-robin).
    """

    def test_source_death_mid_heal_fails_over_and_commits(
        self, lighthouse, monkeypatch
    ):
        """Replica 2 crashes and rejoins; its assigned heal source (replica
        0) drops every serve of chunk 0. The heal must exhaust the
        same-source budget, fail over to replica 1's standby snapshot,
        commit that same step, and converge bitwise."""
        monkeypatch.setenv("TORCHFT_RETRY_MAX_ATTEMPTS", "2")
        monkeypatch.setenv("TORCHFT_RETRY_BASE_S", "0.01")
        injector = (
            EventInjector()
            .fail_at(replica=2, step=2)
            .kill_heal_source_at(replica=0, step=2, chunk=0, times=-1)
        )
        addr = f"127.0.0.1:{lighthouse.port}"
        # min_replica_size=3 keeps the survivors blocked in quorum while
        # replica 2 restarts, so the rejoin is guaranteed to go through a
        # heal rather than the survivors finishing and shutting down first
        results = run_replicas(
            [Runner(i, addr, injector, min_replica_size=3, http_timeout=3.0)
             for i in range(3)]
        )
        assert injector.count == 2  # the crash + the armed source kill
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)
        healed = results[2]
        assert healed["timings"]["heal_failovers"] >= 1
        assert healed["timings"]["heal_attempts"] >= 1
        assert healed["metrics"]["heals"] >= 1
        assert healed["metrics"]["errors"] == 0  # degraded, never errored

    def test_corrupt_chunk_refetched_never_loaded(self, lighthouse):
        """Replica 2's heal source serves one corrupted chunk (canonical
        crc trailer): the receiver must detect the mismatch, re-fetch, and
        converge bitwise — corrupt bytes are never loaded."""
        injector = (
            EventInjector()
            .fail_at(replica=2, step=2)
            .corrupt_heal_chunk_at(replica=0, step=2, chunk=0, times=1)
        )
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [Runner(i, addr, injector, min_replica_size=3) for i in range(3)]
        )
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)
        healed = results[2]
        assert healed["timings"]["chunk_crc_failures"] >= 1
        assert healed["metrics"]["errors"] == 0

    def test_control_plane_blip_degrades_to_slower_step(self, lighthouse):
        """A one-shot should_commit RPC flake (shorter than the quorum
        timeout) must yield a successful, merely slower step: rpc_retries
        > 0 somewhere, zero errors, full convergence."""
        injector = EventInjector().flake_rpc(
            "should_commit", times=1, delay_s=0.05
        )
        addr = f"127.0.0.1:{lighthouse.port}"
        try:
            results = run_replicas(
                [Runner(i, addr, injector, min_replica_size=2) for i in range(2)]
            )
        finally:
            injector.clear_rpc_faults()
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)
        assert sum(r["timings"]["rpc_retries"] for r in results) >= 1
        assert all(r["metrics"]["errors"] == 0 for r in results)

    def test_quorum_rpc_flake_retries_cleanly(self, lighthouse):
        """Same, for the quorum RPC itself — the blip lands inside the
        overlapped quorum window and the step completes."""
        injector = EventInjector().flake_rpc("quorum", times=1)
        addr = f"127.0.0.1:{lighthouse.port}"
        try:
            results = run_replicas(
                [Runner(i, addr, injector, min_replica_size=2) for i in range(2)]
            )
        finally:
            injector.clear_rpc_faults()
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)
        assert sum(r["timings"]["rpc_retries"] for r in results) >= 1
        assert all(r["metrics"]["errors"] == 0 for r in results)


class TestPGTransportHealing:
    """Healing over PGTransport with a dedicated recovery PG (the
    reference's train_ddp.py default transport) — the Manager's per-quorum
    transport-configure hook keeps the recovery PG's world in lockstep."""

    def test_init_sync_heals_over_pg_transport(self, lighthouse):
        injector = EventInjector()
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [Runner(i, addr, injector, min_replica_size=2, transport="pg")
             for i in range(2)]
        )
        # replicas start with DIFFERENT params; init_sync must have healed
        # over the PG transport to make them bitwise equal
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)

    def test_crash_and_rejoin_heals_in_place(self, lighthouse):
        injector = EventInjector().fail_at(replica=1, step=2)
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [Runner(i, addr, injector, min_replica_size=1,
                    transport="pg-inplace")
             for i in range(2)]
        )
        assert injector.count == 1
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)

    def test_transient_configure_fault_recovers_via_quorum_bump(
        self, lighthouse
    ):
        """One replica's transport.configure fails transiently: the step's
        commit vote fails, the next quorum request carries
        commit_failures>0, the lighthouse bumps quorum_id, and EVERY
        replica re-rendezvouses under the new id (a one-sided same-id
        retry would block on the collective mesh rendezvous forever)."""
        injector = EventInjector()
        addr = f"127.0.0.1:{lighthouse.port}"
        results = run_replicas(
            [Runner(0, addr, injector, min_replica_size=1, transport="pg",
                    transport_configure_fails=1),
             Runner(1, addr, injector, min_replica_size=1, transport="pg")]
        )
        assert_params_equal(results)
        assert all(r["steps"] == NUM_STEPS for r in results)


class TestMultiRankGroups:
    """Replica groups with group_world_size > 1 (reference scenario:
    manager_integ_test multi-rank groups): the group leader's ManagerServer
    barriers all group ranks per quorum, each group-rank stratum forms its
    own cross-group PG world (store prefix includes group_rank), and the
    2-phase commit ANDs every rank's vote."""

    def test_two_groups_times_two_ranks(self):
        lighthouse = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=2, join_timeout_ms=5000,
            quorum_tick_ms=20, heartbeat_timeout_ms=2000,
        )
        addr = f"127.0.0.1:{lighthouse.port}"
        GROUPS, RANKS, STEPS_N = 2, 2, 3
        store_ready = {g: threading.Event() for g in range(GROUPS)}
        store_addrs: Dict[int, str] = {}

        def worker(group: int, rank: int):
            params = {"w": np.full(4, float(group + 1), np.float32)}

            def load_state(sd):
                params["w"] = np.asarray(sd["w"], np.float32)

            kwargs = dict(
                pg=ProcessGroupHost(timeout=10.0),
                load_state_dict=load_state,
                state_dict=lambda: {"w": params["w"].copy()},
                min_replica_size=2,
                use_async_quorum=False,
                replica_id=f"mrg_{group}",
                timeout=10.0,
                quorum_timeout=10.0,
                group_rank=rank,
                group_world_size=RANKS,
            )
            if rank == 0:
                manager = Manager(lighthouse_addr=addr, **kwargs)
                store_addrs[group] = manager.store_addr
                store_ready[group].set()
            else:
                assert store_ready[group].wait(20)
                manager = Manager(
                    lighthouse_addr=addr,
                    store_addr=store_addrs[group], **kwargs,
                )
            try:
                for _ in range(STEPS_N):
                    manager.start_quorum()
                    grads = {"w": (params["w"] * 0.1).astype(np.float32)}
                    reduced = (
                        manager.allreduce(grads).get_future().wait(timeout=30)
                    )
                    if manager.should_commit():
                        params["w"] = (params["w"] - reduced["w"]).astype(
                            np.float32
                        )
                return params["w"].copy(), manager.current_step()
            finally:
                manager.shutdown(wait=False)

        with ThreadPoolExecutor(max_workers=GROUPS * RANKS) as ex:
            futs = {
                (g, r): ex.submit(worker, g, r)
                for g in range(GROUPS)
                for r in range(RANKS)
            }
            results = {k: f.result(timeout=120) for k, f in futs.items()}
        lighthouse.shutdown()

        # The FT contract for multi-rank groups is per-rank-stratum
        # cross-GROUP consistency: rank r of every group holds identical
        # state. Strata may legitimately differ from each other — under
        # init_sync the primary is spread per group rank (reference
        # manager.rs:532-546), so stratum r adopts the state of
        # max_participants[r % n]. With intra-group sharding (FSDP) that
        # composes into one consistent model; with replicated params (this
        # test) each stratum tracks its own primary's trajectory.
        for r in range(RANKS):
            np.testing.assert_array_equal(
                results[(0, r)][0], results[(1, r)][0]
            )
        assert all(v[1] == STEPS_N for v in results.values())


class TestDevicePlaneShardedHeal:
    """The flagship TPU heal path end to end: device-plane Managers
    (ProcessGroupXLA, local mode), each replica group owning a 2-device
    in-group mesh with NamedSharding'd params, one replica crashing and
    rejoining — its heal rides PGTransport with an in-place template, so
    recovered leaves land directly on the rejoiner's shardings (a pure
    data swap for compiled programs; SURVEY hard-part #4)."""

    def test_crash_rejoin_heals_onto_sharding(self, cpu_devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from torchft_tpu.checkpointing import PGTransport
        from torchft_tpu.process_group_xla import ProcessGroupXLA

        lighthouse = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=2000,
            quorum_tick_ms=20, heartbeat_timeout_ms=800,
        )
        addr = f"127.0.0.1:{lighthouse.port}"
        kill_once = threading.Event()
        healed_sharding: Dict[int, object] = {}
        # shardings AS DELIVERED by the transport, recorded BEFORE any
        # repair: the property under test is that in-place receive lands
        # leaves on the rejoiner's sharding — a load_state that silently
        # device_puts would make the final assertion vacuous
        delivered: Dict[int, list] = {0: [], 1: []}

        def replica(rid: int):
            mesh = Mesh(
                np.array(cpu_devices[2 * rid: 2 * rid + 2]), ("fsdp",)
            )
            shard = NamedSharding(mesh, P("fsdp"))
            for attempt in range(3):
                # per-replica DIFFERENT init: init_sync must heal from the
                # primary for final equality to hold
                w0 = jnp.full((16,), float(rid + 1), jnp.float32)
                state = {"w": jax.device_put(w0, shard)}

                def load_state(sd, state=state, shard=shard, rid=rid):
                    w = sd["w"]
                    ok = isinstance(w, jax.Array) and w.sharding == shard
                    delivered[rid].append(ok)
                    if not ok:
                        w = jax.device_put(jnp.asarray(np.asarray(w)), shard)
                    state["w"] = w

                def template():
                    # the Manager's live composite (late-bound `manager`)
                    return manager.state_dict_template()

                recovery_pg = ProcessGroupHost(timeout=10.0)
                transport = PGTransport(
                    recovery_pg, timeout=10.0, state_dict_template=template
                )
                manager = Manager(
                    pg=ProcessGroupXLA(timeout=10.0, mode="local"),
                    load_state_dict=load_state,
                    state_dict=lambda state=state: {"w": state["w"]},
                    min_replica_size=1,
                    use_async_quorum=False,
                    replica_id=f"sharded_heal_{rid}",
                    lighthouse_addr=addr,
                    timeout=10.0,
                    quorum_timeout=10.0,
                    checkpoint_transport=transport,
                )
                died = False
                try:
                    while manager.current_step() < NUM_STEPS:
                        manager.start_quorum()
                        if (
                            rid == 1
                            and manager.current_step() >= 2
                            and not kill_once.is_set()
                        ):
                            kill_once.set()
                            raise InjectedFailure("die")
                        grads = {
                            "g": jnp.full((4,), 0.1 * (rid + 1), jnp.float32)
                        }
                        avg = manager.allreduce(grads).get_future().wait(30)
                        if manager.should_commit():
                            # post-vote read: the heal lands during the vote
                            w = state["w"]
                            state["w"] = w - float(jnp.sum(avg["g"])) * 0.01 * (
                                jnp.ones((16,), jnp.float32)
                            )
                            state["w"] = jax.device_put(state["w"], shard)
                        if manager.last_quorum_healed():
                            healed_sharding[rid] = state["w"].sharding
                    return np.asarray(state["w"]), manager.current_step()
                except InjectedFailure:
                    died = True
                finally:
                    manager.shutdown(wait=False)
                    recovery_pg.shutdown()
                assert died
                # AFTER teardown (heartbeats stopped, sockets closed): give
                # the survivor's next quorum a beat to observe the death
                time.sleep(0.3)
            raise RuntimeError("replica exhausted attempts")

        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [ex.submit(replica, r) for r in range(2)]
            results = [f.result(timeout=180) for f in futs]
        lighthouse.shutdown()

        # both replicas converge bitwise despite different inits + a crash
        np.testing.assert_array_equal(results[0][0], results[1][0])
        assert all(r[1] == NUM_STEPS for r in results)
        # the rejoiner healed, and its healed state sits on ITS OWN mesh
        assert 1 in healed_sharding
        assert "fsdp" in str(healed_sharding[1])
        # the transport DELIVERED every healed leaf already on the
        # rejoiner's sharding (recorded pre-repair): in-place receive is
        # doing the placement, not load_state's fallback
        assert delivered[1] and all(delivered[1]), delivered
