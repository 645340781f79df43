"""Manager unit tests with a mocked ManagerClient.

Mirrors the reference manager_test.py (happy path, healing sync/async,
not-enough-participants, allreduce errors, pg.errored propagation,
fixed-with-spares, quorum failure, max_retries).
"""

import time
from unittest.mock import MagicMock, patch

import numpy as np
import pytest

from torchft_tpu.coordination import QuorumResult
from torchft_tpu.manager import Manager, WorldSizeMode
from torchft_tpu.process_group import ProcessGroupDummy, ReduceOp
from torchft_tpu.work import Future


def make_quorum(
    quorum_id=1,
    replica_rank=0,
    replica_world_size=2,
    heal=False,
    max_step=0,
    max_replica_rank=0,
    max_world_size=2,
    recover_src_replica_rank=None,
    recover_dst_replica_ranks=(),
):
    return QuorumResult(
        quorum_id=quorum_id,
        replica_rank=replica_rank,
        replica_world_size=replica_world_size,
        recover_src_manager_address="mock://recover",
        recover_src_replica_rank=recover_src_replica_rank,
        recover_dst_replica_ranks=list(recover_dst_replica_ranks),
        store_address="mockstore:1",
        max_step=max_step,
        max_replica_rank=max_replica_rank,
        max_world_size=max_world_size,
        heal=heal,
        replica_ids=["a", "b"],
    )


def make_manager(pg=None, quorum=None, use_async_quorum=True, **kwargs):
    """Build a Manager with all remote endpoints mocked out."""
    pg = pg or ProcessGroupDummy()
    transport = MagicMock()
    transport.metadata.return_value = "mock://ckpt"
    # default to the single-source heal path: a bare MagicMock attribute is
    # truthy, which would silently reroute recv_checkpoint mocks through
    # recv_checkpoint_multi. Multi-source tests flip this explicitly.
    transport.supports_multi_source = False
    with (
        patch("torchft_tpu.manager.ManagerServer") as server,
        patch("torchft_tpu.manager.KvStoreServer") as store,
        patch("torchft_tpu.manager.KvClient") as kv,
        patch("torchft_tpu.manager.ManagerClient") as client_cls,
    ):
        server.return_value.address.return_value = "mock:1234"
        store.return_value.port = 1
        client = client_cls.return_value
        if quorum is not None:
            client._quorum.return_value = quorum
        client.should_commit.side_effect = lambda rank, step, ok, timeout: ok
        m = Manager(
            pg=pg,
            load_state_dict=kwargs.pop("load_state_dict", MagicMock()),
            state_dict=kwargs.pop("state_dict", lambda: {"w": np.ones(2)}),
            min_replica_size=kwargs.pop("min_replica_size", 2),
            use_async_quorum=use_async_quorum,
            replica_id="test",
            lighthouse_addr="mock:1",
            checkpoint_transport=transport,
            timeout=kwargs.pop("timeout", 5.0),
            **kwargs,
        )
        m._test_client = client
        m._test_transport = transport
        return m


class TestQuorumHappyPath:
    def test_quorum_and_commit(self):
        m = make_manager(quorum=make_quorum())
        m.start_quorum()
        m.wait_quorum()
        assert m.num_participants() == 2
        assert m.is_participating()
        assert m.participating_rank() == 0
        assert m.should_commit()
        assert m.current_step() == 1
        assert m.batches_committed() == 2

    def test_allreduce_avg(self):
        m = make_manager(quorum=make_quorum())
        m.start_quorum()
        grads = {"w": np.full((3,), 4.0, dtype=np.float32)}
        out = m.allreduce(grads).get_future().wait(timeout=10)
        # dummy PG world 1: sum == input, then divided by num_participants=2
        np.testing.assert_allclose(out["w"], 2.0)

    def test_allreduce_chain_race_many_iterations(self):
        """Host-plane staging resolves on a background thread; the chain
        must always deliver the rebuilt pytree, never the raw leaf list
        (regression: the staging closure captured a rebound variable, so
        when the instant-resolving PG won the race the caller got the
        pre-normalize list)."""
        m = make_manager(quorum=make_quorum())
        m.start_quorum()
        grads = {"w": np.full((3,), 4.0, dtype=np.float32)}
        for _ in range(200):
            out = m.allreduce(grads).get_future().wait(timeout=10)
            assert isinstance(out, dict), f"raw leaves leaked: {type(out)}"
            np.testing.assert_allclose(out["w"], 2.0)

    def test_shutdown_fails_queued_staging_promptly(self):
        """shutdown(wait=False) must fail the staged future of a queued
        (never-dispatched) host-plane allreduce immediately — not leave its
        waiter to ride out the full timeout (regression)."""
        import threading
        import time as _time

        from torchft_tpu.process_group import ProcessGroup

        release = threading.Event()

        class SlowPG(ProcessGroup):
            def configure(self, *a, **k):
                pass

            def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
                release.wait(5)  # occupy the staging worker
                from torchft_tpu.work import DummyWork

                return DummyWork(list(arrays))

            def errored(self):
                return None

            def abort(self):
                pass

            def shutdown(self):
                release.set()

            def size(self):
                return 1

            def rank(self):
                return 0

            def allgather(self, arrays):  # pragma: no cover - unused
                raise NotImplementedError

            broadcast = reduce_scatter = alltoall = send = recv = allgather

        m = make_manager(pg=SlowPG(), quorum=make_quorum(), timeout=30.0)
        m.start_quorum()
        first = m.allreduce({"w": np.ones(2, np.float32)})
        second = m.allreduce({"w": np.ones(2, np.float32)})  # queued
        t0 = _time.monotonic()
        m.shutdown(wait=False)
        # swallow-to-default semantics: the failed dispatch resolves to the
        # zeros default well before the 30s manager timeout
        out = second.get_future().wait(timeout=10)
        assert _time.monotonic() - t0 < 8.0
        np.testing.assert_allclose(out["w"], 0.0)
        first.get_future().wait(timeout=10)

    def test_wire_phase_bounded_when_pg_never_resolves(self):
        """The stage deadline must cover the WIRE phase, not just dispatch:
        a PG whose allreduce dispatches fine but whose future never resolves
        (hung peer whose abort path also failed) must fail the staged op at
        ~manager timeout and swallow to zeros — not block the train loop
        until the caller's wait() expires (regression: the old watchdog was
        a `with` around the dispatching frame, disarmed the moment the op
        was queued on the PG worker)."""
        import time as _time

        from torchft_tpu.process_group import ProcessGroup
        from torchft_tpu.work import Future, FutureWork

        class HungWirePG(ProcessGroup):
            def configure(self, *a, **k):
                pass

            def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
                return FutureWork(Future())  # dispatches, never resolves

            def errored(self):
                return None

            def abort(self):
                pass

            def shutdown(self):
                pass

            def size(self):
                return 1

            def rank(self):
                return 0

            def allgather(self, arrays):  # pragma: no cover - unused
                raise NotImplementedError

            broadcast = reduce_scatter = alltoall = send = recv = allgather

        m = make_manager(pg=HungWirePG(), quorum=make_quorum(), timeout=2.0)
        m.start_quorum()
        t0 = _time.monotonic()
        out = m.allreduce({"w": np.ones(2, np.float32)}).get_future().wait(
            timeout=30
        )
        elapsed = _time.monotonic() - t0
        assert elapsed < 10.0, f"wire phase unbounded: took {elapsed:.1f}s"
        np.testing.assert_allclose(out["w"], 0.0)  # swallowed to zeros
        assert m.errored() is not None
        m.shutdown(wait=False)

    def test_backstop_bounds_op_queued_behind_wedged_stage(self):
        """An op queued behind a stage() that wedges FOREVER (D2H against a
        hung device) never gets its stage-start deadline armed — the
        submission-time 2x backstop must bound it anyway (regression: with
        only the stage-start watchdog, op N+1's future never resolved)."""
        import threading
        import time as _time

        from torchft_tpu.process_group import ProcessGroup
        from torchft_tpu.work import DummyWork

        unstick = threading.Event()

        class WedgedPG(ProcessGroup):
            def configure(self, *a, **k):
                pass

            def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
                unstick.wait(60)  # wedge the single staging worker
                return DummyWork(list(arrays))

            def errored(self):
                return None

            def abort(self):
                pass

            def shutdown(self):
                unstick.set()

            def size(self):
                return 1

            def rank(self):
                return 0

            def allgather(self, arrays):  # pragma: no cover - unused
                raise NotImplementedError

            broadcast = reduce_scatter = alltoall = send = recv = allgather

        m = make_manager(pg=WedgedPG(), quorum=make_quorum(), timeout=1.0)
        m.start_quorum()
        first = m.allreduce({"w": np.ones(2, np.float32)})  # wedges stage()
        second = m.allreduce({"w": np.ones(2, np.float32)})  # queued forever
        t0 = _time.monotonic()
        out = second.get_future().wait(timeout=30)
        elapsed = _time.monotonic() - t0
        assert elapsed < 8.0, f"queued op unbounded: took {elapsed:.1f}s"
        np.testing.assert_allclose(out["w"], 0.0)
        first.get_future().wait(timeout=30)
        unstick.set()
        m.shutdown(wait=False)

    def test_host_staging_survives_buffer_donation(self):
        """The staging thread reads the gradients after allreduce() returns;
        a caller donating its buffers in the next jitted step must not turn
        the contribution into an error/zeros (regression: staging captured
        the caller's buffers instead of private copies)."""
        import threading
        import jax
        import jax.numpy as jnp

        from torchft_tpu.process_group import ProcessGroup
        from torchft_tpu.work import DummyWork

        gate = threading.Event()

        class GatedPG(ProcessGroup):
            def configure(self, *a, **k):
                pass

            def allreduce(self, arrays, op=ReduceOp.SUM, donate=False):
                gate.wait(5)  # hold the op until the caller donated
                return DummyWork([np.asarray(a) for a in arrays])

            def errored(self):
                return None

            def abort(self):
                pass

            def shutdown(self):
                gate.set()

            def size(self):
                return 1

            def rank(self):
                return 0

            def allgather(self, arrays):  # pragma: no cover - unused
                raise NotImplementedError

            broadcast = reduce_scatter = alltoall = send = recv = allgather

        m = make_manager(pg=GatedPG(), quorum=make_quorum())
        m.start_quorum()
        grads = {"w": jnp.full((4,), 4.0, jnp.float32)}
        work = m.allreduce(grads)
        # donate the gradient buffers before the wire runs
        jax.jit(lambda p: jax.tree_util.tree_map(lambda x: x * 0, p),
                donate_argnums=(0,))(grads)
        gate.set()
        out = work.get_future().wait(timeout=10)
        assert m.errored() is None
        np.testing.assert_allclose(np.asarray(out["w"]), 2.0)  # 4 / 2

    def test_metrics_counters(self):
        m = make_manager(quorum=make_quorum())
        assert m.metrics() == {
            "quorums": 0, "reconfigures": 0, "heals": 0, "commits": 0,
            "commit_failures": 0, "allreduces": 0, "errors": 0,
        }
        m.start_quorum()
        m.allreduce({"w": np.ones(2, np.float32)}).get_future().wait(10)
        assert m.should_commit()
        got = m.metrics()
        assert got["quorums"] == 1
        assert got["reconfigures"] == 1  # quorum_id -1 -> 1
        assert got["allreduces"] == 1
        assert got["commits"] == 1
        assert got["commit_failures"] == 0 and got["errors"] == 0
        m.start_quorum()  # clears the per-step error state first
        m.report_error(RuntimeError("boom"))
        assert not m.should_commit()  # errored step is discarded
        got = m.metrics()
        assert got["errors"] == 1
        assert got["commit_failures"] == 1
        assert got["commits"] == 1  # unchanged

    def test_timeouts_forwarded_to_rpcs(self):
        """Reference test_quorum_happy_timeouts: the quorum RPC carries
        quorum_timeout, the commit vote carries the op timeout — the
        server-side deadline propagation contract."""
        m = make_manager(quorum=make_quorum(), timeout=7.0, quorum_timeout=13.0)
        m.start_quorum()
        m.wait_quorum()
        assert m._test_client._quorum.call_args.kwargs["timeout"] == 13.0
        assert m.should_commit()
        assert m._test_client.should_commit.call_args.kwargs["timeout"] == 7.0

    def test_quorum_no_healing_skips_recovery_but_counts(self):
        """Reference test_quorum_no_healing: with allow_heal=False a
        behind-the-cohort replica does NOT fetch a checkpoint, is not
        participating, but the step still commits and counts the
        participating cohort's batches."""
        m = make_manager(
            quorum=make_quorum(
                heal=True, max_step=1, max_replica_rank=None,
                recover_src_replica_rank=1,
            ),
        )
        m.start_quorum(allow_heal=False)
        out = m.allreduce({"x": np.ones(2, np.float32)}).get_future().wait(10)
        np.testing.assert_allclose(out["x"], 0.0)  # zeros: not participating
        assert not m.is_participating()
        assert m.num_participants() == 2
        assert m.should_commit()
        assert m.current_step() == 1
        assert m.batches_committed() == 2
        # no checkpoint was fetched despite quorum.heal
        assert not m._test_transport.recv_checkpoint.called

    def test_allreduce_numerics_dtypes_and_ops(self):
        """Reference manager_test.py test_manager_numerics: AVG normalizes
        by num_participants for floating dtypes (incl. half/bfloat16);
        SUM/MAX/MIN/PRODUCT pass through unnormalized; integer dtypes work
        for the unnormalized ops; dtype survives the round trip."""
        import jax.numpy as jnp

        m = make_manager(quorum=make_quorum())  # num_participants == 2
        m.start_quorum()
        dtypes = [np.float16, jnp.bfloat16, np.float32, np.int64]
        for dtype in dtypes:
            orig = np.asarray([10], dtype=dtype)
            if np.issubdtype(np.dtype(dtype), np.floating) or dtype is jnp.bfloat16:
                out = m.allreduce({"x": orig}).get_future().wait(10)
                got = np.asarray(out["x"])
                assert got.dtype == np.dtype(dtype), (dtype, got.dtype)
                np.testing.assert_allclose(
                    got.astype(np.float32), [5.0]
                )  # dummy PG world 1: sum == input, then / 2 participants
            for op in (ReduceOp.SUM, ReduceOp.MAX, ReduceOp.MIN,
                       ReduceOp.PRODUCT):
                out = (
                    m.allreduce({"x": orig}, reduce_op=op)
                    .get_future()
                    .wait(10)
                )
                got = np.asarray(out["x"])
                assert got.dtype == np.dtype(dtype), (op, dtype, got.dtype)
                np.testing.assert_allclose(
                    got.astype(np.float32), [10.0], err_msg=str((op, dtype))
                )

    def test_allreduce_sum_no_normalize(self):
        m = make_manager(quorum=make_quorum())
        m.start_quorum()
        out = (
            m.allreduce({"w": np.ones(2)}, reduce_op=ReduceOp.SUM)
            .get_future()
            .wait(timeout=10)
        )
        np.testing.assert_allclose(out["w"], 1.0)

    def test_pg_configured_once_per_quorum_id(self):
        pg = ProcessGroupDummy()
        m = make_manager(pg=pg, quorum=make_quorum(quorum_id=5))
        m.start_quorum()
        m.wait_quorum()
        assert pg.configure_count == 1
        m.start_quorum()
        m.wait_quorum()
        assert pg.configure_count == 1  # same quorum id -> no reconfigure
        m._test_client._quorum.return_value = make_quorum(quorum_id=6)
        m.start_quorum()
        m.wait_quorum()
        assert pg.configure_count == 2

    def test_transport_configured_with_pg_per_quorum(self):
        m = make_manager(quorum=make_quorum(quorum_id=5))
        m.start_quorum()
        m.wait_quorum()
        assert m._test_transport.configure.call_count == 1
        addr = m._test_transport.configure.call_args[0][0]
        assert "/recovery/" in addr  # distinct namespace from the main PG
        m.start_quorum()
        m.wait_quorum()
        assert m._test_transport.configure.call_count == 1  # same quorum id

    def test_failed_transport_configure_retries_next_quorum(self):
        m = make_manager(quorum=make_quorum(quorum_id=5))
        m._test_transport.configure.side_effect = [
            RuntimeError("recovery store down"), None
        ]
        m.start_quorum()
        m.wait_quorum()
        assert m.errored() is not None
        # same quorum id again: the failed reconfigure must be retried, not
        # skipped — otherwise every later heal runs on an unconfigured
        # recovery PG
        m.start_quorum()
        m.wait_quorum()
        assert m._test_transport.configure.call_count == 2
        assert m.current_quorum_id() == 5


class TestHealing:
    def test_async_heal_is_nonparticipating(self):
        q = make_quorum(
            heal=True,
            max_step=3,
            max_replica_rank=None,
            max_world_size=1,
            recover_src_replica_rank=1,
        )
        m = make_manager(quorum=q, min_replica_size=1)
        m._test_transport.recv_checkpoint.return_value = {
            "user": {"default": {"w": np.zeros(2)}},
            "torchft": {"step": 3, "batches_committed": 6},
        }
        with patch("torchft_tpu.manager.ManagerClient") as mc:
            mc.return_value._checkpoint_metadata.return_value = "mock://peer"
            m.start_quorum()
            m.wait_quorum()
        assert m._healing
        assert not m.is_participating()
        assert m.num_participants() == 1
        # healing replica contributes zeros
        out = m.allreduce({"w": np.full(2, 8.0, dtype=np.float32)}).get_future().wait(10)
        np.testing.assert_allclose(out["w"], 0.0)
        # commit applies the pending state dict and restores step
        assert m.should_commit()
        assert m.current_step() == 4  # healed to 3, +1 on commit

    def test_heal_pieces_are_spans_under_heal_recv_and_summed_timings(self):
        """What the HTTP transport times of a receive (one chunk off the
        socket, one leaf placed) reaches the ring as children of heal_recv
        and timings() as sums over its fetch threads; the apply on the main
        thread is heal/heal_apply."""
        q = make_quorum(heal=True, max_step=3, max_replica_rank=None,
                        max_world_size=1, recover_src_replica_rank=1)
        m = make_manager(quorum=q, min_replica_size=1)
        m._test_transport.supports_multi_source = True
        m._test_transport.last_recv_timings.return_value = None

        def recv(sources, step, timeout, on_event):
            now = time.perf_counter()
            on_event("heal_fetch", chunk=0, bytes=100, t0_pc=now - 0.5, t1_pc=now - 0.1)
            on_event("heal_fetch", chunk=1, bytes=60, t0_pc=now - 0.5, t1_pc=now - 0.2)
            on_event("heal_place", leaf=0, bytes=160, t0_pc=now - 0.1, t1_pc=now - 0.05)
            on_event("heal_retry", chunk=1, source="x", attempt=1)   # the old kinds still count
            return {"user": {"default": {"w": np.zeros(2)}},
                    "torchft": {"step": 3, "batches_committed": 6}}

        m._test_transport.recv_checkpoint_multi.side_effect = recv
        with patch("torchft_tpu.manager.ManagerClient") as mc:
            mc.return_value._checkpoint_metadata.return_value = "mock://peer"
            m.start_quorum()
            m.wait_quorum()
        m.allreduce({"w": np.ones(2, dtype=np.float32)}).get_future().wait(10)
        assert m.should_commit()
        t = m.timings()
        assert t["heal_fetch_s"] == pytest.approx(0.7, abs=1e-6)    # 0.4 + 0.3: a sum
        assert t["heal_place_s"] == pytest.approx(0.05, abs=1e-6)
        assert t["heal_apply_s"] >= 0 and t["heal_attempts"] == 2   # 1 + the retry
        spans = m.tracer.export()["spans"]
        recv_span = next(s for s in spans if s["name"] == "heal_recv")
        kids = [s for s in spans if s["parent"] == recv_span["id"]]
        assert sorted((s["name"], s["args"]["bytes"]) for s in kids) == [
            ("heal_fetch", 60), ("heal_fetch", 100), ("heal_place", 160)]
        assert all(s["cat"] == "heal" for s in kids)
        assert [s["name"] for s in spans if s["cat"] == "heal"].count("heal_apply") == 1

    def test_sync_quorum_applies_state_eagerly(self):
        q = make_quorum(
            heal=True,
            max_step=2,
            max_replica_rank=None,
            max_world_size=1,
            recover_src_replica_rank=1,
        )
        load_fn = MagicMock()
        m = make_manager(
            quorum=q, min_replica_size=1, use_async_quorum=False, load_state_dict=load_fn
        )
        m._test_transport.recv_checkpoint.return_value = {
            "user": {"default": {"w": np.ones(2)}},
            "torchft": {"step": 2, "batches_committed": 4},
        }
        with patch("torchft_tpu.manager.ManagerClient") as mc:
            mc.return_value._checkpoint_metadata.return_value = "mock://peer"
            m.start_quorum()
        assert not m._healing  # already applied
        load_fn.assert_called_once()
        assert m.current_step() == 2
        assert m.is_participating()  # sync mode participates after heal
        # functional loops re-read rebound state through this signal
        assert m.last_quorum_healed()

    def test_last_quorum_healed_resets_on_healthy_quorum(self):
        m = make_manager(quorum=make_quorum(), min_replica_size=1,
                         use_async_quorum=False)
        m.start_quorum()
        assert not m.last_quorum_healed()

    def test_send_checkpoint_to_recovering_peers(self):
        q = make_quorum(recover_dst_replica_ranks=[1])
        m = make_manager(quorum=q)
        m.start_quorum()
        m.wait_quorum()
        m._test_transport.send_checkpoint.assert_called_once()
        kwargs = m._test_transport.send_checkpoint.call_args.kwargs
        assert kwargs["dst_ranks"] == [1]
        assert "user" in kwargs["state_dict"]


class TestErrors:
    def test_allreduce_error_returns_zeros_and_blocks_commit(self):
        pg = MagicMock(wraps=ProcessGroupDummy())
        pg.errored.return_value = None
        pg.allreduce.side_effect = RuntimeError("collective failed")
        m = make_manager(pg=pg, quorum=make_quorum())
        m.start_quorum()
        out = m.allreduce({"w": np.full(2, 5.0, dtype=np.float32)}).get_future().wait(10)
        np.testing.assert_allclose(out["w"], 0.0)
        assert m.errored() is not None
        assert not m.should_commit()
        assert m.current_step() == 0

    def test_false_local_vote_logs_reason_at_warning(self, caplog):
        """A False local vote silently discards the whole group's step;
        the REASON must be visible under default logging (a spurious
        device-plane error during a quiet chaos soak was undiagnosable
        from its console log when the reason logged at INFO only)."""
        import logging

        pg = MagicMock(wraps=ProcessGroupDummy())
        pg.errored.return_value = None
        m = make_manager(pg=pg, quorum=make_quorum())
        m.start_quorum()
        m.report_error(RuntimeError("injected device-plane fault"))
        with caplog.at_level(logging.WARNING):
            assert not m.should_commit()
        warnings = [r for r in caplog.records
                    if r.levelno == logging.WARNING
                    and "voting False" in r.getMessage()]
        assert warnings, "no WARNING explaining the False local vote"
        assert "injected device-plane fault" in warnings[0].getMessage()

    def test_errored_fast_path_skips_collective(self):
        pg = MagicMock(wraps=ProcessGroupDummy())
        pg.errored.return_value = None
        m = make_manager(pg=pg, quorum=make_quorum())
        m.start_quorum()
        m.report_error(RuntimeError("earlier error"))
        out = m.allreduce({"w": np.ones(2, dtype=np.float32)}).get_future().wait(10)
        np.testing.assert_allclose(out["w"], 0.0)
        pg.allreduce.assert_not_called()

    def test_pg_errored_propagates_at_commit(self):
        pg = ProcessGroupDummy()
        m = make_manager(pg=pg, quorum=make_quorum())
        m.start_quorum()
        m.wait_quorum()
        with patch.object(pg, "errored", return_value=RuntimeError("pg dead")):
            assert not m.should_commit()

    def test_quorum_rpc_failure_marks_errored(self):
        m = make_manager()
        m._test_client._quorum.side_effect = TimeoutError("lighthouse down")
        m.start_quorum()
        m.wait_quorum()
        assert m.errored() is not None
        assert not m.should_commit()

    def test_not_enough_participants(self):
        q = make_quorum(max_world_size=1, replica_world_size=1)
        m = make_manager(quorum=q, min_replica_size=2)
        m.start_quorum()
        assert not m.should_commit()

    def test_max_retries_raises(self):
        q = make_quorum(max_world_size=1, replica_world_size=1)
        m = make_manager(quorum=q, min_replica_size=2, max_retries=1)
        m.start_quorum()
        assert not m.should_commit()  # failure 1 (== max_retries, tolerated)
        m.start_quorum()
        with pytest.raises(RuntimeError, match="max_retries"):
            m.should_commit()  # failure 2 > max_retries

    def test_commit_failures_reported_to_quorum(self):
        q = make_quorum(max_world_size=1, replica_world_size=1)
        m = make_manager(quorum=q, min_replica_size=2)
        m.start_quorum()
        assert not m.should_commit()
        m.start_quorum()
        m.wait_quorum()
        assert m._test_client._quorum.call_args.kwargs["commit_failures"] == 1


class TestWorldSizeModes:
    def test_fixed_with_spares_clamps_world(self):
        q = make_quorum(
            replica_rank=2, replica_world_size=3, max_replica_rank=2, max_world_size=3
        )
        m = make_manager(quorum=q, min_replica_size=2,
                         world_size_mode=WorldSizeMode.FIXED_WITH_SPARES)
        m.start_quorum()
        assert m.num_participants() == 2
        assert m.participating_rank() is None  # rank 2 is a spare
        assert not m.is_participating()

    def test_fixed_with_spares_participant(self):
        q = make_quorum(
            replica_rank=1, replica_world_size=3, max_replica_rank=1, max_world_size=3
        )
        m = make_manager(quorum=q, min_replica_size=2,
                         world_size_mode=WorldSizeMode.FIXED_WITH_SPARES)
        m.start_quorum()
        assert m.num_participants() == 2
        assert m.participating_rank() == 1


class TestStateDict:
    def test_state_dict_roundtrip(self):
        m = make_manager(quorum=make_quorum())
        m.start_quorum()
        assert m.should_commit()
        sd = m.state_dict()
        assert sd == {"step": 1, "batches_committed": 2}
        m2 = make_manager(quorum=make_quorum())
        m2.load_state_dict(sd)
        assert m2.current_step() == 1
        assert m2.batches_committed() == 2

    def test_register_state_dict_fn_included_in_manager_state(self):
        m = make_manager(quorum=make_quorum())
        m.register_state_dict_fn("extra", MagicMock(), lambda: {"x": 1})
        state = m._manager_state_dict()
        assert set(state["user"].keys()) == {"default", "extra"}
        assert state["torchft"] == {"step": 0, "batches_committed": 0}


class TestInitSyncAndConfig:
    def test_init_sync_forwarded_to_quorum(self):
        """init_sync=False must reach the quorum RPC (the server uses it to
        skip forced recovery at step 0; reference manager.py init_sync)."""
        m = make_manager(quorum=make_quorum(), init_sync=False)
        m.start_quorum()
        m.wait_quorum()
        kwargs = m._test_client._quorum.call_args.kwargs
        assert kwargs["init_sync"] is False

    def test_configure_error_marks_errored(self):
        """A pg.configure failure during reconfiguration must surface via
        errored() and block the commit (reference: configure error path)."""
        pg = ProcessGroupDummy()
        pg.configure = MagicMock(side_effect=RuntimeError("store down"))
        m = make_manager(pg=pg, quorum=make_quorum())
        m.start_quorum()
        m.wait_quorum()
        assert m.errored() is not None
        assert not m.should_commit()

    def test_commit_failures_forwarded(self):
        """commit_failures must be sent with each quorum request so the
        lighthouse can bump quorum_id after repeated failures."""
        m = make_manager(quorum=make_quorum())
        m.start_quorum()
        m.wait_quorum()
        assert m._test_client._quorum.call_args.kwargs["commit_failures"] == 0


class TestWrapFuture:
    def test_wrap_future_success_passthrough(self):
        m = make_manager(quorum=make_quorum())
        fut = Future()
        wrapped = m.wrap_future(fut, default="dflt")
        fut.set_result("ok")
        assert wrapped.wait(5) == "ok"
        assert m.errored() is None

    def test_wrap_future_error_swallowed_to_default(self):
        m = make_manager(quorum=make_quorum())
        fut = Future()
        wrapped = m.wrap_future(fut, default="dflt")
        fut.set_exception(RuntimeError("collective died"))
        assert wrapped.wait(5) == "dflt"
        assert m.errored() is not None

    def test_wrap_future_timeout_swallowed_to_default(self):
        m = make_manager(quorum=make_quorum())
        fut = Future()  # never completed
        wrapped = m.wrap_future(fut, default="dflt", timeout=0.1)
        assert wrapped.wait(10) == "dflt"
        assert m.errored() is not None


class TestStateDictLock:
    def test_disallow_blocks_manager_state_dict(self):
        """While the state-dict lock is write-held (training mutating params),
        _manager_state_dict readers must block until allowed again."""
        import threading

        m = make_manager(quorum=make_quorum())
        m.disallow_state_dict_read()
        got = []
        t = threading.Thread(
            target=lambda: got.append(m._manager_state_dict()), daemon=True
        )
        t.start()
        t.join(0.3)
        assert t.is_alive(), "read must block while disallowed"
        m.allow_state_dict_read()
        t.join(5)
        assert not t.is_alive() and got


class AutoModePG(ProcessGroupDummy):
    """PG that can't know whether it needs sync quorum until its first
    configure resolves the mode (auto-mode backends)."""

    def __init__(self):
        super().__init__()
        self.resolved = False

    @property
    def requires_sync_quorum(self):
        return not self.resolved

    def configure(self, store_addr, replica_rank, replica_world_size, quorum_id=0):
        super().configure(store_addr, replica_rank, replica_world_size, quorum_id)
        self.resolved = True


class TestAutoModeSyncQuorumTax:
    def test_async_quorum_restored_after_configure_resolves(self):
        """Sampling requires_sync_quorum once at construction would tax
        every later step with a synchronous quorum RPC; the Manager must
        re-evaluate per start_quorum and hand async quorum back."""
        pg = AutoModePG()
        m = make_manager(pg=pg, quorum=make_quorum(), use_async_quorum=True)
        assert m._use_async_quorum is False  # safety valve at construction

        m.start_quorum()  # sync quorum: configure runs, mode resolves
        m.wait_quorum()
        assert pg.resolved
        assert m.should_commit()

        m.start_quorum()  # re-evaluation point
        assert m._use_async_quorum is True
        m.wait_quorum()
        assert m.should_commit()

    def test_sync_requested_caller_never_flips(self):
        pg = AutoModePG()
        m = make_manager(pg=pg, quorum=make_quorum(), use_async_quorum=False)
        m.start_quorum()
        m.wait_quorum()
        assert pg.resolved
        m.start_quorum()
        assert m._use_async_quorum is False  # caller chose sync; honor it
