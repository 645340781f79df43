"""Device-plane (ProcessGroupXLA) tests.

Local mode runs replicas as threads over the virtual 8-device CPU mesh
(exactly how the driver's dryrun exercises multi-chip sharding); the
distributed-mode tests spawn real processes that join a per-quorum
jax.distributed world, then reconfigure to a smaller world and abort
mid-flight — the reconfigure/abort semantics the reference exercises on
NCCL (reference: process_group_test.py:894-950 resiliency harness).
"""

import os
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from torchft_tpu.coordination import KvStoreServer
from torchft_tpu.tracing import SpanRecorder, TraceConfig
from torchft_tpu.process_group import ReduceOp
from torchft_tpu.process_group_xla import ProcessGroupXLA

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def store():
    s = KvStoreServer("127.0.0.1:0")
    yield s
    s.shutdown()


def run_parallel(world, fn):
    with ThreadPoolExecutor(max_workers=world) as ex:
        futs = [ex.submit(fn, r) for r in range(world)]
        return [f.result(timeout=120) for f in futs]


def make_pgs(store, world, quorum_id=1):
    pgs = [ProcessGroupXLA(timeout=30.0, mode="local") for _ in range(world)]
    addr = f"127.0.0.1:{store.port}/xla"
    run_parallel(world, lambda r: pgs[r].configure(addr, r, world, quorum_id))
    return pgs


class TestLocalMode:
    def test_allreduce_sum_lands_on_device(self, store):
        world = 4
        pgs = make_pgs(store, world)
        outs = run_parallel(
            world,
            lambda r: pgs[r]
            .allreduce([jnp.full((8,), float(r + 1))], ReduceOp.SUM)
            .get_future()
            .wait(30),
        )
        for r, out in enumerate(outs):
            assert isinstance(out[0], jax.Array), "result left the device"
            np.testing.assert_allclose(np.asarray(out[0]), np.full(8, 10.0))
            # each replica's result lives on its own lead device
            assert out[0].devices() == {pgs[r]._world.leads[r]}

    def test_allreduce_ops(self, store):
        world = 2
        pgs = make_pgs(store, world)
        for op, expect in [
            (ReduceOp.SUM, 3.0),
            (ReduceOp.AVG, 1.5),
            (ReduceOp.MAX, 2.0),
            (ReduceOp.MIN, 1.0),
            (ReduceOp.PRODUCT, 2.0),
        ]:
            outs = run_parallel(
                world,
                lambda r, op=op: pgs[r]
                .allreduce([jnp.full((4,), float(r + 1))], op)
                .get_future()
                .wait(30),
            )
            np.testing.assert_allclose(np.asarray(outs[0][0]), np.full(4, expect))

    def test_multi_leaf_batched(self, store):
        world = 2
        pgs = make_pgs(store, world)
        outs = run_parallel(
            world,
            lambda r: pgs[r]
            .allreduce(
                [jnp.full((2, 3), float(r)), jnp.full((5,), 10.0 * r)],
                ReduceOp.SUM,
            )
            .get_future()
            .wait(30),
        )
        np.testing.assert_allclose(np.asarray(outs[1][0]), np.ones((2, 3)))
        np.testing.assert_allclose(np.asarray(outs[1][1]), np.full(5, 10.0))

    def test_allgather_broadcast(self, store):
        world = 3
        pgs = make_pgs(store, world)
        rows = run_parallel(
            world,
            lambda r: pgs[r]
            .allgather([jnp.full((2,), float(r))])
            .get_future()
            .wait(30),
        )
        for row in rows:
            for src in range(world):
                np.testing.assert_allclose(
                    np.asarray(row[src][0]), np.full(2, float(src))
                )
        outs = run_parallel(
            world,
            lambda r: pgs[r]
            .broadcast([jnp.full((2,), float(r))], root=1)
            .get_future()
            .wait(30),
        )
        for out in outs:
            np.testing.assert_allclose(np.asarray(out[0]), np.full(2, 1.0))

    def test_reduce_scatter_alltoall(self, store):
        world = 2
        pgs = make_pgs(store, world)
        # input_chunks[r][leaf]: rank's contribution destined for rank r
        outs = run_parallel(
            world,
            lambda r: pgs[r]
            .reduce_scatter(
                [[jnp.full((2,), float(r + 1))], [jnp.full((2,), 10.0 * (r + 1))]],
                ReduceOp.SUM,
            )
            .get_future()
            .wait(30),
        )
        np.testing.assert_allclose(np.asarray(outs[0][0]), np.full(2, 3.0))
        np.testing.assert_allclose(np.asarray(outs[1][0]), np.full(2, 30.0))

        a2a = run_parallel(
            world,
            lambda r: pgs[r]
            .alltoall([jnp.full((2,), float(10 * r + d)) for d in range(world)])
            .get_future()
            .wait(30),
        )
        # rank r receives chunk r from each src: src's value 10*src + r
        for r in range(world):
            for src in range(world):
                np.testing.assert_allclose(
                    np.asarray(a2a[r][src]), np.full(2, float(10 * src + r))
                )

    def test_send_recv(self, store):
        world = 2
        pgs = make_pgs(store, world)

        def go(r):
            if r == 0:
                return pgs[0].send([jnp.arange(4.0)], dst=1, tag=7).get_future().wait(30)
            return pgs[1].recv(src=0, tag=7).get_future().wait(30)

        res = run_parallel(world, go)
        np.testing.assert_allclose(np.asarray(res[1][0]), np.arange(4.0))

    def test_reconfigure_smaller_world(self, store):
        """Quorum change: 4 replicas -> one dies -> rebuild as 3."""
        pgs = make_pgs(store, 4, quorum_id=1)
        outs = run_parallel(
            4,
            lambda r: pgs[r]
            .allreduce([jnp.ones(2)], ReduceOp.SUM)
            .get_future()
            .wait(30),
        )
        np.testing.assert_allclose(np.asarray(outs[0][0]), np.full(2, 4.0))

        survivors = pgs[:3]
        addr = f"127.0.0.1:{store.port}/xla"
        run_parallel(3, lambda r: survivors[r].configure(addr, r, 3, 2))
        outs = run_parallel(
            3,
            lambda r: survivors[r]
            .allreduce([jnp.ones(2)], ReduceOp.SUM)
            .get_future()
            .wait(30),
        )
        np.testing.assert_allclose(np.asarray(outs[0][0]), np.full(2, 3.0))
        assert survivors[0]._world.mesh.shape["replica"] == 3

    # abort -> fail -> reconfigure -> succeed, for every collective (the
    # host plane has the same matrix in test_process_group.py; the device
    # plane's rendezvous/slot machinery must honor the identical contract)
    _COLLECTIVES = {
        "allreduce": lambda pg, rank, world: pg.allreduce(
            [jnp.ones(2)], ReduceOp.SUM
        ),
        "allgather": lambda pg, rank, world: pg.allgather(
            [jnp.full((2,), float(rank))]
        ),
        "broadcast": lambda pg, rank, world: pg.broadcast(
            [jnp.full((2,), float(rank))], root=0
        ),
        "reduce_scatter": lambda pg, rank, world: pg.reduce_scatter(
            [[jnp.full((2,), float(rank))] for _ in range(world)],
            ReduceOp.SUM,
        ),
        "alltoall": lambda pg, rank, world: pg.alltoall(
            [jnp.full((2,), float(rank * 10 + d)) for d in range(world)]
        ),
    }

    @pytest.mark.parametrize("collective", sorted(_COLLECTIVES))
    def test_abort_reconfigure_matrix(self, store, collective):
        world = 2
        issue = self._COLLECTIVES[collective]
        pgs = make_pgs(store, world)
        # rank 0 deposits; rank 1 aborts instead of arriving
        work = issue(pgs[0], 0, world)
        pgs[1].abort()
        with pytest.raises(RuntimeError, match="aborted"):
            work.get_future().wait(10)
        assert pgs[0].errored() is not None

        addr = f"127.0.0.1:{store.port}/xla_{collective}"
        run_parallel(world, lambda r: pgs[r].configure(addr, r, world, 9))
        assert pgs[0].errored() is None
        outs = run_parallel(
            world,
            lambda r: issue(pgs[r], r, world).get_future().wait(30),
        )
        # value checks: the fresh generation must compute, not just return
        if collective == "allreduce":
            np.testing.assert_allclose(np.asarray(outs[0][0]), np.full(2, 2.0))
        elif collective == "allgather":
            np.testing.assert_allclose(np.asarray(outs[0][0][0]), 0.0)
            np.testing.assert_allclose(np.asarray(outs[0][1][0]), 1.0)
        elif collective == "broadcast":
            for out in outs:
                np.testing.assert_allclose(np.asarray(out[0]), 0.0)
        elif collective == "reduce_scatter":
            for rank, out in enumerate(outs):
                np.testing.assert_allclose(np.asarray(out[0]), 1.0)  # 0+1
        elif collective == "alltoall":
            for rank, out in enumerate(outs):
                np.testing.assert_allclose(np.asarray(out[0]), 0.0 + rank)
                np.testing.assert_allclose(np.asarray(out[1]), 10.0 + rank)

    @staticmethod
    def _allreduce_avg(pg, leaf, world, quantize):
        """What Manager.allreduce does with a device-native PG, less the
        quorum: its data plane, driven with a PG, a span recorder and a
        pool, and no Manager."""
        from torchft_tpu import bucketing

        pipeline = bucketing.BucketPipeline(
            pg, SpanRecorder("stub", TraceConfig(enabled=False)),
            bucketing.BufferPool(),
        )
        try:
            return pipeline.allreduce_leaves(
                [leaf], ReduceOp.SUM, quantize=quantize, participating=True,
                divisor=world, place=bucketing.leaf_placer(), timeout=60.0,
            ).wait(60)[0]
        finally:
            pipeline.shutdown(wait=False)

    def test_manager_allreduce_stays_on_device(self, store):
        """The managed allreduce's data plane with a device-native PG: no
        host staging, the result is a jax.Array produced by the XLA
        reduction."""
        world = 2
        pgs = make_pgs(store, world, quorum_id=5)
        outs = run_parallel(
            world,
            lambda r: self._allreduce_avg(
                pgs[r], jnp.full((4,), float(r + 1)), world, quantize=False
            ),
        )
        for out in outs:
            assert isinstance(out, jax.Array)
            np.testing.assert_allclose(np.asarray(out), np.full(4, 1.5))

    def test_manager_quantized_allreduce_on_device(self, store):
        """should_quantize over a device-native PG: the fp8 pipeline packs
        the compressed wire into uint8 device arrays and ships it through
        the PG's own collectives (the gate that silently disabled this is
        gone)."""
        world = 2
        pgs = make_pgs(store, world, quorum_id=6)
        rng = np.random.RandomState(5)
        base = rng.randn(600).astype(np.float32)
        outs = run_parallel(
            world,
            lambda r: self._allreduce_avg(
                pgs[r], jnp.asarray(base * (r + 1)), world, quantize=True
            ),
        )
        amax = float(np.abs(base).max())
        for out in outs:
            assert isinstance(out, jax.Array)
            np.testing.assert_allclose(
                np.asarray(out), base * 1.5, rtol=0.15, atol=amax / 4
            )


_DIST_WORKER = r"""
import sys, time
rank = int(sys.argv[1]); world = int(sys.argv[2]); store_port = sys.argv[3]
scenario = sys.argv[4]
sys.path.insert(0, {repo!r})
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
from torchft_tpu.process_group import ReduceOp
from torchft_tpu.process_group_xla import ProcessGroupXLA

pg = ProcessGroupXLA(timeout=60.0, mode="distributed")
addr = f"127.0.0.1:{{store_port}}/dist"
pg.configure(addr, rank, world, quorum_id=1)
out = pg.allreduce([jnp.full((4,), float(rank + 1))], ReduceOp.SUM).get_future().wait(60)
expect = world * (world + 1) / 2
assert np.allclose(np.asarray(out[0]), expect), (np.asarray(out[0]), expect)
print(f"RANK{{rank}} WORLD{{world}} OK", flush=True)

if scenario == "reconfigure":
    # rank world-1 "dies"; survivors rebuild as world-1 under quorum 2
    if rank == world - 1:
        pg.shutdown()
        sys.exit(0)
    pg.configure(addr, rank, world - 1, quorum_id=2)
    out = pg.allreduce([jnp.full((4,), 10.0 * (rank + 1))], ReduceOp.SUM).get_future().wait(60)
    expect = 10.0 * (world - 1) * world / 2
    assert np.allclose(np.asarray(out[0]), expect), (np.asarray(out[0]), expect)
    print(f"RANK{{rank}} RECONFIGURED OK", flush=True)
elif scenario == "abort":
    if rank == 0:
        time.sleep(0.5)
        pg.abort()
        assert pg.errored() is not None
        print(f"RANK{{rank}} ABORTED OK", flush=True)
    else:
        try:
            pg.allreduce([jnp.ones(4)], ReduceOp.SUM).get_future().wait(20)
            print(f"RANK{{rank}} UNEXPECTED SUCCESS", flush=True)
        except BaseException as e:
            print(f"RANK{{rank}} OP FAILED AS EXPECTED: {{type(e).__name__}}", flush=True)
pg.shutdown()
"""


def _spawn_dist(store, world, scenario, timeout=180):
    script = _DIST_WORKER.format(repo=REPO)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # one CPU device per process
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(r), str(world), str(store.port), scenario],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for r in range(world)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n<TIMEOUT>"
        outs.append(out)
    return outs


@pytest.mark.slow
class TestDistributedMode:
    def test_allreduce_and_reconfigure(self, store):
        outs = _spawn_dist(store, 3, "reconfigure")
        for r in range(3):
            assert f"RANK{r} WORLD3 OK" in outs[r], outs[r]
        for r in range(2):
            assert f"RANK{r} RECONFIGURED OK" in outs[r], outs[r]

    def test_failed_join_leaves_no_orphaned_service(self, monkeypatch):
        """A join that raises in-process (client construction failure) must
        shut rank 0's coordination service down and clear jax global state —
        otherwise the next configure() rebinds over a live service still
        holding the port. (The world-never-filled case is process-fatal on
        this toolchain instead — covered by the restart-on-shrink design.)"""
        import socket

        from jax._src import distributed as _dist
        from jax._src.lib import _jax as _jaxlib

        from torchft_tpu.process_group_xla import _join_distributed_world

        def _boom(*a, **k):
            raise RuntimeError("client construction failed")

        monkeypatch.setattr(
            _jaxlib, "get_distributed_runtime_client", _boom
        )
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        with pytest.raises(RuntimeError, match="client construction"):
            _join_distributed_world(
                f"127.0.0.1:{port}", rank=0, world_size=2, timeout=3
            )
        assert _dist.global_state.service is None
        assert _dist.global_state.client is None
        # the port must be free again: the service was really shut down
        deadline = time.monotonic() + 10
        while True:
            probe = socket.socket()
            try:
                probe.bind(("0.0.0.0", port))
                probe.close()
                break
            except OSError:
                probe.close()
                if time.monotonic() > deadline:
                    pytest.fail(f"port {port} still held by orphaned service")
                time.sleep(0.2)

    def test_abort_unblocks_peer(self, store):
        outs = _spawn_dist(store, 2, "abort")
        assert "RANK0 ABORTED OK" in outs[0], outs[0]
        # The wedged peer must not hang: either its op fails with a Python
        # exception, or the JAX coordination service's fatal-error handler
        # terminates the process (the launcher-restart recovery path) —
        # which of the two wins the race is runtime timing.
        unblocked = (
            "OP FAILED AS EXPECTED" in outs[1]
            or "Terminating process" in outs[1]
        )
        assert unblocked and "<TIMEOUT>" not in outs[1], outs[1]
