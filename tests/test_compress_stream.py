"""Compressed streaming collectives: codec, error feedback, ring failover.

Tier-1 coverage for the fp8/int8 compressed wire (ops/quantization.py
int8 + CompressedWire surface), the Manager's compressed streaming
pipeline with per-bucket error feedback, the host compressed ring's
mid-collective link failover (process_group._ring_allreduce_compressed),
and the pins that keep the default path honest:

- ``TORCHFT_COMPRESS=off`` (the default) stays bit-identical to the
  uncompressed streamed pipeline, which itself stays bit-identical to the
  serial unbucketed path — compression must be invisible until asked for.
- ``should_quantize=True`` on a multi-leaf tree STREAMS compressed
  buckets (``GradStream.num_buckets > 1``) instead of silently dropping
  to the serial monolithic path — the grad-accum + quantize interplay
  examples/train_ddp.py ``--grad-accum --quantize`` depends on.
- a mid-collective link kill re-routes (ring re-form, or open-chain
  fallback at world=3), the step COMMITS, ``collective_reroute`` ticks in
  ``Manager.timings()``, and a flight-recorder breadcrumb names the link.
"""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from torchft_tpu.ops.quantization import (
    COMPRESS_MODES,
    CompressedWire,
    compress_bucket,
    decompress_bucket,
    is_compressed_wire,
    quantize_int8_rowwise,
    dequantize_int8_rowwise,
    resolve_compress_mode,
)


# ---------------------------------------------------------------------------
# Codec round-trips
# ---------------------------------------------------------------------------
class TestCodecRoundTrip:
    @pytest.mark.parametrize("mode", ["fp8", "int8"])
    def test_roundtrip_within_one_quant_step(self, mode):
        rng = np.random.RandomState(0)
        flat = (rng.randn(1300) * 3.0).astype(np.float32)
        wire = compress_bucket(flat, mode)
        assert is_compressed_wire(wire)
        assert wire.mode == mode and wire.n == 1300 and wire.dtype == "float32"
        out = decompress_bucket(wire)
        assert out.dtype == np.float32 and out.shape == flat.shape
        # rowwise-scaled: per-element error bounded by ~one quant step of
        # that row's amax (fp8 e4m3 mantissa ~2^-3 rel; int8 step 2/254)
        step = np.abs(flat).max() * (0.15 if mode == "fp8" else 0.01)
        np.testing.assert_allclose(out, flat, atol=step)

    @pytest.mark.parametrize("mode", ["fp8", "int8"])
    def test_all_zero_rows_roundtrip_exactly(self, mode):
        flat = np.zeros(1024, np.float32)
        wire = compress_bucket(flat, mode)
        # scale clamps to 1.0 on zero-amax rows: codes are exact zeros
        np.testing.assert_array_equal(wire.scales, np.ones(2, np.float32))
        np.testing.assert_array_equal(decompress_bucket(wire), flat)

    def test_fp8_amax_overflow_rows_scale_down(self):
        # magnitudes far beyond fp8's 448 max normal must ride the scales,
        # not saturate the codes
        flat = np.array([1e6, -5e5, 3.0, 0.25] * 128, np.float32)
        out = decompress_bucket(compress_bucket(flat, "fp8"))
        np.testing.assert_allclose(out, flat, rtol=0.08, atol=1e6 * 0.07)

    def test_int8_nonfinite_rows_saturate(self):
        flat = np.array([np.inf, -np.inf, np.nan, 2.0] + [1.0] * 508,
                        np.float32)
        payload, scales, n = quantize_int8_rowwise(flat)
        assert np.isfinite(scales).all()
        out = dequantize_int8_rowwise(payload, scales, n)
        # non-finite inputs land at the row's finite saturation point, and
        # the finite neighbours survive the poison
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[3:], flat[3:], rtol=0.02)

    def test_bfloat16_dtype_roundtrips_by_name(self):
        import ml_dtypes

        flat = np.arange(16, dtype=ml_dtypes.bfloat16)
        wire = compress_bucket(flat, "fp8")
        assert wire.dtype == "bfloat16"
        out = decompress_bucket(wire)
        assert out.dtype == np.dtype(ml_dtypes.bfloat16)

    def test_wire_is_a_plain_tuple_on_the_wire(self):
        # process_group._to_host passes tuples through untouched; the wire
        # must remain one (NamedTuple) or it would need PG special-casing
        wire = compress_bucket(np.ones(4, np.float32), "int8")
        assert isinstance(wire, tuple) and isinstance(wire, CompressedWire)


class TestResolveCompressMode:
    def test_default_off(self, monkeypatch):
        monkeypatch.delenv("TORCHFT_COMPRESS", raising=False)
        assert resolve_compress_mode() == "off"
        assert resolve_compress_mode(None) == "off"

    def test_ctor_arg_then_env_precedence(self, monkeypatch):
        monkeypatch.delenv("TORCHFT_COMPRESS", raising=False)
        assert resolve_compress_mode("fp8") == "fp8"
        monkeypatch.setenv("TORCHFT_COMPRESS", "int8")
        assert resolve_compress_mode("fp8") == "int8"  # env wins
        monkeypatch.setenv("TORCHFT_COMPRESS", "")
        assert resolve_compress_mode("fp8") == "off"  # blank env = off

    def test_bad_value_raises_with_valid_set(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_COMPRESS", "fp4")
        with pytest.raises(ValueError, match="fp4"):
            resolve_compress_mode()
        monkeypatch.delenv("TORCHFT_COMPRESS", raising=False)
        with pytest.raises(ValueError, match=str(COMPRESS_MODES)):
            resolve_compress_mode("zstd")


class TestDoctorCompressCheck:
    """doctor.py check_compress_env mirrors the Manager's own resolution:
    same funnel, same rejection."""

    def test_default_off_passes(self, monkeypatch):
        from torchft_tpu.doctor import check_compress_env

        monkeypatch.delenv("TORCHFT_COMPRESS", raising=False)
        status, detail = check_compress_env()
        assert status is True and "off" in detail

    def test_bad_value_fails_actionably(self, monkeypatch):
        from torchft_tpu.doctor import check_compress_env

        monkeypatch.setenv("TORCHFT_COMPRESS", "fp4")
        status, detail = check_compress_env()
        assert status is False
        assert "fp4" in detail and "off/fp8/int8" in detail

    def test_compress_on_passes(self, monkeypatch):
        from torchft_tpu.doctor import check_compress_env

        monkeypatch.setenv("TORCHFT_COMPRESS", "int8")
        status, detail = check_compress_env()
        assert status is True and "int8" in detail


# ---------------------------------------------------------------------------
# Error feedback: the residual math BucketPipeline._compress_bucket_ef runs
# ---------------------------------------------------------------------------
def _ef_stream(g: np.ndarray, mode: str, steps: int):
    """Reference EF loop: compress (grad + carried residual), accumulate
    the dequantized wire, carry work - dequant(wire) into the next step."""
    resid = np.zeros_like(g)
    total = np.zeros_like(g)
    for _ in range(steps):
        work = g + resid
        deq = decompress_bucket(compress_bucket(work, mode))
        resid = work - deq
        total += deq
    return total, resid


class TestErrorFeedback:
    @pytest.mark.parametrize("mode", ["fp8", "int8"])
    def test_residual_telescopes_exactly(self, mode):
        rng = np.random.RandomState(7)
        g = (rng.randn(777) * 2.0).astype(np.float32)
        steps = 20
        total, resid = _ef_stream(g, mode, steps)
        # telescoping identity: sum(wire_k) + resid_N == N * g, so the
        # cumulative wire error IS the final residual — bounded by one
        # quantization step, however many steps ran
        np.testing.assert_allclose(total + resid, steps * g, atol=1e-3)

    @pytest.mark.parametrize("mode", ["fp8", "int8"])
    def test_ef_beats_open_loop_accumulation(self, mode):
        rng = np.random.RandomState(11)
        g = (rng.randn(777) * 2.0).astype(np.float32)
        steps = 50
        total_ef, _ = _ef_stream(g, mode, steps)
        # open loop: the same fixed bucket quantized without feedback
        # repeats the identical per-element bias every step
        deq = decompress_bucket(compress_bucket(g, mode))
        err_ef = np.abs(total_ef - steps * g).max()
        err_open = np.abs(steps * deq - steps * g).max()
        assert err_ef < err_open / 5, (err_ef, err_open)

    @pytest.mark.parametrize("mode", ["fp8", "int8"])
    def test_zero_bucket_keeps_zero_residual(self, mode):
        total, resid = _ef_stream(np.zeros(600, np.float32), mode, 5)
        np.testing.assert_array_equal(total, np.zeros(600, np.float32))
        np.testing.assert_array_equal(resid, np.zeros(600, np.float32))


# ---------------------------------------------------------------------------
# PG-level compressed ring: correctness + mid-collective failover
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def kvstore():
    from torchft_tpu.coordination import KvStoreServer

    store = KvStoreServer("127.0.0.1:0")
    yield store
    store.shutdown()


def _make_pgs(store, world: int, quorum_id: int, prefix: str):
    from torchft_tpu.process_group import ProcessGroupHost

    pgs = [ProcessGroupHost(timeout=15.0) for _ in range(world)]
    addr = f"127.0.0.1:{store.port}/{prefix}"
    with ThreadPoolExecutor(max_workers=world) as ex:
        list(ex.map(
            lambda r: pgs[r].configure(addr, r, world, quorum_id=quorum_id),
            range(world),
        ))
    return pgs


def _ring_allreduce(pgs, inputs, mode, op, timeout=30):
    def run(rank):
        wire = compress_bucket(inputs[rank], mode)
        out = pgs[rank].allreduce([wire], op).get_future().wait(
            timeout=timeout
        )
        return decompress_bucket(out[0])

    with ThreadPoolExecutor(max_workers=len(pgs)) as ex:
        return list(ex.map(run, range(len(pgs))))


class TestCompressedRing:
    WORLD = 3

    def _inputs(self, seed=3, n=5000):
        rng = np.random.RandomState(seed)
        return [rng.randn(n).astype(np.float32) for _ in range(self.WORLD)]

    def _check(self, outs, expected):
        # every rank holds the identical reduced codes -> bitwise equality
        for o in outs[1:]:
            np.testing.assert_array_equal(outs[0], o)
        # hop-requantization compounds codec noise: codec-scale tolerance
        np.testing.assert_allclose(
            outs[0], expected, rtol=0.25, atol=np.abs(expected).max() / 8
        )

    @pytest.mark.parametrize("mode", ["fp8", "int8"])
    def test_three_rank_sum(self, kvstore, mode):
        from torchft_tpu.process_group import ReduceOp

        pgs = _make_pgs(kvstore, self.WORLD, 1, f"cring_{mode}")
        try:
            inputs = self._inputs()
            outs = _ring_allreduce(pgs, inputs, mode, ReduceOp.SUM)
        finally:
            for pg in pgs:
                pg.shutdown()
        self._check(outs, sum(inputs))

    def test_three_rank_avg(self, kvstore):
        from torchft_tpu.process_group import ReduceOp

        pgs = _make_pgs(kvstore, self.WORLD, 1, "cring_avg")
        try:
            inputs = self._inputs(seed=5)
            outs = _ring_allreduce(pgs, inputs, "fp8", ReduceOp.AVG)
        finally:
            for pg in pgs:
                pg.shutdown()
        self._check(outs, sum(inputs) / self.WORLD)

    def test_link_fault_reroutes_and_stays_routed(self, kvstore):
        """A link killed mid-collective (hop 2) forces a re-route — at
        world=3 a severed edge leaves no Hamiltonian cycle, so the ring
        falls back to the open chain — and the collective still returns
        the correct reduction on every rank. The dead link then persists:
        the NEXT collective on the same generation routes around it from
        attempt 0, with no fresh reroute events."""
        from torchft_tpu.process_group import ReduceOp

        pgs = _make_pgs(kvstore, self.WORLD, 1, "cring_kill")
        reroutes: list = []
        for pg in pgs:
            pg.set_reroute_observer(
                lambda pair, att: reroutes.append((tuple(sorted(pair)), att))
            )
        try:
            for pg in pgs:
                pg.inject_link_fault(0, 1, at_hop=2)
            inputs = self._inputs(seed=9)
            outs = _ring_allreduce(pgs, inputs, "fp8", ReduceOp.SUM)
            self._check(outs, sum(inputs))
            assert reroutes and all(p == (0, 1) for p, _ in reroutes), reroutes

            # second collective: known-dead link avoided without rediscovery
            del reroutes[:]
            outs2 = _ring_allreduce(pgs, inputs, "int8", ReduceOp.SUM)
            self._check(outs2, sum(inputs))
            assert reroutes == [], reroutes
        finally:
            for pg in pgs:
                pg.shutdown()

    def test_collectives_allreduce_compressed_api(self, kvstore):
        """The public collectives.allreduce_compressed wrapper: flatten,
        compress, ride the ring, decompress, unflatten."""
        from torchft_tpu.collectives import allreduce_compressed
        from torchft_tpu.process_group import ReduceOp

        world = 2
        pgs = _make_pgs(kvstore, world, 1, "ccoll")
        rng = np.random.RandomState(21)
        lists = [
            [rng.randn(600).astype(np.float32),
             rng.randn(40).astype(np.float32)]
            for _ in range(world)
        ]
        try:
            def run(rank):
                return allreduce_compressed(
                    lists[rank], ReduceOp.AVG, pgs[rank], mode="fp8"
                ).get_future().wait(timeout=30)

            with ThreadPoolExecutor(max_workers=world) as ex:
                outs = list(ex.map(run, range(world)))
        finally:
            for pg in pgs:
                pg.shutdown()
        for i in range(2):
            np.testing.assert_array_equal(outs[0][i], outs[1][i])
            expected = (lists[0][i] + lists[1][i]) / 2
            np.testing.assert_allclose(
                outs[0][i], expected, rtol=0.2,
                atol=np.abs(expected).max() / 8,
            )


# ---------------------------------------------------------------------------
# Manager-level: compressed streaming, EF, pins, failover telemetry
# ---------------------------------------------------------------------------
def _run_manager_fleet(body, world=2, steps=3, compress=None,
                       bucket_cap_bytes=4096, min_replicas=None):
    """Spin a lighthouse + ``world`` Managers in threads; ``body(rid,
    manager, step)`` runs once per step per replica between the quorum and
    the commit vote. Returns {rid: [body results]} and {rid: timings}."""
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.process_group import ProcessGroupHost

    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=min_replicas or world,
        join_timeout_ms=5000, quorum_tick_ms=20, heartbeat_timeout_ms=5000,
    )
    barrier = threading.Barrier(world)
    results: dict = {}
    timings: dict = {}
    errors: list = []

    def replica(rid):
        manager = None
        try:
            manager = Manager(
                pg=ProcessGroupHost(timeout=30.0),
                load_state_dict=lambda sd: None,
                state_dict=lambda: {},
                min_replica_size=min_replicas or world,
                use_async_quorum=False,
                replica_id=f"cstream_{rid}",
                lighthouse_addr=f"127.0.0.1:{lh.port}",
                timeout=30.0,
                quorum_timeout=30.0,
                bucket_cap_bytes=bucket_cap_bytes,
                compress=compress,
            )
            outs = []
            for i in range(steps):
                barrier.wait(timeout=120)
                manager.start_quorum()
                outs.append(body(rid, manager, i))
                assert manager.should_commit(), f"rid={rid} step={i}"
            results[rid] = outs
            timings[rid] = manager.timings()
        except BaseException as e:  # noqa: BLE001
            errors.append(e)
            barrier.abort()
            raise
        finally:
            if manager is not None:
                manager.shutdown(wait=False)

    threads = [threading.Thread(target=replica, args=(r,), daemon=True)
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=240)
    lh.shutdown()
    assert not errors, errors
    assert set(results) == set(range(world))
    return results, timings


def _tree(rng, leaves=6, n=3000):
    return {f"w{i}": rng.randn(n).astype(np.float32) for i in range(leaves)}


class TestManagerCompressedStreaming:
    def test_off_is_bit_identical_to_serial_path(self, monkeypatch):
        """The default-off pin: with compression off the streamed
        multi-bucket pipeline must keep returning EXACTLY what the serial
        unbucketed path (bucket_cap_bytes=0 -> no plan) returns — the
        compression layer is invisible until asked for."""
        monkeypatch.delenv("TORCHFT_COMPRESS", raising=False)
        base = _tree(np.random.RandomState(1))

        def body(rid, manager, step):
            contrib = {k: v * (rid + 1) for k, v in base.items()}
            assert manager._compress == "off"
            streamed = manager.allreduce_streamed(contrib).wait(timeout=60)
            serial = manager.allreduce_streamed(
                contrib, bucket_cap_bytes=0
            ).wait(timeout=60)
            for k in base:
                np.testing.assert_array_equal(
                    np.asarray(streamed[k]), np.asarray(serial[k]),
                    err_msg=f"leaf {k}: streamed path drifted from serial",
                )
            return {k: np.asarray(v) for k, v in streamed.items()}

        results, _ = _run_manager_fleet(body, bucket_cap_bytes=4000 * 4)
        for k in base:
            np.testing.assert_array_equal(results[0][0][k], results[1][0][k])

    @pytest.mark.parametrize("mode", ["fp8", "int8"])
    def test_compressed_stream_matches_expected_average(self, mode):
        base = _tree(np.random.RandomState(2))

        def body(rid, manager, step):
            contrib = {k: v * (rid + 1) for k, v in base.items()}
            return manager.allreduce_streamed(contrib).wait(timeout=60)

        results, _ = _run_manager_fleet(
            body, compress=mode, bucket_cap_bytes=4000 * 4
        )
        expected = {k: v * 1.5 for k, v in base.items()}  # avg of 1x, 2x
        for k in base:
            a = np.asarray(results[0][0][k])
            np.testing.assert_array_equal(a, np.asarray(results[1][0][k]))
            # codec-scale: the int8 step at these amaxes is ~0.03 and hop
            # requantization compounds it
            np.testing.assert_allclose(a, expected[k], rtol=0.1, atol=0.15)

    def test_should_quantize_streams_multi_bucket(self):
        """The grad-accum interplay pin (examples/train_ddp.py
        ``--grad-accum --quantize``): a quantized multi-leaf tree on the
        host streaming path must ride the pipeline as MULTIPLE compressed
        buckets, not silently drop to the serial monolithic path."""
        base = _tree(np.random.RandomState(4))

        def body(rid, manager, step):
            contrib = {k: v * (rid + 1) for k, v in base.items()}
            stream = manager.allreduce_streamed(contrib, should_quantize=True)
            assert stream.num_buckets > 1, (
                "quantized tree fell back to a single serial bucket"
            )
            return stream.wait(timeout=60)

        results, timings = _run_manager_fleet(
            body, bucket_cap_bytes=4000 * 4
        )
        expected = {k: v * 1.5 for k, v in base.items()}
        for k in base:
            a = np.asarray(results[0][0][k])
            np.testing.assert_array_equal(a, np.asarray(results[1][0][k]))
            np.testing.assert_allclose(a, expected[k], rtol=0.1, atol=0.15)

    def test_link_kill_commits_with_reroute_telemetry(self):
        """Mid-step link kill at world=3 on the compressed stream: the
        step COMMITS (in-collective failover, not step discard),
        ``collective_reroute`` ticks in timings(), and the flight recorder
        holds a breadcrumb naming the dead link."""
        import torchft_tpu.flight_recorder as fr_mod
        from torchft_tpu._test.event_injector import EventInjector

        base = _tree(np.random.RandomState(6), leaves=4)
        injector = EventInjector().kill_link(0, 1, step=1, at_hop=1)

        def body(rid, manager, step):
            injector.check(rid, step, pg=manager._pg)
            contrib = {k: v * (rid + 1) for k, v in base.items()}
            return manager.allreduce_streamed(contrib).wait(timeout=60)

        results, timings = _run_manager_fleet(
            body, world=3, steps=3, compress="fp8",
            bucket_cap_bytes=4000 * 4,
        )
        assert injector.count >= 1
        assert sum(t.get("collective_reroute", 0.0)
                   for t in timings.values()) >= 1, timings
        events = [e for e in list(fr_mod.recorder._events)
                  if e["kind"] == "collective_reroute"]
        assert events, "no collective_reroute flight-recorder breadcrumb"
        assert tuple(sorted(events[0]["link"])) == (0, 1), events[0]
        # every rank applied the identical re-routed average
        expected = {k: v * 2.0 for k, v in base.items()}  # avg of 1,2,3x
        for k in base:
            a = np.asarray(results[0][-1][k])
            for rid in (1, 2):
                np.testing.assert_array_equal(
                    a, np.asarray(results[rid][-1][k])
                )
            np.testing.assert_allclose(a, expected[k], rtol=0.2, atol=0.3)


# ---------------------------------------------------------------------------
# The pipeline's span tree (tracing.py): children where the work happens
# ---------------------------------------------------------------------------
class TestPipelineSpanTree:
    """One streamed allreduce per step through a real ProcessGroupHost at a
    world of one. Per bucket: pack > (d2h, [codec], dispatch), wire >
    wire_run, unpack > ([decode], h2d, divide: the average is taken where
    the leaf has landed; uncompressed, recycle); the three old spans keep
    their names, category and ``(quorum_id, step)``."""

    STEPS = 3
    # clocks: context spans stamp the wall clock at entry, the stage spans
    # are perf_counter marks re-anchored to it when the op resolves
    SLACK_US = 2_000
    # what a stage may hold beside its children: the loop's own statements
    # between two spans (checked on the calmest of STEPS steps, so that one
    # descheduled thread on a loaded host does not fail the test)
    REMAINDER_US = 5_000

    def _ring(self, compress):
        tracers = {}

        def body(rid, manager, step):
            tracers[rid] = manager.tracer
            tree = _tree(np.random.RandomState(step), leaves=6, n=30_000)
            manager.allreduce_streamed(tree).wait(timeout=60)

        _run_manager_fleet(body, world=1, steps=self.STEPS, compress=compress,
                           bucket_cap_bytes=2 * 30_000 * 4)
        dump = tracers[0].export()
        assert dump["dropped"] == 0
        return dump["spans"]

    @pytest.mark.parametrize("compress", [None, "fp8"])
    def test_children_lie_inside_and_cover_their_stage(self, compress):
        spans = self._ring(compress)
        by_id = {s["id"]: s for s in spans}
        assert len(by_id) == len(spans)                     # ids are unique
        kids = {}
        for s in spans:
            kids.setdefault(s["parent"], []).append(s)
        want = {
            "pack": ["d2h"] + (["codec"] if compress else []) + ["dispatch"],
            "wire": ["wire_run"],
            # uncompressed, the world of one hands the donated staging
            # buffer back as the result: recycle decides when it may refill
            "unpack": (["decode"] if compress else []) + ["h2d", "divide"]
            + ([] if compress else ["recycle"]),
        }
        names = {s["name"] for s in spans if s["cat"] == "allreduce"}
        assert ("codec" in names) == ("decode" in names) == bool(compress)
        remainder = {}                                      # step -> worst bucket
        for stage, children in want.items():
            stages = [s for s in spans if (s["cat"], s["name"]) == ("allreduce", stage)]
            assert len(stages) == 3 * self.STEPS            # 3 buckets a step
            for st in stages:
                got = sorted(kids[st["id"]], key=lambda s: s["ts_us"])
                assert [s["name"] for s in got] == children, (stage, got)
                assert all(s["args"]["bucket"] == st["args"]["bucket"]
                           and s["step"] == st["step"] for s in got)
                lo, hi = st["ts_us"], st["ts_us"] + st["dur_us"]
                for s in got:
                    assert s["ts_us"] >= lo - self.SLACK_US, (stage, s, st)
                    assert s["ts_us"] + s["dur_us"] <= hi + self.SLACK_US, (stage, s, st)
                if stage != "wire":   # wire also holds the wait in the PG's queue
                    left = st["dur_us"] - sum(s["dur_us"] for s in got)
                    assert left >= -self.SLACK_US
                    remainder[st["step"]] = max(remainder.get(st["step"], 0), left)
                # the allreduce span is the stages' parent, and closes last
                ar = by_id[st["parent"]]
                assert (ar["cat"], ar["name"]) == ("allreduce", "allreduce")
                assert ar["args"] == {"buckets": 3, "bytes": 6 * 30_000 * 4,
                                      "segment": 0}  # one op a step
        assert min(remainder.values()) <= self.REMAINDER_US, remainder
        for s in spans:
            if s["name"] == "wire_run":
                a = s["args"]
                assert a["world"] == 1 and a["queued_us"] >= 0
                assert a["bytes"] == 2 * 30_000 * 4
            if s["name"] == "d2h":
                assert s["args"]["bytes"] == 2 * 30_000 * 4
                assert ("queued_us" in s["args"]) == (s["args"]["bucket"] == 0)
            if s["name"] in ("decode", "h2d", "divide"):
                first = "decode" if compress else "h2d"
                assert ("queued_us" in s["args"]) == (s["name"] == first)
            if s["name"] == "divide":   # numpy leaves: averaged in numpy
                assert (a := s["args"])["where"] == "host" and a["leaves"] == 2
            if s["name"] == "codec":
                assert s["args"]["bytes_out"] < s["args"]["bytes"]

    def test_old_spans_keep_name_category_and_step(self):
        spans = self._ring(None)
        for step in range(self.STEPS):
            mine = [s for s in spans if s["step"] == step]
            got = sorted((s["cat"], s["name"]) for s in mine
                         if s["name"] in ("pack", "wire", "unpack", "quorum_rpc",
                                          "commit_vote"))
            assert got == sorted(
                [("allreduce", n) for n in ("pack", "wire", "unpack")] * 3
                + [("quorum", "quorum_rpc"), ("commit", "commit_vote")]), step
            assert all(s["quorum_id"] is not None for s in mine
                       if s["cat"] in ("allreduce", "commit"))
            # the whole-method spans the profiler-only annotations became
            assert {("quorum", "async_quorum"), ("commit", "should_commit")} <= {
                (s["cat"], s["name"]) for s in mine}
