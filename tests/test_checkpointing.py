"""Checkpoint transport tests (reference pattern: http_transport_test.py,
pg_transport_test.py)."""

import contextlib
import pickle
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.checkpointing import HTTPTransport, PGTransport
from torchft_tpu.checkpointing._serialization import (
    flatten_state,
    split_chunks,
    unflatten_state,
)
from torchft_tpu.checkpointing.transport import plan_wire_ranges
from torchft_tpu.coordination import KvStoreServer
from torchft_tpu.process_group import (
    ErrorSwallowingProcessGroupWrapper,
    ProcessGroupDummy,
    ProcessGroupHost,
)
from torchft_tpu.process_group_xla import ProcessGroupXLA


def make_state():
    return {
        "model": {
            "w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "b": jnp.ones((4,), dtype=jnp.bfloat16),
        },
        "step": 7,
        "opt": [np.full((2, 2), 3.0), {"lr": 0.1}],
    }


def assert_state_equal(a, b):
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        if hasattr(x, "shape"):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            assert x == y


class TestSerialization:
    def test_roundtrip(self):
        state = make_state()
        spec, payloads = flatten_state(state)
        out = unflatten_state(spec, payloads)
        assert_state_equal(state, out)

    def test_bfloat16_preserved(self):
        state = {"x": jnp.array([1.5, 2.5], dtype=jnp.bfloat16)}
        spec, payloads = flatten_state(state)
        out = unflatten_state(spec, payloads)
        assert str(out["x"].dtype) == "bfloat16"

    def test_split_chunks_balanced(self):
        sizes = [100, 1, 1, 1, 50, 49]
        chunks = split_chunks(sizes, 2)
        assert sorted(i for c in chunks for i in c) == list(range(6))
        totals = [sum(sizes[i] for i in c) for c in chunks]
        assert max(totals) <= 102

    def test_split_chunks_more_chunks_than_leaves(self):
        chunks = split_chunks([10], 4)
        assert sum(len(c) for c in chunks) == 1


class TestHTTPTransport:
    def test_send_recv_roundtrip(self):
        src = HTTPTransport(timeout=10.0, num_chunks=3)
        dst = HTTPTransport(timeout=10.0)
        try:
            state = make_state()
            src.send_checkpoint([1], step=5, state_dict=state, timeout=10.0)
            out = dst.recv_checkpoint(0, src.metadata(), step=5, timeout=10.0)
            assert_state_equal(state, out)
        finally:
            src.shutdown()
            dst.shutdown()

    def test_wrong_step_rejected(self):
        src = HTTPTransport(timeout=5.0)
        dst = HTTPTransport(timeout=5.0)
        try:
            src.send_checkpoint([1], step=5, state_dict={"a": 1}, timeout=5.0)
            with pytest.raises(Exception):
                dst.recv_checkpoint(0, src.metadata(), step=6, timeout=5.0)
        finally:
            src.shutdown()
            dst.shutdown()

    def test_disallow_blocks_serving(self):
        src = HTTPTransport(timeout=2.0)
        dst = HTTPTransport(timeout=2.0)
        try:
            src.send_checkpoint([1], step=1, state_dict={"a": 1}, timeout=2.0)
            src.disallow_checkpoint()
            with pytest.raises(Exception):
                dst.recv_checkpoint(0, src.metadata(), step=1, timeout=2.0)
            # re-allow with new step
            src.send_checkpoint([1], step=2, state_dict={"a": 2}, timeout=2.0)
            out = dst.recv_checkpoint(0, src.metadata(), step=2, timeout=5.0)
            assert out == {"a": 2}
        finally:
            src.shutdown()
            dst.shutdown()

    def test_concurrent_receivers(self):
        src = HTTPTransport(timeout=10.0, num_chunks=2)
        dst = HTTPTransport(timeout=10.0)
        try:
            state = make_state()
            src.send_checkpoint([1, 2], step=3, state_dict=state, timeout=10.0)
            with ThreadPoolExecutor(max_workers=3) as ex:
                outs = list(
                    ex.map(
                        lambda _: dst.recv_checkpoint(
                            0, src.metadata(), step=3, timeout=10.0
                        ),
                        range(3),
                    )
                )
            for out in outs:
                assert_state_equal(state, out)
        finally:
            src.shutdown()
            dst.shutdown()


class TestHTTPRestageAtomicity:
    def test_reader_mid_stream_survives_restage(self):
        """A receiver that started fetching step N must get a CONSISTENT
        step-N body even if the sender restages step N+1 mid-stream
        (regression: the handler used to dereference live attributes per
        frame, mixing two steps' leaves into one response)."""
        import socket as _socket
        import struct
        import urllib.parse

        import numpy as np

        src = HTTPTransport(timeout=10.0)
        try:
            # large enough that loopback socket buffers cannot absorb the
            # whole body (which would let the serve finish before the
            # restage and make the test vacuous)
            n = 8_000_000  # 32 MB
            state_n = {"w": np.full(n, 1.0, np.float32)}
            state_n1 = {"w": np.full(n, 2.0, np.float32)}
            src.send_checkpoint([1], step=5, state_dict=state_n, timeout=10.0)

            url = urllib.parse.urlparse(src.metadata())
            # generous timeout: a loaded 1-vCPU host can starve the server
            # thread for several seconds without anything being wrong
            s = _socket.create_connection((url.hostname, url.port), timeout=30)
            s.sendall(b"GET /checkpoint/5/chunk_0 HTTP/1.1\r\n"
                      b"Host: x\r\nConnection: close\r\n\r\n")
            # read headers + a small prefix of the body, then pause
            buf = b""
            while b"\r\n\r\n" not in buf:
                got = s.recv(4096)
                assert got, "server closed before headers"
                buf += got
            body = buf.split(b"\r\n\r\n", 1)[1]
            while len(body) < 4096:
                got = s.recv(4096)
                assert got, "server closed mid-body"
                body += got

            # the serve-complete counter only bumps after the full body is
            # written; zero proves the stream really is still in flight
            assert src._served_fetches == 0
            # restage a different step while the stream is mid-flight
            src.send_checkpoint([1], step=6, state_dict=state_n1, timeout=10.0)

            while True:
                got = s.recv(1 << 16)
                if not got:
                    break
                body += got
            s.close()

            # v2 wire frame: leaf_idx, offset, nbytes (byte range)
            frame = struct.Struct("<qqq")
            leaf_idx, off, nbytes = frame.unpack(body[: frame.size])
            assert leaf_idx == 0
            assert off == 0
            payload = np.frombuffer(
                body[frame.size: frame.size + nbytes], np.float32
            )
            # every byte must come from step 5's snapshot
            np.testing.assert_array_equal(payload, state_n["w"])
        finally:
            src.shutdown()


@contextlib.contextmanager
def host_pg_pair(tag, timeout=10.0):
    """Two ProcessGroupHost ranks configured against one store: [0] sends,
    [1] receives."""
    store = KvStoreServer("127.0.0.1:0")
    pgs = [ProcessGroupHost(timeout=timeout) for _ in range(2)]
    try:
        addr = f"127.0.0.1:{store.port}/{tag}"
        with ThreadPoolExecutor(max_workers=2) as ex:
            list(ex.map(lambda r: pgs[r].configure(addr, r, 2, 51), range(2)))
        yield pgs
    finally:
        for pg in pgs:
            pg.shutdown()
        store.shutdown()


def heal_over(pgs, state, template=None, step=4, timeout=10.0):
    """One heal from pgs[0] to pgs[1]; what the receiver got."""
    sender = PGTransport(pgs[0], timeout=timeout)
    receiver = PGTransport(
        pgs[1], timeout=timeout,
        state_dict_template=None if template is None else (lambda: template),
    )
    with ThreadPoolExecutor(max_workers=2) as ex:
        fs = ex.submit(sender.send_checkpoint, [1], step, state, timeout)
        fr = ex.submit(
            receiver.recv_checkpoint, 0, "<pg_transport>", step, timeout
        )
        fs.result(timeout=30)
        return fr.result(timeout=30)


def spy_on_recv_into(pg):
    """For each recv_into on ``pg``: how many buffers it was handed and how
    many of them came back as themselves (the frames landed in them)."""
    calls = []
    real = pg.recv_into

    def spy(buffers, src, tag=0):
        work = real(buffers, src, tag)

        def note(fut):
            got = [] if fut.exception() is not None else fut.value()
            calls.append(
                (len(buffers), sum(g is b for g, b in zip(got, buffers)))
            )

        work.get_future().add_done_callback(note)
        return work

    pg.recv_into = spy
    return calls


class _NoRecvInto:
    """Proxy hiding recv_into (a wrapper PG without the raw-frame surface)."""

    def __init__(self, pg):
        self._inner = pg

    def __getattr__(self, name):
        if name == "recv_into":
            raise AttributeError(name)
        return getattr(self._inner, name)


class TestPGTransport:
    def test_send_recv_over_host_pg(self):
        store = KvStoreServer("127.0.0.1:0")
        pgs = [ProcessGroupHost(timeout=10.0) for _ in range(2)]
        try:
            addr = f"127.0.0.1:{store.port}/ckpt"

            def cfg(rank):
                pgs[rank].configure(addr, rank, 2, quorum_id=9)

            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(cfg, range(2)))

            state = make_state()
            sender = PGTransport(pgs[0], timeout=10.0)
            receiver = PGTransport(pgs[1], timeout=10.0)

            with ThreadPoolExecutor(max_workers=2) as ex:
                fs = ex.submit(
                    sender.send_checkpoint, [1], 4, state, 10.0
                )
                fr = ex.submit(
                    receiver.recv_checkpoint, 0, "<pg_transport>", 4, 10.0
                )
                fs.result(timeout=30)
                out = fr.result(timeout=30)
            assert_state_equal(state, out)
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()

    @pytest.mark.parametrize("pg", [
        "dummy", "xla_local", "proxy", "error_swallowing",
    ])
    def test_a_pg_without_recv_into_is_refused_in_words(self, pg):
        """The one wire lands raw frames in the receiver's buffers, so the
        recovery PG has to have recv_into: one that has not is refused
        where it is handed in, not on the first heal."""
        made = {
            "dummy": ProcessGroupDummy,
            "xla_local": lambda: ProcessGroupXLA(mode="local"),
            "proxy": lambda: _NoRecvInto(ProcessGroupHost()),
            "error_swallowing": lambda: ErrorSwallowingProcessGroupWrapper(
                ProcessGroupHost()
            ),
        }[pg]()
        with pytest.raises(TypeError, match="recv_into") as refused:
            PGTransport(made, timeout=1.0)
        assert type(made).__name__ in str(refused.value)

    @pytest.mark.parametrize("form", [
        "per_leaf", "batched", "other", "ranged_without_crcs",
    ])
    def test_a_header_that_is_not_ranged_is_refused_in_words(self, form):
        """The header comes from another process. One that is not (step,
        spec, "ranged", ranges, crcs) is answered with what came, well
        inside the timeout, and not by waiting for frames."""
        state = {"w": np.arange(8, dtype=np.float32)}
        spec, _ = flatten_state(state)
        ranges = plan_wire_ranges([m.nbytes for m in spec.leaves], 1 << 20)
        header = {
            "per_leaf": (4, spec),
            "batched": (4, spec, True),
            "other": (4, spec, "other", ranges, [0]),
            "ranged_without_crcs": (4, spec, "ranged", ranges),
        }[form]
        with host_pg_pair(f"header_{form}") as pgs:
            pgs[0].send(
                [np.frombuffer(pickle.dumps(header), np.uint8)], 1, tag=1
            ).wait(5.0)
            t0 = time.monotonic()
            with pytest.raises(RuntimeError, match="not the ranged wire") as said:
                PGTransport(pgs[1], timeout=10.0).recv_checkpoint(
                    0, "<pg_transport>", 4, 10.0
                )
            assert time.monotonic() - t0 < 5.0
        assert "TreeSpecPayload" in str(said.value)
        if form != "per_leaf":
            assert repr(header[2]) in str(said.value)

    def test_a_payload_above_the_chunk_splits_and_lands_in_place(
        self, monkeypatch
    ):
        """Chunk borders that fall inside leaves: the round trip holds and
        every range's frame lands in the template's own memory. Every
        chunk clears the host PG's 64 KiB raw-frame threshold, or it would
        ride the pickled path and be copied in."""
        chunk = 96 * 1024
        monkeypatch.setenv("TORCHFT_STREAM_CHUNK_BYTES", str(chunk))
        n = 32 * 1024  # 128 KiB an f32 leaf
        state = {f"w{i}": np.full(n, float(i), np.float32) for i in range(5)}
        template = {f"w{i}": np.zeros(n, np.float32) for i in range(5)}
        plan = plan_wire_ranges([4 * n] * 5, chunk)
        assert len(plan) == 7 and any(len(c) == 2 for c in plan), plan
        with host_pg_pair("ckpt_chunks") as pgs:
            calls = spy_on_recv_into(pgs[1])
            out = heal_over(pgs, state, template)
        for i in range(5):
            np.testing.assert_array_equal(out[f"w{i}"], state[f"w{i}"])
            assert out[f"w{i}"] is template[f"w{i}"], (
                f"leaf w{i} not absorbed in place across a chunk border"
            )
        # the header's recv has no buffers; each chunk's ranges all landed
        assert calls == [(0, 0)] + [(len(c), len(c)) for c in plan]

    def test_the_sender_leaves_at_most_a_window_of_chunks_unwaited(
        self, monkeypatch
    ):
        """Back-pressure: the issue loop runs SEND_WINDOW chunks ahead of
        the wire and no further, however long the plan."""
        monkeypatch.setenv("TORCHFT_STREAM_CHUNK_BYTES", str(64 * 1024))
        state = {"w": np.arange(262_144, dtype=np.float32)}  # 16 chunks
        with host_pg_pair("ckpt_window") as pgs:
            real_send = pgs[0].send
            book = {"issued": 0, "waited": 0, "most": 0}

            class Counted:
                def __init__(self, work):
                    self._work = work

                def wait(self, timeout=None):
                    done = self._work.wait(timeout)
                    book["waited"] += 1
                    return done

            def send(arrays, dst, tag=0):
                work = real_send(arrays, dst, tag)
                if tag != 2:
                    return work
                book["issued"] += 1
                book["most"] = max(
                    book["most"], book["issued"] - book["waited"]
                )
                return Counted(work)

            pgs[0].send = send
            out = heal_over(pgs, state)
        np.testing.assert_array_equal(out["w"], state["w"])
        assert book["issued"] == book["waited"] == 16
        assert book["most"] == PGTransport.SEND_WINDOW

    _CHUNK = 64 * 1024
    # trees the ranged tests of TestChunkedStreaming do not send
    _TREES = {
        "empty": lambda: {},
        "zero_size_leaf": lambda: {
            "a": np.arange(20_000, dtype=np.float32),
            "b": np.zeros((0, 4), np.float32),
            "c": np.arange(6, dtype=np.int32),
        },
        "pickled_leaf_between_arrays": lambda: {
            "a": np.arange(20_000, dtype=np.float32),
            "b": "a leaf that is no array",
            "c": np.arange(20_000, dtype=np.float64),
        },
        "bfloat16_leaf": lambda: {
            "w": np.arange(40_000).astype(jnp.bfloat16),
        },
        "many_small_leaves_in_one_chunk": lambda: {
            f"w{i:02d}": np.full(16, float(i), np.float32) for i in range(50)
        },
        "a_leaf_of_exactly_a_chunk": lambda: {
            "w": (np.arange(TestPGTransport._CHUNK) % 251).astype(np.uint8),
            "tail": np.arange(3, dtype=np.float32),
        },
        "a_leaf_of_a_chunk_and_a_byte": lambda: {
            "w": (np.arange(TestPGTransport._CHUNK + 1) % 251).astype(np.uint8),
            "tail": np.arange(3, dtype=np.float32),
        },
    }

    @pytest.mark.parametrize("templated", [False, True],
                             ids=["fresh", "into_template"])
    @pytest.mark.parametrize("tree", sorted(_TREES))
    def test_round_trip_on_the_ranged_wire(self, monkeypatch, tree, templated):
        """What the per-leaf and the batched wire's tests held, on the wire
        that stays: the tree comes back leaf for leaf, dtype for dtype, and
        with a template every array leaf IS the template's."""
        monkeypatch.setenv("TORCHFT_STREAM_CHUNK_BYTES", str(self._CHUNK))
        state = self._TREES[tree]()
        template = None
        if templated:
            template = jax.tree_util.tree_map(
                lambda x: np.zeros_like(x) if isinstance(x, np.ndarray) else x,
                state,
            )
        with host_pg_pair(f"rt_{tree}_{int(templated)}") as pgs:
            out = heal_over(pgs, state, template)
        assert_state_equal(state, out)
        want, got = (jax.tree_util.tree_leaves(t) for t in (state, out))
        held = jax.tree_util.tree_leaves(template) if templated else got
        for w, g, t in zip(want, got, held):
            if isinstance(w, np.ndarray):
                assert g.dtype == w.dtype and g.shape == w.shape
                assert not templated or g is t

    def test_inplace_recv_places_on_template_sharding(self):
        store = KvStoreServer("127.0.0.1:0")
        pgs = [ProcessGroupHost(timeout=10.0) for _ in range(2)]
        try:
            addr = f"127.0.0.1:{store.port}/ckpt2"

            def cfg(rank):
                pgs[rank].configure(addr, rank, 2, quorum_id=10)

            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(cfg, range(2)))

            state = {"w": jnp.ones((4, 4), dtype=jnp.float32) * 5}
            template = {"w": jnp.zeros((4, 4), dtype=jnp.float32)}
            sender = PGTransport(pgs[0], timeout=10.0)
            receiver = PGTransport(
                pgs[1], timeout=10.0, state_dict_template=lambda: template
            )
            with ThreadPoolExecutor(max_workers=2) as ex:
                fs = ex.submit(sender.send_checkpoint, [1], 0, state, 10.0)
                fr = ex.submit(
                    receiver.recv_checkpoint, 0, "<pg_transport>", 0, 10.0
                )
                fs.result(timeout=30)
                out = fr.result(timeout=30)
            assert isinstance(out["w"], jax.Array)
            np.testing.assert_allclose(np.asarray(out["w"]), 5.0)
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()


class TestInplaceDegradedPaths:
    """A template that cannot absorb the incoming leaves must warn and fall
    back to the wire buffer — never die mid-stream or silently coerce."""

    def _roundtrip(self, state, template, tag):
        with host_pg_pair(tag) as pgs:
            return heal_over(pgs, state, template, step=0)

    def test_host_template_absorbs_in_place(self):
        state = {"w": np.arange(64, dtype=np.float32)}
        template = {"w": np.zeros(64, dtype=np.float32)}
        out = self._roundtrip(state, template, "inplace-ok")
        assert out["w"] is template["w"]  # landed IN the template buffer
        np.testing.assert_array_equal(out["w"], state["w"])

    def test_large_leaf_streams_directly_into_template(self):
        """Leaves above the raw-frame threshold (64 KiB) take the
        recv_into fast path: the wire frame lands in the template's own
        memory. The fallback (recv + copyto) would produce identical
        outputs, so the fast path is pinned by SPYING on recv_into —
        identity alone can't detect its regression."""
        n = 64 * 1024  # 256 KiB of f32: raw-frame path on the host PG
        state = {"w": np.arange(n, dtype=np.float32)}
        template = {"user": {"w": np.zeros(n, dtype=np.float32)}}
        with host_pg_pair("inplace-raw") as pgs:
            calls = spy_on_recv_into(pgs[1])
            out = heal_over(pgs, {"user": state}, template, step=0)
        assert out["user"]["w"] is template["user"]["w"]
        np.testing.assert_array_equal(out["user"]["w"], state["w"])
        # the big leaf went through recv_into AND was absorbed in place
        assert (1, 1) in calls, calls

    def test_recv_into_identity_contract(self):
        """ProcessGroupHost.recv_into: a matching buffer IS the returned
        entry (raw path), a mismatched buffer yields a fresh array, and
        sub-threshold pickled messages ignore the buffers."""
        from torchft_tpu.coordination import KvStoreServer
        from torchft_tpu.process_group import ProcessGroupHost

        store = KvStoreServer("127.0.0.1:0")
        pgs = [ProcessGroupHost(timeout=10.0) for _ in range(2)]
        try:
            addr = f"127.0.0.1:{store.port}/recvinto"
            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(lambda r: pgs[r].configure(addr, r, 2, 42),
                            range(2)))
            big = np.arange(64 * 1024, dtype=np.float32)  # raw-frame path

            # matching buffer: identity
            buf = np.zeros_like(big)
            w = pgs[0].send([big], 1, tag=5)
            got = pgs[1].recv_into([buf], 0, tag=5).get_future().wait(10)
            w.wait(10)
            assert got[0] is buf
            np.testing.assert_array_equal(buf, big)

            # mismatched dtype: fresh allocation, data still correct
            wrong = np.zeros(big.shape, np.int32)
            w = pgs[0].send([big], 1, tag=6)
            got = pgs[1].recv_into([wrong], 0, tag=6).get_future().wait(10)
            w.wait(10)
            assert got[0] is not wrong
            np.testing.assert_array_equal(got[0], big)

            # small message: pickled path, buffers ignored
            small = np.arange(4, dtype=np.float32)
            sbuf = np.zeros(4, np.float32)
            w = pgs[0].send([small], 1, tag=7)
            got = pgs[1].recv_into([sbuf], 0, tag=7).get_future().wait(10)
            w.wait(10)
            assert got[0] is not sbuf
            np.testing.assert_array_equal(got[0], small)
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()

    def test_dtype_mismatch_warns_and_keeps_values_exact(self, caplog):
        state = {"w": np.arange(64, dtype=np.float32)}
        template = {"w": np.zeros(64, dtype=np.int32)}  # same shape, wrong dtype
        with caplog.at_level("WARNING",
                             logger="torchft_tpu.checkpointing.pg_transport"):
            out = self._roundtrip(state, template, "inplace-dtype")
        assert out["w"] is not template["w"]  # no silent unsafe coercion
        assert out["w"].dtype == np.float32
        np.testing.assert_array_equal(out["w"], state["w"])
        assert any("in-place receive degraded" in r.message
                   for r in caplog.records)

    def test_inplace_recv_lands_on_multidevice_sharding(self, cpu_devices):
        """SURVEY hard-part #4 (healing while compiled): recovered state
        must land with the template's NamedSharding over the mesh — a pure
        data swap that can't invalidate jitted programs."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(cpu_devices[:8]).reshape(8), ("x",))
        sharding = NamedSharding(mesh, P("x"))
        template = {
            "w": jax.device_put(jnp.zeros((16, 4), jnp.float32), sharding)
        }
        state = {"w": np.arange(64, dtype=np.float32).reshape(16, 4)}
        step = jax.jit(lambda t: t["w"].sum())
        step(template)  # compiled against the template's sharding

        out = self._roundtrip(state, template, "inplace-sharded")
        assert isinstance(out["w"], jax.Array)
        assert out["w"].sharding == sharding
        np.testing.assert_array_equal(np.asarray(out["w"]), state["w"])

        # the healed tree must hit the SAME executable — sharding-identical
        # arrays are a pure data swap, no retrace/recompile
        assert float(step(out)) == float(np.sum(state["w"]))
        assert step._cache_size() == 1

    def test_device_template_dtype_mismatch_warns_keeps_values(self, caplog):
        state = {"w": np.arange(64, dtype=np.float32)}
        template = {"w": jnp.zeros(64, dtype=jnp.bfloat16)}  # device, wrong dtype
        with caplog.at_level("WARNING",
                             logger="torchft_tpu.checkpointing.pg_transport"):
            out = self._roundtrip(state, template, "inplace-dev-dtype")
        assert out["w"].dtype == np.float32  # no silent astype truncation
        np.testing.assert_array_equal(np.asarray(out["w"]), state["w"])
        assert any("in-place receive degraded" in r.message
                   for r in caplog.records)

    def test_sender_tree_larger_than_template_warns_not_crashes(self, caplog):
        state = {"a": np.ones(16, np.float32), "b": np.full(16, 2, np.float32)}
        template = {"a": np.zeros(16, np.float32)}  # one leaf short
        with caplog.at_level("WARNING",
                             logger="torchft_tpu.checkpointing.pg_transport"):
            out = self._roundtrip(state, template, "inplace-short")
        np.testing.assert_array_equal(out["a"], state["a"])
        np.testing.assert_array_equal(out["b"], state["b"])
        assert any("in-place receive degraded" in r.message
                   for r in caplog.records)


class TestHTTPInplace:
    """The default transport's in-place receive: matching host leaves
    stream from the socket DIRECTLY into the template's buffers; device
    templates device_put; mismatches warn and degrade."""

    def _roundtrip(self, state, template):
        send = HTTPTransport(timeout=20.0, num_chunks=2)
        recv = HTTPTransport(timeout=20.0, state_dict_template=lambda: template)
        try:
            send.send_checkpoint([1], 3, state, 20.0)
            return recv.recv_checkpoint(0, send.metadata(), 3, 20.0)
        finally:
            send.shutdown()
            recv.shutdown()

    def test_host_template_absorbs_stream(self):
        state = {"w": np.arange(64, dtype=np.float32),
                 "b": np.full(32, 2.0, np.float32)}
        template = {"w": np.zeros(64, np.float32), "b": np.zeros(32, np.float32)}
        out = self._roundtrip(state, template)
        assert out["w"] is template["w"]  # streamed INTO the template
        assert out["b"] is template["b"]
        np.testing.assert_array_equal(out["w"], state["w"])
        np.testing.assert_array_equal(out["b"], state["b"])

    def test_device_template_lands_on_sharding(self, cpu_devices):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(cpu_devices[:4]).reshape(4), ("x",))
        sharding = NamedSharding(mesh, P("x"))
        template = {"w": jax.device_put(jnp.zeros((8, 2), jnp.float32), sharding)}
        state = {"w": np.arange(16, dtype=np.float32).reshape(8, 2)}
        out = self._roundtrip(state, template)
        assert isinstance(out["w"], jax.Array)
        assert out["w"].sharding == sharding
        np.testing.assert_array_equal(np.asarray(out["w"]), state["w"])

    def test_dtype_mismatch_warns_keeps_values(self, caplog):
        state = {"w": np.arange(64, dtype=np.float32)}
        template = {"w": np.zeros(64, np.int32)}
        with caplog.at_level(
            "WARNING", logger="torchft_tpu.checkpointing.http_transport"
        ):
            out = self._roundtrip(state, template)
        assert out["w"] is not template["w"]
        assert out["w"].dtype == np.float32
        np.testing.assert_array_equal(out["w"], state["w"])
        assert any("in-place receive degraded" in r.message
                   for r in caplog.records)

    def test_sender_tree_larger_than_template_warns(self, caplog):
        state = {"a": np.ones(16, np.float32), "b": np.full(16, 2, np.float32)}
        template = {"a": np.zeros(16, np.float32)}
        with caplog.at_level(
            "WARNING", logger="torchft_tpu.checkpointing.http_transport"
        ):
            out = self._roundtrip(state, template)
        np.testing.assert_array_equal(out["a"], state["a"])
        np.testing.assert_array_equal(out["b"], state["b"])
        assert any("in-place receive degraded" in r.message
                   for r in caplog.records)

    def test_non_callable_template_rejected(self):
        with pytest.raises(TypeError, match="zero-arg callable"):
            HTTPTransport(state_dict_template={"w": np.zeros(4)})

    def test_structural_drift_never_streams_into_wrong_buffers(self, caplog):
        """Shape-coincident structural drift (sender gained a key) must
        degrade the WHOLE receive — index-aligned placement would stream
        the sender's 'b' leaf into the template's 'c' buffer."""
        state = {"a": np.full(16, 1.0, np.float32),
                 "b": np.full(16, 2.0, np.float32)}
        template = {"a": np.zeros(16, np.float32),
                    "c": np.zeros(16, np.float32)}  # same count, drifted keys
        with caplog.at_level(
            "WARNING", logger="torchft_tpu.checkpointing.http_transport"
        ):
            out = self._roundtrip(state, template)
        # data correct, and NO template buffer was written
        np.testing.assert_array_equal(out["a"], state["a"])
        np.testing.assert_array_equal(out["b"], state["b"])
        np.testing.assert_array_equal(template["a"], 0.0)
        np.testing.assert_array_equal(template["c"], 0.0)
        assert any("tree structure differs" in r.message
                   for r in caplog.records)


def make_big_state():
    """Leaves above the raw-frame threshold, mixed dtypes incl bf16, plus a
    pickled non-array leaf — the streaming-path shapes."""
    rng = np.random.default_rng(5)
    return {
        "w_f32": rng.standard_normal(40_000).astype(np.float32),
        "w_bf16": jnp.asarray(rng.standard_normal(50_000), jnp.bfloat16),
        "tiny": np.arange(3.0),
        "meta": {"lr": 0.25, "name": "big"},
    }


class TestStreamingPaths:
    """Large-leaf streaming through both transports: HTTP frames straight
    from staged arrays into preallocated receive buffers; PG ships raw
    frames for >=64KiB leaves (no pickle copy)."""

    def test_http_large_mixed_state(self):
        state = make_big_state()
        send = HTTPTransport(timeout=20.0, num_chunks=3)
        recv = HTTPTransport(timeout=20.0)
        try:
            send.send_checkpoint([1], 11, state, 20.0)
            out = recv.recv_checkpoint(0, send.metadata(), 11, 20.0)
            assert_state_equal(state, out)
            assert out["w_bf16"].dtype == jnp.bfloat16
        finally:
            send.shutdown()
            recv.shutdown()

    def test_pg_large_mixed_state_uses_raw_frames(self):
        store = KvStoreServer("127.0.0.1:0")
        pgs = [ProcessGroupHost(timeout=20.0) for _ in range(2)]
        try:
            addr = f"127.0.0.1:{store.port}/bigckpt"
            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(lambda r: pgs[r].configure(addr, r, 2, 19), range(2)))
            state = make_big_state()
            sender = PGTransport(pgs[0], timeout=20.0)
            receiver = PGTransport(pgs[1], timeout=20.0)
            with ThreadPoolExecutor(max_workers=2) as ex:
                fs = ex.submit(sender.send_checkpoint, [1], 5, state, 20.0)
                fr = ex.submit(receiver.recv_checkpoint, 0, "<pg_transport>", 5, 20.0)
                fs.result(timeout=60)
                out = fr.result(timeout=60)
            assert_state_equal(state, out)
            # the big leaves really took the raw-frame path: raw frames are
            # counted by send_raw, whose traffic dwarfs the pickled headers
            sent = pgs[0]._gen.comm.bytes_sent
            payload = 40_000 * 4 + 50_000 * 2
            assert sent < payload * 1.5, (sent, payload)
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()


class TestChunkedStreaming:
    """Byte-range chunking: a single huge leaf splits across >2 wire chunks
    on both transports, recovers bitwise-identical, reports per-stream
    timings, and aborts cleanly on a corrupted mid-stream plan."""

    def test_plan_wire_ranges_splits_single_large_leaf(self):
        from torchft_tpu.checkpointing.transport import plan_wire_ranges

        plan = plan_wire_ranges([100], 30)
        assert [r for c in plan for r in c] == [
            (0, 0, 30), (0, 30, 30), (0, 60, 30), (0, 90, 10)
        ]
        # multi-leaf packing; zero-byte leaves still ride as a range so the
        # receiver can finalize them
        plan = plan_wire_ranges([10, 0, 25], 16)
        flat = [r for c in plan for r in c]
        assert (1, 0, 0) in flat
        covered = {}
        for j, off, ln in flat:
            covered[j] = covered.get(j, 0) + ln
        assert covered[0] == 10 and covered[2] == 25

    def test_http_single_leaf_multi_chunk_bitwise_equal(self):
        # one 1 MiB leaf forced into 4 chunks — leaf-granularity chunking
        # could never split this
        state = {"params": {"w": np.arange(262_144, dtype=np.float32)}}
        src = HTTPTransport(timeout=10.0, num_chunks=4)
        dst = HTTPTransport(timeout=10.0)
        try:
            src.send_checkpoint([1], 7, state, 10.0)
            out = dst.recv_checkpoint(0, src.metadata(), 7, 10.0)
            np.testing.assert_array_equal(out["params"]["w"], state["params"]["w"])
            stats = dst.last_recv_timings()
            assert stats is not None and stats.num_chunks > 2
            assert stats.total_bytes == state["params"]["w"].nbytes
            assert stats.mb_per_s > 0
        finally:
            src.shutdown()
            dst.shutdown()

    def test_http_mid_stream_corruption_aborts(self):
        """A wire plan whose ranges overlap (duplicate chunk served twice)
        must abort the recv with an error — never return torn state."""
        state = {"w": np.arange(262_144, dtype=np.float32)}
        src = HTTPTransport(timeout=5.0, num_chunks=4)
        dst = HTTPTransport(timeout=5.0)
        try:
            src.send_checkpoint([1], 7, state, 5.0)
            step, spec, payloads, assignments = src._staged
            src._staged = (step, spec, payloads, [assignments[0]] * 2)
            with pytest.raises((ConnectionError, OSError, RuntimeError)):
                dst.recv_checkpoint(0, src.metadata(), 7, 5.0)
        finally:
            src.shutdown()
            dst.shutdown()

    def test_pg_ranged_single_leaf_multi_chunk_bitwise_equal(self, monkeypatch):
        # shrink the chunk knob so a 1 MiB leaf pipelines as 16 ranged
        # chunks over the host PG (recv_into path)
        monkeypatch.setenv("TORCHFT_STREAM_CHUNK_BYTES", str(64 * 1024))
        store = KvStoreServer("127.0.0.1:0")
        pgs = [ProcessGroupHost(timeout=10.0) for _ in range(2)]
        try:
            addr = f"127.0.0.1:{store.port}/rangedckpt"
            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(lambda r: pgs[r].configure(addr, r, 2, 21), range(2)))
            state = {"params": {"w": np.arange(262_144, dtype=np.float32)}}
            sender = PGTransport(pgs[0], timeout=10.0)
            receiver = PGTransport(pgs[1], timeout=10.0)
            with ThreadPoolExecutor(max_workers=2) as ex:
                fs = ex.submit(sender.send_checkpoint, [1], 6, state, 10.0)
                fr = ex.submit(receiver.recv_checkpoint, 0, "<pg_transport>", 6, 10.0)
                fs.result(timeout=30)
                out = fr.result(timeout=30)
            np.testing.assert_array_equal(out["params"]["w"], state["params"]["w"])
            stats = receiver.last_recv_timings()
            assert stats is not None and stats.num_chunks > 2
            assert stats.total_bytes == state["params"]["w"].nbytes
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()

    def test_pg_ranged_mid_stream_sender_death_aborts(self):
        """Sender dies after the first ranged chunk: the pipelined receiver
        must surface an error within its timeout, not hang or return torn
        state."""
        from torchft_tpu.checkpointing._serialization import payload_memoryview
        from torchft_tpu.checkpointing.pg_transport import _chunk_crc

        store = KvStoreServer("127.0.0.1:0")
        pgs = [ProcessGroupHost(timeout=3.0) for _ in range(2)]
        try:
            addr = f"127.0.0.1:{store.port}/deadckpt"
            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(lambda r: pgs[r].configure(addr, r, 2, 23), range(2)))
            state = {"w": np.arange(262_144, dtype=np.float32)}
            spec, payloads = flatten_state(state)
            wire = payload_memoryview(payloads[0])
            ranges = plan_wire_ranges([len(wire)], 64 * 1024)
            wires = [np.frombuffer(wire, np.uint8)]
            crcs = [_chunk_crc(wires, chunk) for chunk in ranges]
            header = pickle.dumps((6, spec, "ranged", ranges, crcs))

            def half_send():
                # the real wire: header on tag=1, chunk payloads on tag=2
                pgs[0].send(
                    [np.frombuffer(header, np.uint8)], 1, tag=1
                ).wait(timeout=5.0)
                j, off, ln = ranges[0][0]
                pgs[0].send(
                    [np.frombuffer(wire[off : off + ln], np.uint8)], 1, tag=2
                ).wait(timeout=5.0)
                # ...and nothing more: chunks 2..N never arrive

            receiver = PGTransport(pgs[1], timeout=3.0)
            with ThreadPoolExecutor(max_workers=2) as ex:
                fs = ex.submit(half_send)
                fr = ex.submit(
                    receiver.recv_checkpoint, 0, "<pg_transport>", 6, 3.0
                )
                fs.result(timeout=10)
                with pytest.raises(Exception):
                    fr.result(timeout=30)
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()


class TestResilientRecv:
    """Wire v3 resilience: crc-verified chunks, ranged resume after a
    mid-transfer source death, and multi-peer failover (ISSUE 4)."""

    @staticmethod
    def _policy(attempts=3):
        from torchft_tpu.retry import RetryPolicy

        return RetryPolicy(max_attempts=attempts, base_s=0.0, jitter=0.0)

    def test_corrupt_chunk_detected_and_refetched(self):
        """A flipped payload byte (canonical crc trailer) is caught by the
        receiver's running crc32; the chunk is re-fetched from byte 0 and
        the corrupt bytes are never credited into the result."""
        state = {"w": np.arange(65_536, dtype=np.float32)}
        src = HTTPTransport(timeout=10.0, num_chunks=4)
        dst = HTTPTransport(timeout=10.0, retry_policy=self._policy())
        events = []
        try:
            src.send_checkpoint([1], 5, state, 10.0)
            src.inject_chunk_fault(2, "corrupt", times=1)
            out = dst.recv_checkpoint_multi(
                [("src", lambda: src.metadata())],
                step=5,
                timeout=10.0,
                on_event=lambda kind, **f: events.append((kind, f)),
            )
            np.testing.assert_array_equal(out["w"], state["w"])
            stats = dst.last_recv_timings()
            assert stats is not None
            assert stats.crc_failures == 1
            assert stats.failovers == 0
            crc_events = [f for k, f in events if k == "chunk_crc_failure"]
            assert len(crc_events) == 1 and crc_events[0]["chunk"] == 2
        finally:
            src.shutdown()
            dst.shutdown()

    def test_fetch_and_place_are_timed_events(self):
        """One heal_fetch per chunk (socket -> buffers, its bytes) and one
        heal_place per leaf that is placed onto a device template, each
        with perf_counter endpoints; a host template the socket streams
        into directly is never placed."""
        import jax.numpy as jnp

        state = {"w": np.arange(65_536, dtype=np.float32),
                 "b": np.arange(1_024, dtype=np.float32)}
        for template, placed in (
            ({"w": jnp.zeros(65_536, jnp.float32), "b": jnp.zeros(1_024, jnp.float32)}, 2),
            ({"w": np.zeros(65_536, np.float32), "b": np.zeros(1_024, np.float32)}, 0),
        ):
            src = HTTPTransport(timeout=10.0, num_chunks=4)
            dst = HTTPTransport(timeout=10.0, state_dict_template=lambda: template)
            events = []
            lock = threading.Lock()

            def on_event(kind, **f):
                with lock:
                    events.append((kind, f))

            try:
                src.send_checkpoint([1], 5, state, 10.0)
                out = dst.recv_checkpoint_multi(
                    [("src", lambda: src.metadata())], step=5, timeout=10.0,
                    on_event=on_event)
            finally:
                src.shutdown()
                dst.shutdown()
            np.testing.assert_array_equal(np.asarray(out["w"]), state["w"])
            fetch = [f for k, f in events if k == "heal_fetch"]
            place = [f for k, f in events if k == "heal_place"]
            assert sorted(f["chunk"] for f in fetch) == [0, 1, 2, 3]
            assert sum(f["bytes"] for f in fetch) == 4 * (65_536 + 1_024)
            assert len(place) == placed
            assert sorted(f["bytes"] for f in place) == sorted(
                [4 * 1_024, 4 * 65_536][:placed])
            assert all(f["t1_pc"] >= f["t0_pc"] for f in fetch + place)

    def test_source_stall_resumes_at_verified_offset(self):
        """A v3 source dropping the connection mid-chunk is re-fetched with
        a ranged request from the last verified byte, not from scratch."""
        state = {"w": np.arange(262_144, dtype=np.float32)}
        src = HTTPTransport(timeout=10.0, num_chunks=1)
        dst = HTTPTransport(timeout=10.0, retry_policy=self._policy())
        events = []
        try:
            src.send_checkpoint([1], 9, state, 10.0)
            src.inject_chunk_fault(0, "die", times=1)
            out = dst.recv_checkpoint_multi(
                [("src", lambda: src.metadata())],
                step=9,
                timeout=10.0,
                on_event=lambda kind, **f: events.append((kind, f)),
            )
            np.testing.assert_array_equal(out["w"], state["w"])
            stats = dst.last_recv_timings()
            assert stats is not None and stats.retries == 1
            retry_events = [f for k, f in events if k == "heal_retry"]
            assert len(retry_events) == 1
            # resumed mid-body: the offset reflects the verified prefix
            assert 0 < retry_events[0]["resume_offset"] < state["w"].nbytes
        finally:
            src.shutdown()
            dst.shutdown()

    def test_failover_to_second_peer_mid_heal(self):
        """Primary dies on every serve of chunk 0: the receiver exhausts its
        same-source budget, fails over to the fallback peer, and completes
        the heal — the fallback resumes the half-fetched chunk rather than
        restarting the receive."""
        state = {"w": np.arange(262_144, dtype=np.float32), "step": 42}
        primary = HTTPTransport(timeout=10.0, num_chunks=2)
        fallback = HTTPTransport(timeout=10.0, num_chunks=2)
        dst = HTTPTransport(timeout=10.0, retry_policy=self._policy(attempts=2))
        events = []
        try:
            primary.send_checkpoint([1], 7, state, 10.0)
            fallback.send_checkpoint([1], 7, state, 10.0)
            primary.inject_chunk_fault(0, "die", times=-1)
            out = dst.recv_checkpoint_multi(
                [
                    ("primary", lambda: primary.metadata()),
                    ("fallback", lambda: fallback.metadata()),
                ],
                step=7,
                timeout=10.0,
                on_event=lambda kind, **f: events.append((kind, f)),
            )
            assert_state_equal(out, state)
            stats = dst.last_recv_timings()
            assert stats is not None and stats.failovers == 1
            fo = [f for k, f in events if k == "heal_failover"]
            assert len(fo) == 1 and fo[0]["source"] == "fallback"
        finally:
            primary.shutdown()
            fallback.shutdown()
            dst.shutdown()

    def test_unreachable_primary_falls_back(self):
        """A metadata_fn that cannot even resolve its peer (dead manager)
        costs one attempt and the heal proceeds on the next source."""
        state = make_state()
        fallback = HTTPTransport(timeout=10.0, num_chunks=2)
        dst = HTTPTransport(timeout=10.0, retry_policy=self._policy())

        def dead_metadata():
            raise ConnectionError("manager gone")

        try:
            fallback.send_checkpoint([1], 3, state, 10.0)
            out = dst.recv_checkpoint_multi(
                [
                    ("dead", dead_metadata),
                    ("fallback", lambda: fallback.metadata()),
                ],
                step=3,
                timeout=10.0,
            )
            assert_state_equal(out, state)
            stats = dst.last_recv_timings()
            assert stats is not None and stats.failovers == 1
        finally:
            fallback.shutdown()
            dst.shutdown()

    def test_all_sources_exhausted_raises_with_context(self):
        state = {"w": np.arange(4096, dtype=np.float32)}
        src = HTTPTransport(timeout=5.0, num_chunks=1)
        dst = HTTPTransport(timeout=5.0, retry_policy=self._policy(attempts=2))
        try:
            src.send_checkpoint([1], 2, state, 5.0)
            src.inject_chunk_fault(0, "die", times=-1)
            with pytest.raises(RuntimeError, match="all 2/2 source"):
                dst.recv_checkpoint_multi(
                    [
                        ("p", lambda: src.metadata()),
                        ("q", lambda: src.metadata()),
                    ],
                    step=2,
                    timeout=5.0,
                )
        finally:
            src.shutdown()
            dst.shutdown()

    def test_v2_sender_interop_restarts_chunk_without_resume(self, monkeypatch):
        """Against a pre-crc (v2) peer the receiver sends no crc/offset
        query params; a stall falls back to a full-chunk restart and the
        heal still completes bitwise-identical."""
        from torchft_tpu.checkpointing import http_transport as ht

        state = {"w": np.arange(65_536, dtype=np.float32)}
        src = HTTPTransport(timeout=10.0, num_chunks=2)
        dst = HTTPTransport(timeout=10.0, retry_policy=self._policy())
        try:
            monkeypatch.setattr(ht, "_WIRE_VERSION", 2)
            src.send_checkpoint([1], 4, state, 10.0)
            src.inject_chunk_fault(1, "die", times=1)
            events = []
            out = dst.recv_checkpoint_multi(
                [("src", lambda: src.metadata())],
                step=4,
                timeout=10.0,
                on_event=lambda kind, **f: events.append((kind, f)),
            )
            np.testing.assert_array_equal(out["w"], state["w"])
            retry_events = [f for k, f in events if k == "heal_retry"]
            # v2 restart: the retry re-fetches from byte 0, never a suffix
            assert len(retry_events) == 1
            assert retry_events[0]["resume_offset"] == 0
        finally:
            src.shutdown()
            dst.shutdown()

    def test_pg_ranged_crc_mismatch_discards_heal(self, monkeypatch):
        """A sender whose advertised per-chunk crc disagrees with the bytes
        on the wire must fail the recv (detection-only on the push-based
        plane) instead of silently loading corrupt state."""
        from torchft_tpu.checkpointing import pg_transport as pt

        monkeypatch.setenv("TORCHFT_STREAM_CHUNK_BYTES", str(64 * 1024))
        real_crc = pt._chunk_crc
        monkeypatch.setattr(
            pt, "_chunk_crc", lambda wires, chunk: real_crc(wires, chunk) ^ 1
        )
        store = KvStoreServer("127.0.0.1:0")
        pgs = [ProcessGroupHost(timeout=5.0) for _ in range(2)]
        try:
            addr = f"127.0.0.1:{store.port}/crcckpt"
            with ThreadPoolExecutor(max_workers=2) as ex:
                list(ex.map(lambda r: pgs[r].configure(addr, r, 2, 31), range(2)))
            state = {"w": np.arange(262_144, dtype=np.float32)}
            sender = PGTransport(pgs[0], timeout=5.0)
            receiver = PGTransport(pgs[1], timeout=5.0)
            with ThreadPoolExecutor(max_workers=2) as ex:
                fs = ex.submit(sender.send_checkpoint, [1], 8, state, 5.0)
                fr = ex.submit(
                    receiver.recv_checkpoint, 0, "<pg_transport>", 8, 5.0
                )
                with pytest.raises(RuntimeError, match="crc"):
                    fr.result(timeout=30)
                try:
                    fs.result(timeout=30)
                except Exception:
                    pass  # sender may observe the aborted stream
        finally:
            for pg in pgs:
                pg.shutdown()
            store.shutdown()
