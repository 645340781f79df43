"""Checkpoint transport over ProcessGroup point-to-point sends.

Design mirror of the reference PGTransport
(torchft/checkpointing/pg_transport.py:168-305): a pickled spec (tree
structure + per-leaf metadata) followed by raw per-leaf buffers, sent via a
*second* process group dedicated to recovery so healing traffic never
interleaves with training collectives. Supports in-place receive into an
existing state pytree: leaves are rebuilt with the template's dtype/sharding
(``jax.device_put`` to the template leaf's sharding), the JAX analog of the
reference's HBM-to-HBM in-place recv (pg_transport.py:235-305).

One wire (docs/protocol.md): a header ``(step, spec, "ranged", ranges,
crcs)`` on tag 1, then one tag-2 message per chunk of byte ranges. The
recovery PG must stream raw frames into caller buffers (``recv_into``:
:class:`ProcessGroupHost`); ``__init__`` refuses one that cannot.
"""

from __future__ import annotations

import logging
import pickle
import zlib
from datetime import timedelta
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from torchft_tpu.checkpointing._serialization import (
    TensorMeta,
    TreeSpecPayload,
    can_absorb,
    flatten_state,
    leaf_from_bytes,
    place_leaf_like,
    template_leaves_for,
)
from torchft_tpu.checkpointing.transport import (
    CheckpointTransport,
    StreamTimings,
    pipelined,
    plan_wire_ranges,
    stream_chunk_bytes,
)
from torchft_tpu.process_group import ProcessGroup

logger = logging.getLogger(__name__)

__all__ = ["PGTransport"]


def _chunk_crc(wires: List[np.ndarray], chunk: List[Tuple[int, int, int]]) -> int:
    """crc32 over a chunk's concatenated range payloads, in plan order."""
    crc = 0
    for j, off, ln in chunk:
        crc = zlib.crc32(wires[j][off : off + ln], crc)
    return crc & 0xFFFFFFFF


class PGTransport(CheckpointTransport[Any]):
    """Send checkpoints over PG send/recv.

    ``state_dict_template`` (optional callable returning a pytree) enables
    in-place receive: received leaves are placed onto the same device/sharding
    as the template's leaves; host ndarray template leaves are written
    in place (``np.copyto``) so repeated heals reuse one allocation.

    In-place contract (same as the reference's HBM in-place recv,
    pg_transport.py:235-305): leaves land in the template AS THEY ARRIVE,
    so a mid-stream failure (sender died, timeout) raises with the template
    torn between old and new state. That is safe exactly when a failed heal
    is never committed and is retried before the state is used — which the
    Manager protocol guarantees (a recv_checkpoint exception reaches
    ``report_error``, the step is discarded at should_commit, and the next
    quorum heals again). Callers outside the Manager who hand their live
    state as the template must either provide the same guarantee or pass a
    scratch template.
    """

    def __init__(
        self,
        pg: ProcessGroup,
        timeout: "float | timedelta" = 60.0,
        state_dict_template: Optional[Callable[[], Any]] = None,
        snapshot_send: bool = True,
    ) -> None:
        """``snapshot_send=False`` streams straight from the caller's
        arrays (no per-heal checkpoint copy). Safe only when nothing
        mutates registered numpy state while send_checkpoint runs — true
        under a sync-quorum Manager (the trainer is blocked inside
        start_quorum during the heal) or when all mutable state is
        jax.Arrays (immutable buffers; functional updates rebind instead
        of writing in place). An async-quorum host-plane trainer that
        mutates numpy state in place (EMA buffers, running stats) must
        keep the default or a heal can read a torn leaf."""
        if not callable(getattr(pg, "recv_into", None)):
            # checked where the PG is handed in: a wrapper that hides the
            # raw-frame receive would otherwise fail on the first heal
            raise TypeError(
                f"PGTransport needs a recovery process group with "
                f"recv_into(buffers, src, tag) (raw frames received into "
                f"the caller's buffers, as ProcessGroupHost has); "
                f"{type(pg).__name__} has none"
            )
        self._pg = pg
        self._snapshot_send = snapshot_send
        self._timeout = (
            timeout.total_seconds() if isinstance(timeout, timedelta) else timeout
        )
        if state_dict_template is not None and not callable(state_dict_template):
            # fail at construction, not on the first heal (where the
            # TypeError would surface as an endlessly-retried heal error)
            raise TypeError(
                "state_dict_template must be a zero-arg callable returning "
                "the template pytree, not the pytree itself "
                f"(got {type(state_dict_template).__name__})"
            )
        self._template_fn = state_dict_template

    def metadata(self) -> str:
        return "<pg_transport>"

    def configure(
        self,
        store_addr: str,
        replica_rank: int,
        replica_world_size: int,
        quorum_id: int = 0,
    ) -> None:
        """Rendezvous the recovery PG with the current quorum (called by
        the Manager after its own PG reconfigure; see
        CheckpointTransport.configure). The recovery PG must be a separate
        instance from the Manager's — the host plane rejects mixing p2p
        and collective traffic on one generation."""
        self._pg.configure(
            store_addr, replica_rank, replica_world_size, quorum_id=quorum_id
        )

    # most chunks handed to the PG and not yet waited for: the writer
    # thread stays fed and the plan is never queued whole ahead of the wire
    SEND_WINDOW = 4

    def send_checkpoint(
        self, dst_ranks: List[int], step: int, state_dict: Any, timeout
    ) -> None:
        # snapshot_send=False streams straight from the caller's arrays
        # (see __init__); the default copies numpy leaves so a training
        # loop mutating them mid-stream cannot tear the checkpoint
        spec, payloads = flatten_state(
            state_dict, snapshot=self._snapshot_send
        )
        # Each message carries a chunk of BYTE RANGES (leaf_idx, offset,
        # nbytes) planned by plan_wire_ranges, so a single multi-GB leaf
        # splits across messages and the receiver overlaps the recv of
        # chunk i+1 with the device placement of chunk i (pipelined heal).
        # The plan rides the header: no cross-host determinism requirement
        # on the chunk-size knob. So does a crc32 per chunk, over its
        # concatenated range payloads.
        wires = [
            buf.reshape(-1).view(np.uint8)
            if isinstance(buf, np.ndarray)
            else np.frombuffer(buf, dtype=np.uint8)
            for buf in payloads
        ]
        ranges = plan_wire_ranges(
            [m.nbytes for m in spec.leaves], stream_chunk_bytes()
        )
        crcs = [_chunk_crc(wires, chunk) for chunk in ranges]
        header = pickle.dumps((step, spec, "ranged", ranges, crcs))
        for dst in dst_ranks:
            self._pg.send([np.frombuffer(header, dtype=np.uint8)], dst, tag=1).wait(
                self._timeout
            )
            pending: List[Any] = []
            for chunk in ranges:
                bufs = [wires[j][off : off + ln] for (j, off, ln) in chunk]
                pending.append(self._pg.send(bufs, dst, tag=2))
                if len(pending) >= self.SEND_WINDOW:
                    pending.pop(0).wait(self._timeout)
            for work in pending:
                work.wait(self._timeout)

    def recv_checkpoint(self, src_rank: int, metadata: str, step: int, timeout) -> Any:
        timeout_s = (
            timeout.total_seconds() if isinstance(timeout, timedelta) else timeout
        )
        header = self._pg.recv(src_rank, tag=1).get_future().wait(timeout_s)
        # the header comes from another process: anything but the ranged
        # form is refused in words, not waited on for frames that this
        # receiver could not place
        got = pickle.loads(bytes(header[0]))
        if not (
            isinstance(got, tuple) and len(got) == 5 and got[2] == "ranged"
        ):
            # spec and ranges are long: their types say enough
            came = (
                [
                    x if isinstance(x, (int, str)) else type(x).__name__
                    for x in got
                ]
                if isinstance(got, tuple)
                else type(got).__name__
            )
            raise RuntimeError(
                f"checkpoint header from rank {src_rank} is not the ranged "
                f'wire (step, spec, "ranged", ranges, crcs): got {came}'
            )
        got_step, spec, _, ranges, crcs = got
        if got_step != step:
            raise RuntimeError(f"expected checkpoint step {step}, got {got_step}")

        template_leaves: Optional[List[Any]] = None
        if self._template_fn is not None:
            # returns None (one warning) when the sender's tree STRUCTURE
            # differs from the template's — index-aligned placement would
            # risk streaming leaves into the wrong buffers
            template_leaves = template_leaves_for(
                spec, self._template_fn(), logger
            )
        return self._recv_ranged(
            src_rank, spec, ranges, crcs, template_leaves, timeout_s
        )

    def _recv_ranged(
        self,
        src_rank: int,
        spec: TreeSpecPayload,
        ranges: List[List[Any]],
        crcs: List[int],
        template_leaves: Optional[List[Any]],
        timeout_s: float,
    ) -> Any:
        """Receive the ranged wire: one message per chunk of byte ranges
        (the plan rode the header). The recv of chunk i+1 runs on a worker
        thread while this thread finalizes (device-places) the leaves
        chunk i completed — the pipelining that hides placement behind the
        wire for multi-chunk heals.

        ``crcs`` are verified per chunk after the copy into the destination
        views — detection only on this push-based wire: a mismatch raises,
        the Manager's discard-and-retry heal protocol re-requests the
        transfer, and the corrupt bytes are never finalized into leaves."""
        # flat uint8 destination per leaf: absorb-capable template leaves
        # expose their own memory (frames stream straight in), the rest
        # get a wire buffer reused across that leaf's ranges
        dests: List[np.ndarray] = []
        absorbed: List[bool] = []
        for i, meta in enumerate(spec.leaves):
            absorbs = (
                template_leaves is not None
                and meta.kind == "array"
                and can_absorb(
                    template_leaves[i],
                    meta.shape,
                    meta.dtype,
                    require_contiguous=True,
                )
            )
            dests.append(
                template_leaves[i].reshape(-1).view(np.uint8)
                if absorbs
                else np.empty(meta.nbytes, np.uint8)
            )
            absorbed.append(absorbs)

        payloads: List[Optional[Any]] = [None] * len(spec.leaves)
        remaining: List[int] = [m.nbytes for m in spec.leaves]

        def _finalize(i: int) -> None:
            meta = spec.leaves[i]
            if absorbed[i]:
                assert template_leaves is not None
                payloads[i] = template_leaves[i]
                return
            leaf = leaf_from_bytes(meta, dests[i])
            if template_leaves is not None and meta.kind == "array":
                leaf = place_leaf_like(leaf, template_leaves[i], logger)
            payloads[i] = leaf

        def transfer(item: Any) -> List[Any]:
            ci, chunk = item
            gviews = [dests[j][off : off + ln] for (j, off, ln) in chunk]
            got = self._pg.recv_into(gviews, src_rank, tag=2) \
                .get_future().wait(timeout_s)
            n_got = len(got) if got else 0
            if n_got != len(chunk):
                err = self._pg.errored()
                raise RuntimeError(
                    f"ranged recv from rank {src_rank} returned {n_got} of "
                    f"{len(chunk)} ranges (pg errored: {err})"
                )
            for k, (j, _off, ln) in enumerate(chunk):
                if got[k] is gviews[k]:
                    continue  # absorbed straight into the destination
                src = got[k]
                buf = (
                    src.reshape(-1).view(np.uint8)
                    if isinstance(src, np.ndarray)
                    else np.frombuffer(src, np.uint8)
                )
                if buf.size != ln:
                    raise RuntimeError(
                        f"ranged recv: range {k} of chunk carries "
                        f"{buf.size} bytes, plan says {ln}"
                    )
                np.copyto(gviews[k], buf)
            got_crc = 0
            for gv in gviews:
                got_crc = zlib.crc32(gv, got_crc)
            if got_crc & 0xFFFFFFFF != crcs[ci] & 0xFFFFFFFF:
                raise RuntimeError(
                    f"ranged recv: chunk {ci} crc32 mismatch "
                    f"(got {got_crc & 0xFFFFFFFF:#010x}, header says "
                    f"{crcs[ci] & 0xFFFFFFFF:#010x}); discarding heal"
                )
            return chunk

        def finish(chunk: List[Any]) -> None:
            for j, _off, ln in chunk:
                remaining[j] -= ln
                if remaining[j] < 0:
                    raise RuntimeError(
                        f"leaf {j}: overlapping/duplicate wire ranges"
                    )
                if remaining[j] == 0 and payloads[j] is None:
                    _finalize(j)

        timings = StreamTimings()
        pipelined(
            list(enumerate(ranges)),
            transfer,
            finish,
            depth=2,
            timings=timings,
            size_of=lambda c: sum(ln for (_j, _o, ln) in c),
        )
        self._last_recv_timings = timings

        missing = [i for i, p in enumerate(payloads) if p is None]
        if missing:
            raise RuntimeError(f"ranged checkpoint missing leaves {missing}")

        import jax

        treedef = pickle.loads(spec.treedef_bytes)
        return jax.tree_util.tree_unflatten(treedef, payloads)

    def shutdown(self, wait: bool = True) -> None:
        pass  # the PG is owned by the caller
