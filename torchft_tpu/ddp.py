"""Fault-tolerant data parallelism helpers.

Role-equivalent of the reference's torchft/ddp.py:31-104. Torch DDP installs
autograd-hook comm buckets; JAX has explicit gradients, so the idiomatic
equivalent is a function (and an optax transform) that averages a gradient
pytree across replica groups through the Manager — picking up quorum
participation, zero-contribution for non-participants, and error swallowing.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

from torchft_tpu.manager import Manager
from torchft_tpu.process_group import ReduceOp
from torchft_tpu.work import GradStream, Work

__all__ = ["DistributedDataParallel", "PureDistributedDataParallel", "ft_allreduce_gradients"]


def ft_allreduce_gradients(
    manager: Manager, grads: Any, should_quantize: bool = False
) -> Any:
    """Average a gradient pytree across participating replica groups.

    Blocking convenience over the managed allreduce (reference comm-hook
    behavior, ddp.py:66-79): on communicator failure the step's gradients
    resolve to zeros and ``manager.should_commit()`` will discard the step.
    Routes through the bucket pipeline (bit-identical to one collective
    for the whole tree when uncompressed) so buckets unpack while later
    ones are still on the wire. ``should_quantize=True`` streams too on a
    host PG — buckets ride the wire
    fp8/int8-compressed with error feedback — and otherwise falls back to
    the monolithic quantized collective inside the Manager.
    """
    return manager.allreduce_streamed(
        grads, should_quantize=should_quantize
    ).wait()


class DistributedDataParallel:
    """Bundles a Manager with gradient averaging for the replicated dim.

    The single-tree variant issues one allreduce for the whole gradient
    pytree (reference DDP buckets exist to batch hook-delivered grads; with
    explicit grads one tree-level collective is already "bucketed").
    """

    def __init__(self, manager: Manager, should_quantize: bool = False) -> None:
        self._manager = manager
        self._should_quantize = should_quantize

    def allreduce_gradients(self, grads: Any) -> Work:
        """Async: returns a Work whose future resolves to averaged grads."""
        return self._manager.allreduce(grads, should_quantize=self._should_quantize)

    def allreduce_gradients_streamed(self, grads: Any) -> GradStream:
        """Async with per-bucket completion: a GradStream whose ``ready(i)``
        flips as each bucket lands. Quantized trees stream compressed
        buckets where the Manager supports it (host PG, streaming on) and
        degenerate to one bucket otherwise (the monolithic fp8 pipeline
        packs its own wire buffer)."""
        return self._manager.allreduce_streamed(
            grads, should_quantize=self._should_quantize
        )

    def average_gradients(self, grads: Any) -> Any:
        """Blocking: returns the averaged gradient pytree."""
        return self.allreduce_gradients_streamed(grads).wait()


class PureDistributedDataParallel(DistributedDataParallel):
    """Per-bucket variant (reference's per-parameter hooks, ddp.py:82-104):
    leaves pack into flat same-dtype buckets (shared
    ``torchft_tpu/bucketing.py``) and one allreduce is issued per bucket, so
    later buckets overlap earlier ones while a pytree of hundreds of leaves
    still costs only ``ceil(total_bytes / cap)`` collectives. Quantized
    trees stream compressed buckets with error feedback when the Manager
    supports it (host PG, streaming on); otherwise the Manager falls back
    to its monolithic quantized collective."""

    def __init__(
        self,
        manager: Manager,
        should_quantize: bool = False,
        bucket_cap_bytes: Optional[int] = None,
    ) -> None:
        from torchft_tpu.bucketing import DEFAULT_BUCKET_CAP_BYTES

        super().__init__(manager, should_quantize)
        self._bucket_cap_bytes = (
            int(bucket_cap_bytes)
            if bucket_cap_bytes is not None
            else DEFAULT_BUCKET_CAP_BYTES
        )

    def average_gradients(self, grads: Any) -> Any:
        import jax

        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if len(leaves) <= 1 or self._bucket_cap_bytes <= 0:
            works = [
                self._manager.allreduce(
                    leaf, should_quantize=self._should_quantize
                )
                for leaf in leaves
            ]
            reduced = [w.get_future().wait() for w in works]
            return jax.tree_util.tree_unflatten(treedef, reduced)

        # one streamed managed allreduce carrying THIS wrapper's cap: the
        # Manager packs/unpacks with the shared bucketing plan and streams
        # per-bucket collectives, so later buckets ride the wire while
        # earlier ones unpack — strictly more overlap than the old
        # pack-here-then-wait-per-flat shape, same numerics. Quantized
        # trees take the same call: the Manager streams them compressed
        # (host PG, streaming on) or falls back to its monolithic
        # quantized collective.
        return self._manager.allreduce_streamed(
            grads,
            bucket_cap_bytes=self._bucket_cap_bytes,
            should_quantize=self._should_quantize,
        ).wait()
