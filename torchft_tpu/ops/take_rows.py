"""Row gather (``x[take]``, each row taken many times) for the expert
block's dispatch: the source is put into VMEM first, and XLA's gather does
the rest.

XLA's TPU gather has two speeds (PERF.md section 6, PR 39 has the table).
Out of a source in HBM it moves 4 KB rows at 35 ns a row: 2.2-2.3 ms for
65,536 rows, 234 GB/s of traffic. Out of a source that its memory-space
assignment happened to prefetch into VMEM it moves them at 6 ns a row, the
rate at which HBM takes the result (0.41 ms for the same rows). Whether a
``[T, d]`` source of 33.5 MB is prefetched is the scheduler's guess, and in
the compiled step of ``olmoe-1b-7b.bare-routed`` it guessed yes for the
rematerialised dispatch and no for the forward one. The kernel here
(``take_rows_stage`` in a device trace) leaves nothing to guess: one DMA
copies the source into a VMEM buffer that is the kernel's OUTPUT, so the
gather that follows finds it there. A gather moves bits: the result is
``x[take]`` either way, whatever the dtype.

A row-by-row DMA kernel was measured too and is not here: Mosaic slices a
tiled dimension of an HBM reference by whole tiles only, a row of a plain
``[N, d]`` array is a sublane (bf16: half a sublane) of 8-row tiles, and the
change of view that makes a row whole tiles is a pass over the array that
costs what the DMAs save (``benchmarks/take_rows_check.py`` keeps it).

Staged is what ``models/moe._take_rows`` gathers, the dispatch and its
rematerialised copy, and nothing else: with the combine's backward pass
staged as well, two staged buffers were alive in one backward pass and XLA's
memory-space assignment failed at compile time ("overlaps with another
chunk") for one expert block compiled alone, though not for either cell's
whole step. One staged buffer at a time compiled at every shape tried.

The staging is taken on what the call can see (:func:`applies`): a TPU
whose VMEM this module knows, one device, a source of whole tiles that
takes a third of that VMEM at most. Everything else is ``x[take]`` alone;
nothing selects between them.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["take_rows", "applies"]

# bytes of VMEM a core has, by ``device_kind``: 128 MiB on a v5e (the size
# JAX's Pallas TPU notes give; a 33.5 MB staged buffer beside XLA's own use
# of VMEM compiled and ran there: my chip runs, PR 39). A kind that is not
# here stages nothing
VMEM_BYTES = {"TPU v5 lite": 128 * 2**20}
_SUBLANES, _LANES = 8, 128


def applies(x: jax.Array) -> bool:
    """Whether :func:`take_rows` stages ``x`` in VMEM before the gather."""
    if jax.default_backend() != "tpu" or jax.device_count() != 1 or x.ndim != 2:
        return False
    room = VMEM_BYTES.get(jax.devices()[0].device_kind, 0) // 3
    return (x.shape[0] % (_SUBLANES * 4 // x.dtype.itemsize) == 0
            and x.shape[1] % _LANES == 0 and x.size * x.dtype.itemsize <= room)


def _stage_kernel(x_ref, after_ref, o_ref, sem):
    del after_ref
    copy = pltpu.make_async_copy(x_ref, o_ref, sem)
    copy.start()
    copy.wait()


def stage(x: jax.Array, after: jax.Array, *, interpret: bool = False) -> jax.Array:
    """``x`` as it is, in a buffer that lives in VMEM. ``after`` is an
    operand the kernel never reads: the copy cannot be scheduled before it
    exists. Staged when the source is ready and not when the indices are,
    the buffer sat through the router, the top-k and the sort in OLMoE's
    forward pass, and XLA moved it back to HBM to make room."""
    return pl.pallas_call(
        _stage_kernel,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        out_shape=pltpu.VMEM(x.shape, x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        name="take_rows_stage",
        interpret=interpret,
    )(x, after)


def take_rows(x: jax.Array, take: jax.Array) -> jax.Array:
    """``x[take]`` for x ``[N, d]`` and take ``[M]`` int32 in ``[0, N)``."""
    return (stage(x, take) if applies(x) else x)[take]
