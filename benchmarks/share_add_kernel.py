"""Rows in token order added into ``[tokens, d]`` by a Pallas kernel, each
token's run summed in float32 and rounded once: measured for a share's two
adds (``models/moe._add_in_token_order``) by PR 60, three to seven times as
fast an add as XLA's scatter-add, and NOT in the program: kept here, as
``benchmarks/take_rows_check.py`` keeps its row-DMA kernel, for
``share_gmm_check.py rows``' form ``token_order_kernel`` and for whoever takes
it up (ROADMAP S12 (b1)).

Why not: XLA makes what is added to a share's sums next (the shared expert's
output; the router's cotangent) the scatter-add's OPERAND and rounds the
whole sum once. A kernel stands between two XLA fusions and rounds where it
writes bf16: ``ling-3.0-flash.bare-kda-32k`` read ``grad_norm_rel`` 0.000315
against its limit of 0.00023 and the parent's 0.000065; with the sums handed
out in float32 and added to the router's float32 cotangent and the shared
expert's output before one rounding, 0.000267 there and
``deepseek-v2.bare-mla-yarn``'s ``loss_abs`` 0.000120 against 0.0001 (PERF.md
section 6, PR 60; section 7, PR 60 (b)).

The grid runs over column blocks and, inside a block, over VISITS: a token
tile (``TOKENS`` tokens) beside one of the row tiles (``ROWS`` rows) that hold
rows of its tokens. The rows are in token order, so a token tile's rows are
one contiguous range and its row tiles follow each other; a visit multiplies
the tile's one-hot matrix ``[TOKENS, ROWS]`` (row r belongs to token t) with
the row tile ``[ROWS, columns]`` on the MXU and adds the product to a float32
accumulator, which is stored when the token tile's last visit is done. Every
token tile is visited at least once, so every output tile is written and
nothing is filled with zeros first. The visits are laid out by the caller's
XLA code from the sorted keys (:func:`_visits`: a few ``searchsorted`` over
the keys) and reach the kernel as prefetched scalars: whole tiles only, no
DMA of single rows. There are at most ``token tiles + row tiles`` of them, a
static bound; the grid steps past the last visit do nothing (their blocks
are the last visit's, so nothing moves either). On a v5e: 0.75 ms for
DeepSeek-V2's 15,360 rows of 5,120 into 16,384 tokens, 1.42 for Mellum's
131,072 of 2,304 into 32,768, 0.69 for Ling's 49,152 of 2,560, 0.41 for
Nemotron's 24,576 of 2,688 into 16,384 (my chip runs, PR 60).

A row that holds no pair carries the key ``tokens``, which matches no token:
its one-hot column is zeros. The row itself must be finite all the same
(0 x NaN is NaN): the callers select such rows away before they gather.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["share_add"]

TOKENS = 512   # tokens an output tile holds
ROWS = 512     # rows a visit multiplies
COLUMNS = 1280  # the widest column block: a float32 accumulator of 2.6 MB
_LANES = 128


def _column_block(d: int) -> int:
    """The widest multiple of 128 lanes under ``COLUMNS`` that divides ``d``;
    ``d`` itself where it is no multiple of 128 (a debug width)."""
    if d % _LANES:
        return d
    return max(c for c in range(_LANES, min(d, COLUMNS) + 1, _LANES) if d % c == 0)


def _visits(key_s, tokens: int, tb: int, tr: int):
    """key_s [rows_n] ascending -> (token tile [M], row tile [M], count): the
    visits in order, ``M = token tiles + row tiles``. Token tile i owns the
    rows ``[at[i], at[i + 1])``, so it visits the row tiles from ``at[i] //
    tr`` to ``(at[i + 1] - 1) // tr``, one at least (a tile with no row
    visits the row tile its range would start in and adds zeros). The steps
    past ``count`` repeat the last visit."""
    n_tt, n_rt = tokens // tb, key_s.shape[0] // tr
    at = jnp.searchsorted(key_s, jnp.arange(n_tt + 1, dtype=jnp.int32) * tb).astype(jnp.int32)
    first = jnp.minimum(at[:-1] // tr, n_rt - 1)
    last = jnp.maximum((at[1:] - 1) // tr, first)
    ends = jnp.cumsum(last - first + 1)  # visits up to and with tile i
    step = jnp.minimum(jnp.arange(n_tt + n_rt, dtype=jnp.int32), ends[-1] - 1)
    tile = jnp.searchsorted(ends, step, side="right").astype(jnp.int32)
    row_tile = first[tile] + step - (ends[tile] - (last[tile] - first[tile] + 1))
    return tile, row_tile.astype(jnp.int32), ends[-1].astype(jnp.int32)


def _kernel(tile_ref, row_tile_ref, count_ref, key_ref, rows_ref, out_ref, acc_ref):
    del row_tile_ref  # the block maps read it
    m, steps = pl.program_id(1), pl.num_programs(1)
    tile = tile_ref[m]
    tb, tr = acc_ref.shape[0], rows_ref.shape[0]

    @pl.when((m == 0) | (tile_ref[jnp.maximum(m - 1, 0)] != tile))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(m < count_ref[0])
    def _():
        token = tile * tb + jax.lax.broadcasted_iota(jnp.int32, (tb, tr), 0)
        onehot = jnp.where(token == key_ref[...], 1.0, 0.0).astype(rows_ref.dtype)
        acc_ref[...] += jnp.dot(
            onehot, rows_ref[...], preferred_element_type=jnp.float32,
            precision=(jax.lax.Precision.HIGHEST if rows_ref.dtype == jnp.float32 else None))

    @pl.when((m == steps - 1) | (tile_ref[jnp.minimum(m + 1, steps - 1)] != tile))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnums=(2,))
def share_add(rows: jax.Array, key_s: jax.Array, tokens: int) -> jax.Array:
    """rows [rows_n, d] and their tokens key_s [rows_n] int32, ascending
    (``tokens`` for a row that belongs to none) -> [tokens, d] in ``rows``'
    dtype: each token's rows summed in float32, in order, rounded once."""
    rows_n, d = rows.shape
    tb, tr, dc = math.gcd(tokens, TOKENS), math.gcd(rows_n, ROWS), _column_block(d)
    tile, row_tile, count = _visits(key_s, tokens, tb, tr)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(d // dc, tile.shape[0]),
        in_specs=[pl.BlockSpec((1, tr), lambda j, m, tile, row_tile, count: (0, row_tile[m])),
                  pl.BlockSpec((tr, dc), lambda j, m, tile, row_tile, count: (row_tile[m], j))],
        out_specs=pl.BlockSpec((tb, dc), lambda j, m, tile, row_tile, count: (tile[m], j)),
        scratch_shapes=[pltpu.VMEM((tb, dc), jnp.float32)])
    params = ({"interpret": True} if _interpret() else
              {"compiler_params": pltpu.CompilerParams(
                  dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=64 * 2**20)})
    return pl.pallas_call(
        _kernel, grid_spec=grid_spec, out_shape=jax.ShapeDtypeStruct((tokens, d), rows.dtype),
        name="share_add", **params,
    )(tile, row_tile, count.reshape(1), key_s.reshape(1, rows_n), rows)
