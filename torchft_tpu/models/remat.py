"""Shared rematerialization policy for scanned transformer layer bodies.

One policy module for every model family (llama, moe) so the remat semantics
can't diverge: modes are "none" / "dots" / "attn" / "full" (bools accepted
as aliases for none/full for backward compatibility).

On TPU the interesting trade is HBM capacity vs backward-pass FLOPs:

- "full": `jax.checkpoint` over the layer — saves only the carry, recomputes
  the entire layer forward in backward (~+33% step FLOPs). The conservative
  choice for models/sequences at the edge of HBM (the Llama-3-8B seq-8192
  HSDP target uses this).
- "dots": saves matmul outputs (`dots_with_no_batch_dims_saveable`) plus any
  value tagged `checkpoint_name(..., "attn_out")` — the attention kernel is
  a custom_vjp whose output is not a dot in the jaxpr, so without the tag
  the whole flash forward would be recomputed in backward. Near-no-remat
  backward FLOPs at a fraction of no-remat activation memory.
- "attn": saves ONLY the tagged attention outputs; every plain matmul is
  recomputed in backward. The attention kernel is the one block whose
  recompute is disproportionately expensive (a full Pallas flash forward),
  while the dense matmuls recompute at MXU speed from residuals already in
  HBM — so this keeps nearly full-remat's memory footprint but removes the
  most expensive third of the recompute. Not measured on the chip since
  PR 21's bring-up. Numerically pinned by the grad-equivalence test.
- "none": XLA saves all residuals.
"""

from __future__ import annotations

from typing import Any, Callable

import jax

__all__ = ["remat_wrap", "ATTN_OUT_NAME"]

ATTN_OUT_NAME = "attn_out"


def remat_wrap(layer: Callable, remat: Any) -> Callable:
    """Apply the requested rematerialization mode to a scanned layer body."""
    if remat in (False, "none"):
        return layer
    if remat in (True, "full"):
        return jax.checkpoint(layer)
    if remat == "dots":
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(ATTN_OUT_NAME),
        )
        return jax.checkpoint(layer, policy=policy)
    if remat == "attn":
        policy = jax.checkpoint_policies.save_only_these_names(ATTN_OUT_NAME)
        return jax.checkpoint(layer, policy=policy)
    raise ValueError(f"unknown remat mode: {remat!r}")
