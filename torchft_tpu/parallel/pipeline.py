"""Pipeline parallelism: GPipe over a ``pp`` mesh axis.

The reference's only pipeline use is torch.distributed.pipelining model
splitting to create DiLoCo fragments (SURVEY.md §2.4, train_diloco.py); a
TPU-native framework owns the real thing. Design:

- **Layers are already scanned** over a stacked leading dim (models/llama),
  so a pipeline stage is just that stack sharded over ``pp``: each device
  holds ``L/P`` layers and runs its local sub-scan.
- **Microbatch rotation via ppermute.** A static tick loop (``M + P - 1``
  ticks for M microbatches over P stages) where every tick runs the local
  stage and rotates activations one stage down the ring. Bubble ticks
  compute-and-discard (`jnp.where` selects), keeping control flow
  compiler-static — no data-dependent branching, exactly one compiled tick
  body.
- **SPMD composition.** Everything runs inside ``shard_map``; the tick
  count ``M + P - 1`` is static (mesh axis size), so the loop lowers to a
  scan and is reverse-differentiable — pipeline backward falls out of
  jax.grad with no hand-written schedule.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "pipeline_apply",
    "make_pp_llama_loss",
    "pp_param_specs",
    "pp_degrade_axes",
]


def pipeline_apply(
    layer_fn: Callable[[Any, Any], Any],
    layer_params: Any,
    x: jax.Array,
    axis_name: str = "pp",
    num_microbatches: Optional[int] = None,
) -> jax.Array:
    """Run stacked layers as a pipeline over ``axis_name``. Call inside
    shard_map.

    ``layer_fn(h, one_layer_params) -> (h, None)`` is the scanned layer body;
    ``layer_params`` leaves are the LOCAL stage's stack [L/P, ...];
    ``x`` [B, ...] is this device's full activation batch. Returns the
    pipeline output on the LAST stage; zeros elsewhere (callers psum-select).
    """
    P_ = lax.psum(1, axis_name)  # static: mesh axis size
    stage = lax.axis_index(axis_name)
    M = num_microbatches or P_
    B = x.shape[0]
    assert B % M == 0, f"batch {B} must divide into {M} microbatches"
    mubs = x.reshape(M, B // M, *x.shape[1:])

    def local_stack(h):
        h, _ = lax.scan(layer_fn, h, layer_params)
        return h

    perm = [(i, (i + 1) % P_) for i in range(P_)]
    state = jnp.zeros_like(mubs[0])
    out = jnp.zeros_like(mubs)

    def tick(t, carry):
        state, out = carry
        # stage 0 ingests microbatch t; other stages take the rotated state
        inject = lax.dynamic_index_in_dim(
            mubs, jnp.clip(t, 0, M - 1), 0, keepdims=False
        )
        h_in = jnp.where(stage == 0, inject, state)
        h_out = local_stack(h_in)
        # the last stage emits microbatch t-(P-1) once the pipe is full
        emit_idx = t - (P_ - 1)
        emitted = lax.dynamic_update_index_in_dim(
            out, h_out, jnp.clip(emit_idx, 0, M - 1), 0
        )
        out = jnp.where((stage == P_ - 1) & (emit_idx >= 0), emitted, out)
        state = lax.ppermute(h_out, axis_name, perm)
        return state, out

    state, out = lax.fori_loop(0, M + P_ - 1, tick, (state, out), unroll=False)
    return out.reshape(B, *x.shape[1:])


def pp_param_specs(cfg: Any) -> Any:
    """PartitionSpecs for the llama pytree with layers sharded over pp.

    Within-layer dims could additionally carry fsdp/tp exactly as in
    llama_param_specs; kept pp-pure here so the pipeline axis composes by
    spec merge when needed.
    """
    return {
        "embed": P(None, None),
        "layers": {
            "attn_norm": P("pp", None),
            "wq": P("pp", None, None),
            "wk": P("pp", None, None),
            "wv": P("pp", None, None),
            "wo": P("pp", None, None),
            "ffn_norm": P("pp", None),
            "w_gate": P("pp", None, None),
            "w_up": P("pp", None, None),
            "w_down": P("pp", None, None),
        },
        "final_norm": P(None),
        "lm_head": P(None, None),
    }


def pp_degrade_axes(cfg: Any) -> Any:
    """Degrade-in-place hook: per-leaf reshard axes for shrinking the
    pipeline by one stage. Layer stacks are sharded over ``pp`` on dim 0,
    so losing a stage is a dim-0 reshard of every ``layers`` leaf: each of
    the P-1 survivors picks up a slightly deeper local sub-stack
    (np.array_split semantics), and the scanned sub-stacks still
    concatenate to the identical full model — the bubble count just grows
    by the shrunken P. Feed this to degrade.reshard_from_survivors /
    reshard_full."""
    from torchft_tpu.parallel.degrade import axes_from_specs

    return axes_from_specs(pp_param_specs(cfg), "pp")


def make_pp_llama_loss(cfg: Any, mesh: Mesh, num_microbatches: Optional[int] = None,
                       remat: Any = "dots"):
    """Build a pipeline-parallel llama loss fn over mesh axis ``pp``.

    Embedding and the LM head run replicated on every stage (they are cheap
    relative to the layer stack at depth); only the last stage's logits are
    real, selected by a psum mask. Returns loss_fn(params, tokens, targets).

    The layer body is the canonical one (models/llama.make_llama_layer_body)
    wrapped in the shared remat policy — at the 8B/70B depths pipelining
    targets, per-stage activation residency without remat would hit the HBM
    ceiling.
    """
    from torchft_tpu.models.llama import _rmsnorm, make_llama_layer_body
    from torchft_tpu.models.remat import remat_wrap

    layer = remat_wrap(make_llama_layer_body(cfg), remat)

    def loss_local(layers, embed, final_norm, lm_head, tokens, targets):
        h = embed[tokens]
        h = pipeline_apply(
            layer, layers, h, axis_name="pp", num_microbatches=num_microbatches
        )
        # only the last stage holds real activations: mask-and-psum selects
        # them onto every stage (logit-sized allreduce; fine at loss time)
        P_ = lax.psum(1, "pp")
        is_last = (lax.axis_index("pp") == P_ - 1).astype(h.dtype)
        h = lax.psum(h * is_last, "pp")
        h = _rmsnorm(h, final_norm, cfg.norm_eps)
        logits = (h @ lm_head).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - tgt)

    layer_specs = pp_param_specs(cfg)["layers"]

    def loss_fn(params, tokens, targets):
        fn = shard_map(
            loss_local,
            mesh=mesh,
            in_specs=(layer_specs, P(None, None), P(None), P(None, None), P(None, None), P(None, None)),
            out_specs=P(),
            check_vma=False,
        )
        return fn(
            params["layers"],
            params["embed"],
            params["final_norm"],
            params["lm_head"],
            tokens,
            targets,
        )

    return loss_fn
