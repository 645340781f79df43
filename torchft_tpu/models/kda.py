"""Kimi Delta Attention (KDA, arXiv:2510.26692), the one mixer every kind
with such a layer calls (``models/ling.py``'s five layers in six,
``models/solar.py``'s three in four): a gated delta rule with a per-channel
decay (``ops/kda.py``) between short convolutions and a gated, head-wise
normalised output (H heads of ``cfg.kda_head_dim``)::

    q, k, v = silu(conv4(u @ wq)), silu(conv4(u @ wk)), silu(conv4(u @ wv))
    q, k    = l2norm(q) / sqrt(d_k), l2norm(k)                  # a head at a time
    f       = u @ w_f + dt_bias                                 # or, through a rank:
    f       = (u @ w_fa) @ w_fb + dt_bias                       # where the layer has ``w_fa``
    g       = decay_floor * sigmoid(exp(A_log) * f)             # in (decay_floor, 0), or
    g       = -exp(A_log) * softplus(f)                         # decay_floor None: (-inf, 0)
    beta    = beta_max * sigmoid(u @ w_beta)                    # one a head
    o       = kda(q, k, v, g, beta)                             # ops/kda.py
    gate    = sigmoid(u @ w_g)                                  # one a head, or
    gate    = sigmoid((u @ w_ga) @ w_gb + b_g)                  # one a CHANNEL, where ``w_ga``
    out     = (rmsnorm_head(o) * gate) @ wo

What differs between the kinds is what the layer's leaves and the caller say:
the decay's form (a published lower bound, ``kda_lower_bound``: flash-linear-
attention's ``safe_gate``; none: Kimi Linear's own, unbounded below), the
rank of the decay's and the gate's projections (``kda_use_full_proj`` false:
pairs of ``[d, r]`` and ``[r, H d_k]``), the gate's granularity and beta's
range (``beta_max`` 2: ``allow_neg_eigval``, arXiv:2411.12537: the
transition ``I - beta k k^T`` then has an eigenvalue in (-1, 1)). The bound
and the range are what the model's form promises, so ``kda`` is told them
where the step is traced and runs the body that may rely on them.

The scopes ``kda/in_proj``, ``kda/conv``, ``kda/gate``, ``kda/scan`` and
``kda/out`` are what ``kda.mixer_s`` reads in a device trace; the low-rank
pairs lie under ``kda/gate`` and ``kda/out``. The element-wise passes of
the second and the last line round ``kda`` are ``ops/kda_passes.py``'s two
kernel pairs where the shape tiles (``kda_qkg_*`` under ``kda/gate``,
``kda_gate_*`` under ``kda/out``), its ``jax.numpy`` forms otherwise.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from torchft_tpu.models.decoder import _causal_conv
from torchft_tpu.ops.kda import BOUNDED_FLOOR, kda
from torchft_tpu.ops.kda_passes import kda_gate, kda_qkg, tiles

__all__ = ["kda_mixer"]

_F32 = jnp.float32

# SiLU of the depthwise causal convolution, x [B,T,di], w [k,di], no bias:
# the one program every kind's mixer runs (``decoder._causal_conv``)
_short_conv = partial(_causal_conv, b=None)


def kda_mixer(u: jax.Array, w: Dict[str, jax.Array], cfg: Any,
              decay_floor: Optional[float], beta_max: float, counted: bool = False,
              out_block: int = 0) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """One layer's KDA from its normalised input ``u`` [B,S,d] to ``wo``'s
    output. ``decay_floor`` and ``beta_max`` are the kind's own constants,
    both the FORM ``g`` and ``beta`` are computed in here and the promise
    ``ops.kda.kda`` is handed, so the two cannot disagree (no default: a
    caller says which model it is; ``tests/test_solar_kernels.py`` holds
    what reaches the kernel to them at saturated gates). And (``counted``;
    Ling's lowered step, pinned in ``tests/test_ling.py``, has no such
    reductions and stays the parent's) two float32 means off the kernel's path:
    ``decay_past_bound_share``, the (position, channel) pairs whose step log
    decay is under ``ops.kda.BOUNDED_FLOOR`` (what the bounded body could not
    have taken), and ``beta_over_one_share``, the (position, head) pairs
    whose transition has a negative eigenvalue; else None. ``out_block`` > 0
    (a kind at the HBM edge, as ``llama.swiglu``'s ``block``): where the
    norm and the gate run in their ``jax.numpy`` form, over blocks of that
    many positions, each rematerialised, so that their float32 temporaries
    (three of [S, H d_k]) exist a block at a time, forward and backward; a
    position's result is the same. The kernels have no such temporaries and
    run whole."""
    (B, S, _), H, dk = u.shape, cfg.n_heads, cfg.kda_head_dim
    heads = lambda m: m.reshape(B, S, H, dk)  # noqa: E731
    with jax.named_scope("kda/in_proj"):
        q, k, v = u @ w["wq"], u @ w["wk"], u @ w["wv"]
    with jax.named_scope("kda/conv"):
        q, k, v = (_short_conv(m, w[c])
                   for m, c in ((q, "conv_q"), (k, "conv_k"), (v, "conv_v")))
    with jax.named_scope("kda/gate"):
        # the decay sits in an exponent and sums over positions: float32
        # from the product on, as the selective scan's step size
        if "w_fa" in w:
            f = jnp.matmul(u @ w["w_fa"], w["w_fb"], preferred_element_type=_F32)
        else:
            f = jnp.matmul(u, w["w_f"], preferred_element_type=_F32)
        q, k, g = kda_qkg(q, k, f, w["dt_bias"], w["A_log"], decay_floor)
        beta = jax.nn.sigmoid(jnp.matmul(u, w["w_beta"], preferred_element_type=_F32))
        if beta_max != 1.0:
            beta = beta_max * beta
    with jax.named_scope("kda/scan"):
        o = kda(heads(q), heads(k), heads(v), heads(g), beta,
                decay_floor=decay_floor, beta_max=beta_max)
    with jax.named_scope("kda/out"):
        def gated(o, u):  # the head-wise norm times the gate: [B, s, H dk]
            if "w_ga" not in w:
                logits = jnp.matmul(u, w["w_g"], preferred_element_type=_F32)
            else:
                # the leaves' product in the activations' dtype, as it rounds;
                # ``kda_gate`` widens it for its kernels (its module says why)
                logits = (u @ w["w_ga"]) @ w["w_gb"] + w["b_g"]
            return kda_gate(o, logits, w["o_norm"], cfg.norm_eps)

        o = o.reshape(B, S, H * dk)
        # the kernels' tile is their own block of positions
        if out_block and S > out_block and not tiles(o, dk):
            if S % out_block:
                raise ValueError(f"kda out_block {out_block} must divide seq len {S}")
            blocks = lambda m: jnp.swapaxes(  # noqa: E731
                m.reshape(B, S // out_block, out_block, *m.shape[2:]), 0, 1)
            out = jax.lax.map(jax.checkpoint(lambda ou: gated(*ou)), (blocks(o), blocks(u)))
            out = jnp.swapaxes(out, 0, 1).reshape(B, S, H * dk) @ w["wo"]
        else:
            out = gated(o, u) @ w["wo"]
    if not counted:
        return out, None
    with jax.named_scope("kda/count"):
        return out, {"decay_past_bound_share": jnp.mean((g < BOUNDED_FLOOR).astype(_F32)),
                     "beta_over_one_share": jnp.mean((beta > 1.0).astype(_F32))}
