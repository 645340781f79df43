"""Fused causal attention for the training hot path.

The reference has no attention kernels of its own (it trains via torchtitan,
whose SDPA/flash comes from PyTorch); in a standalone TPU framework the
attention kernel is ours to own. On TPU this dispatches to the Pallas
splash/flash kernels (tiled online-softmax, never materializes the S x S
score matrix in HBM — the O(S) memory path that makes long sequences and big
batches fit); on the CPU test platform the plain XLA implementation with
identical semantics runs.

Layout contract matches torchft_tpu.models.llama: q [B, S, Hq, hd],
k/v [B, S, Hkv, hd] (GQA: Hq a multiple of Hkv), causal, scaled by
1/sqrt(hd). Output [B, S, Hq, hd].

``window`` (None: every earlier position) makes it a causal WINDOW: query
``i`` sees the keys ``j <= i`` with ``i - j < window``, its own among them
(``transformers``' ``kv_idx > q_idx - sliding_window``). The splash kernel
is built with a local mask and never visits a key/value block that lies
wholly outside the window, so its time follows the window's work and not the
sequence's (:func:`window_block_share`); the XLA path masks; the flash
kernel has no such mask and refuses.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "causal_attention",
    "xla_attention",
    "flash_attention_tpu",
    "splash_attention_tpu",
    "window_block_share",
]


def _repeat_kv(q: jax.Array, k: jax.Array, v: jax.Array):
    groups = q.shape[2] // k.shape[2]
    if groups > 1:
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    return k, v


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array, cfg: Any,
                  window: Optional[int] = None) -> jax.Array:
    """Plain XLA causal GQA attention (materialized scores, f32 softmax),
    over the last ``window`` positions where one is given."""
    hd = q.shape[-1]
    k, v = _repeat_kv(q, k, v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    S = q.shape[1]
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    if window is not None:
        mask = mask & ~jnp.tril(jnp.ones((S, S), jnp.bool_), -window)
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_attention_tpu(
    q: jax.Array, k: jax.Array, v: jax.Array, cfg: Any,
    window: Optional[int] = None,
) -> jax.Array:
    """Pallas flash attention (TPU only; full custom-vjp fwd+bwd). Causal
    over the whole sequence and nothing else: a ``window`` is refused."""
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        BlockSizes,
        flash_attention,
    )

    if window is not None:
        raise ValueError(
            f"flash attention has no window mask (window={window}): the "
            "splash kernel runs a causal window (TORCHFT_TPU_ATTENTION=auto "
            "or splash)")

    hd = q.shape[-1]
    k, v = _repeat_kv(q, k, v)
    # kernel layout is [B, H, S, hd]
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    S = qt.shape[2]
    if S % 128 != 0:
        raise ValueError(f"flash attention requires seq_len % 128 == 0, got {S}")
    # largest MXU-friendly block that divides S
    blk = next(b for b in (512, 256, 128) if S % b == 0)
    block_sizes = BlockSizes(
        block_q=blk,
        block_k_major=blk,
        block_k=blk,
        block_b=1,
        block_q_major_dkv=blk,
        block_k_major_dkv=blk,
        block_k_dkv=blk,
        block_q_dkv=blk,
        block_k_major_dq=blk,
        block_k_dq=blk,
        block_q_dq=blk,
    )
    out = flash_attention(
        qt,
        kt,
        vt,
        causal=True,
        sm_scale=1.0 / math.sqrt(hd),
        block_sizes=block_sizes,
    )
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# the splash kernel's tile a side under a window (see _splash_tile)
WINDOW_TILE = 512


def _splash_tile(seq_len: int, window: Optional[int]) -> int:
    """The splash kernel's tile, rows of queries and of keys alike, at
    ``seq_len``.

    Causal over the whole sequence: the largest tile that divides S, up to
    1024: larger tiles amortize the online-softmax bookkeeping until VMEM
    runs out — at 2048 the forward kernel is RESOURCE_EXHAUSTED in vmem on a
    v5e ([4, 2048, 16|8, 128] bf16, jax 0.9.0 / libtpu 0.0.34; chip run,
    PR 21).

    Under a window the tile also decides how much the kernel computes
    outside it: a tile is visited whole or not at all, so a query tile of
    ``b`` rows under a window of ``w`` visits ``w / b + 1`` key/value tiles
    (one 1,024 tile a side under a 1,024 window computes twice the
    in-window work, 512 a side half as much again). Read on a v5e at
    [1, 32768, 32 | 4, 128] bf16 under a window of 1,024 (chip run, PR 43),
    forward / forward and backward: tiles of 512 8.49 / 30.9 ms, of 1,024
    10.3 / 35.2, of 256 14.2 / 48.1, 512 x 1,024 10.5 / 36.6, 1,024 x 512
    10.2 / 36.3, 256 x 512 9.8 / 38.1; the causal kernel there 67.8 / 251.7
    at 1,024 and 74.3 / 301.3 at 512. So a window takes ``WINDOW_TILE`` =
    512 a side and the whole sequence keeps 1,024; no knob.
    """
    tile = next(b for b in (1024, 512, 256, 128) if seq_len % b == 0)
    return tile if window is None else min(tile, WINDOW_TILE)


def _visited(seq_len: int, tile: int, window: Optional[int]) -> int:
    """The (i, j) score entries a splash kernel of ``tile`` visits: whole
    tiles, those that hold an allowed pair (``j <= i`` and, under a window,
    ``i - j < window``). The others it skips."""
    tiles = 0
    for lo in range(0, seq_len, tile):  # a tile of queries lo .. lo + tile - 1
        first = 0 if window is None else max(lo - window + 1, 0)
        tiles += (lo + tile - 1) // tile - first // tile + 1
    return tiles * tile * tile


def window_block_share(seq_len: int, window: Optional[int]) -> float:
    """What the splash kernel built for ``window`` at ``seq_len`` visits,
    over what the causal kernel visits there: what is left of a full
    layer's kernel work (1.0: nothing is skipped). Known when the kernel is
    built; off the TPU it says what the kernel WOULD skip."""
    if window is None or seq_len % 128:
        return 1.0
    return (_visited(seq_len, _splash_tile(seq_len, window), window)
            / _visited(seq_len, _splash_tile(seq_len, None), None))


@functools.lru_cache(maxsize=16)
def _splash_kernel(n_q_heads: int, seq_len: int, block: int, block_kv: int,
                   interpret: bool, window: Optional[int] = None):
    """Build (and cache) a splash-attention kernel: mask construction and
    kernel specialization are trace-time work worth amortizing. ``window``
    (None: causal over the whole sequence) makes the mask a causal local
    one, whose out-of-window blocks the kernel never visits.

    ``block`` tiles the query dimension, ``block_kv`` the key/value
    dimension (asymmetric tiles let a sweep trade VMEM pressure on the KV
    side against online-softmax bookkeeping on the Q side).

    Construction runs under ``ensure_compile_time_eval``: the kernel bakes
    mask partials as arrays, and if those were created inside an outer trace
    (first call typically happens inside a remat'd scan body) the cache
    would leak that trace's tracers into every later jaxpr.
    """
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    one = (sm.CausalMask((seq_len, seq_len)) if window is None else
           sm.LocalMask((seq_len, seq_len), window_size=(window - 1, 0), offset=0))
    mask = sm.MultiHeadMask([one] * n_q_heads)
    block = min(block, seq_len)
    block_kv = min(block_kv, seq_len)
    bs = sk.BlockSizes(
        block_q=block,
        block_kv=block_kv,
        block_kv_compute=block_kv,
        block_q_dkv=block,
        block_kv_dkv=block_kv,
        block_kv_dkv_compute=block_kv,
        block_q_dq=block,
        block_kv_dq=block_kv,
    )
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(
            mask=mask,
            block_sizes=bs,
            head_shards=1,
            q_seq_shards=1,
            interpret=interpret,
        )


def splash_attention_tpu(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cfg: Any,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """GQA-native splash attention (fwd+bwd Pallas kernels), causal over
    the whole sequence or over the last ``window`` positions.

    Unlike `flash_attention_tpu` this never materializes the repeated K/V
    heads: the kernel maps query-head groups onto shared KV heads directly,
    cutting attention HBM traffic by the GQA group factor (4x for the
    llama3 configs). The reference has no attention kernels of its own (it
    delegates to torchtitan/PyTorch SDPA); this is the framework's.
    """
    hd = q.shape[-1]
    # kernel layout is [heads, S, hd] per example; scale folded into q
    # (splash takes no sm_scale argument)
    scale = 1.0 / math.sqrt(hd)
    qt = (jnp.swapaxes(q, 1, 2) * jnp.asarray(scale, q.dtype))
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    S = qt.shape[2]
    blk = _splash_tile(S, window)
    # benchmark escape hatch: benchmarks/mfu_sweep.py sweeps these to find
    # the best tiles for a given chip generation; training code leaves them
    # unset. BLOCK sets both dimensions, BLOCK_KV overrides the kv side.
    blk_env = os.environ.get("TORCHFT_TPU_SPLASH_BLOCK")
    if blk_env:
        blk = int(blk_env)
        if S % blk != 0:
            raise ValueError(
                f"TORCHFT_TPU_SPLASH_BLOCK={blk} does not divide seq_len {S}"
            )
    blk_kv = blk
    blk_kv_env = os.environ.get("TORCHFT_TPU_SPLASH_BLOCK_KV")
    if blk_kv_env:
        blk_kv = int(blk_kv_env)
        if S % blk_kv != 0:
            raise ValueError(
                f"TORCHFT_TPU_SPLASH_BLOCK_KV={blk_kv} does not divide "
                f"seq_len {S}"
            )
    kernel = _splash_kernel(qt.shape[1], S, blk, blk_kv, interpret, window)
    out = jax.vmap(kernel)(qt, kt, vt)  # [B, Hq, S, hd]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _on_tpu() -> bool:
    # not cached: the active backend can change in-process (e.g. a virtual
    # CPU device context during dryruns), and default_backend() is cheap
    return jax.default_backend() == "tpu"


ATTENTION_CHOICES = ("auto", "splash", "flash", "reference")

# Which kernel the last causal_attention dispatch resolved to ("splash" /
# "flash" / "xla"). Set at trace time; callers that report a speed record
# it so the kernel behind the number is stated, not implied.
LAST_DISPATCH: "str | None" = None


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, cfg: Any,
                     window: Optional[int] = None) -> jax.Array:
    """Backend-dispatching causal attention, over the whole sequence or
    (``window``) over each query's last ``window`` positions.

    On TPU: splash attention when the model is GQA/MQA (KV heads stay
    unrepeated — group-factor less HBM traffic), its values are narrower
    than its keys (latent attention: the flash kernel takes one width) or
    the layer has a window (the flash kernel has no such mask: pinned to
    ``flash`` a window is an error), plain flash otherwise;
    shapes the kernels cannot tile are an error there, never a quiet
    switch to materialized scores. Off TPU (the CPU test platform) the XLA
    reference runs. ``TORCHFT_TPU_ATTENTION=auto|splash|flash|reference``
    pins a kernel; ``reference`` is the XLA implementation on any backend.
    """
    global LAST_DISPATCH
    choice = os.environ.get("TORCHFT_TPU_ATTENTION", "auto")
    if choice not in ATTENTION_CHOICES:
        raise ValueError(
            f"TORCHFT_TPU_ATTENTION={choice!r}: expected one of "
            f"{ATTENTION_CHOICES}"
        )
    if choice == "reference" or not _on_tpu():
        LAST_DISPATCH = "xla"
        return xla_attention(q, k, v, cfg, window)
    S, hd = q.shape[1], q.shape[-1]
    if S % 128 != 0 or hd not in (64, 128, 256):
        raise ValueError(
            f"attention shape seq_len={S} head_dim={hd} window={window} does "
            "not tile the TPU kernels (seq_len % 128 == 0, head_dim in "
            "64/128/256; a window is splash's local mask at any length); set "
            "TORCHFT_TPU_ATTENTION=reference to run the XLA reference "
            "(materialized f32 scores) on purpose"
        )
    if choice == "splash" or (choice == "auto" and (
            q.shape[2] != k.shape[2] or v.shape[-1] != hd or window is not None)):
        LAST_DISPATCH = "splash"
        return splash_attention_tpu(q, k, v, cfg, window=window)
    LAST_DISPATCH = "flash"
    return flash_attention_tpu(q, k, v, cfg, window)
