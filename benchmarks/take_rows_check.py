"""The expert block's row gathers alone on the chip (ISSUE 39, step 0), at the
four shapes the two MoE cells run, bf16: OLMoE's dispatch (``[8192, 2048]``
-> 65,536 rows, each token's row 8 times, sorted by expert) and unsort
(65,536 rows permuted), LFM2's pair at 32,768 rows (``fan`` 4, 32 experts).

Part 1, the gathers: each variant's seconds a call and GB/s of traffic (rows
read + rows written), every result held bitwise against ``x[take]``:

- ``xla``: ``x[take]``, what ``models/moe.py`` runs;
- ``xla_promise``: ``x.at[take].get(mode="promise_in_bounds", ...)`` with
  ``unique_indices`` where the take is a permutation: what XLA's bounds
  handling costs;
- ``xla_tiles``: XLA's gather over rows viewed ``[N, 8, d/8]`` (whole
  tiles), with the two changes of view;
- ``kernel_<block>``: the Pallas kernel below (``take_rows`` in a trace),
  plain ``[N, d]`` in and out, the two changes of view included;
- ``kernel_alone_<block>`` / ``hbm_to_hbm_<block>``: the kernel between
  arrays already viewed by rows (what the DMAs themselves take), through the
  output's VMEM block and straight from HBM to HBM;
- ``view``: one change of view of the output's size alone.

The kernel moves a row as ONE DMA. It needs the rows viewed ``[N, 8, d/8]``:
a row of the plain ``[N, d]`` array is a sublane (bf16: half a sublane) of
8-row tiles, Mosaic slices a tiled dimension of an HBM reference by whole
tiles only, and a row viewed ``[8, d/8]`` is whole tiles and one contiguous
piece of HBM. XLA makes the change of view, a pass over the array. The
program does not call the kernel (PERF.md section 6, PR 39: the two passes
cost what the DMAs save); it stands here so that the table can be taken
again.

Part 2, what PR 39 shipped instead. ``staged`` among the gathers: the
dispatch's source copied into VMEM first (``ops/take_rows.py``), where XLA's
gather runs at its fast rate whatever the scheduler would have guessed. The
T*k scalars: ``v[at]`` against a sort by the inverse permutation,
``take_along_axis`` against the masked maximum, the scatter-add of ones
against the masked sum. The combine's forward and backward pass
(``models/moe._combine``) against the expression autodiff was given until
then (``was_combine``). One expert block (``moe_ffn`` under
``jax.checkpoint``, value and every gradient) as it was and as it is, with
each leaf's largest difference over the leaf's largest magnitude: on the
chip XLA fuses round other boundaries and a bf16 leaf moves by one ulp.

    chiprun -- python3 benchmarks/take_rows_check.py [block ...]

Writes one JSON line a measurement; exits 2 without a TPU.
"""

import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from torchft_tpu.models import moe  # noqa: E402
from torchft_tpu.ops import take_rows as staged_take  # noqa: E402

D = 2048
# name, tokens, experts, choices a token, an expert's width
SHAPES = [("olmoe", 8192, 64, 8, 1024), ("lfm2", 8192, 32, 4, 1792)]
_SUBLANES = 8


def takes(seed: int, tokens: int, experts: int, k: int):
    """(order, inverse) of a random routing, as ``_dropless_ffn`` makes
    them: ``order // k`` is the dispatch's take (``fan`` k), ``inverse`` the
    unsort's (a permutation)."""
    expert_of = jax.random.randint(jax.random.PRNGKey(seed), (tokens * k,), 0, experts)
    order = jnp.argsort(expert_of, stable=True).astype(jnp.int32)
    inverse = jnp.zeros_like(order).at[order].set(
        jnp.arange(tokens * k, dtype=jnp.int32), unique_indices=True)
    return order, inverse


def timed(f, *args, n: int = 20) -> float:
    jax.block_until_ready(f(*args))
    t0 = time.monotonic()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / n


def _kernel(take_ref, x_ref, o_ref, sem, *, block: int):
    def start(g, carry):
        for u in range(_SUBLANES):  # unrolled: eight rows a turn of the loop
            r = g * _SUBLANES + u
            pltpu.make_async_copy(x_ref.at[take_ref[0, r]], o_ref.at[r], sem).start()
        return carry

    def wait(g, carry):  # every copy is a row: the source's index is no matter
        for u in range(_SUBLANES):
            pltpu.make_async_copy(x_ref.at[0], o_ref.at[g * _SUBLANES + u], sem).wait()
        return carry

    jax.lax.fori_loop(0, block // _SUBLANES, start, 0)
    jax.lax.fori_loop(0, block // _SUBLANES, wait, 0)


def take_rows(x, take, *, block: int = 256, interpret: bool = False):
    """``x[take]`` for x ``[N, d]`` (d a multiple of 1024) and take ``[M]``
    int32 in ``[0, N)``: the indices reach SMEM a block at a time, a grid
    step starts one copy a row from HBM into its block of the output in
    VMEM and waits for them, the pipeline writes the block back under the
    next step's reads. Any M: the indices are padded to whole blocks and
    the last block of the output is written in part."""
    (n, d), m = x.shape, take.shape[0]
    block = min(block, -(-m // _SUBLANES) * _SUBLANES)
    steps = -(-m // block)
    take = jnp.pad(take.astype(jnp.int32), (0, steps * block - m))
    tile = (_SUBLANES, d // _SUBLANES)
    out = pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid=(steps,),
        in_specs=[pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((block, *tile), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((m, *tile), x.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        name="take_rows",
        interpret=interpret,
    )(take.reshape(steps, 1, block), x.reshape(n, *tile))
    return out.reshape(m, d)


def _hbm_kernel(take_ref, x_ref, o_ref, sem, *, block: int, rows: int):
    base = pl.program_id(0) * block
    count = jnp.minimum(block, rows - base)

    def start(r, carry):
        pltpu.make_async_copy(x_ref.at[take_ref[0, r]], o_ref.at[base + r], sem).start()
        return carry

    def wait(r, carry):
        pltpu.make_async_copy(x_ref.at[0], o_ref.at[base + r], sem).wait()
        return carry

    jax.lax.fori_loop(0, count, start, 0)
    jax.lax.fori_loop(0, count, wait, 0)


def hbm_to_hbm(x3, take, block: int):
    """The gather with no VMEM between: a row's DMA goes from HBM to HBM."""
    m = take.shape[0]
    steps = -(-m // block)
    take = jnp.pad(take, (0, steps * block - m)).reshape(steps, 1, block)
    return pl.pallas_call(
        functools.partial(_hbm_kernel, block=block, rows=m),
        grid=(steps,),
        in_specs=[pl.BlockSpec((None, 1, block), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((m, *x3.shape[1:]), x3.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        name="take_rows_hbm",
    )(take, x3)


def kernel_alone(x3, take, block: int):
    n = x3.shape[0]
    return take_rows(x3.reshape(n, -1), take, block=block).reshape(-1, *x3.shape[1:])


def was_combine(rows, weights, inverse, order):
    """The combine as autodiff was given it until PR 39: the unsort a
    ``_take_rows`` whose cotangent is gathered at ``order``."""
    (T, k), d = weights.shape, rows.shape[-1]
    picked = moe._take_rows(rows, inverse, order, 1).reshape(T, k, d)
    return jnp.sum(picked * weights[..., None], axis=1)


def gathers(blocks):
    by_rows = lambda x: x.reshape(x.shape[0], 8, -1)  # noqa: E731
    for name, tokens, experts, k, _ in SHAPES:
        order, unsort = takes(0, tokens, experts, k)
        key = jax.random.PRNGKey(1)
        for what, take, n, unique in ((f"{name}_dispatch", order // k, tokens, False),
                                      (f"{name}_unsort", unsort, tokens * k, True)):
            x = jax.random.normal(key, (n, D)).astype(jnp.bfloat16)
            m = take.shape[0]
            traffic = 2 * m * D * x.dtype.itemsize
            want = jax.jit(lambda x, t: x[t])(x, take)
            variants = {
                "xla": (lambda x, t: x[t], x),
                "xla_promise": (lambda x, t: x.at[t].get(
                    mode="promise_in_bounds", unique_indices=unique), x),
                "xla_tiles": (lambda x, t: by_rows(x)[t].reshape(m, D), x),
                "view": (lambda x, t: by_rows(x), want),
            }
            if staged_take.applies(x):
                variants["staged"] = (staged_take.take_rows, x)
            for b in blocks:
                variants[f"kernel_{b}"] = (functools.partial(take_rows, block=b), x)
                variants[f"kernel_alone_{b}"] = (
                    functools.partial(kernel_alone, block=b), jax.jit(by_rows)(x))
                variants[f"hbm_to_hbm_{b}"] = (
                    functools.partial(hbm_to_hbm, block=b), jax.jit(by_rows)(x))
            for variant, (f, arg) in variants.items():
                f = jax.jit(f)
                line = {"shape": what, "rows_in": n, "rows_out": m, "variant": variant}
                try:
                    got = f(arg, take)
                except Exception as e:  # noqa: BLE001 - a shape the compiler refuses
                    line["refused"] = str(e).splitlines()[0][:200]
                    print(json.dumps(line), flush=True)
                    continue
                if variant != "view":
                    line["bitwise"] = bool(jnp.array_equal(got.reshape(m, D), want))
                s = timed(f, arg, take)
                line.update(seconds=s, traffic_gb_s=traffic / s / 1e9,
                            hbm_share_pct=100 * traffic / s / 819e9)
                print(json.dumps(line), flush=True)


def combines():
    """The combine alone, output and both cotangents, as it was and as it is."""
    for name, tokens, experts, k, _ in SHAPES:
        order, inverse = takes(0, tokens, experts, k)
        keys = jax.random.split(jax.random.PRNGKey(2), 3)
        rows = jax.random.normal(keys[0], (tokens * k, D)).astype(jnp.bfloat16)
        weights = jax.nn.softmax(jax.random.normal(keys[1], (tokens, k))).astype(jnp.bfloat16)
        g = jax.random.normal(keys[2], (tokens, D)).astype(jnp.bfloat16)

        def both(combine):
            def f(rows, weights, g):
                out, pullback = jax.vjp(
                    lambda r, w: combine(r, w, inverse, order), rows, weights)
                return (out, *pullback(g))
            return jax.jit(f)

        was, now = both(was_combine), both(moe._combine)
        same = [bool(jnp.array_equal(a, b)) for a, b in
                zip(was(rows, weights, g), now(rows, weights, g))]
        print(json.dumps({
            "shape": f"{name}_combine", "rows": tokens * k,
            "was_fwd_bwd_s": timed(was, rows, weights, g),
            "now_fwd_bwd_s": timed(now, rows, weights, g),
            "bitwise_out_drows_dweights": same}), flush=True)


def was_scores_at(scores, idx):
    return jnp.take_along_axis(scores, idx, axis=-1)


def was_counts(idx, num_experts):
    return jnp.zeros((num_experts,), jnp.int32).at[idx.reshape(-1)].add(1)


def scalars():
    """The T*k scalars the block moves, as XLA's gather and scatter-add and
    as ``models/moe.py`` moves them now."""
    for name, tokens, experts, k, _ in SHAPES:
        order, inverse = takes(0, tokens, experts, k)
        v = jax.random.normal(jax.random.PRNGKey(4), (tokens * k,)).astype(jnp.bfloat16)
        scores = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(5), (tokens, experts)))
        idx = jax.lax.top_k(scores, k)[1]
        pairs = {
            "permute": (lambda: v[order], lambda: moe._permuted(v, inverse)),
            "scores_at": (lambda: was_scores_at(scores, idx),
                          lambda: moe._scores_at(scores, idx)),
            "counts": (lambda: was_counts(idx, experts), lambda: moe._counts(idx, experts)),
        }
        for what, (was, now) in pairs.items():
            was, now = jax.jit(was), jax.jit(now)
            print(json.dumps({
                "shape": f"{name}_{what}", "scalars": tokens * k,
                "was_s": timed(was), "now_s": timed(now),
                "bitwise": bool(jnp.array_equal(was(), now()))}), flush=True)


WAS = {"_combine": was_combine, "take_rows": lambda x, take: x[take],
       "_permuted": lambda v, back: v[jnp.argsort(back)], "_scores_at": was_scores_at,
       "_counts": was_counts}


def blocks_of_experts():
    """One expert block under ``jax.checkpoint``, value and every gradient,
    as it was before PR 39 and as it is."""
    now = {name: getattr(moe, name) for name in WAS}
    for name, tokens, experts, k, width in SHAPES:
        cfg = dataclasses.replace(
            moe.MOE_CONFIGS["olmoe_1b_7b"], dim=D, ffn_hidden=width,
            num_experts=experts, top_k=k)
        keys = jax.random.split(jax.random.PRNGKey(3), 6)
        x = jax.random.normal(keys[0], (1, tokens, D)).astype(jnp.bfloat16)
        router = jax.random.normal(keys[1], (D, experts)) / D ** 0.5
        w = [(jax.random.normal(kk, shape) / shape[1] ** 0.5).astype(jnp.bfloat16)
             for kk, shape in zip(keys[2:5], ((experts, D, width), (experts, D, width),
                                              (experts, width, D)))]
        target = jax.random.normal(keys[5], x.shape).astype(jnp.bfloat16)

        def grads():
            def loss(x, router, *w):
                block = jax.checkpoint(lambda *a: moe.moe_ffn(*a, cfg)[0])
                return jnp.sum((block(x, router, *w) * target).astype(jnp.float32))
            return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))

        line = {"shape": f"{name}_block", "tokens": tokens}
        out = {}
        try:
            for label, patch in (("was", WAS), ("now", now)):
                for attr, f in patch.items():
                    setattr(moe, attr, f)
                f = grads()
                out[label] = jax.tree_util.tree_leaves(f(x, router, *w))
                line[f"{label}_value_and_grads_s"] = timed(f, x, router, *w, n=10)
        finally:
            for attr, f in now.items():
                setattr(moe, attr, f)
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        line["largest_difference_over_largest_magnitude"] = dict(zip(
            ("value", "x", "router", "w_gate", "w_up", "w_down"),
            (float(jnp.max(jnp.abs(f32(a) - f32(b))) / jnp.max(jnp.abs(f32(b))))
             for a, b in zip(out["now"], out["was"]))))
        print(json.dumps(line), flush=True)


def main(argv):
    if jax.devices()[0].platform != "tpu":
        sys.exit(2)
    gathers([int(a) for a in argv] or [256, 1024])
    scalars()
    combines()
    blocks_of_experts()


if __name__ == "__main__":
    main(sys.argv[1:])
