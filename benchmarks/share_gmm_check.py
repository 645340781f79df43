"""A share's three grouped products alone on the chip (ISSUE 51, step 0), at
the two share cells' shapes, bf16, tiles as ``models/moe._grouped_matmul``
sets them: Mellum's buffer (131,072 rows x 2304, 16 matrices of 2304 x 896,
an even share of 65,536 pairs) and Ling's (49,152 x 2560, 16 of 2560 x 768,
even share 8,192).

Part 1, ``products``: ``silu(rows @ w_gate) * (rows @ w_up) @ w_down`` forward
and forward + backward (the rows' and the three stacks' cotangents: nine
grouped products) at loads of 0, 0.7, 1.0 and 1.5 x the even share, the pairs
drawn over the 16 experts at random, under the two kinds of group sizes:

- ``padded``: the parent's, the free rows given to the last expert as rows of
  zeros (every row of the buffer belongs to a group);
- ``true``: the pairs' own counts (``moe._share_sizes``): the free rows
  belong to no group.

Each line holds the wall seconds a call and, from a profiler trace of the
same calls, the seconds in the kernels alone (device operations named
``gmm`` / ``tgmm``) and the row tiles the sizes make the kernel visit.
Expected: kernel seconds proportional to the visited tiles, that is to
``load / room`` plus at most one tile of 512 rows an expert.

Part 2, ``block``: ``moe._share_ffn`` itself (route, gather, products, select,
scatter-add) under ``jax.checkpoint``, value and every gradient, with
``_share_sizes`` as it is and replaced by the parent's padded sizes: seconds a
call, whether everything that leaves the block is finite, and each leaf's
largest difference between the two over the leaf's largest magnitude (0.0:
equal bit for bit).

Part 3, ``loads <cell> <seed> ...``: what a share cell's kernels are given
under each seed: the cell's own model and batch as ``chipbench/jobs/bare.py``
makes them from ``--seed``, one forward pass with the stats: ``held_pair_share``
(over T*k), ``visited_row_share`` (of the buffer) and ``load_max_over_mean``.
A bare run prints none of them, and since PR 51 the step's time follows them.

    chiprun -- python3 benchmarks/share_gmm_check.py [mellum|ling ...]
    chiprun -- python3 benchmarks/share_gmm_check.py loads <cell> <seed> ...

Writes one JSON line a measurement; exits 2 without a TPU.
"""

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from torchft_tpu.models import moe  # noqa: E402

HELD = 16
# name -> (tokens, k, experts of the router, dim, expert width, share_room)
SHAPES = {
    "mellum": (32768, 8, 64, 2304, 896, 2.0),
    "ling": (32768, 8, 512, 2560, 768, 6.0),
}
LOADS = (0.0, 0.7, 1.0, 1.5)
CALLS = 5


def emit(**line):
    print(json.dumps(line), flush=True)


def padded_sizes(counts, rows_n):
    """The parent's group sizes: the free rows are the last expert's."""
    ends = jnp.minimum(jnp.cumsum(counts), rows_n).at[-1].set(rows_n)
    return jnp.diff(ends, prepend=0).astype(jnp.int32)


def draw_counts(rng, pairs):
    """``pairs`` pairs over the held experts, as a router's first steps spread
    them: multinomial over near-even probabilities."""
    p = rng.dirichlet(np.full(HELD, 20.0))
    return jnp.asarray(rng.multinomial(pairs, p), jnp.int32)


def visited_tiles(sizes, tm=512):
    sizes = np.asarray(sizes)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    tiles = -(-ends // tm) - starts // tm
    return int(np.where(sizes == 0, 0, tiles).sum())


def kernel_seconds(fn, args):
    """Seconds a call in device operations named gmm / tgmm, from a trace."""
    from chipbench import xplane

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(CALLS):
                out = fn(*args)
            jax.block_until_ready(out)
        (events,) = xplane.read(xplane.find(tmp))["devices"].values()
    return sum(end - start for name, start, end in events if "gmm" in name) / 1e9 / CALLS


def wall_seconds(fn, args):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / CALLS


def products(name):
    T, k, E, d, W, room = SHAPES[name]
    even = T * k * HELD // E
    cfg = moe.MoEConfig(num_experts=E, top_k=k, capacity_factor=None,
                        held_experts=(0, HELD), share_room=room)
    rows_n = cfg.share_rows(T)
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    rows = jax.random.normal(key[0], (rows_n, d), jnp.bfloat16)
    cot = jax.random.normal(key[1], (rows_n, d), jnp.bfloat16)
    w_gate = jax.random.normal(key[2], (HELD, d, W), jnp.bfloat16) / d ** 0.5
    w_up = jax.random.normal(key[3], (HELD, d, W), jnp.bfloat16) / d ** 0.5
    w_down = jax.random.normal(key[4], (HELD, W, d), jnp.bfloat16) / W ** 0.5

    def chain(rows, w_gate, w_up, w_down, sizes):
        h = jax.nn.silu(moe._grouped_matmul(rows, w_gate, sizes)) * moe._grouped_matmul(
            rows, w_up, sizes)
        return moe._grouped_matmul(h, w_down, sizes)

    def value(rows, w_gate, w_up, w_down, sizes):
        valid = (jnp.arange(rows_n) < jnp.sum(sizes))[:, None]
        rows = jnp.where(valid, rows, 0)
        out = jnp.where(valid, chain(rows, w_gate, w_up, w_down, sizes), 0)
        return jnp.sum((out * cot).astype(jnp.float32))

    forward = jax.jit(chain)
    both = jax.jit(jax.value_and_grad(value, argnums=(0, 1, 2, 3)))
    rng = np.random.default_rng(51)
    for load in LOADS:
        counts = draw_counts(rng, int(load * even))
        for kind, sizes in (("padded", padded_sizes(counts, rows_n)),
                            ("true", moe._share_sizes(counts, rows_n))):
            args = (rows, w_gate, w_up, w_down, sizes)
            v, grads = both(*args)
            emit(part="products", shape=name, rows=rows_n, even=even, load=load, sizes=kind,
                 visited_tiles=visited_tiles(sizes), all_tiles=rows_n // 512,
                 forward_s=wall_seconds(forward, args), forward_gmm_s=kernel_seconds(forward, args),
                 both_s=wall_seconds(both, args), both_gmm_s=kernel_seconds(both, args),
                 finite=bool(jnp.isfinite(v) & jnp.all(jnp.asarray(
                     [jnp.all(jnp.isfinite(g.astype(jnp.float32))) for g in grads]))))


def block(name):
    T, k, E, d, W, room = SHAPES[name]
    key = jax.random.split(jax.random.PRNGKey(1), 6)
    x = jax.random.normal(key[0], (T, d), jnp.bfloat16)
    cot = jax.random.normal(key[1], (T, d), jnp.bfloat16)
    w_gate = jax.random.normal(key[2], (HELD, d, W), jnp.bfloat16) / d ** 0.5
    w_up = jax.random.normal(key[3], (HELD, d, W), jnp.bfloat16) / d ** 0.5
    w_down = jax.random.normal(key[4], (HELD, W, d), jnp.bfloat16) / W ** 0.5
    gates = jax.random.uniform(key[5], (T, k), jnp.float32)
    even = T * k * HELD // E
    rng = np.random.default_rng(52)
    base = moe.MoEConfig(num_experts=E, top_k=k, capacity_factor=None,
                         held_experts=(0, HELD), share_room=room)
    rows_n = base.share_rows(T)

    def make():
        @jax.jit
        def run(x, gates, idx, w_gate, w_up, w_down):
            def value(x, gates, w_gate, w_up, w_down):
                out, stats = jax.checkpoint(
                    lambda *a: moe._share_ffn(a[0], a[1], idx, base, *a[2:]))(
                        x, gates, w_gate, w_up, w_down)
                return jnp.sum((out * cot).astype(jnp.float32)), (out, stats)
            (v, (out, stats)), grads = jax.value_and_grad(value, argnums=(0, 1, 2, 3, 4),
                                                          has_aux=True)(
                x, gates, w_gate, w_up, w_down)
            return {"out": out, "x": grads[0], "gates": grads[1], "w_gate": grads[2],
                    "w_up": grads[3], "w_down": grads[4]}, stats
        return run

    for load in LOADS + (2.5 * room / 2.0,):  # the last: over the room
        # ``held`` pairs on the held experts, the rest on absent ones: a
        # token's k experts need not differ for the block's arithmetic
        held = min(int(load * even), T * k)
        counts = np.asarray(draw_counts(rng, held))
        expert = np.concatenate([np.repeat(np.arange(HELD), counts),
                                 rng.integers(HELD, E, T * k - held)])
        idx = jnp.asarray(rng.permutation(expert).reshape(T, k), jnp.int32)
        args = (x, gates, idx, w_gate, w_up, w_down)
        got, seconds, gmm_s = {}, {}, {}
        for kind in ("true", "padded"):
            was = moe._share_sizes
            if kind == "padded":
                moe._share_sizes = padded_sizes
            try:
                run = make()  # a fresh jit a kind: the sizes are read at trace time
                got[kind], stats = jax.block_until_ready(run(*args))
                seconds[kind] = wall_seconds(run, args)
                gmm_s[kind] = kernel_seconds(run, args)
            finally:
                moe._share_sizes = was
        worst = {}
        for leaf in got["true"]:
            a, b = (np.asarray(got[s][leaf].astype(jnp.float32)) for s in ("true", "padded"))
            worst[leaf] = float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
        emit(part="block", shape=name, rows=rows_n, even=even, load=load,
             held_pairs=int(stats["held_pairs"]), overflow=int(stats["overflow"]),
             visited=float(stats["visited"]),
             true_s=seconds["true"], padded_s=seconds["padded"],
             true_gmm_s=gmm_s["true"], padded_gmm_s=gmm_s["padded"],
             finite=all(bool(jnp.all(jnp.isfinite(v.astype(jnp.float32))))
                        for v in got["true"].values()),
             rel_diff=worst)


def loads(name, seeds):
    from chipbench import manifest
    from chipbench.jobs.bare import SEEDS

    cell = manifest.Cell(manifest.ROOT, manifest.load(), name)
    cfg, adapter = cell.config, cell.adapter()
    recipe = cfg["recipe"]
    init_, loss_, _ = adapter.program()
    pc = adapter.config(cfg)
    init = jax.jit(lambda seed: init_(jax.random.PRNGKey(seed), pc))
    stats_of = jax.jit(lambda params, tokens: loss_(
        params, tokens, tokens, pc, with_stats=True, remat=recipe["remat"])[1])
    for seed in seeds:
        tokens = jax.random.randint(jax.random.PRNGKey((seed + 1) % SEEDS),
                                    (recipe["batch_size"], recipe["seq_len"]), 0,
                                    cfg["vocab_size"])
        stats = stats_of(init(seed % SEEDS), tokens)
        emit(part="loads", cell=name, seed=seed, **{
            k: float(stats[k]) for k in ("held_pair_share", "visited_row_share",
                                         "load_max_over_mean", "overflow_pairs")})


def main(argv):
    if jax.default_backend() != "tpu":
        print("share_gmm_check: no TPU", file=sys.stderr)
        return 2
    emit(device=jax.devices()[0].device_kind, jax=jax.__version__)
    if argv[:1] == ["loads"]:
        loads(argv[1], [int(x) for x in argv[2:]])
        return 0
    for name in argv or list(SHAPES):
        products(name)
        block(name)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
