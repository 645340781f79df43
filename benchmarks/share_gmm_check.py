"""A share's grouped products (ISSUE 51, step 0) and its row moves (ISSUEs 55
and 60, step 0) alone on the chip, at the four share cells' shapes, bf16, tiles
as ``models/moe._grouped_matmul`` sets them: Mellum's buffer (131,072 rows x
2304, 16 matrices of 2304 x 896, an even share of 65,536 pairs), Ling's
(49,152 x 2560, 16 of 2560 x 768, even share 8,192), Nemotron's (24,576 x
2688, 8 ungated experts of 2688 x 1856, even share 6,144) and DeepSeek-V2's
(15,360 x 5120, 10 of 5120 x 1536, even share 6,144).

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
scatter-add) under ``jax.checkpoint``, value and every gradient, as it is
(``true``), with ``_share_sizes`` replaced by PR 50's padded sizes (``padded``)
and with the row moves as PR 51 left them, one gather and one scatter-add of
XLA's over every row of the buffer (``whole``): seconds a call, whether
everything that leaves the block is finite, and each leaf's largest
difference from the block as it is over the leaf's largest magnitude (0.0:
equal bit for bit).

Part 3, ``loads <cell> <seed> ...``: what a share cell's kernels are given
under each seed: the cell's own model and batch as ``chipbench/jobs/bare.py``
makes them from ``--seed``, one forward pass with the stats: ``held_pair_share``
(over T*k), ``visited_row_share`` and ``moved_row_share`` (of the buffer) and
``load_max_over_mean``. A bare run prints none of them, and since PR 51 the
step's time follows them.

Part 4, ``rows``: the two row moves alone (the dispatch's gather into the
buffer, the combine's weighed add out of it, and both pullbacks; no product
between them), value and the gradients of the tokens and the weights, at
loads of 0, 0.7, 1.0 and 1.5 x the even share and at the room exactly, in
four forms: ``whole`` (PR 51's: XLA's one gather and one scatter-add over
every row), ``token_order`` (``moe._share_take`` / ``moe._share_add`` as they
are: the two gathers loops over row tiles that end with the pairs, PR 55; the
two adds in token order, PR 60: one sort of the held pairs' tokens, the rows
gathered into that order, XLA's scatter-add of them sorted, in column blocks
of ``moe.ADD_COLUMNS`` at most) and ``token_order_one_call`` (the same as ONE
scatter-add whatever the width: what ISSUE 60 asked for first) and
``token_order_kernel`` (the same with ``benchmarks/share_add_kernel.py`` for
the scatter-add: measured, and not in the program for its rounding). Wall and
device-busy seconds a call, each leaf's largest difference from the whole
form, and at the even share the device's operations one by one (instruction,
the tail of its ``op_name``, seconds a call). What step 0 of PR 60 found
(PERF.md section 6): XLA sorts and gathers before EVERY one of these
scatter-adds, at DeepSeek's shape too, and ``indices_are_sorted=True`` changes
nothing there; what is slow is its sorted scatter-add at a width of 5,120:
30.3 ms for 15,360 rows, 32.2 for 32,768 and 35.8 for 65,536, so 28.5 ms
whatever the rows (its pass over the OPERAND's 16,384 rows, 1.7 us each) and
110 ns a row, where rows of 2,304 to 2,688 take 60 to 115 ns. In column blocks
it is fast again (two blocks of 2,560: 4.0 ms, five of 1,024: 2.7). The
kernel, which adds a token's run as a one-hot product, took 0.75 ms there,
1.42 on Mellum (XLA 7.90), 0.69 on Ling (4.61), 0.41 on Nemotron (2.82).
(Earlier, PR 55: all four moves as loops were linear in
the load too, but a tile's scatter-add costs 2.3 x a row of XLA's sorted one
over the whole buffer; a ``lax.switch`` over static prefixes grows the program
by 0.6 GB.)

    chiprun -- python3 benchmarks/share_gmm_check.py [products|block|rows ...] [<shape> ...]
    chiprun -- python3 benchmarks/share_gmm_check.py loads <cell> <seed> ...

Writes one JSON line a measurement; exits 2 without a TPU.
"""

import contextlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))  # share_add_kernel

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from torchft_tpu.models import moe  # noqa: E402

# name -> (tokens, k, experts of the router, held, dim, expert width, share_room, form)
SHAPES = {
    "mellum": (32768, 8, 64, 16, 2304, 896, 2.0, "swiglu"),
    "ling": (32768, 8, 512, 16, 2560, 768, 6.0, "swiglu"),
    "nemotron": (16384, 6, 128, 8, 2688, 1856, 4.0, "relu2"),
    "deepseek": (16384, 6, 160, 10, 5120, 1536, 2.5, "swiglu"),
}
LOADS = (0.0, 0.7, 1.0, 1.5)
CALLS = 5


def emit(**line):
    print(json.dumps(line), flush=True)


def padded_sizes(counts, rows_n):
    """The parent's group sizes: the free rows are the last expert's."""
    ends = jnp.minimum(jnp.cumsum(counts), rows_n).at[-1].set(rows_n)
    return jnp.diff(ends, prepend=0).astype(jnp.int32)


def draw_counts(rng, pairs, held):
    """``pairs`` pairs over the ``held`` experts, as a router's first steps
    spread them: multinomial over near-even probabilities."""
    p = rng.dirichlet(np.full(held, 20.0))
    return jnp.asarray(rng.multinomial(pairs, p), jnp.int32)


def share_of(name):
    """A shape's (configuration, even share, expert stacks)."""
    T, k, E, held, d, W, room, act = SHAPES[name]
    cfg = moe.MoEConfig(num_experts=E, top_k=k, capacity_factor=None, expert_act=act,
                        held_experts=(0, held), share_room=room)
    key = jax.random.split(jax.random.PRNGKey(2), 3)
    stacks = [jax.random.normal(key[0], (held, d, W), jnp.bfloat16) / d ** 0.5,
              jax.random.normal(key[1], (held, d, W), jnp.bfloat16) / d ** 0.5,
              jax.random.normal(key[2], (held, W, d), jnp.bfloat16) / W ** 0.5]
    if act == "relu2":
        stacks[0] = None  # ungated: no ``w_gate`` anywhere
    return cfg, T * k * held // E, stacks


def draw_idx(rng, name, pairs):
    """[T, k] experts with ``pairs`` pairs on the held ones, the rest on absent
    ones: a token's k experts need not differ for the block's arithmetic."""
    T, k, E, held = SHAPES[name][:4]
    counts = np.asarray(draw_counts(rng, pairs, held))
    expert = np.concatenate([np.repeat(np.arange(held), counts),
                             rng.integers(held, E, T * k - pairs)])
    return jnp.asarray(rng.permutation(expert).reshape(T, k), jnp.int32)


def visited_tiles(sizes, tm=512):
    sizes = np.asarray(sizes)
    ends = np.cumsum(sizes)
    starts = ends - sizes
    tiles = -(-ends // tm) - starts // tm
    return int(np.where(sizes == 0, 0, tiles).sum())


def traced_events(fn, args):
    """The device's operations (name, start, end in ns) over ``CALLS`` calls."""
    from chipbench import xplane

    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp):
            for _ in range(CALLS):
                out = fn(*args)
            jax.block_until_ready(out)
        (events,) = xplane.read(xplane.find(tmp))["devices"].values()
    return events


def kernel_seconds(fn, args):
    """Seconds a call in device operations named gmm / tgmm, from a trace."""
    return sum(end - start for name, start, end in traced_events(fn, args)
               if "gmm" in name) / 1e9 / CALLS


def busy_seconds(fn, args):
    """Seconds a call in which the device ran an operation, from a trace."""
    from chipbench import xplane

    return sum(end - start for start, end in xplane.busy_intervals(
        traced_events(fn, args))) / 1e9 / CALLS


def wall_seconds(fn, args):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / CALLS


def products(name):
    T, held, d = (SHAPES[name][i] for i in (0, 3, 4))
    cfg, even, (w_gate, w_up, w_down) = share_of(name)
    rows_n = cfg.share_rows(T)
    key = jax.random.split(jax.random.PRNGKey(0), 2)
    rows = jax.random.normal(key[0], (rows_n, d), jnp.bfloat16)
    cot = jax.random.normal(key[1], (rows_n, d), jnp.bfloat16)

    def chain(rows, w_gate, w_up, w_down, sizes):
        h = moe._hidden(rows, w_gate, w_up, lambda r, w: moe._grouped_matmul(r, w, sizes),
                        cfg.expert_act)
        return moe._grouped_matmul(h, w_down, sizes)

    def value(rows, w_gate, w_up, w_down, sizes):
        valid = (jnp.arange(rows_n) < jnp.sum(sizes))[:, None]
        rows = jnp.where(valid, rows, 0)
        out = jnp.where(valid, chain(rows, w_gate, w_up, w_down, sizes), 0)
        return jnp.sum((out * cot).astype(jnp.float32))

    forward = jax.jit(chain)
    both = jax.jit(jax.value_and_grad(value, argnums=(0, 2, 3) if w_gate is None
                                      else (0, 1, 2, 3)))
    rng = np.random.default_rng(51)
    for load in LOADS:
        counts = draw_counts(rng, int(load * even), held)
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


def whole_take(flat, take, by_token, n, tokens):
    """``moe._share_take`` as PR 51 left the dispatch: XLA's one gather over
    every row of the buffer, selected after it."""
    valid = (jnp.arange(take.shape[0]) < n)[:, None]
    return jnp.where(valid, flat[take], 0)


def whole_add(rows, weights, take, by_token, n, tokens):
    """``moe._share_add`` as PR 51 left the combine: every row selected and
    weighed, XLA's one scatter-add of them all."""
    valid = (jnp.arange(take.shape[0]) < n)[:, None]
    return jnp.zeros((tokens, rows.shape[1]), rows.dtype).at[take].add(
        jnp.where(valid, rows, 0) * weights)


@contextlib.contextmanager
def patched(**fns):
    """``models/moe.py`` with some of its names bound anew, for one trace."""
    was = {name: getattr(moe, name) for name in fns}
    for name, fn in fns.items():
        setattr(moe, name, fn)
    try:
        yield
    finally:
        for name, fn in was.items():
            setattr(moe, name, fn)


KINDS = {"true": {}, "padded": {"_share_sizes": padded_sizes},
         "whole": {"_share_take": whole_take, "_share_add": whole_add}}


def worst_diff(got, want):
    """Each leaf's largest difference over ``want``'s largest magnitude."""
    worst = {}
    for leaf in want:
        a, b = (np.asarray(t[leaf].astype(jnp.float32)) for t in (got, want))
        worst[leaf] = float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))
    return worst


def all_finite(tree):
    return all(bool(jnp.all(jnp.isfinite(v.astype(jnp.float32)))) for v in tree.values())


def block(name):
    T, k, d, room = (SHAPES[name][i] for i in (0, 1, 4, 6))
    base, even, stacks = share_of(name)
    key = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(key[0], (T, d), jnp.bfloat16)
    cot = jax.random.normal(key[1], (T, d), jnp.bfloat16)
    gates = jax.random.uniform(key[2], (T, k), jnp.float32)
    rng = np.random.default_rng(52)
    rows_n = base.share_rows(T)

    def make():
        @jax.jit
        def run(x, gates, idx, *stacks):
            def value(x, gates, *stacks):
                out, stats = jax.checkpoint(
                    lambda *a: moe._share_ffn(a[0], a[1], idx, base, *a[2:]))(
                        x, gates, *stacks)
                return jnp.sum((out * cot).astype(jnp.float32)), (out, stats)
            leaves = ("x", "gates", "w_gate", "w_up", "w_down")
            at = [i for i, leaf in enumerate((x, gates, *stacks)) if leaf is not None]
            (v, (out, stats)), grads = jax.value_and_grad(value, argnums=at, has_aux=True)(
                x, gates, *stacks)
            return {"out": out, **{leaves[i]: g for i, g in zip(at, grads)}}, stats
        return run

    runs = {}
    for kind, fns in KINDS.items():
        with patched(**fns):  # the functions are read at trace time: a jit a kind
            runs[kind] = make()
            jax.block_until_ready(runs[kind](x, gates, draw_idx(rng, name, 0), *stacks))
    for load in LOADS + (2.5 * room / 2.0,):  # the last: over the room
        args = (x, gates, draw_idx(rng, name, min(int(load * even), T * k)), *stacks)
        got = {kind: run(*args) for kind, run in runs.items()}
        stats = got["true"][1]
        emit(part="block", shape=name, rows=rows_n, even=even, load=load,
             held_pairs=int(stats["held_pairs"]), overflow=int(stats["overflow"]),
             visited=float(stats["visited"]), moved=float(stats["moved"]),
             **{f"{kind}_s": wall_seconds(run, args) for kind, run in runs.items()},
             **{f"{kind}_gmm_s": kernel_seconds(run, args) for kind, run in runs.items()},
             finite=all_finite(got["true"][0]),
             rel_diff=worst_diff(got["true"][0], got["padded"][0]),
             rel_diff_whole=worst_diff(got["true"][0], got["whole"][0]))


def kernel_add_in_token_order(rows, by_token, tokens):
    """``moe._add_in_token_order`` with the Pallas kernel of
    ``benchmarks/share_add_kernel.py`` for XLA's scatter-add: measured by PR 60
    and not in the program (its docstring says why)."""
    from share_add_kernel import share_add

    key_s, perm = by_token
    return share_add(rows[perm], key_s, tokens)


def by_operation(fn, args, least_s=5e-5):
    """[(instruction, its ``op_name``'s tail, seconds a call)] of the device's
    operations over ``CALLS`` calls, a parent's time less its children's, the
    longest first, those under ``least_s`` a call left out."""
    from chipbench import xplane
    from chipbench.jobs.bare_routed import scopes_of

    took = xplane.self_times(traced_events(fn, args))
    scope = scopes_of(fn.lower(*args).compile().as_text(), took)
    return [(name, "/".join(scope.get(name, "").split("/")[-3:]), round(s / CALLS, 6))
            for name, s in sorted(took.items(), key=lambda kv: -kv[1]) if s / CALLS >= least_s]


def rows(name):
    T, k, d, room = (SHAPES[name][i] for i in (0, 1, 4, 6))
    cfg, even, _ = share_of(name)
    rows_n = cfg.share_rows(T)
    key = jax.random.split(jax.random.PRNGKey(3), 3)
    x = jax.random.normal(key[0], (T, d), jnp.bfloat16)
    cot = jax.random.normal(key[1], (T, d), jnp.bfloat16)
    weights = jax.random.uniform(key[2], (rows_n, 1), jnp.float32).astype(jnp.bfloat16)
    rng = np.random.default_rng(55)

    def make(take_rows, add_rows):
        @jax.jit
        def run(x, weights, take, n):
            by_token = moe._token_order(take, n, T)  # dead code in the whole form

            def value(x, weights):
                # the barrier stands where the grouped products do: the buffer
                # is written before it is read, both ways
                buf = jax.lax.optimization_barrier(take_rows(x, take, by_token, n, T))
                out = add_rows(buf, weights, take, by_token, n, T)
                return jnp.sum((out * cot).astype(jnp.float32)), out
            (_, out), (d_x, d_weights) = jax.value_and_grad(value, argnums=(0, 1),
                                                            has_aux=True)(x, weights)
            return {"out": out, "x": d_x, "weights": d_weights}
        return run

    # the functions are read at trace time: a jit a form, first called as patched
    forms = {"whole": (make(whole_take, whole_add), {}),
             "token_order": (make(moe._share_take, moe._share_add), {}),
             "token_order_one_call": (make(moe._share_take, moe._share_add),
                                      {"_add_block": lambda d: d}),
             "token_order_kernel": (make(moe._share_take, moe._share_add),
                                    {"_add_in_token_order": kernel_add_in_token_order})}
    for load in LOADS + (room,):  # the last: the room exactly
        idx = draw_idx(rng, name, min(int(load * even), T * k))
        local = jnp.where(idx.reshape(-1) < cfg.n_held, idx.reshape(-1), cfg.n_held)
        n = jnp.sum(local < cfg.n_held).astype(jnp.int32)
        take = jnp.argsort(local, stable=True).astype(jnp.int32)[:rows_n] // k
        args = (x, weights, take, n)
        line, want = {}, None
        for form, (run, fns) in forms.items():
            with patched(**fns):
                got = run(*args)
                want = want or got
                line[form] = {"s": wall_seconds(run, args), "busy_s": busy_seconds(run, args),
                              "finite": all_finite(got), "rel_diff": worst_diff(got, want)}
                if load == 1.0:
                    line[form]["by_operation"] = by_operation(run, args)
        emit(part="rows", shape=name, rows=rows_n, even=even, load=load, pairs=int(n),
             moved_tiles=int(moe._moved_tiles(n, rows_n)), **line)


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
                                         "moved_row_share", "load_max_over_mean",
                                         "overflow_pairs")})


def main(argv):
    if jax.default_backend() != "tpu":
        print("share_gmm_check: no TPU", file=sys.stderr)
        return 2
    emit(device=jax.devices()[0].device_kind, jax=jax.__version__)
    if argv[:1] == ["loads"]:
        loads(argv[1], [int(x) for x in argv[2:]])
        return 0
    parts = {"products": products, "block": block, "rows": rows}
    unknown = [a for a in argv if a not in parts and a not in SHAPES]
    if unknown:
        print(f"share_gmm_check: {unknown}: a part of {list(parts)}, a shape of "
              f"{list(SHAPES)}, or 'loads <cell> <seed> ...'", file=sys.stderr)
        return 2
    for name in [a for a in argv if a in SHAPES] or list(SHAPES):
        for part in [a for a in argv if a in parts] or list(parts):
            parts[part](name)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
