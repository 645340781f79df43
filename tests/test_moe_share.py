"""A share's grouped products visit the held pairs only (PR 51) and its
gathers go as far as the pairs do (PR 55): the block against PR 50's padded
sizes and against PR 51's whole-buffer gathers at every load, on the
interpreted kernels, whose NaN in every row they do not write is the test of
the selects; and the counters that say what part of the buffer the products
visit and the gathers touch; and a share's two adds into ``[T, d]`` in token
order (PR 60) against XLA's one scatter-add in the buffer's order."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ling import _program
from torchft_tpu.models import CONFIGS, moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parents_share_ffn(flat, gates, idx, cfg, w_gate, w_up, w_down):
    """``moe._share_ffn`` as it stood before PR 51: every free row of the
    buffer is the LAST held expert's, a row of zeros that the grouped products
    multiply, and the rows are weighed without a select."""
    (T, d), k = flat.shape, idx.shape[1]
    first, held = cfg.held_experts
    rows_n = cfg.share_rows(T)
    local = idx.reshape(T * k) - first
    local = jnp.where((local >= 0) & (local < held), local, held)
    counts = moe._counts(local[:, None], held)
    pairs = jnp.sum(counts)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)[:rows_n]
    valid = (jnp.arange(rows_n) < pairs)[:, None]
    ends = jnp.minimum(jnp.cumsum(counts), rows_n).at[-1].set(rows_n)
    sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    weights = jnp.where(valid, gates.reshape(T * k, 1)[order], 0.0)
    rows = jnp.where(valid, flat[order // k], 0)
    h = jax.nn.silu(moe._grouped_matmul(rows, w_gate, sizes)) * moe._grouped_matmul(
        rows, w_up, sizes)
    rows = moe._grouped_matmul(h, w_down, sizes)
    out = jnp.zeros((T, d), flat.dtype).at[order // k].add(rows * weights.astype(flat.dtype))
    return out, {"counts": counts, "held_pairs": pairs,
                 "overflow": jnp.maximum(pairs - rows_n, 0)}


@pytest.fixture(scope="module")
def check():
    spec = importlib.util.spec_from_file_location(
        "share_gmm_check", os.path.join(ROOT, "benchmarks", "share_gmm_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _share_ffn_of_pr51(check):
    """``moe._share_ffn`` as it stood from PR 51 to PR 54: round the grouped
    products ONE gather and ONE scatter-add of XLA's over every row of the
    buffer, whatever it holds, and autodiff's pullbacks of both: step 0's
    ``whole`` form (``benchmarks/share_gmm_check.py``), the one copy kept."""
    def ffn(*args):
        with check.patched(**check.KINDS["whole"]):
            return moe._share_ffn(*args)
    return ffn


# 2,048 pairs, 4 of 16 experts held, a room of 3: a buffer of 1,536 rows, three
# row tiles of 512 (``_grouped_matmul``'s): held pairs -> row tiles visited
SHARE_LOADS = {"none": (0, 0), "far_under": (200, 1), "across_a_tile": (700, 2),
               "the_room": (1536, 3), "overflow": (1800, 3)}
# held pairs, given the row moves' tile: ``SHARE_LOADS``' and the moves' own
# borders: the pairs end with a move tile, and a row past it
MOVE_LOADS = {"a_tiles_border": lambda tile: 2 * tile, "a_row_past_it": lambda tile: 2 * tile + 1,
              **{name: (lambda tile, pairs=pairs: pairs)
                 for name, (pairs, _) in SHARE_LOADS.items()}}


def _share_case(held_pairs, dtype):
    cfg = moe.MoEConfig(num_experts=16, top_k=4, capacity_factor=None, held_experts=(4, 4),
                        share_room=3.0, dtype=dtype)
    T, k, d, W = 512, 4, 32, 16
    assert cfg.share_rows(T) == 1536
    rng = np.random.default_rng(held_pairs)
    absent = np.setdiff1d(np.arange(16), np.arange(4, 8))
    expert = np.concatenate([rng.integers(4, 8, held_pairs),
                             rng.choice(absent, T * k - held_pairs)])
    idx = jnp.asarray(rng.permutation(expert).reshape(T, k), jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(held_pairs), 6)
    n = lambda key, *shape: (jax.random.normal(key, shape) / np.sqrt(shape[-2])).astype(dtype)  # noqa: E731
    args = (jax.random.normal(ks[0], (T, d)).astype(dtype), jax.random.uniform(ks[1], (T, k)),
            n(ks[2], 4, d, W), n(ks[3], 4, d, W), n(ks[4], 4, W, d))
    return cfg, idx, args, jax.random.normal(ks[5], (T, d)).astype(dtype)


def _value_and_grads(ffn, cfg, idx, args, cot):
    """The block under ``jax.checkpoint``: ((value, (output, stats)), the five
    gradients: tokens, gates, the three expert stacks)."""
    def value(flat, gates, *stacks):
        out, stats = jax.checkpoint(lambda *a: ffn(a[0], a[1], idx, cfg, *a[2:]))(
            flat, gates, *stacks)
        return jnp.sum((out * cot).astype(jnp.float32)), (out, stats)
    return jax.jit(jax.value_and_grad(value, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)


def _assert_the_blocks_agree(got, want):
    """(output, the five gradients) of two forms of the block: bit for bit,
    and finite."""
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


# the larger tile is ``slow``: the small one walks the same loops over more
# tiles at every load and both dtypes (ROADMAP D11: 12 cases, 156 of this
# file's 486 test-seconds; PR 56)
@pytest.mark.parametrize("tile", [pytest.param(2048, marks=pytest.mark.slow), 128],
                         ids=["tile_512", "tile_128"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("load", sorted(MOVE_LOADS))
def test_a_shares_gathers_go_as_far_as_the_pairs_and_nothing_else_moves(
        load, dtype, tile, monkeypatch, check):
    """The dispatch's gather and the gather of the combine's pullback are
    loops over row tiles that end with the tile the last held pair lies in
    (PR 55): the tiles past it are gathered by nobody. The block's output,
    its stats and all five gradients are what PR 51's ONE gather over every
    row gave, bit for bit, and finite, at every load of ``SHARE_LOADS``,
    where the pairs end exactly on a move tile's border and a row past it, at
    the tile a buffer this short is given (512 of its 1,536 rows) and at 128.
    Finite is the test of the selects INSIDE the last tile: past the pairs
    the buffer holds what the grouped products found (NaN under the
    interpreter). ``moved`` counts the rows of the tiles the loops ran."""
    monkeypatch.setattr(moe, "MOVE_TILE", tile)
    rows_n = _share_case(0, dtype)[0].share_rows(512)
    tile = moe._move_tile(rows_n)
    assert tile == min(tile, 512) and rows_n % tile == 0
    held_pairs = MOVE_LOADS[load](tile)
    cfg, idx, args, cot = _share_case(held_pairs, dtype)
    ((_, (out, stats)), grads), ((_, (was_out, was_stats)), was_grads) = (
        _value_and_grads(ffn, cfg, idx, args, cot)
        for ffn in (moe._share_ffn, _share_ffn_of_pr51(check)))
    _assert_the_blocks_agree((out, *grads), (was_out, *was_grads))
    for key, want in was_stats.items():
        np.testing.assert_array_equal(stats[key], want)
    tiles = -(-min(held_pairs, rows_n) // tile)
    assert float(stats["moved"]) == pytest.approx(tiles * tile / rows_n, rel=1e-6)
    assert 0 <= float(stats["moved"]) - float(stats["visited"]) < tile / rows_n
    if load in ("a_tiles_border", "a_row_past_it") and 2 * tile < rows_n:
        assert tiles == 2 + (load == "a_row_past_it")


# the even share of ``_share_case`` is 512 pairs (2,048 pairs, 4 of 16 experts
# held): held pairs at 0, 0.7, 1.0 and 1.5 x it, and over the buffer's 1,536
TOKEN_ORDER_LOADS = {"none": 0, "x0.7": 358, "even": 512, "x1.5": 768, "overflow": 1800}


@pytest.mark.parametrize("columns", [None, 8], ids=["one_block", "four_blocks"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("load", sorted(TOKEN_ORDER_LOADS))
def test_a_shares_rows_come_home_in_token_order(load, dtype, columns, check, monkeypatch):
    """The combine's add and the add of the dispatch's pullback sort the held
    pairs by token, gather the rows into that order and scatter-add them
    sorted, in column blocks (PR 60: ``moe._token_order``,
    ``moe._add_in_token_order``; here the rows' 32 columns as one block and
    as four of 8, the bound patched). The sort is stable, so a token's rows
    are added in the buffer's order, and the CPU adds them one by one either
    way: the block's output and all five gradients are what XLA's ONE
    scatter-add in the buffer's order gave
    (``benchmarks/share_gmm_check.py``'s ``whole``), bit for bit, at every
    load; and finite, though every row past the pairs is gathered too (NaN
    under the interpreter): they are selected away before the gather and
    dropped by their key after it."""
    if columns:
        monkeypatch.setattr(moe, "_add_block", lambda d: columns)
    cfg, idx, args, cot = _share_case(TOKEN_ORDER_LOADS[load], dtype)
    ((_, (out, stats)), grads), ((_, (was_out, was_stats)), was_grads) = (
        _value_and_grads(ffn, cfg, idx, args, cot)
        for ffn in (moe._share_ffn, _share_ffn_of_pr51(check)))
    assert int(stats["held_pairs"]) == TOKEN_ORDER_LOADS[load]
    _assert_the_blocks_agree((out, *grads), (was_out, *was_grads))
    for key, want in was_stats.items():
        np.testing.assert_array_equal(stats[key], want)


@pytest.mark.parametrize("d,block", [(5120, 2560), (2304, 2304), (2560, 2560), (2688, 2688),
                                     (4096, 2048), (7168, 1792), (32, 32)])
def test_the_adds_column_block_is_the_widest_that_xla_takes_at_speed(d, block):
    """``moe._add_block``: the widest multiple of 128 lanes under
    ``ADD_COLUMNS`` that divides the width: the three older share cells'
    rows go as ONE block (XLA's own program), DeepSeek's 5,120 as two of
    2,560; a debug width that is no multiple of 128 goes whole."""
    assert moe._add_block(d) == block and d % block == 0


@pytest.mark.parametrize("pairs", [0, 1, 700, 1536, 1800])
def test_the_token_order_is_a_stable_permutation_with_the_free_rows_last(pairs):
    """``moe._token_order``: ``perm`` is a permutation of the buffer's rows;
    the first ``min(pairs, rows_n)`` keys are the held rows' tokens ascending,
    a token's rows in the buffer's order (the sort is stable), and every key
    after them is ``T``, the key the scatter-add drops."""
    T, rows_n = 512, 1536
    rng = np.random.default_rng(pairs)
    take = jnp.asarray(rng.integers(0, T, rows_n), jnp.int32)
    key_s, perm = jax.jit(moe._token_order, static_argnums=2)(take, jnp.int32(pairs), T)
    key_s, perm, n = np.asarray(key_s), np.asarray(perm), min(pairs, rows_n)
    assert key_s.dtype == perm.dtype == np.int32
    np.testing.assert_array_equal(np.sort(perm), np.arange(rows_n))
    np.testing.assert_array_equal(perm[:n], np.argsort(np.asarray(take)[:n], kind="stable"))
    np.testing.assert_array_equal(key_s[:n], np.asarray(take)[perm[:n]])
    assert (np.diff(key_s[:n]) >= 0).all() and (key_s[n:] == T).all()
    np.testing.assert_array_equal(perm[n:], np.arange(n, rows_n))


@pytest.mark.parametrize("rows_n,tile", [(131072, 2048), (49152, 2048), (24576, 2048),
                                         (1536, 512), (24, 8)])
def test_the_move_tile_divides_the_buffer(rows_n, tile):
    """A buffer's rows are whole move tiles: ``MOVE_TILE`` at the three share
    cells' buffers, the largest part of it that divides a shorter one."""
    assert moe._move_tile(rows_n) == tile and rows_n % tile == 0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("load", sorted(SHARE_LOADS))
def test_a_shares_products_visit_the_held_pairs_only_and_nothing_else_moves(load, dtype):
    """The sizes a share hands its grouped products are the held pairs' own
    (PR 51): the buffer's free rows belong to no group, the kernels' grid
    ends with the last pair, and the rows past it hold what the kernel found
    (the interpreter poisons them with NaN). The block's output, its stats
    and all five gradients (tokens, gates, the three expert stacks) are what
    the parent's padded sizes gave, bit for bit, and finite, at every load:
    no held pair, far under the room, a tile's border crossed, the room
    exactly, overflow. Finite is the test of the selects: a product of a
    poisoned row with a zero weight or a mask is NaN."""
    held_pairs, tiles = SHARE_LOADS[load]
    cfg, idx, args, cot = _share_case(held_pairs, dtype)
    ((_, (out, stats)), grads), ((_, (was_out, was_stats)), was_grads) = (
        _value_and_grads(ffn, cfg, idx, args, cot) for ffn in (moe._share_ffn, _parents_share_ffn))
    _assert_the_blocks_agree((out, *grads), (was_out, *was_grads))
    for key, want in was_stats.items():
        np.testing.assert_array_equal(stats[key], want)
    rows_n = cfg.share_rows(512)
    assert int(stats["held_pairs"]) == held_pairs
    assert int(stats["overflow"]) == max(held_pairs - rows_n, 0)
    assert float(stats["visited"]) == pytest.approx(min(held_pairs, rows_n) / rows_n, rel=1e-6)
    # what the kernels are told: sizes that end with the pairs, so `tiles` row
    # tiles of the three are visited (megablox: ``num_active_tiles``)
    sizes = moe._share_sizes(stats["counts"], rows_n)
    assert int(sizes.sum()) == min(held_pairs, rows_n)
    if held_pairs <= rows_n:
        np.testing.assert_array_equal(sizes, stats["counts"])
    assert -(-int(sizes.sum()) // 512) == tiles


def test_the_interpreter_poisons_what_a_grouped_product_does_not_visit():
    """What gives the finiteness above its teeth: under the interpreter a row
    tile past the last group comes back as NaN (a chip leaves the bits it
    found). Should that change, the test of the selects tests nothing."""
    rows = jnp.ones((1024, 32), jnp.float32)
    out = moe._grouped_matmul(rows, jnp.ones((2, 32, 16)), jnp.asarray([100, 200], jnp.int32))
    assert bool(jnp.all(out[:300] == 32.0)) and bool(jnp.all(jnp.isnan(out[512:])))


@pytest.mark.parametrize("room", [4.0, 0.5], ids=["room", "overflow"])
def test_visited_row_share_counts_the_rows_the_products_visit(room):
    """``expert_scalars``' ``visited_row_share``: ``min(pairs, rows) / rows``
    a layer, the mean over layers; every layer at 1.0 once its share
    overflows; and a trainer logs it where it logs ``held_pair_share``."""
    from torchft_tpu.models import mellum as M

    cfg = dataclasses.replace(CONFIGS["mellum_debug"], dtype=jnp.float32, share_room=room)
    params = M.mellum_init(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, 256)
    rows = cfg.share_rows(96)
    per_layer = M.mellum_hidden(params, tok, cfg)[1]
    held = np.asarray(per_layer["held_pairs"])
    want = np.minimum(held, rows) / rows
    np.testing.assert_allclose(np.asarray(per_layer["visited"]), want, rtol=1e-6)
    assert ((held > rows) == (want == 1.0)).all() and (held > rows).any() == (room < 1)
    _, stats = M.mellum_loss_and_stats(params, tok, tok, cfg)
    np.testing.assert_allclose(float(stats["visited_row_share"]), want.mean(), rtol=1e-6)
    assert "visited" not in stats and "held_pair_share" in stats
    for name in ("mellum_debug", "ling_debug"):
        m, c, p, t = _program(name)
        logged = jax.eval_shape(lambda p: m.loss(p, t, t, c)[1], p)["moe_stats"]  # noqa: B023
        assert {"moe_visited_row_share", "moe_held_pair_share"} <= set(logged)
    m, c, p, t = _program("olmoe_like")  # every row a pair: no share, no counter
    assert not {"moe_visited_row_share", "moe_moved_row_share"} & set(jax.eval_shape(
        lambda p: m.loss(p, t, t, c)[1], p)["moe_stats"])


@pytest.mark.parametrize("name", ["ling_debug", "mellum_debug", "nemotron_h_debug"])
def test_moved_row_share_is_logged_beside_visited_row_share(name):
    """``expert_scalars``' ``moved_row_share`` (PR 55): the mean over the
    layers of ``moved``, the visited part in whole move tiles, so never under
    ``visited_row_share`` and less than a tile over it; every kind that holds
    a share logs it where it logs ``visited_row_share``."""
    m, cfg, p, tok = _program(name)
    stats = jax.jit(lambda p: m.loss(p, tok, tok, cfg)[1])(p)["moe_stats"]
    moved, visited = float(stats["moe_moved_row_share"]), float(stats["moe_visited_row_share"])
    rows_n = cfg.share_rows(tok.size)
    assert 0 < visited <= moved <= 1 and moved - visited < moe._move_tile(rows_n) / rows_n
