"""models/staged.py: the chain of gradient programs equals ONE
``jax.value_and_grad`` of the kind's loss function: loss, stats and every
gradient leaf, for one segment, one layer a segment and an uneven last
segment; on one device and on a two-device ``fsdp`` mesh; and the parts come
in one order whatever the values."""

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.bucketing import SEGMENT_FLOOR_BYTES
from torchft_tpu.models import CONFIGS, model_fns
from torchft_tpu.models.staged import segments, staged_value_and_grad
from torchft_tpu.ops import attention as attention_ops
from torchft_tpu.parallel.mesh import batch_sharding, make_hsdp_mesh, shard_params
from torchft_tpu.parallel.ring_attention import make_sp_attention_fn

# five layers: two a segment leaves one for the last
KINDS = {
    "llama": dataclasses.replace(CONFIGS["debug"], n_layers=5),
    "moe_capacity": dataclasses.replace(CONFIGS["moe_debug"], n_layers=5),
    "moe_dropless": dataclasses.replace(
        CONFIGS["moe_debug"], n_layers=5, num_experts=8, top_k=4,
        capacity_factor=None, norm_topk_prob=False, qk_norm=True),
    "jamba": dataclasses.replace(CONFIGS["jamba_debug"], dtype=jnp.float32),
    # four passes over the five layers, the loss over the four exits in chunks
    "ouro": dataclasses.replace(CONFIGS["ouro_debug"], n_layers=5, loss_chunk=16),
    "ouro_one_pass": dataclasses.replace(CONFIGS["ouro_debug"], n_layers=5, total_ut_steps=1),
}
# layers a segment -> the parts the chain hands out (head, segments)
SPLITS = {"one_segment": (5, 2), "one_layer_a_segment": (1, 6),
          "uneven_last_segment": (2, 4)}
# Tier-1 keeps all three splits of the dense kind and, of every wider kind,
# the split that has every mechanism at once (segments of two layers and an
# uneven last one); those kinds' other two splits are ``slow`` (ROADMAP D11:
# this file took 521 of tier-1's 7,047 test-seconds; PR 56).
_WIDE = ("moe_capacity", "moe_dropless", "ouro", "ouro_one_pass")


def _case(kind, split):
    wide = kind in _WIDE and split != "uneven_last_segment"
    return pytest.param(kind, split, marks=pytest.mark.slow) if wide else (kind, split)


_ALL = [(k, s) for k in KINDS for s in SPLITS
        if k != "jamba" or s == "one_segment"]  # the hybrid is one program
CASES = [_case(*c) for c in _ALL]


def _setup(kind, dtype=jnp.float32, mesh=None):
    cfg = dataclasses.replace(KINDS[kind], dtype=dtype)
    fns = model_fns(cfg)
    params = fns.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    targets = jnp.roll(tokens, -1, axis=1)
    attention_fn = None
    if mesh is not None:
        params = shard_params(params, mesh, fns.param_specs(cfg))
        tokens, targets = (jax.device_put(x, batch_sharding(mesh))
                           for x in (tokens, targets))
        attention_fn = make_sp_attention_fn(mesh, attention_ops.causal_attention)
    loss_fn = partial(fns.loss, cfg=cfg, attention_fn=attention_fn, remat="full")
    stages = fns.stages and fns.stages(cfg, attention_fn)
    return cfg, params, tokens, targets, loss_fn, stages


def _layer_bytes(params):
    leaves = jax.tree_util.tree_leaves(params["layers"])
    return sum(x.size * x.dtype.itemsize for x in leaves) // leaves[0].shape[0]


def _chain(params, tokens, targets, loss_fn, stages, per_segment, **kw):
    floor = per_segment * _layer_bytes(params) if stages else SEGMENT_FLOOR_BYTES
    run, assemble = staged_value_and_grad(
        stages, loss_fn, floor_bytes=floor, **kw)
    parts = []
    out = run(params, tokens, targets, parts.append)
    return out, parts, jax.jit(assemble)(parts)


def _worst(got, want):
    """Largest |got - want| of any leaf over that leaf's largest |want|."""
    def rel(a, b):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    return max(jax.tree_util.tree_leaves(
        jax.tree_util.tree_map(rel, got, want)), default=0.0)


@pytest.mark.parametrize("kind,split", CASES)
def test_float32_chain_equals_one_value_and_grad(kind, split):
    """float32 parameters: to 1e-6 of each leaf's largest value (read: 0.0
    in every case on this CPU backend: the same operations in the same
    order, cut into programs)."""
    per_segment, n_parts = SPLITS[split]
    cfg, params, tokens, targets, loss_fn, stages = _setup(kind)
    (loss, stats), parts, grads = _chain(
        params, tokens, targets, loss_fn, stages, per_segment)
    (want_loss, want_stats), want = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params, tokens, targets)
    assert len(parts) == (n_parts if stages else 1)
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    assert _worst(stats, want_stats) <= 1e-6
    assert _worst(grads, want) <= 1e-6
    for leaf, like in zip(jax.tree_util.tree_leaves(grads),
                          jax.tree_util.tree_leaves(params)):
        assert leaf.shape == like.shape and leaf.dtype == like.dtype


@pytest.mark.parametrize("kind,split", [_case(*c) for c in _ALL if c[0] != "jamba"])
def test_bf16_chain_equals_one_value_and_grad(kind, split):
    """bf16 parameters and gradients, as the cells train. Two orders of the
    same additions may differ by bf16's rounding of a sum, 2**-8 of it: the
    limit is two such roundings of a leaf's largest value. Read on this CPU
    backend: 0.0 for every kind and split, loss and leaves (the chain runs
    the one program's operations in its order)."""
    per_segment, _ = SPLITS[split]
    cfg, params, tokens, targets, loss_fn, stages = _setup(kind, jnp.bfloat16)
    (loss, _stats), _parts, grads = _chain(
        params, tokens, targets, loss_fn, stages, per_segment)
    (want_loss, _), want = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params, tokens, targets)
    assert abs(float(loss) - float(want_loss)) <= 2.0 ** -7 * abs(float(want_loss))
    assert _worst(grads, want) <= 2.0 ** -7
    assert all(g.dtype == p.dtype for g, p in zip(
        jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(params)))


@pytest.mark.parametrize("kind,split", [
    ("llama", "one_layer_a_segment"), ("llama", "uneven_last_segment"),
    ("moe_capacity", "one_layer_a_segment"), ("moe_dropless", "uneven_last_segment"),
    ("jamba", "one_segment"), ("ouro", "uneven_last_segment")])
def test_on_a_two_device_fsdp_mesh(kind, split):
    """In-group sharding runs the same chain: parameters over ``fsdp``, the
    trainer's attention function, every gradient leaf pinned to its
    parameter's sharding; equal to the one program on one device (float32:
    the mesh reduces in another order, so 1e-5)."""
    per_segment, _ = SPLITS[split]
    mesh = make_hsdp_mesh(jax.devices()[:2], dp=1, fsdp=2, sp=1, tp=1)
    cfg, params, tokens, targets, loss_fn, stages = _setup(kind, mesh=mesh)
    shardings = jax.tree_util.tree_map(lambda x: x.sharding, params)
    (loss, _stats), parts, grads = _chain(
        params, tokens, targets, loss_fn, stages, per_segment,
        shardings=shardings)
    _cfg, p1, t1, y1, loss_1, _ = _setup(kind)
    (want_loss, _), want = jax.jit(
        jax.value_and_grad(loss_1, has_aux=True))(p1, t1, y1)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    assert _worst(grads, want) <= 1e-5
    if stages:  # a part's leaves already sit where their parameters do
        for part in parts[1:]:
            for name, leaf in part["layers"].items():
                assert leaf.sharding.is_equivalent_to(
                    shardings["layers"][name], leaf.ndim), name
        assert parts[-1]["embed"].sharding.is_equivalent_to(shardings["embed"], 2)


@pytest.mark.parametrize("kind", ["llama", "moe_dropless"])
def test_the_parts_come_in_one_order_whatever_the_values(kind):
    """Two groups hold different data (and, before the first heal, different
    parameters): each hands its parts to the host exchange in the same
    order with the same shapes, every part a tree of more than one leaf (a
    lone leaf has no bucket plan), nothing emitted before its program is
    dispatched."""
    cfg, params, tokens, targets, loss_fn, stages = _setup(kind)
    run, _assemble = staged_value_and_grad(
        stages, loss_fn, floor_bytes=2 * _layer_bytes(params))
    seen = []
    for group in range(2):
        other = jax.tree_util.tree_map(lambda x: x * (1 + group), params)
        toks = jnp.roll(tokens, group, axis=0) * (1 - group)
        order = []
        run(other, toks, targets, lambda part: order.append(
            [(jax.tree_util.keystr(k), v.shape, str(v.dtype)) for k, v
             in jax.tree_util.tree_flatten_with_path(part)[0]]))
        seen.append(order)
    assert seen[0] == seen[1]
    assert [len(part) > 1 for part in seen[0]] == [True] * 4
    assert [name for name, _, _ in seen[0][0]] == ["['final_norm']", "['lm_head']"]
    assert "['embed']" in [name for name, _, _ in seen[0][-1]]
    # top layers first: the backward pass reaches them first
    assert [part[1][1][0] for part in seen[0][1:]] == [2, 2, 1]


@pytest.mark.parametrize("kind", ["ouro", "ouro_one_pass"])
def test_a_looped_kind_emits_its_layers_in_the_last_pass_of_the_backward(kind):
    """Four passes over one stack: the head's part (the leaves whose gradient
    is whole after program A: not the norm every pass boundary reads), NOTHING
    while the backward goes through passes 4, 3 and 2 (three segment programs
    each, adding into the segments' trees), then pass 1's segments, top
    layers first, ``embed`` and ``final_norm`` with the last; one pass is the
    dense chain's order. Told from the programs dispatched between the
    emits."""
    cfg, params, tokens, targets, loss_fn, stages = _setup(kind)
    assert stages.loops == cfg.total_ut_steps
    log, parts, real_jit = [], [], jax.jit

    def jit(fn=None, **kw):  # the chain's programs, each call written down
        if fn is None:
            return lambda f: jit(f, **kw)
        made = real_jit(fn, **kw)

        def call(*a, **k):
            log.append(fn.__name__)
            return made(*a, **k)
        return call

    jax.jit = jit
    try:
        run, assemble = staged_value_and_grad(
            stages, loss_fn, floor_bytes=2 * _layer_bytes(params))
    finally:
        jax.jit = real_jit

    def emit(part):
        log.append("emit:" + ",".join(sorted(part)))
        parts.append(part)

    run(params, tokens, targets, emit)
    want = ["forward_and_exits", "emit:exit_gate,lm_head"]
    for t in reversed(range(cfg.total_ut_steps)):
        want.append("boundary_backward")
        for s in range(3):
            want.append("segment_backward")
            if t == 0:
                want += ["emit:layers"] if s < 2 else [
                    "embed_backward", "emit:embed,final_norm,layers"]
    assert log == want
    assert [jax.tree_util.tree_leaves(p["layers"])[0].shape[0] for p in parts[1:]] == [2, 2, 1]
    (_, _), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params, tokens, targets)
    assert _worst(jax.jit(assemble)(parts), grads) <= 1e-6


def test_one_pass_of_a_looped_kind_is_a_plain_mean_cross_entropy():
    """``total_ut_steps`` 1: the one exit takes all of the mass, the entropy
    is 0, the gate gets no gradient, and the loss is the dense kind's mean
    cross-entropy of the same states."""
    from torchft_tpu.models.llama import head_loss
    from torchft_tpu.models.ouro import ouro_exits

    cfg, params, tokens, targets, loss_fn, _ = _setup("ouro_one_pass")
    (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, tokens, targets)
    h = ouro_exits(params, tokens, cfg)
    assert h.shape[0] == 1
    assert float(loss) == pytest.approx(float(head_loss(h[0], params["lm_head"], targets)),
                                        rel=1e-6)
    stats = stats["loop_stats"]
    assert float(stats["loop_exit_step_mean"]) == float(stats["loop_p_last"]) == 1.0
    assert float(stats["loop_exit_entropy"]) == 0.0
    assert sorted(stats) == ["loop_ce_1", "loop_exit_entropy", "loop_exit_step_mean",
                             "loop_p_last"]
    assert all(float(jnp.max(jnp.abs(g))) == 0 for g in jax.tree_util.tree_leaves(
        grads["exit_gate"]))


@pytest.mark.parametrize("n_layers,layer_bytes,floor,want", [
    (5, 10, 20, [(3, 2), (1, 2), (0, 1)]),  # an uneven last segment
    (4, 100, 20, [(3, 1), (2, 1), (1, 1), (0, 1)]),  # a layer is over the floor
    (2, 10, 10 ** 9, [(0, 2)]),  # all of them are under it: one segment
    (4, 10, 15, [(2, 2), (0, 2)]),  # merged only UNTIL the floor is reached
    (1, 10, 0, [(0, 1)]),
])
def test_segments_are_whole_layers_merged_up_to_the_floor(
        n_layers, layer_bytes, floor, want):
    got = segments(n_layers, layer_bytes, floor)
    assert got == want
    assert sorted(l for l0, n in got for l in range(l0, l0 + n)) == list(range(n_layers))


def test_the_floor_at_the_benchmarks_depths():
    """Mistral-7B's layer (0.44 GB of bf16 gradients) and OLMoE's (0.84 GB)
    are a segment each; InternLM2-1.8B's two (0.126 GB each) travel
    together."""
    assert SEGMENT_FLOOR_BYTES == 1 << 27
    mistral = 2 * (2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096)
    internlm2 = 2 * (2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 8192 + 2 * 2048)
    assert segments(4, mistral) == [(3, 1), (2, 1), (1, 1), (0, 1)]
    assert segments(2, internlm2) == [(0, 2)]
    assert len(segments(24, internlm2)) == 12
