"""The fifth kind of the one trainer's model (``models/ling.py``): what
``moe_ffn`` gained for it (a chip's share of the experts) with the shares of
a layer adding up to the layer. Beside it the guard of the one decoder over
runs and the one registry of kinds (PR 48): every kind's lowered program
pinned to what it was before the four copies of the scan became one,
``model_fns`` finding every preset's kind by class, ``init``'s tree as it
was, and the one fold of layers into runs. The kernels and the router are
``tests/test_ling_kernels.py``'s, the kind itself ``tests/test_ling_kind.py``'s,
the faults ``tests/test_ling_faults.py``'s and ``tests/test_ling_faults_moe.py``'s,
the run under the Manager ``tests/test_ling_manager.py``'s; the program against
the plain reference, whole, is ``tests/chipbench/test_reference_ling.py``'s."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ling_helpers import reference, rel as _rel
from torchft_tpu.models import CONFIGS, model_fns
from torchft_tpu.models import ling as L
from torchft_tpu.models import moe
from torchft_tpu.models.ling import LING_CONFIGS, LingConfig


# ----------------------------------------------------------------- the share

def _block(cfg, seed=0, T=96):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    d, E, W = cfg.dim, cfg.num_experts, cfg.moe_intermediate_size
    n = lambda k, *shape: jax.random.normal(k, shape) / np.sqrt(shape[-2])  # noqa: E731
    return {"x": jax.random.normal(ks[0], (1, T, d)), "router": n(ks[1], d, E),
            "w_gate": n(ks[2], E, d, W), "w_up": n(ks[3], E, d, W), "w_down": n(ks[4], E, W, d),
            "shared": (n(ks[5], d, W), n(ks[6], d, W), n(ks[7], W, d)),
            "bias": 0.01 * jax.random.normal(ks[8], (E,))}


def test_the_shares_add_up_to_the_uncut_layer():
    """32 toy experts in 4 shares of 8: the four partial results, with the
    shared expert (which every chip computes alike) counted once, are what
    the uncut reference gives for the whole layer, and what the program
    gives when it holds all 32; each share's counts are its experts' among
    the whole layer's, and no pair is computed twice or by nobody."""
    cfg = dataclasses.replace(LING_CONFIGS["ling_debug"], dtype=jnp.float32, num_experts=32,
                              n_group=4, topk_group=2, top_k=4, held_experts=None)
    b = _block(cfg)
    ffn = lambda c, at, shared: moe.moe_ffn(  # noqa: E731
        b["x"], b["router"], b["w_gate"][at], b["w_up"][at], b["w_down"][at], c,
        bias=b["bias"], shared=shared)
    whole, stats = ffn(cfg, slice(None), b["shared"])
    file = {"num_experts_per_tok": 4, "n_group": 4, "topk_group": 2, "norm_topk_prob": True,
            "moe_router_enable_expert_bias": True, "routed_scaling_factor": 2.5,
            "deployment": {"experts_held": [0, 32]}}
    want, _ = reference._routed(
        b["x"][0], {**{k: b[k] for k in ("router", "w_gate", "w_up", "w_down")},
                    "shared_gate": b["shared"][0], "shared_up": b["shared"][1],
                    "shared_down": b["shared"][2]}, b["bias"], file, jnp.matmul, jnp.matmul)
    assert _rel(whole[0], want) < 2e-6
    parts, held = [], 0
    for first in range(0, 32, 8):
        share = dataclasses.replace(cfg, held_experts=(first, 8), share_room=8.0)
        out, st = ffn(share, slice(first, first + 8), None)
        parts.append(out)
        np.testing.assert_array_equal(st["counts"], stats["counts"][first:first + 8])
        assert int(st["overflow"]) == 0
        held += int(st["held_pairs"])
    assert held == 96 * 4
    once = ffn(dataclasses.replace(cfg, held_experts=(0, 8)), slice(0, 8), b["shared"])[0] \
        - parts[0]  # the shared expert alone
    assert _rel(sum(parts) + once, whole) < 2e-6


def test_a_shares_buffer_holds_its_room_and_counts_what_it_cannot():
    cfg = dataclasses.replace(LING_CONFIGS["ling_debug"], dtype=jnp.float32)
    assert cfg.share_rows(64) == 64 * 4  # room 4 of a quarter: every pair
    published = LING_CONFIGS["ling_3_0_flash_share"]
    assert published.share_rows(32768) == 6 * 16384 and published.n_held == 32
    assert dataclasses.replace(published, held_experts=(0, 16)).share_rows(32768) == 49152
    assert dataclasses.replace(published, share_room=1.5).share_rows(32768) == 24576
    b = _block(cfg)
    tight = dataclasses.replace(cfg, share_room=0.5)
    at = slice(4, 8)
    out, st = moe.moe_ffn(b["x"], b["router"], b["w_gate"][at], b["w_up"][at],
                          b["w_down"][at], tight, bias=b["bias"])
    rows = tight.share_rows(96)
    assert rows < int(st["held_pairs"]) and int(st["overflow"]) == int(st["held_pairs"]) - rows
    assert bool(jnp.all(jnp.isfinite(out)))
    with pytest.raises(ValueError, match="held_experts"):
        dataclasses.replace(cfg, held_experts=(12, 8))
    with pytest.raises(ValueError, match="dropless"):
        moe._refuse_dropless_ep(cfg, ("ep",))


# ------------------------------------- the programs, the registry, the runs

def _program(name):
    """(the kind's functions, its configuration, a parameter tree and a
    batch) of a pinned case."""
    cfg = (dataclasses.replace(moe.MOE_CONFIGS["debug"], capacity_factor=None,
                               norm_topk_prob=False, qk_norm=True, num_experts=8, top_k=4)
           if name == "olmoe_like" else CONFIGS[name])
    m = model_fns(cfg)
    tok = jax.random.randint(jax.random.PRNGKey(8), (2, 32), 0, cfg.vocab_size)
    return m, cfg, m.init(jax.random.PRNGKey(7), cfg), tok


def _sha(lowered):
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


PARENT = {  # at the parent commit of PR 48 (the tree of PR 45): sha256 of the
    # lowered value-and-grad program of the loss alone, of the loss with the
    # stats a trainer logs, and the loss on the machine that wrote this
    "debug": ("b960470496fcaaa9", "c1960f290d473a89", "0x1.8279000000000p+2"),
    "jamba_debug": ("f85418fbe0152758", "2e2695f5fc513a54", "0x1.6808bc0000000p+2"),
    "lfm2_debug": ("0cef71e6b94158fc", "06996da6beee2394", "0x1.5d5c380000000p+2"),
    # ling_debug and mellum_debug hold a share: pinned anew by PR 51 (the
    # share's group sizes are the held pairs' own, a select before the
    # scatter-add, ``visited_row_share`` beside the loss); the losses are the
    # parent's to the bit, and so is every other line of this table.
    # ling_debug's pair pinned anew by PR 53: the short convolution is ONE
    # program for every kind (``ops/short_conv.py``; at the debug widths its
    # ``jax.numpy`` form, which widens the padded sequence once where Ling's
    # ``widen_late`` widened each shifted view); the loss is PR 51's to the
    # bit, and jamba_debug and lfm2_debug lower to what they did.
    # Both pinned anew by PR 55: a share's two gathers (the dispatch's, the
    # combine's pullback's) are loops over row tiles under the held pairs'
    # count (``moe._share_take``, ``moe._share_add``), and
    # ``moved_row_share`` stands beside the loss; the losses are the parent's
    # to the bit, and every other line of this table and ``STAGED_HEAD`` are
    # as they were: ``_dropless_ffn`` and ``_capacity_ffn`` lower to what
    # they did.
    # ling_debug's pair pinned anew by PR 58: the lowered program holds the
    # delta-rule kernels' bodies. ``kda_bwd``'s is now a chunk's two halves
    # each linearised once (``ops/kda.py``: ``_state_free`` for the block's
    # four chunks as one batch, ``_through_state`` chunk by chunk) with the
    # inverse's pullback in closed form (``_inverse``); ``kda_fwd``'s holds a
    # ``custom_vjp_call`` round the same ten products and reads its block
    # whole before cutting it into chunks. The loss is the parent's to the
    # bit, and no other line moved
    # Both pinned anew by PR 60: a share's two adds into ``[T, d]`` (the
    # combine's, the dispatch's pullback's) go in token order: one sort of the
    # held pairs' tokens a layer (``moe._token_order``), the rows gathered into
    # that order and XLA's scatter-add of them sorted, in column blocks where
    # the rows are wider than ``moe.ADD_COLUMNS`` (``moe._add_in_token_order``).
    # The losses are the parent's to the bit (a stable sort keeps a token's
    # rows in the buffer's order and the CPU adds them one by one either
    # way), and every other line of this table and ``STAGED_HEAD`` are as
    # they were: ``_dropless_ffn`` and ``_capacity_ffn`` lower to what they
    # did.
    # ling_debug's pair pinned anew by PR 65: ``kda_fwd``'s body takes the
    # state-free half of its block's four chunks as one batch before its
    # loop (``jax.vmap`` of ``_state_free_bounded``, as ``kda_bwd``'s has
    # since PR 58) and walks ``_through_state`` chunk by chunk from there;
    # ``kda_bwd``'s body is the parent's. The loss is the parent's to the
    # bit, and no other line moved
    # ling_debug's pair pinned anew by PR 66: the same operations in another
    # order. The mixer's element-wise passes round the delta rule are
    # ``ops/kda_passes.py``'s two entries (at the debug widths their
    # ``jax.numpy`` forms, the parent's arithmetic and roundings): ``beta``
    # is traced after q's and k's norms where it stood before them, the
    # gate's logits before the head-wise norm where they stood after it, and
    # ``g`` and ``o`` reach and leave ``kda`` through reshapes of
    # ``[B, S, H d_k]``. The loss is the parent's to the bit, and no other
    # line moved
    "ling_debug": ("e6aaa4cc9693283d", "ed4503029526adb0", "0x1.7f8a480000000p+2"),
    "mellum_debug": ("ef928f8d611def70", "aad2023958af691a", "0x1.73ce5a0000000p+2"),
    "moe_debug": ("5edda971e37627ff", "e04dd041fd034fbf", "0x1.91db320000000p+2"),
    "olmoe_like": ("8f3d99d1a380ce09", "fe422e374d02371d", "0x1.8c5e1e0000000p+2"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_the_other_kinds_programs_and_losses_are_the_parents(name):
    """Every kind lowers to the very program it lowered to before the one
    decoder over runs and the one registry (PR 48), with and without the
    stats beside the loss, and what ``moe_ffn`` gained for Ling is absent
    unless a configuration asks for it (PR 40: OLMoE's block, the capacity
    path, LFM2's decoder). The program is pinned to the byte; the loss to a
    relative 1e-4, because its last bits are a machine's (the same program
    gave 0x1.5d5b34p+2 where PR 40 pinned LFM2's and 0x1.5d5c38p+2 here)."""
    m, cfg, p, tok = _program(name)
    alone = jax.jit(jax.value_and_grad(lambda p: m.loss(p, tok, tok, cfg)[0]))
    logged = jax.jit(jax.value_and_grad(lambda p: m.loss(p, tok, tok, cfg), has_aux=True))
    want = PARENT[name]
    assert (_sha(alone.lower(p)), _sha(logged.lower(p))) == want[:2]
    value = float(jax.jit(lambda p: m.loss(p, tok, tok, cfg)[0])(p))
    assert abs(value / float.fromhex(want[2]) - 1) < 1e-4, (value.hex(), want[2])


STAGED_HEAD = {"debug": "bb3d6a7c80c06d2a", "moe_debug": "c130898646d9826a"}


@pytest.mark.parametrize("name", sorted(STAGED_HEAD))
def test_the_staged_kinds_head_programs_are_the_parents(name):
    """The head of the chain of programs (``models/staged.py``), where
    ``model_fns`` puts the logged names on an MoE's stats: lowered with its
    backward pass, the parent's to the byte."""
    m, cfg, p, tok = _program(name)
    s = m.stages(cfg, None)
    h, emitted = jax.eval_shape(
        lambda embed, layers: jax.lax.scan(s.layer, s.embed(embed, tok), layers),
        p["embed"], p["layers"])
    head = jax.jit(jax.value_and_grad(
        lambda hp, h, e: s.head(hp, h, e, tok), argnums=(0, 1), has_aux=True))
    rest = {k: v for k, v in p.items() if k not in ("embed", "layers")}
    assert _sha(head.lower(rest, h, emitted)) == STAGED_HEAD[name]


def _kinds():
    from torchft_tpu.models import (brumby, deepseek, jamba, lfm2, llama, mellum, nemotron_h,
                                    ouro, solar)
    from torchft_tpu.parallel.mesh import llama_param_specs

    # class -> (init, param_specs, whether the gradient is staged, frozen: the
    # keys, or what gives them from the configuration)
    return {
        llama.LlamaConfig: (llama.llama_init, llama_param_specs, True, ()),
        moe.MoEConfig: (moe.moe_init, moe.moe_param_specs, True, ()),
        jamba.JambaConfig: (jamba.jamba_init, jamba.jamba_param_specs, False, ()),
        lfm2.Lfm2Config: (lfm2.lfm2_init, lfm2.lfm2_param_specs, False, ("expert_bias",)),
        LingConfig: (L.ling_init, L.ling_param_specs, False, ("expert_bias",)),
        mellum.MellumConfig: (mellum.mellum_init, mellum.mellum_param_specs, False, ()),
        ouro.OuroConfig: (ouro.ouro_init, ouro.ouro_param_specs, True, ()),
        nemotron_h.NemotronHConfig: (nemotron_h.nemotron_h_init, nemotron_h.nemotron_h_param_specs,
                                     False, ("expert_bias",)),
        brumby.BrumbyConfig: (brumby.brumby_init, brumby.brumby_param_specs, False, ()),
        deepseek.DeepseekConfig: (deepseek.deepseek_init, deepseek.deepseek_param_specs,
                                  False, deepseek.frozen_keys),
        solar.SolarConfig: (solar.solar_init, solar.solar_param_specs, False, ("expert_bias",)),
    }


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_model_fns_finds_every_presets_kind_by_class_most_derived_first(name):
    """A LingConfig is an MoEConfig is a LlamaConfig: it gets Ling's
    functions, and so does a class derived from it that registers nothing;
    ``frozen`` and ``stages`` as they were when ``model_fns`` was a ladder
    (since PR 67 a kind's ``frozen`` may follow the configuration: DeepSeek's
    is nothing for V2 and the whole trunk in V3.2's warm-up stage)."""
    cfg = CONFIGS[name]
    init, specs, staged, frozen = _kinds()[type(cfg)]
    for c in (cfg, dataclasses.make_dataclass("Derived", [], bases=(type(cfg),), frozen=True)(
            **dataclasses.asdict(cfg))):
        m = model_fns(c)
        assert m.init is init and m.param_specs is specs
        assert m.frozen == (frozen(c) if callable(frozen) else frozen)
        assert (m.stages is not None) == staged
    assert len({type(c) for c in CONFIGS.values()}) == len(_kinds())
    with pytest.raises(TypeError, match="no kind of model"):
        model_fns(object())


TREES = {  # at the parent commit: sha256 over every leaf's (path, shape, dtype)
    # in tree order, the leaves, the names of the runs' stacks
    "debug": ("ab22f6ab1a4fe53f", 12, None),
    "moe_debug": ("411b627a054a4a47", 13, None),
    "jamba_debug": ("9ef5e4d9e5123b5f", 71, ["00_mamba", "01_attn", "02_mamba", "03_attn",
                                              "04_mamba"]),
    "lfm2_debug": ("d2529a11bfc1b8f1", 62, [
        "00_conv_dense", "01_attn_moe", "02_conv_moe", "03_conv_moe", "04_attn_moe",
        "05_conv_moe"]),
    "ling_debug": ("5913a79d83a6c6ae", 81, ["00_kda_dense", "01_kda_moe", "02_mla_moe",
                                             "03_kda_moe"]),
    "mellum_debug": ("80b94baba2163b45", 51, ["00_window", "01_full", "02_window", "03_full"]),
}


@pytest.mark.parametrize("name", sorted(TREES))
def test_inits_tree_is_the_parents(name):
    """Structure, shapes, dtypes and the runs' names (the heal's, the
    checksum's and the bucket plan's order), not bits: bits are a
    machine's."""
    cfg = CONFIGS[name]
    tree = jax.eval_shape(lambda key: model_fns(cfg).init(key, cfg), jax.random.PRNGKey(7))
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    digest = hashlib.sha256(repr([(jax.tree_util.keystr(k), v.shape, str(v.dtype))
                                  for k, v in leaves]).encode()).hexdigest()[:16]
    runs = TREES[name][2]
    assert (digest, len(leaves)) == TREES[name][:2]
    if runs is not None:
        assert sorted(tree["layers"]) == [r[0] for r in cfg.runs()] == runs


def test_layers_fold_into_runs_by_the_kinds_rule():
    """The one fold (``decoder.runs_of``) under the three rules: equal
    neighbours merge under a name of the kind's choosing (Jamba), under the
    kind itself (Mellum), and dense layers only, an expert layer alone (LFM2,
    Ling)."""
    from torchft_tpu.models.decoder import runs_of

    jamba = runs_of(["mamba", "mamba", "attention", "mamba"],
                    name=lambda kind: "attn" if kind == "attention" else kind)
    assert jamba == [("00_mamba", "mamba", 2), ("01_attn", "attention", 1),
                     ("02_mamba", "mamba", 1)]
    assert runs_of(("window",) * 3 + ("full",) + ("window",)) == [
        ("00_window", "window", 3), ("01_full", "full", 1), ("02_window", "window", 1)]
    kinds = [("kda", "dense"), ("kda", "dense"), ("mla", "dense"), ("kda", "moe"),
             ("kda", "moe"), ("mla", "moe")]
    assert runs_of(kinds, name="_".join, merges=lambda kind: kind[1] == "dense") == [
        ("00_kda_dense", ("kda", "dense"), 2), ("01_mla_dense", ("mla", "dense"), 1),
        ("02_kda_moe", ("kda", "moe"), 1), ("03_kda_moe", ("kda", "moe"), 1),
        ("04_mla_moe", ("mla", "moe"), 1)]
    assert runs_of([]) == []
    for name in ("jamba2_3b", "lfm2_8b_a1b", "ling_3_0_flash_share", "mellum2_12b_a2_5b_share"):
        runs = CONFIGS[name].runs()
        assert sum(n for _, _, n in runs) == CONFIGS[name].n_layers
        assert [r[0] for r in runs] == sorted(r[0] for r in runs)
