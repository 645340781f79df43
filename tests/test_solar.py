"""The twelfth kind of the one trainer's model (``models/solar.py``): KDA in
Kimi Linear's unbounded form with ``beta`` to 2 and rank-r decay and gate
projections (``models/kda.py``, the one mixer Ling's kind calls too) round a
gated grouped-query attention layer without positions, every layer ending in
a share of sigmoid-routed experts beside a shared one. The kind through
``model_fns`` against the plain reference on seeded weights (logits, loss,
the gradient of every leaf), THE ADD-UP TEST THAT TIES THE SHARE TO THE MODEL
(the expert shares' routed parts plus the shared expert once are the uncut
reference's block), each fault of ``benchmarks/solar_check_faults.py`` seen
by the part it is put into, the counters, the registry, the published cut's
count, what is refused and the trainer's ``--config``. The kernels are
``tests/test_solar_kernels.py``'s, the cell's check
``tests/chipbench/test_reference_solar_open2.py``'s, the run under the
Manager ``tests/test_solar_manager.py``'s, the other kinds' lowered programs
``tests/test_ling.py``'s pins."""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import reference_solar_open2 as reference  # noqa: E402
from torchft_tpu.models import CONFIGS, kinds, model_fns, moe, split_frozen  # noqa: E402
from torchft_tpu.models import solar as M  # noqa: E402
from torchft_tpu.models.solar import SolarConfig  # noqa: E402

DEBUG = dataclasses.replace(CONFIGS["solar_debug"], dtype=jnp.float32)
SEQ = 80  # across a chunk's border of the kernels; no whole number of blocks

_spec = importlib.util.spec_from_file_location(
    "solar_check_faults", f"{ROOT}/benchmarks/solar_check_faults.py")
faults = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(faults)


def _file_of(cfg: SolarConfig, **changed) -> dict:
    """The configuration object as the keys the reference reads."""
    first, held = cfg.held_experts or (0, cfg.num_experts)
    return {"num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
            "num_attention_heads": cfg.n_heads, "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.head_dim, "use_rope": False, "use_gqa_gate": True,
            "kda_allow_neg_eigval": True, "gqa_layers": list(cfg.gqa_layers),
            "linear_attn_config": {"head_dim": cfg.kda_head_dim, "num_heads": cfg.n_heads,
                                   "short_conv_kernel_size": cfg.kda_conv, "num_kv_heads": None},
            "num_experts_per_tok": cfg.top_k, "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling,
            "deployment": {"experts_held": [first, held], "router_outputs": cfg.num_experts,
                           "published_layers": [0, cfg.n_layers - 1]},
            **changed}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _flat(tree):
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def both():
    """``solar_debug`` in float32 (GQA, KDA, KDA, KDA; 4 of 16 experts held)
    and the reference's loss differentiated as it stands, on the same seeded
    weights."""
    m = model_fns(DEBUG)
    params = m.init(jax.random.PRNGKey(0), DEBUG)
    trainable, frozen = split_frozen(params, m.frozen)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, DEBUG.vocab_size)
    (a, stats), ga = jax.jit(jax.value_and_grad(
        lambda p: m.loss({**p, **frozen}, tok, tok, DEBUG), has_aux=True))(trainable)
    file = _file_of(DEBUG)
    with jax.default_matmul_precision("highest"):
        b, gb = jax.jit(jax.value_and_grad(lambda p: reference.loss(
            reference.forward({**p, **frozen}, tok, file)[0], tok)))(trainable)
        logits, routing = jax.jit(lambda p: reference.forward(p, tok, file))(params)
    return {"a": float(a), "b": float(b), "ga": _flat(ga), "gb": _flat(gb), "stats": stats,
            "params": params, "tok": tok, "logits": logits, "routing": routing}


def test_the_forward_pass_and_the_loss_are_the_plain_references(both):
    got = jax.jit(lambda p: M.solar_forward(p, both["tok"], DEBUG))(both["params"])
    assert got.shape == (2, SEQ, DEBUG.vocab_size) and got.dtype == jnp.float32
    assert _rel(got, both["logits"]) < 2e-5
    assert abs(both["a"] - both["b"]) < 3e-6


def test_routing_decisions_are_the_references_apart_from_arithmetic(both):
    """Routing freely the program chooses the reference's experts; under
    replay of OTHER experts (the reference's, rolled by one) it uses those
    and still reports what it would have chosen."""
    _, free = jax.jit(lambda p: M.solar_loss_and_stats(p, both["tok"], both["tok"], DEBUG))(
        both["params"])
    ref = np.asarray(both["routing"]["routing"])
    np.testing.assert_array_equal(np.sort(np.asarray(free["routing"]), -1), np.sort(ref, -1))
    np.testing.assert_allclose(free["p_kth"], both["routing"]["p_kth"], rtol=1e-5)
    other = jnp.asarray((ref + 1) % DEBUG.num_experts)
    loss, replayed = jax.jit(lambda p: M.solar_loss_and_stats(
        p, both["tok"], both["tok"], DEBUG, routing=other))(both["params"])
    assert abs(float(loss) - both["a"]) > 1e-4
    np.testing.assert_array_equal(np.sort(np.asarray(replayed["routing"])[0], -1),
                                  np.sort(ref[0], -1))  # the first layer's input is unmoved


GQA = ["norm", "wq", "wk", "wv", "w_g", "wo", "ffn_norm"]
KDA = ["norm", "wq", "wk", "wv", "w_fa", "w_fb", "conv_q", "conv_k", "conv_v", "A_log", "dt_bias",
       "w_beta", "o_norm", "w_ga", "w_gb", "b_g", "wo", "ffn_norm"]
FFN = ["router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up", "shared_down"]


@pytest.mark.parametrize("leaf", ["embed", "final_norm", "lm_head"]
                         + [f"00_gqa_moe.{n}" for n in GQA + FFN]
                         + [f"{run}.{n}" for run in ("01_kda_moe", "03_kda_moe")
                            for n in KDA + FFN])
def test_every_leafs_gradient_is_the_plain_references(both, leaf):
    ga, gb = both["ga"], both["gb"]
    key = ("['layers']['%s']['%s']" % tuple(leaf.split(".")) if "." in leaf else f"['{leaf}']")
    assert sorted(ga) == sorted(gb) and len(ga) == 3 + len(GQA + FFN) + 3 * len(KDA + FFN)
    assert "['expert_bias']" not in ga  # state: no gradient is taken
    assert _rel(ga[key], gb[key]) < 5e-5, leaf


def test_the_counters_ride_the_loss_under_the_names_a_trainer_logs(both):
    assert sorted(both["stats"]) == ["kda_stats", "moe_stats"]
    moe_ = {k: float(v) for k, v in both["stats"]["moe_stats"].items()}
    assert sorted(moe_) == ["moe_bias_moved_share", "moe_held_pair_share",
                            "moe_load_max_over_mean", "moe_moved_row_share",
                            "moe_overflow_pairs", "moe_visited_row_share"]
    assert moe_["moe_overflow_pairs"] == 0 and 0.05 < moe_["moe_held_pair_share"] < 0.6
    assert 0 < moe_["moe_bias_moved_share"] < 1  # the selection reads the frozen bias
    kda = {k: float(v) for k, v in both["stats"]["kda_stats"].items()}
    assert sorted(kda) == ["kda_beta_over_one_share", "kda_decay_past_bound_share"]
    # beta = 2 sigmoid(.) is over 1 for half of a symmetric input; at Kimi
    # Linear's initialisation a few (position, channel) pairs in a thousand
    # decay faster than Ling's bound allows
    assert 0.3 < kda["kda_beta_over_one_share"] < 0.7
    assert 0 < kda["kda_decay_past_bound_share"] < 0.05


# ---- the share tied to the model (the model-configs guide, section 4)

def test_the_expert_shares_routed_parts_add_up_to_the_uncut_references():
    """20 toy experts in four shares of 5 (not a power of two, as the
    published 320 in 32 of 10): the four routed parts, with the shared expert
    (which every chip computes alike) counted ONCE, are what the uncut
    reference gives for the whole block; each share's counts are its
    experts' among the whole layer's, and no pair is computed twice or by
    nobody."""
    cfg = dataclasses.replace(DEBUG, num_experts=20, top_k=4, held_experts=None)
    ks = jax.random.split(jax.random.PRNGKey(3), 9)
    d, E, W, T = cfg.dim, 20, cfg.moe_intermediate_size, 96
    n = lambda k, *shape: jax.random.normal(k, shape) / np.sqrt(shape[-2])  # noqa: E731
    x, router = jax.random.normal(ks[0], (1, T, d)), n(ks[1], d, E)
    w = {"w_gate": n(ks[2], E, d, W), "w_up": n(ks[3], E, d, W), "w_down": n(ks[4], E, W, d)}
    shared = (n(ks[5], d, W), n(ks[6], d, W), n(ks[7], W, d))
    bias = 0.01 * jax.random.normal(ks[8], (E,))
    ffn = lambda c, at, shared: moe.moe_ffn(  # noqa: E731
        x, router, w["w_gate"][at], w["w_up"][at], w["w_down"][at], c, bias=bias, shared=shared)
    whole, stats = ffn(cfg, slice(None), shared)
    want, _ = reference._routed(
        x[0], {"router": router, **w, "shared_gate": shared[0], "shared_up": shared[1],
               "shared_down": shared[2]}, bias, _file_of(cfg), jnp.matmul, jnp.matmul)
    assert _rel(whole[0], want) < 2e-6
    parts, held = [], 0
    for first in range(0, E, 5):
        share = dataclasses.replace(cfg, held_experts=(first, 5), share_room=8.0)
        out, st = ffn(share, slice(first, first + 5), None)
        parts.append(out)
        np.testing.assert_array_equal(st["counts"], stats["counts"][first:first + 5])
        assert int(st["overflow"]) == 0
        held += int(st["held_pairs"])
    assert held == T * 4
    once = reference._swiglu(x[0], *shared, jnp.matmul)  # the shared expert alone
    assert _rel(sum(parts)[0] + once, want) < 2e-6


# ---- each fault, seen by the part it is put into

def _mixer_inputs(name, seed=0):
    params = M.solar_init(jax.random.PRNGKey(seed), DEBUG)
    w = jax.tree_util.tree_map(lambda x: x[0], params["layers"][name])
    return jax.random.normal(jax.random.PRNGKey(seed + 1), (1, 96, DEBUG.dim)), w


# fault -> (the part it is put into, the least it moves that part's output)
FAULT_SEEN_IN = {
    "beta_not_doubled": ("kda", 0.05), "decay_clipped": ("kda", 1e-4),
    "head_wise_gate": ("kda", 0.05), "lost_tap": ("kda", 0.05), "bf16_state": ("kda", 1e-4),
    "gqa_rope": ("gqa", 0.05), "no_gqa_gate": ("gqa", 0.05), "no_shared": ("moe", 0.05),
}


@pytest.mark.parametrize("name", sorted(FAULT_SEEN_IN))
def test_each_fault_moves_the_one_part_it_is_put_into(name):
    """The program's mixer or block in float32 is the reference's to
    rounding; with the fault of ``benchmarks/solar_check_faults.py`` in, it
    is off by at least the share stated. The decay's clip shows only where a
    decay passes -5: the KDA case runs on an input scaled so that a tenth
    of them do. (Whether the cell's CHECK refuses the fault is
    ``tests/chipbench/test_reference_solar_open2.py``'s and the chip's.)"""
    part, least = FAULT_SEEN_IN[name]
    file = _file_of(DEBUG)
    if part == "kda":
        u, w = _mixer_inputs("01_kda_moe")
        u = 4.0 * u
        run = lambda: M.kda_mixer(u, w, DEBUG, decay_floor=None, beta_max=M.BETA_MAX)[0]  # noqa: E731
        want = reference._kda(u, w, file, jnp.matmul)
    elif part == "gqa":
        u, w = _mixer_inputs("00_gqa_moe")
        run = lambda: M._gqa_mixer(u, w, DEBUG, M._attention)  # noqa: E731
        want = reference._gqa(u, w, file, jnp.matmul)
    else:
        u, w = _mixer_inputs("03_kda_moe")
        bias = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (16,))
        run = lambda: M.moe_ffn(  # noqa: E731
            u, w["router"], w["w_gate"], w["w_up"], w["w_down"], DEBUG, bias=bias,
            shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))[0]
        want = reference._routed(u[0], w, bias, file, jnp.matmul, jnp.matmul)[0][None]
    jax.clear_caches()
    assert _rel(run(), want) < 2e-5
    jax.clear_caches()
    with faults.fault(name, DEBUG):
        off = _rel(run(), want)
    jax.clear_caches()
    assert off > least, (name, off)


def test_the_layer_body_reads_the_faults_where_the_mixers_tests_do():
    """The faults that patch ``models/solar.py``'s own names (``BETA_MAX``,
    ``kda_mixer``, ``_gqa_mixer``, ``_attention``, ``moe_ffn``) are read by
    the layer body when it is traced: one layer's output moves under each."""
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 64, DEBUG.dim))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(5), (16,))
    for kind, run, names in (("kda", "01_kda_moe", ("beta_not_doubled", "head_wise_gate")),
                             ("gqa", "00_gqa_moe", ("gqa_rope", "no_gqa_gate", "no_shared"))):
        w = _mixer_inputs(run)[1]
        layer = lambda: M._layer_body(DEBUG, kind, M._attention)(h, (w, bias, None))[0]  # noqa: E731
        base = layer()
        for name in names:
            jax.clear_caches()
            with faults.fault(name, DEBUG):
                assert _rel(layer(), base) > 1e-3, name
    jax.clear_caches()


@pytest.mark.parametrize("kind", ["ling", "solar"])
def test_what_reaches_the_kernel_is_inside_what_its_kind_promises(monkeypatch, kind):
    """``decay_floor`` and ``beta_max`` are promises ``ops.kda.kda`` takes on
    trust (a broken one is an inf in the bounded body, silently): with every
    gate saturated (the input x 1e4) the ``g`` and ``beta`` that
    ``kda_mixer`` hands the kernel are finite and inside them, Ling's
    promise is the bounded body's, and Solar's form does use what it may:
    decays past -5 and ``beta`` over 1."""
    import ling_helpers
    from torchft_tpu.models import kda as mixer
    from torchft_tpu.models import ling
    from torchft_tpu.ops.kda import BOUNDED_FLOOR

    seen = {}

    def spy(q, k, v, g, beta, **promised):
        seen.update(g=np.asarray(g), beta=np.asarray(beta), **promised)
        return jnp.zeros_like(v)

    monkeypatch.setattr(mixer, "kda", spy)
    if kind == "ling":
        cfg = dataclasses.replace(CONFIGS["ling_debug"], dtype=jnp.float32)
        u, w = ling_helpers._mixer_inputs(cfg, "01_kda_moe")
        ling._kda_mixer(1e4 * u, w, cfg)
        assert seen["decay_floor"] == cfg.kda_lower_bound >= BOUNDED_FLOOR
        assert seen["beta_max"] == 1.0 and seen["g"].min() == seen["decay_floor"]
    else:
        u, w = _mixer_inputs("01_kda_moe")
        M.kda_mixer(1e4 * u, w, DEBUG, decay_floor=None, beta_max=M.BETA_MAX)
        assert seen["decay_floor"] is None and seen["beta_max"] == 2.0
        assert seen["g"].min() < BOUNDED_FLOOR and seen["beta"].max() > 1.0
    assert np.isfinite(seen["g"]).all() and seen["g"].max() <= 0.0
    assert 0.0 <= seen["beta"].min() and seen["beta"].max() <= seen["beta_max"]


# ---- the registry, the tree, the published cut, what is refused, the trainer

def test_the_kind_stands_in_the_registry_beside_the_others():
    assert "SolarConfig" in {c.__name__ for c in kinds._KINDS}
    m = model_fns(DEBUG)
    assert m.init is M.solar_init and m.stages is None
    assert m.frozen == M.SOLAR_FROZEN == ("expert_bias",)
    assert model_fns(CONFIGS["ling_debug"]).init is not M.solar_init
    assert [n for n, c in CONFIGS.items() if isinstance(c, SolarConfig)] == [
        "solar_debug", "solar_open2_250b_share"]


def test_the_leaves_have_their_dtypes_and_every_one_has_a_spec():
    cfg = CONFIGS["solar_debug"]
    params = M.solar_init(jax.random.PRNGKey(0), cfg)
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == cfg.num_params()
    specs = M.solar_param_specs(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda x: 0, specs, is_leaf=lambda x: not isinstance(x, dict)))
    assert [r[0] for r in cfg.runs()] == ["00_gqa_moe", "01_kda_moe", "02_kda_moe", "03_kda_moe"]
    f32 = {jax.tree_util.keystr(k[-1:]) for k, v in jax.tree_util.tree_leaves_with_path(params)
           if v.dtype == jnp.float32}
    assert f32 == {"['router']", "['A_log']", "['dt_bias']", "['expert_bias']"}
    w = params["layers"]["01_kda_moe"]
    # Kimi Linear's initialisation: A in [1, 16), a zero input's step in
    # [0.001, 0.1], so its log decay -A x dt lies in (-1.6, -0.001]
    assert 0 <= float(w["A_log"].min()) and float(w["A_log"].max()) < np.log(16.0)
    dt = np.asarray(jax.nn.softplus(w["dt_bias"]))
    assert 0.001 * 0.99 < dt.min() and dt.max() < 0.1 * 1.01
    assert float(jnp.abs(w["b_g"].astype(jnp.float32)).max()) == 0
    assert w["w_fa"].shape == (1, 64, 8) and w["w_gb"].shape == (1, 8, 64)
    assert params["layers"]["00_gqa_moe"]["wk"].shape == (1, 64, 32)  # 2 kv heads of 16


def test_the_published_cut_counts_what_the_issue_counted():
    cfg = CONFIGS["solar_open2_250b_share"]
    assert cfg.kinds() == ["gqa", "kda", "kda", "kda"]
    assert cfg.num_params() == 1_420_941_120  # ISSUE 64's count
    assert dataclasses.replace(cfg, held_experts=(0, 8)).num_params() == (
        1_420_941_120 - 4 * 2 * 3 * 4096 * 1280)  # the fallback's 8 held
    assert cfg.share_rows(16384) == 3 * 4096 and cfg.n_held == 10
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.kda_head_dim) == (64, 8, 128, 128)


def test_what_the_configuration_and_the_specs_refuse():
    cfg = CONFIGS["solar_debug"]
    for change, match in (({"gqa_layers": (4,)}, "gqa_layers"),
                          ({"n_kv_heads": 3}, "n_kv_heads"),
                          ({"capacity_factor": 1.25, "held_experts": None}, "capacity_factor"),
                          ({"held_experts": (14, 4)}, "held_experts"),
                          ({"router_score": "tanh"}, "router_score")):
        with pytest.raises(ValueError, match=match):
            dataclasses.replace(cfg, **change)
    with pytest.raises(ValueError, match="dropless"):
        M.solar_param_specs(cfg, type("Mesh", (), {"shape": {"ep": 2, "fsdp": 1}})())
    with pytest.raises(ValueError, match="out_block"):
        M.solar_loss(M.solar_init(jax.random.PRNGKey(0), dataclasses.replace(
            cfg, kda_out_block=48)), jnp.zeros((1, 64), jnp.int32), jnp.zeros((1, 64), jnp.int32),
            dataclasses.replace(cfg, kda_out_block=48))
    # Ling's bound is Ling's key: a floor under -5 is refused there, with
    # the kernel no longer the reason
    with pytest.raises(ValueError, match="another model's form"):
        dataclasses.replace(CONFIGS["ling_debug"], kda_lower_bound=-8.0)


def test_the_norm_and_gate_in_blocks_are_the_whole():
    """``kda_out_block``: the same values, forward and in every gradient of
    the mixer."""
    u, w = _mixer_inputs("01_kda_moe")
    f = lambda block: jax.value_and_grad(lambda u, w: jnp.sum(M.kda_mixer(  # noqa: E731
        u, w, DEBUG, decay_floor=None, beta_max=M.BETA_MAX, out_block=block)[0] ** 2),
        argnums=(0, 1))(u, w)
    (a, ga), (b, gb) = f(0), f(16)
    assert abs(float(a) / float(b) - 1) < 1e-6
    for (k, x), y in zip(jax.tree_util.tree_leaves_with_path(ga), jax.tree_util.tree_leaves(gb)):
        if float(jnp.linalg.norm(x)):
            assert _rel(y, x) < 2e-5, jax.tree_util.keystr(k)


def test_remat_loss_chunk_and_causality_work_as_for_the_other_kinds():
    params = M.solar_init(jax.random.PRNGKey(0), DEBUG)
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 256)
    base, stats = M.solar_loss_and_stats(params, tok, tok, DEBUG)
    for kw in ({"remat": "none"}, {"loss_chunk": 16}, {"routing": stats["routing"]}):
        assert abs(float(M.solar_loss(params, tok, tok, DEBUG, **kw)) - float(base)) < 2e-6, kw
    assert stats["routing"].shape == (4, 64, 4) and stats["p_kth"].shape == (4, 64)
    # a token's output is unchanged by later tokens: every mixer is causal
    full = M.solar_forward(params, tok, DEBUG)
    np.testing.assert_allclose(np.asarray(full)[:, :40],
                               np.asarray(M.solar_forward(params, tok[:, :40], DEBUG)),
                               rtol=2e-4, atol=2e-5)


def test_the_trainer_trains_the_debug_preset(tmp_path):
    """``--config solar_debug`` through ``examples/train_llama_hsdp.py``
    under a Manager on the CPU: committed steps, finite losses, the MoE's
    and the KDA layers' counters on the SUMMARY."""
    from test_trainer_model_kinds import _train

    s = _train("solar_debug", tmp_path, "--steps", "3")
    assert s["config"] == "solar_debug" and s["committed"] == 3 and s["discarded"] == 0, s
    assert all(5.0 < x < 7.0 for x in s["losses"])
    assert {"kda_decay_past_bound_share", "kda_beta_over_one_share", "moe_held_pair_share",
            "moe_overflow_pairs"} <= set(s["model_stats"])
    assert all(v == 0 for v in s["model_stats"]["moe_overflow_pairs"])
    assert all(0.3 < v < 0.7 for v in s["model_stats"]["kda_beta_over_one_share"])
