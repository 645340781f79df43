"""DeepSeek sparse attention's warm-up stage as a configuration of the
eleventh kind (``models/deepseek.py`` with ``dsa_stage="warmup"`` over
``models/dsa.py`` and ``ops/dsa.py``): the kind through ``model_fns`` against
the plain reference on seeded weights (the last layer's output, the loss,
each layer's KL, the gradient of every indexer leaf, the routing), FROZEN
MEANS FROZEN (what the gradient program, the optimizer and a step see of the
trunk), each fault a check must refuse seen by that comparison, the share
tied to the uncut layer, the reference's published sparse forward at a
budget that covers every key, the registry, the refusal of the stage that is
not built, and the published cut's counts. The cell's own check is
``tests/chipbench/test_rehearsal_deepseek_v32.py``'s; DeepSeek-V2's and
Ling's lowered programs are ``tests/test_ling.py``'s pins."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import reference_deepseek_v32 as reference  # noqa: E402
from test_deepseek import _file_of as _v2_file_of  # noqa: E402
from torchft_tpu.models import CONFIGS, model_fns, moe, split_frozen  # noqa: E402
from torchft_tpu.models import deepseek as M  # noqa: E402
from torchft_tpu.models import dsa as D  # noqa: E402
from torchft_tpu.ops import dsa as K  # noqa: E402

DEBUG = dataclasses.replace(CONFIGS["dsv32_debug"], dtype=jnp.float32)
SEQ = 80  # beyond ``yarn_original_max`` (32); no whole number of the reference's blocks
RUNS = ("00_dense", "01_moe", "02_moe")
LEAVES = ("w_iq", "w_ik", "k_norm", "k_bias", "w_iw")


def _file_of(cfg, **changed):
    """The configuration object as the keys the reference reads: DeepSeek-V2's
    (``tests/test_deepseek.py``) and the indexer's."""
    return {**_v2_file_of(cfg), "scoring_func": "sigmoid", "index_n_heads": cfg.index_n_heads,
            "index_head_dim": cfg.index_head_dim, "index_topk": cfg.index_topk, **changed}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)


def _program(cfg, params, tok):
    """(loss, stats, the indexers' gradient) of the kind's own loss."""
    m = model_fns(cfg)
    trainable, held = split_frozen(params, m.frozen)
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        lambda t: M.deepseek_loss_and_stats({**t, **held}, tok, tok, cfg), has_aux=True))(
            trainable)
    return float(loss), stats, grads["indexer"]


@pytest.fixture(scope="module")
def both():
    """``dsv32_debug`` in float32 (a dense layer and two expert layers, all 8
    heads, an indexer of 4 heads, 4 of 16 experts) and the reference's loss
    differentiated as it stands, on the same seeded weights."""
    reference.QUERY_BLOCK = 32
    params = model_fns(DEBUG).init(jax.random.PRNGKey(0), DEBUG)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, DEBUG.vocab_size)
    loss, stats, grads = _program(DEBUG, params, tok)
    with jax.default_matmul_precision("highest"):
        b, gb = jax.jit(jax.value_and_grad(
            lambda ix: reference.loss_of(ix, params, tok, _file_of(DEBUG))))(params["indexer"])
        hidden, kls, routing = jax.jit(lambda p: reference.forward(p, tok, _file_of(DEBUG)))(
            params)
    return {"a": loss, "b": float(b), "ga": grads, "gb": gb, "stats": stats, "params": params,
            "tok": tok, "hidden": hidden, "kls": kls, "routing": routing}


def test_the_last_layers_output_and_the_loss_are_the_plain_references(both):
    stats = both["stats"]
    assert stats["hidden"].shape == (2, SEQ, DEBUG.dim)
    assert _rel(stats["hidden"], both["hidden"]) < 2e-5
    assert abs(both["a"] - both["b"]) < 3e-6 and 0.5 < both["a"] < 3.0
    assert abs(both["a"] - float(jnp.sum(both["kls"]))) < 3e-6
    np.testing.assert_array_equal(np.asarray(stats["routing"]), both["routing"]["routing"])


@pytest.mark.parametrize("layer", range(3))
def test_each_layers_kl_is_the_plain_references(both, layer):
    got, want = float(both["stats"]["kl_layers"][layer]), float(both["kls"][layer])
    assert abs(got / want - 1) < 2e-5 and want > 0.05


@pytest.mark.parametrize("leaf", [f"{run}.{name}" for run in RUNS for name in LEAVES])
def test_every_indexer_leafs_gradient_is_the_plain_references(both, leaf):
    run, name = leaf.split(".")
    assert sorted(both["ga"]) == sorted(both["gb"]) == sorted(RUNS)
    assert sorted(both["ga"][run]) == sorted(LEAVES)
    assert _rel(both["ga"][run][name], both["gb"][run][name]) < 5e-5, leaf
    assert float(jnp.linalg.norm(both["gb"][run][name])) > 1e-3


def test_the_counters_ride_the_loss_under_the_names_a_trainer_logs(both):
    _, stats = model_fns(DEBUG).loss(both["params"], both["tok"], both["tok"], DEBUG)
    assert sorted(stats) == ["dsa_stats", "frozen_stats", "moe_stats"]
    assert sorted(stats["dsa_stats"]) == ["dsa_kl_first", "dsa_kl_last", "dsa_topk_mass"]
    assert float(stats["dsa_stats"]["dsa_kl_first"]) == pytest.approx(float(both["kls"][0]),
                                                                     rel=1e-4)
    assert float(stats["dsa_stats"]["dsa_kl_last"]) == pytest.approx(float(both["kls"][2]),
                                                                    rel=1e-4)
    share = float(stats["frozen_stats"]["frozen_param_share"])
    assert share == pytest.approx(1 - DEBUG.num_trainable() / DEBUG.num_params()) and share > 0.9
    got = {k: float(v) for k, v in stats["moe_stats"].items()}
    assert got["moe_overflow_pairs"] == 0 and 0 < got["moe_bias_moved_share"] < 1


def test_the_topk_mass_is_the_targets_mass_on_the_indexers_best_keys(both):
    """By the definition, every ``[T, T]`` in memory: of the rows sampled,
    the share of ``p`` on the ``index_topk`` largest ``I`` of the row, the
    mean over rows and layers; an untrained indexer's is far under 1."""
    file, params, tok = _file_of(DEBUG), both["params"], both["tok"]
    masses = []
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(params["embed"])[tok]
        for i, kind in enumerate(reference.kinds(file)):
            _, _, w, ix, bias = reference._weights(params, file, i)
            u = reference._rmsnorm(h, w["norm"], file["rms_norm_eps"])
            c_q, c, k_r = reference.latents(u, w, file, jnp.matmul)
            kv = (c @ w["w_kvb"]).reshape(2, SEQ, -1, 32)
            _, _, p = reference.mixer_block(w, ix, u, c_q, kv, k_r, 0, SEQ, file, jnp.matmul)
            I = reference.index_scores(ix, reference.index_keys(ix, u, file, jnp.matmul), u, c_q,
                                       0, file, jnp.matmul)
            seen = np.tril(np.ones((SEQ, SEQ), bool))
            keep = reference.select(I, seen, DEBUG.index_topk)
            rows = (np.arange(1, D.MASS_ROWS + 1) * SEQ) // D.MASS_ROWS - 1
            masses.append(float(jnp.mean(jnp.sum(jnp.where(keep, p, 0.0), axis=-1)[0, rows])))
            h, _ = reference.mixed(w, ix, h, file)
            h, _ = reference.fed(kind, w, bias, h, file)
    assert float(both["stats"]["topk_mass"]) == pytest.approx(np.mean(masses), abs=2e-5)
    assert 0.2 < np.mean(masses) < 0.9


# ---- frozen means frozen

def test_the_gradient_program_holds_no_frozen_leafs_cotangent(both):
    """The lowered value-and-grad program's outputs are the loss and the
    fifteen indexer leaves' gradients, and no instruction of it makes a
    gradient as wide as a trunk's matrix: no ``[.., dim]``-wide
    ``transpose(jvp(..))`` but the indexers' own projections'."""
    m = model_fns(DEBUG)
    trainable, held = split_frozen(both["params"], m.frozen)
    assert m.frozen == ("embed", "layers", "expert_bias") and sorted(trainable) == ["indexer"]
    f = jax.jit(lambda t, h: jax.value_and_grad(
        lambda t: M.deepseek_loss({**t, **h}, both["tok"], both["tok"], DEBUG))(t))
    out = jax.eval_shape(f, trainable, held)
    assert len(jax.tree_util.tree_leaves(out)) == 1 + 15
    text = f.lower(trainable, held).as_text(debug_info=True)
    for scope in ("mla/q", "mla/kv", "mla/out", "ffn/block", "moe/experts", "moe/shared"):
        assert f"transpose(jvp({scope}" not in text, scope
    assert "dsa/index_q" in text and "dsa/kl" in text


def test_the_optimizer_sees_the_indexers_alone_and_a_step_moves_nothing_else(both):
    m = model_fns(DEBUG)
    params = both["params"]
    trainable, held = split_frozen(params, m.frozen)
    tx = optax.adamw(1e-3, weight_decay=0.1)
    opt_state = tx.init(trainable)
    moments = [x for x in jax.tree_util.tree_leaves(opt_state) if x.ndim]
    assert sum(x.size for x in moments) == 2 * DEBUG.num_trainable()

    @jax.jit
    def step(trainable, opt_state, held):
        loss, grads = jax.value_and_grad(
            lambda t: M.deepseek_loss({**t, **held}, both["tok"], both["tok"], DEBUG))(trainable)
        updates, opt_state = tx.update(grads, opt_state, trainable)
        return optax.apply_updates(trainable, updates), opt_state, loss

    from test_trainer_model_kinds import _checksum

    before, losses = _checksum(held), []
    for _ in range(3):
        trainable, opt_state, loss = step(trainable, opt_state, held)
        losses.append(float(loss))
        assert _checksum(held) == before  # the trainer's frozen_checksum
    assert losses[-1] < losses[0]
    for a, b in zip(jax.tree_util.tree_leaves(held),
                    jax.tree_util.tree_leaves(split_frozen(params, m.frozen)[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert any(not np.array_equal(a, b) for a, b in zip(
        jax.tree_util.tree_leaves(trainable), jax.tree_util.tree_leaves(params["indexer"])))


# ---- faults the comparison must see: each put into the PROGRAM

def _dense_kl(p_of=lambda P: jnp.mean(P, axis=1), swap=False, strict=False):
    """``ops.dsa.index_kl`` by the dense formula with one thing wrong."""
    def kl(q, k, scale, qI, kI, w):
        T = q.shape[1]
        seen = jnp.tril(jnp.ones((T, T), bool), -1 if strict else 0)
        seen = seen.at[0, 0].set(True)
        s = jnp.einsum("bthd,bshd->bhts", q * jnp.asarray(scale, q.dtype), k)
        p = p_of(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
        I = jnp.einsum("btj,btjs->bts", w, jax.nn.relu(jnp.einsum("btjd,bsd->btjs", qI, kI)))
        logq = jnp.where(seen, jax.nn.log_softmax(jnp.where(seen, I, -jnp.inf), axis=-1), 0.0)
        logp = jnp.where(p > 0, jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        if swap:
            return jnp.sum(jnp.where(seen, jnp.exp(logq) * (logq - logp), 0.0), axis=-1)
        return jnp.sum(p * (logp - logq), axis=-1)
    return kl


def _rmsnorm_for_layernorm(x, weight, bias, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def _indexer_without(factor):
    real = D.indexer

    def indexer(ix, u, cq, cfg, table):
        qI, kI, w = real(ix, u, cq, cfg, table)
        return qI, kI, w * factor
    return indexer


def _no_turn_on_the_key(real):
    return lambda x, table: x if x.shape[2] == 1 else real(x, table)


FAULTS = {
    "no_relu": [(K, "RELU", False)],
    "w_without_the_head_factor": [(D, "indexer", _indexer_without(DEBUG.index_n_heads ** 0.5))],
    "target_from_head_0": [(K, "TARGET_HEADS", 1)],
    "head_sum_not_divided": [(D, "index_kl", _dense_kl(lambda P: jnp.sum(P, axis=1)))],
    "kl_swapped": [(D, "index_kl", _dense_kl(swap=True))],
    "no_rotary_on_the_key": [(D, "_turn_first", _no_turn_on_the_key(D._turn_first))],
    "rmsnorm_for_layernorm": [(D, "_layernorm", _rmsnorm_for_layernorm)],
    "causal_off_by_one": [(D, "index_kl", _dense_kl(strict=True))],
    "p_in_bf16": [(K, "P_DTYPE", jnp.bfloat16)],
    "i_in_bf16": [(K, "I_DTYPE", jnp.bfloat16)],
}
# what a float32 program is held to (the agreement above is 100 x tighter)
LOSS_ABS, KL_REL, GRAD_REL = 2e-5, 5e-5, 5e-4


def test_the_dense_formula_without_a_fault_is_the_program(both, monkeypatch):
    monkeypatch.setattr(D, "index_kl", _dense_kl())
    loss, _, grads = _program(DEBUG, both["params"], both["tok"])
    assert abs(loss - both["b"]) < 3e-6
    assert all(_rel(grads[r][n], both["gb"][r][n]) < 5e-5 for r in RUNS for n in LEAVES)


# two run with the quick tests (a knob of the kernels, a patch of the model);
# the others are a compile of the whole program each, 11 to 14 s: ``slow``
QUICK = ("no_relu", "rmsnorm_for_layernorm")


@pytest.mark.parametrize("fault", [
    pytest.param(f, marks=() if f in QUICK else pytest.mark.slow) for f in sorted(FAULTS)])
def test_each_fault_fails_the_comparison(both, monkeypatch, fault):
    params = both["params"]
    if fault == "rmsnorm_for_layernorm":  # a bias of zero hides it: give the key one
        params = {**params, "indexer": {run: {**ix, "k_bias": ix["k_bias"] + 0.5}
                                        for run, ix in params["indexer"].items()}}
    with jax.default_matmul_precision("highest"):
        want, gb = jax.jit(jax.value_and_grad(lambda ix: reference.loss_of(
            ix, params, both["tok"], _file_of(DEBUG))))(params["indexer"])
    for module, name, value in FAULTS[fault]:
        monkeypatch.setattr(module, name, value)
    loss, stats, grads = _program(DEBUG, params, both["tok"])
    worst = max(_rel(grads[r][n], gb[r][n]) for r in RUNS for n in LEAVES)
    assert abs(loss - float(want)) > LOSS_ABS or worst > GRAD_REL, (loss, float(want), worst)
    assert worst > GRAD_REL  # every one of them moves some leaf's gradient


def test_a_group_scored_by_its_best_one_chooses_other_experts(both):
    greedy = dataclasses.replace(DEBUG, topk_method="group_limited_greedy")
    _, stats, _ = _program(greedy, both["params"], both["tok"])
    differ = np.any(np.sort(np.asarray(stats["routing"]), -1)
                    != np.sort(both["routing"]["routing"], -1), axis=-1)
    assert 0.02 < differ.mean() < 1.0


# ---- the share tied to the model (the model-configs guide, section 4)

def test_the_shares_add_up_to_the_uncut_layer():
    """32 experts in four shares of 8: the shares' routed parts plus the
    shared expert ONCE are the uncut reference's whole expert block; the
    decision (over all 32 under the bias, a group by its best two) is the
    same on every share."""
    uncut = dataclasses.replace(DEBUG, num_experts=32, held_experts=None, n_group=4, top_k=4)
    params = M.deepseek_init(jax.random.PRNGKey(3), uncut)
    w = jax.tree_util.tree_map(lambda x: x[0], params["layers"]["01_moe"])
    bias = params["expert_bias"][0]
    u = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, uncut.dim))
    x, file = u.reshape(-1, uncut.dim), _file_of(uncut)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ w["router"])
        idx, gates, _, _ = reference.choose(scores, bias, file)
        shared = reference._swiglu(x, w["shared_gate"], w["shared_up"], w["shared_down"],
                                   jnp.matmul)
        want = reference._experts(x, reference.gate_of(idx, gates, 0, 32), w, jnp.matmul) + shared
        routed = []
        for first in range(0, 32, 8):
            share = dataclasses.replace(uncut, held_experts=(first, 8), share_room=8.0)
            es = slice(first, first + 8)
            out, stats = moe.moe_ffn(
                u, w["router"], w["w_gate"][es], w["w_up"][es], w["w_down"][es], share,
                bias=bias, shared=(w["shared_gate"], w["shared_up"], w["shared_down"]))
            assert int(stats["overflow"]) == 0
            np.testing.assert_array_equal(np.asarray(stats["routing"]), np.asarray(idx))
            routed.append(out.reshape(-1, uncut.dim) - shared)
    assert _rel(sum(routed) + shared, want) < 2e-5
    assert _rel(routed[0] + shared, want) > 0.05


# ---- the reference's published forward, which the program does not build

def test_the_sparse_forward_with_every_key_in_budget_is_the_dense_forward(both):
    file = _file_of(DEBUG)
    with jax.default_matmul_precision("highest"):
        wide = reference.forward(both["params"], both["tok"],
                                 {**file, "index_topk": SEQ}, sparse=True)
        narrow = reference.forward(both["params"], both["tok"], file, sparse=True)
    assert _rel(wide[0], both["hidden"]) < 1e-6
    np.testing.assert_allclose(wide[1], both["kls"], rtol=1e-6)
    assert _rel(narrow[0], both["hidden"]) > 1e-3  # 16 of up to 80 keys: another model
    seen = np.tril(np.ones((SEQ, SEQ), bool))
    keep = np.asarray(reference.select(jnp.asarray(np.random.RandomState(0).randn(1, SEQ, SEQ)),
                                       seen, 16))
    assert (keep.sum(-1)[0] == np.minimum(np.arange(SEQ) + 1, 16)).all()
    assert not (keep & ~seen).any()


# ---- the registry, the stage that is not built, the published cut

def test_the_registry_finds_the_kind_and_its_frozen_keys_follow_the_configuration():
    v2, v32 = CONFIGS["deepseek_debug"], CONFIGS["dsv32_debug"]
    assert type(v2) is type(v32) is M.DeepseekConfig
    assert model_fns(v2).frozen == () and model_fns(v32).frozen == (
        "embed", "layers", "expert_bias")
    assert model_fns(v2).stages is None and model_fns(v32).init is M.deepseek_init
    params = jax.eval_shape(lambda: M.deepseek_init(jax.random.PRNGKey(0), v32))
    assert sorted(params) == ["embed", "expert_bias", "indexer", "layers"]
    assert sorted(params["indexer"]) == sorted(params["layers"]) == list(RUNS)
    assert params["indexer"]["01_moe"]["k_bias"].dtype == jnp.float32
    assert params["indexer"]["01_moe"]["w_iq"].dtype == v32.dtype == jnp.bfloat16
    specs = model_fns(v32).param_specs(v32)
    assert jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda x: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda x: 0, specs, is_leaf=lambda x: not isinstance(x, dict)))
    leaves = sum(x.size for x in jax.tree_util.tree_leaves(params)) - params["expert_bias"].size
    assert leaves == v32.num_params() and v32.num_trainable() == sum(
        x.size for x in jax.tree_util.tree_leaves(params["indexer"]))


def test_the_second_stage_is_refused_with_a_message():
    with pytest.raises(ValueError, match="dsa_stage='sparse'.*not built.*mask that is data"):
        dataclasses.replace(CONFIGS["dsv32_debug"], dsa_stage="sparse")
    with pytest.raises(ValueError, match="None or 'warmup'"):
        dataclasses.replace(CONFIGS["dsv32_debug"], dsa_stage="dense")
    with pytest.raises(ValueError, match="ALL the layer's heads"):
        dataclasses.replace(CONFIGS["dsv32_debug"], held_heads=(0, 2))


def test_the_published_cut_counts_what_issue_67_counts():
    cfg = CONFIGS["deepseek_v32_share"]
    assert cfg.num_params() == 4_519_675_136 and cfg.num_trainable() == 69_797_120
    assert abs(cfg.softmax_factor - 1.87386) < 1e-5 and cfg.rotary_factor == 1.0
    assert [n for n, _, _ in cfg.runs()] == ["00_dense"] + [f"{i:02d}_moe" for i in (1, 2, 3, 4)]
    assert (cfg.n_heads, cfg.held_heads, cfg.index_n_heads, cfg.index_head_dim,
            cfg.index_topk) == (128, None, 64, 128, 2048)
    ix = D.indexer_leaves(cfg)
    assert {k: v[0] for k, v in ix.items()} == {
        "w_iq": (1536, 8192), "w_ik": (7168, 128), "k_norm": (128,), "k_bias": (128,),
        "w_iw": (7168, 64)}
    assert sum(np.prod(v[0]) for v in ix.values()) == 13_959_424
