"""The ninth kind of the one trainer's model (``models/brumby.py``): the dense
decoder's layer reached through its ``mixer`` seam with power retention
where attention stood. The kind through ``model_fns`` against the plain
reference on seeded weights (loss and the gradient of every leaf), the
registry's one new entry, the feed-forward in blocks against the whole one,
what rides beside the loss, and the trainer's ``--config``. The kernel is
``tests/test_power_retention.py``'s, the cell's check
``tests/chipbench/test_reference_brumby.py``'s, the other kinds' lowered
programs ``tests/test_ling.py``'s pins."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from chipbench import reference_brumby as reference  # noqa: E402
from torchft_tpu.models import CONFIGS, kinds, llama, model_fns  # noqa: E402
from torchft_tpu.models import brumby as M  # noqa: E402
from torchft_tpu.models.brumby import BrumbyConfig  # noqa: E402

DEBUG = CONFIGS["brumby_debug"]


def _file_of(cfg: BrumbyConfig) -> dict:
    """The configuration object as the keys the reference reads."""
    return {"num_hidden_layers": cfg.n_layers, "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _flat(tree):
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.fixture(scope="module")
def both():
    """``brumby_debug`` (float32, two layers, four query heads over two of
    16, 80 positions: no whole number of blocks) and the reference's forward
    and loss differentiated as they stand, on the same seeded weights."""
    m = model_fns(DEBUG)
    params = m.init(jax.random.PRNGKey(0), DEBUG)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 80), 0, DEBUG.vocab_size)
    (a, stats), ga = jax.jit(jax.value_and_grad(
        lambda p: m.loss(p, tok, tok, DEBUG), has_aux=True))(params)
    with jax.default_matmul_precision("highest"):
        b, gb = jax.jit(jax.value_and_grad(lambda p: reference.loss(
            reference.forward(p, tok, _file_of(DEBUG)), tok)))(params)
    return float(a), float(b), _flat(ga), _flat(gb), stats, params, tok


def test_the_loss_is_the_plain_references(both):
    a, b = both[:2]
    assert abs(a - b) < 2e-6


LEAVES = ["attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wg", "bg", "wo", "ffn_norm",
          "w_gate", "w_up", "w_down"]


@pytest.mark.parametrize("leaf", ["embed", "final_norm", "lm_head"]
                         + [f"{layer}.{n}" for layer in ("00", "01") for n in LEAVES])
def test_every_leafs_gradient_is_the_plain_references(both, leaf):
    ga, gb = both[2:4]
    key = ("['layers']['%s_retention']['%s']" % tuple(leaf.split("."))
           if "." in leaf else f"['{leaf}']")
    assert sorted(ga) == sorted(gb) and len(ga) == 3 + 2 * len(LEAVES)
    # the gate's leaves: sums over every later position, of both signs
    assert _rel(ga[key], gb[key]) < (2e-4 if leaf.endswith(("wg", "bg")) else 5e-5), leaf


def test_the_counters_ride_the_loss_under_the_names_a_trainer_logs(both):
    stats = both[4]
    assert sorted(stats) == ["retention_stats"]
    got = {k: float(v) for k, v in stats["retention_stats"].items()}
    assert sorted(got) == ["retention_decay_mean", "retention_den_min"]
    # exp(g) at the initialisation's bias: between the two ends of its span
    assert M.DECAY_SPAN[0] - 0.05 < got["retention_decay_mean"] < M.DECAY_SPAN[1]
    assert 0.0 < got["retention_den_min"] < 1.0


def test_the_kind_is_the_registrys_only_new_entry():
    """The configuration classes, each its own kind (PR 59 added the
    tenth); a BrumbyConfig is a LlamaConfig and is Brumby's; its preset
    stands in ``CONFIGS``."""
    names = sorted(c.__name__ for c in kinds._KINDS)
    # the kinds that stood when this one came, each once; later kinds add theirs
    assert len(names) == len(set(names)) and set(names) >= {
        "BrumbyConfig", "DeepseekConfig", "JambaConfig", "Lfm2Config", "LingConfig",
        "LlamaConfig", "MellumConfig", "MoEConfig", "NemotronHConfig", "OuroConfig"}
    m = model_fns(DEBUG)
    assert m.init is M.brumby_init and m.stages is None and m.frozen == ()
    assert model_fns(CONFIGS["debug"]).init is llama.llama_init
    assert [n for n, c in CONFIGS.items() if isinstance(c, BrumbyConfig)] == ["brumby_debug"]


def test_the_leaves_are_counted_and_every_one_has_a_spec(both):
    params = both[5]
    assert sum(x.size for x in jax.tree_util.tree_leaves(params)) == DEBUG.num_params()
    specs = _flat(jax.tree_util.tree_map(
        lambda s: 0, model_fns(DEBUG).param_specs(DEBUG),
        is_leaf=lambda s: isinstance(s, jax.sharding.PartitionSpec)))
    assert sorted(specs) == sorted(_flat(params))
    w = params["layers"]["01_retention"]
    assert w["wg"].dtype == w["bg"].dtype == jnp.float32 and w["bg"].shape == (1, 2)
    assert [name for name, _, n in DEBUG.runs()] == ["00_retention", "01_retention"]


def test_the_published_cut_counts_what_the_issue_counted():
    with open(os.path.join(ROOT, "chipbench", "configs", "brumby-14b-base.json")) as f:
        cfg = json.load(f)
    from chipbench.adapters import brumby as adapter

    pc = adapter.config(cfg)
    shapes = jax.eval_shape(lambda: M.brumby_init(jax.random.PRNGKey(0), pc))
    leaves = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert leaves == pc.num_params() == adapter.num_params(cfg) == 1_515_894_816
    assert (pc.ffn_block, pc.loss_chunk, pc.retention_chunk) == (
        cfg["recipe"]["ffn_block"], 2048, 256)


@pytest.mark.parametrize("block", [16, 32])
def test_the_feed_forward_in_blocks_is_the_whole_one_to_the_bit(block):
    """Forward in float32: a position's products are the same whether its
    block or the whole sequence is multiplied; the gradients sum the blocks'
    parts in another order and agree to rounding."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    w = {"w_gate": jax.random.normal(ks[0], (24, 40)), "w_up": jax.random.normal(ks[1], (24, 40)),
         "w_down": jax.random.normal(ks[2], (40, 24))}
    x = jax.random.normal(ks[3], (2, 64, 24))
    whole, blocks = jax.jit(llama.swiglu)(x, w), jax.jit(
        lambda x, w: llama.swiglu(x, w, block))(x, w)
    if block == 32:  # at 16 rows this machine's matrix product sums in another order
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(blocks))
    assert _rel(blocks, whole) < 1e-6
    c = jax.random.normal(jax.random.PRNGKey(3), whole.shape)
    ga = jax.grad(lambda x, w: jnp.sum(llama.swiglu(x, w) * c), argnums=(0, 1))(x, w)
    gb = jax.grad(lambda x, w: jnp.sum(llama.swiglu(x, w, block) * c), argnums=(0, 1))(x, w)
    for a, b in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        assert _rel(a, b) < 1e-5
    with pytest.raises(ValueError, match="ffn_block"):
        llama.swiglu(x, w, 48)


def test_the_dense_layer_without_a_mixer_is_the_layer_it_was():
    """The seam adds nothing to a kind that hands in no mixer: the same
    jaxpr as the layer written out (``tests/test_ling.py`` pins the whole
    programs)."""
    cfg = CONFIGS["debug"]
    params = llama.llama_init(jax.random.PRNGKey(0), cfg)
    w = jax.tree_util.tree_map(lambda x: x[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.dim))
    text = str(jax.make_jaxpr(llama.make_llama_layer_body(cfg))(h, w))
    assert "log_sigmoid" not in text and "pallas" not in text and "while" not in text
    out, emitted = jax.eval_shape(llama.make_llama_layer_body(cfg), h, w)
    assert emitted is None and out.shape == h.shape


def test_what_the_configuration_refuses():
    with pytest.raises(ValueError, match="heads"):
        dataclasses.replace(DEBUG, n_kv_heads=3)
    with pytest.raises(ValueError, match="retention_chunk"):
        dataclasses.replace(DEBUG, retention_chunk=0)


def test_the_trainer_trains_the_debug_preset(tmp_path):
    """``--config brumby_debug`` through the launcher and the one trainer:
    two committed steps, the counters in the SUMMARY's ``model_stats``."""
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")}
    out = subprocess.run(
        [sys.executable, "-m", "torchft_tpu.launcher",
         os.path.join(ROOT, "examples", "train_llama_hsdp.py"), "--replica-groups", "1", "--",
         "--config", "brumby_debug", "--batch-size", "2", "--seq-len", "32", "--steps", "2",
         "--virtual-chips", "1"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    summary = [json.loads(ln.split(" SUMMARY ", 1)[1])
               for ln in out.stdout.splitlines() if " SUMMARY " in ln][0]
    assert summary["committed"] == 2 and all(np.isfinite(summary["losses"]))
    assert sorted(summary["model_stats"]) == ["retention_decay_mean", "retention_den_min"]
    assert all(x > 0 for x in summary["model_stats"]["retention_den_min"])
