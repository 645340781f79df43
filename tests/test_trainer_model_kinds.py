"""The one trainer, examples/train_llama_hsdp.py, runs whatever kind the
``--config`` preset is: the same line for a dense, an MoE, a Mamba /
attention hybrid and a short-convolution / attention MoE preset, two
committed steps each under the launcher's lighthouse; the hybrid learns; and
a leaf that is state and no parameter (``ModelFns.frozen``: the fourth
kind's ``expert_bias``) comes out of the steps bitwise as it went in, rides
no allreduce, and reaches a healing group."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _train(config: str, tmp_path, *more: str, groups: int = 1):
    env = {**os.environ, "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")}
    out = subprocess.run(
        [sys.executable, "-m", "torchft_tpu.launcher",
         os.path.join(ROOT, "examples", "train_llama_hsdp.py"),
         "--replica-groups", str(groups), "--", "--config", config,
         "--batch-size", "2", "--seq-len", "32", "--steps", "2",
         "--virtual-chips", "1", *more],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    summaries = [json.loads(ln.split(" SUMMARY ", 1)[1])
                 for ln in out.stdout.splitlines() if " SUMMARY " in ln]
    assert len(summaries) == groups, out.stdout[-3000:]
    return summaries[0] if groups == 1 else summaries


def _checksum(tree) -> int:
    """The trainer's ``param_checksum``: the wrapping uint32 sum of every
    leaf's bit pattern."""
    import numpy as np

    total = 0
    for x in __import__("jax").tree_util.tree_leaves(tree):
        x = np.asarray(x)
        bits = x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)
        total += int(bits.astype(np.uint64).sum())
    return total % 2**32


def _lfm2_init(replica: int):
    import jax

    from torchft_tpu.models import CONFIGS
    from torchft_tpu.models.lfm2 import lfm2_init

    return lfm2_init(jax.random.PRNGKey(replica), CONFIGS["lfm2_debug"])


# the three wider kinds run the same line in tier-1 through the tests below
# (the hybrid's falling loss, the healing group's frozen leaf, two groups of
# the looped kind): here they are ``slow`` (ROADMAP D11: 213 of this file's
# 406 test-seconds; PR 56)
@pytest.mark.parametrize("config,kind", [
    ("moe_debug", "moe"), ("debug", "dense"),
    pytest.param("jamba_debug", "hybrid", marks=pytest.mark.slow),
    pytest.param("lfm2_debug", "lfm2", marks=pytest.mark.slow),
    pytest.param("ouro_debug", "looped", marks=pytest.mark.slow)])
def test_two_committed_steps_under_a_lighthouse(config, kind, tmp_path):
    s = _train(config, tmp_path)
    assert s["config"] == config and s["committed"] == 2 and s["discarded"] == 0
    assert s["state_on_device"] and s["reduced_on_device"]
    assert len(s["losses"]) == 2 and all(5.0 < x < 7.0 for x in s["losses"])  # ln 256
    # the gradient leaves the trainer in parts, an allreduce each: the head's
    # leaves, then the layers with the embedding (these tiny layers are one
    # segment); the hybrid's is one program and one op
    assert s["timings"]["allreduce_ops"] == (1 if kind in ("hybrid", "lfm2") else 2)
    assert (s["frozen_checksum"] is None) == (kind != "lfm2")
    if kind == "lfm2":
        assert sorted(s["model_stats"]) == ["moe_bias_moved_share", "moe_load_max_over_mean"]
        assert all(len(v) == 2 for v in s["model_stats"].values())
        assert all(0.0 < v < 1.0 for v in s["model_stats"]["moe_bias_moved_share"])
        # float32 routers beside the bf16 leaves: a bucket of their own
        assert s["timings"]["allreduce_buckets"] == 2
        # the bias is bitwise what the seed made it; the parameters moved
        params = _lfm2_init(0)
        assert s["frozen_checksum"] == _checksum(params["expert_bias"])
        assert s["param_checksum"] != _checksum(params)
    elif kind == "moe":
        assert sorted(s["model_stats"]) == ["moe_aux_loss", "moe_load_max_over_mean"]
        assert all(len(v) == 2 for v in s["model_stats"].values())
        assert all(v >= 1.0 for v in s["model_stats"]["moe_load_max_over_mean"])
    elif kind == "looped":
        # four passes over one stack: the head's leaves, then (once the
        # backward has been through every pass) the layers with the
        # embedding and the norm between the passes: two ops all the same
        assert sorted(s["model_stats"]) == [
            "loop_ce_1", "loop_ce_2", "loop_ce_3", "loop_ce_4", "loop_exit_entropy",
            "loop_exit_step_mean", "loop_p_last"]
        assert all(len(v) == 2 for v in s["model_stats"].values())
        assert all(2.0 < v < 3.0 for v in s["model_stats"]["loop_exit_step_mean"])
        # a bucket an op: the debug preset is float32 throughout, so the gate
        # travels in the head's bucket
        assert s["timings"]["allreduce_buckets"] == 2
    elif kind == "hybrid":
        assert sorted(s["model_stats"]) == ["ssm_dt_max", "ssm_y_absmax"]
        assert all(len(v) == 2 and min(v) > 0 for v in s["model_stats"].values())
        # float32 A_log and D beside the bf16 leaves: a bucket of their own
        # (the step's ops together: here the one)
        assert s["timings"]["allreduce_buckets"] == 2
    else:
        assert s["model_stats"] == {}
        assert s["timings"]["allreduce_buckets"] == 2  # a bucket an op


@pytest.mark.parametrize("config", ["debug", "moe_debug", "ouro_debug"])
def test_two_groups_issue_their_ops_in_one_order(config, tmp_path):
    """The host exchange matches messages by arrival order: two groups that
    handed over a step's parts in different orders would reduce the head's
    gradient with a layer's (or fail on the sizes). Both commit both steps,
    with as many ops, and end with bitwise-equal parameters (group 1 heals
    from group 0 in step 1, then both apply the same averaged gradients)."""
    a, b = _train(config, tmp_path, groups=2)
    for s in (a, b):
        assert s["committed"] == 2 and s["discarded"] == 0, s
        assert s["timings"]["allreduce_ops"] == 2
    assert a["param_checksum"] == b["param_checksum"]
    assert sorted(s["replica"] for s in (a, b)) == [0, 1]


def test_a_healing_group_holds_the_sources_frozen_leaf(tmp_path):
    """Group 1 starts with a bias of its own seed and heals from group 0 in
    step 1 over HTTP: it ends with group 0's bias, bitwise, and with
    bitwise-equal parameters, the bias among them."""
    a, b = sorted(_train("lfm2_debug", tmp_path, "--steps", "3", groups=2),
                  key=lambda s: s["replica"])
    for s in (a, b):
        assert s["committed"] == 3 and s["discarded"] == 0, s
    assert b["healed"] >= 1 and a["healed"] == 0
    source, own = (_checksum(_lfm2_init(r)["expert_bias"]) for r in (0, 1))
    assert source != own
    assert a["frozen_checksum"] == b["frozen_checksum"] == source
    assert a["param_checksum"] == b["param_checksum"]


def test_the_gradient_handed_on_has_no_frozen_leaf_and_the_update_moves_none():
    """What the trainer does with ``ModelFns.frozen``, in one process: the
    one part the gradient program emits (what ``Manager.allreduce`` is
    handed) holds every trainable leaf and no ``expert_bias``; adamw with
    weight decay over the trainable leaves has no moments for it; after the
    update every trainable leaf has moved and the bias has not, by a bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from torchft_tpu.models import CONFIGS, model_fns, split_frozen
    from torchft_tpu.models.staged import staged_value_and_grad

    cfg = CONFIGS["lfm2_debug"]
    model = model_fns(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    run, assemble = staged_value_and_grad(
        None, lambda p, t, y: model.loss(p, t, y, cfg), frozen=model.frozen)
    parts = []
    (loss, stats) = run(params, tokens, tokens, parts.append)
    assert len(parts) == 1 and "expert_bias" not in parts[0]
    trainable, held = split_frozen(params, model.frozen)
    assert jax.tree_util.tree_structure(parts[0]) == jax.tree_util.tree_structure(trainable)
    assert np.isfinite(float(loss)) and "moe_stats" in stats
    tx = optax.adamw(1e-2, weight_decay=0.1)
    opt_state = tx.init(trainable)
    assert not any("expert_bias" in jax.tree_util.keystr(path)
                   for path, _ in jax.tree_util.tree_leaves_with_path(opt_state))
    updates, _ = tx.update(assemble(parts), opt_state, trainable)
    new = {**optax.apply_updates(trainable, updates), **held}
    for (path, before), after in zip(jax.tree_util.tree_leaves_with_path(params),
                                     jax.tree_util.tree_leaves(new)):
        same = bool(jnp.all(before == after))
        assert same == ("expert_bias" in jax.tree_util.keystr(path)), jax.tree_util.keystr(path)
    # plain adamw over everything, as before this kind: decay alone moves it
    decayed, _ = tx.update(jax.tree_util.tree_map(jnp.zeros_like, params),
                           tx.init(params), params)
    assert float(jnp.abs(decayed["expert_bias"]).max()) > 0
    with pytest.raises(ValueError, match="frozen"):
        staged_value_and_grad(model_fns(CONFIGS["debug"]).stages(CONFIGS["debug"], None),
                              None, frozen=("expert_bias",))


@pytest.mark.parametrize("config", ["debug", "moe_debug", "jamba_debug"])
def test_a_kind_without_frozen_leaves_updates_by_the_program_it_was(config):
    """``split_frozen`` with nothing frozen is the identity, so the update
    the trainer jits for the three standing kinds traces to the jaxpr of
    PR 34's ``tx.update(grads, opt_state, params)`` over every leaf."""
    import jax
    import optax

    from torchft_tpu.models import CONFIGS, model_fns, split_frozen

    cfg = CONFIGS[config]
    model = model_fns(cfg)
    assert model.frozen == ()
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), cfg))
    tx = optax.adamw(3e-4, weight_decay=0.1)
    opt_state = jax.eval_shape(tx.init, params)

    def now(params, opt_state, grads):
        trainable, held = split_frozen(params, model.frozen)
        updates, opt_state = tx.update(grads, opt_state, trainable)
        return {**optax.apply_updates(trainable, updates), **held}, opt_state

    def was(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    trainable, held = split_frozen(params, ())
    assert held == {} and trainable == params
    assert str(jax.make_jaxpr(now)(params, opt_state, params)) == str(
        jax.make_jaxpr(was)(params, opt_state, params))


def test_the_hybrid_commits_steps_with_a_finite_falling_loss(tmp_path):
    """``--config jamba_debug`` under a lighthouse and the Manager: the task
    (every token its own target) is learnable, so ten committed steps at a
    learning rate the tiny widths bear bring the loss down."""
    s = _train("jamba_debug", tmp_path, "--steps", "10", "--lr", "0.01")
    assert s["committed"] == 10 and s["discarded"] == 0
    losses = s["losses"]
    assert len(losses) == 10 and all(0 < x < 7.0 for x in losses)
    assert sum(losses[-3:]) / 3 < sum(losses[:3]) / 3 - 0.5, losses


def test_the_trainer_names_no_model_function():
    text = open(os.path.join(ROOT, "examples", "train_llama_hsdp.py")).read()
    for name in ("llama_init", "llama_loss", "llama_param_specs", "moe_init",
                 "moe_loss", "moe_param_specs", "jamba", "lfm2", "expert_bias"):
        assert name not in text, name
    assert "model_fns(cfg)" in text
