"""The one trainer, examples/train_llama_hsdp.py, runs whatever kind the
``--config`` preset is: the same line for a dense, an MoE and a Mamba /
attention hybrid preset, two committed steps each under the launcher's
lighthouse; and the hybrid learns."""

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


@pytest.mark.parametrize("config,kind", [("moe_debug", "moe"), ("debug", "dense"),
                                         ("jamba_debug", "hybrid")])
def test_two_committed_steps_under_a_lighthouse(config, kind, tmp_path):
    s = _train(config, tmp_path)
    assert s["config"] == config and s["committed"] == 2 and s["discarded"] == 0
    assert s["state_on_device"] and s["reduced_on_device"]
    assert len(s["losses"]) == 2 and all(5.0 < x < 7.0 for x in s["losses"])  # ln 256
    # the gradient leaves the trainer in parts, an allreduce each: the head's
    # leaves, then the layers with the embedding (these tiny layers are one
    # segment); the hybrid's is one program and one op
    assert s["timings"]["allreduce_ops"] == (1 if kind == "hybrid" else 2)
    if kind == "moe":
        assert sorted(s["model_stats"]) == ["moe_aux_loss", "moe_load_max_over_mean"]
        assert all(len(v) == 2 for v in s["model_stats"].values())
        assert all(v >= 1.0 for v in s["model_stats"]["moe_load_max_over_mean"])
    elif kind == "hybrid":
        assert sorted(s["model_stats"]) == ["ssm_dt_max", "ssm_y_absmax"]
        assert all(len(v) == 2 and min(v) > 0 for v in s["model_stats"].values())
        # float32 A_log and D beside the bf16 leaves: a bucket of their own
        # (the step's ops together: here the one)
        assert s["timings"]["allreduce_buckets"] == 2
    else:
        assert s["model_stats"] == {}
        assert s["timings"]["allreduce_buckets"] == 2  # a bucket an op


@pytest.mark.parametrize("config", ["debug", "moe_debug"])
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
                 "moe_loss", "moe_param_specs", "jamba"):
        assert name not in text, name
    assert "model_fns(cfg)" in text
