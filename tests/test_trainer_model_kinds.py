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


def _train(config: str, tmp_path, *more: str) -> dict:
    env = {**os.environ, "PYTHONPATH": ROOT,
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jc")}
    out = subprocess.run(
        [sys.executable, "-m", "torchft_tpu.launcher",
         os.path.join(ROOT, "examples", "train_llama_hsdp.py"),
         "--replica-groups", "1", "--", "--config", config, "--batch-size", "2",
         "--seq-len", "32", "--steps", "2", "--virtual-chips", "1", *more],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    line = next(ln for ln in out.stdout.splitlines() if " SUMMARY " in ln)
    return json.loads(line.split(" SUMMARY ", 1)[1])


@pytest.mark.parametrize("config,kind", [("moe_debug", "moe"), ("debug", "dense"),
                                         ("jamba_debug", "hybrid")])
def test_two_committed_steps_under_a_lighthouse(config, kind, tmp_path):
    s = _train(config, tmp_path)
    assert s["config"] == config and s["committed"] == 2 and s["discarded"] == 0
    assert s["state_on_device"] and s["reduced_on_device"]
    assert len(s["losses"]) == 2 and all(5.0 < x < 7.0 for x in s["losses"])  # ln 256
    if kind == "moe":
        assert sorted(s["model_stats"]) == ["moe_aux_loss", "moe_load_max_over_mean"]
        assert all(len(v) == 2 for v in s["model_stats"].values())
        assert all(v >= 1.0 for v in s["model_stats"]["moe_load_max_over_mean"])
    elif kind == "hybrid":
        assert sorted(s["model_stats"]) == ["ssm_dt_max", "ssm_y_absmax"]
        assert all(len(v) == 2 and min(v) > 0 for v in s["model_stats"].values())
        # float32 A_log and D beside the bf16 leaves: a bucket of their own
        assert s["timings"]["allreduce_buckets"] == 2
    else:
        assert s["model_stats"] == {}


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
