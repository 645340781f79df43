"""PR 21 bring-up contracts that a CPU can check: nothing on the chip path
hides the device (no CPU fallback, no made-up peak), the compile cache can
be placed from outside, and chip_smoke.py refuses to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from torchft_tpu import utils

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_unknown_device_kind_has_no_peak():
    assert utils.peak_flops_per_chip("TPU v5 lite") == 197e12
    for kind in ("cpu", "TPU v5", "TPU v9 ultra", ""):
        with pytest.raises(ValueError, match="no peak FLOP/s known"):
            utils.peak_flops_per_chip(kind)
    # the default reads the local device: the CPU test platform has none
    with pytest.raises(ValueError, match="device_kind 'cpu'"):
        utils.peak_flops_per_chip()


def test_fallback_helpers_are_gone():
    assert not hasattr(utils, "ensure_responsive_backend")
    assert not hasattr(utils, "import_shard_map")


def test_compilation_cache_dir_obeys_the_variable(monkeypatch, tmp_path):
    placed = str(tmp_path / "placed_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert utils.compilation_cache_dir() == placed
    assert os.path.isdir(placed)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == placed


def test_compilation_cache_dir_defaults_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert utils.compilation_cache_dir() == fixed
    assert utils.compilation_cache_dir() == fixed  # no pid, time or temp name
    # exported, so every child shares it
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == fixed


@pytest.mark.parametrize("placed", [True, False])
def test_enable_compilation_cache_sets_no_other_directory(tmp_path, placed):
    """In a child (it mutates jax's config): where the variable is set jax
    already has that directory and the code sets none; otherwise the fixed
    in-checkout path."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if placed:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "placed")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from torchft_tpu.utils import enable_compilation_cache\n"
         "d = enable_compilation_cache()\n"
         "print('CACHE', d, jax.config.jax_compilation_cache_dir)\n"],
        capture_output=True, text=True, timeout=120, env=env, cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr[-800:]
    assert out.stdout.split()[-3:] == ["CACHE", want, want]


def test_chip_env_is_a_pure_partition():
    from torchft_tpu.launcher import chip_env

    """Each worker's chips are a function of (group, rank, chips per
    group) alone, and no two workers of a host share one."""
    assert chip_env(0, 0, 2) == chip_env(0, 0, 2)
    assert chip_env(0, 0, 2)["TPU_VISIBLE_CHIPS"] == "0,1"
    assert chip_env(1, 0, 2)["TPU_VISIBLE_CHIPS"] == "2,3"
    assert chip_env(3, 0, 1)["TPU_VISIBLE_CHIPS"] == "3"
    # two workers per group split the group's range in rank order
    assert chip_env(1, 0, 4, workers_per_group=2)["TPU_VISIBLE_CHIPS"] == "4,5"
    assert chip_env(1, 1, 4, workers_per_group=2)["TPU_VISIBLE_CHIPS"] == "6,7"
    seen = [
        c for g in range(2) for r in range(2)
        for c in chip_env(g, r, 4, 2)["TPU_VISIBLE_CHIPS"].split(",")
    ]
    assert sorted(seen, key=int) == [str(i) for i in range(8)]
    # every worker is a libtpu world of its own, sized to its chips
    env = chip_env(1, 0, 2)
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    x, y, z = map(int, env["TPU_CHIPS_PER_PROCESS_BOUNDS"].split(","))
    assert x * y * z == 2
    with pytest.raises(ValueError):
        chip_env(0, 0, 3)  # no chip grid of three
    with pytest.raises(ValueError):
        chip_env(0, 0, 4, workers_per_group=3)


def test_interpret_mode_only_on_the_cpu_platform(monkeypatch):
    import jax

    from torchft_tpu.ops import quantization as Q

    assert Q._use_interpret() is True  # the CPU test platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert Q._use_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="tpu .* or cpu"):
        Q._use_interpret()


def test_chip_smoke_fails_without_a_tpu():
    """No accelerator: non-zero exit, the reason named, no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert out.returncode != 0
    assert "no TPU" in out.stdout and "platform=cpu" in out.stdout
    for line in out.stdout.splitlines():
        assert not line.startswith("{"), f"printed a result: {line}"


def test_chip_smoke_result_line_has_exactly_the_contract_keys():
    """The driver reads the last stdout line: ok + device, nothing else."""
    import importlib.util
    import json

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    line = mod.result_line({"platform": "tpu", "kind": "TPU v5 lite",
                            "count": 1, "wall_s": 3.2})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program must fail too."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=120, env=env, cwd=str(tmp_path),
    )
    assert out.returncode != 0
    assert "not a torchft_tpu checkout" in out.stdout
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


def test_bench_main_fails_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
    )
    assert out.returncode != 0
    assert "tokens/s" not in out.stdout  # no device metric from a CPU
    assert "TPU" in out.stderr
