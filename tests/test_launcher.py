"""Launcher + lighthouse CLI tests (reference: torchx.py contract)."""

import json
import os
import subprocess
import sys
import textwrap
import urllib.request

import pytest

pytestmark = pytest.mark.slow  # subprocess replica fleets + CLI round-trips

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from torchft_tpu.launcher import (
    GROUP_RANK_ENV,
    LIGHTHOUSE_ENV,
    NUM_REPLICA_GROUPS_ENV,
    REPLICA_GROUP_ID_ENV,
    launch_replica_groups,
)

WORKER_OK = textwrap.dedent(
    f"""
    import os, sys
    assert ":" in os.environ["{LIGHTHOUSE_ENV}"]  # host:port
    rid = int(os.environ["{REPLICA_GROUP_ID_ENV}"])
    n = int(os.environ["{NUM_REPLICA_GROUPS_ENV}"])
    assert 0 <= rid < n
    assert os.environ["{GROUP_RANK_ENV}"] == "0"
    print("worker", rid, "of", n, flush=True)
    """
)

WORKER_FLAKY = textwrap.dedent(
    f"""
    import os, sys, pathlib
    rid = os.environ["{REPLICA_GROUP_ID_ENV}"]
    marker = pathlib.Path(sys.argv[1]) / ("died_" + rid)
    if rid == "1" and not marker.exists():
        marker.write_text("x")
        sys.exit(3)   # first attempt of group 1 crashes
    sys.exit(0)
    """
)


def _script(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(body)
    return str(p)


def test_launch_env_contract(tmp_path):
    code = launch_replica_groups(
        [sys.executable, _script(tmp_path, "ok.py", WORKER_OK)],
        num_groups=2,
        poll_interval=0.2,
    )
    assert code == 0


def test_launch_restarts_failed_group(tmp_path):
    script = _script(tmp_path, "flaky.py", WORKER_FLAKY)
    code = launch_replica_groups(
        [sys.executable, script, str(tmp_path)],
        num_groups=2,
        max_restarts=1,
        poll_interval=0.2,
    )
    assert code == 0
    assert (tmp_path / "died_1").exists()


def test_launch_out_of_restarts_fails(tmp_path):
    script = _script(
        tmp_path, "dead.py", "import sys; sys.exit(2)"
    )
    code = launch_replica_groups(
        [sys.executable, script],
        num_groups=1,
        max_restarts=0,
        poll_interval=0.2,
    )
    assert code == 1


def test_launcher_hands_each_group_its_chips(tmp_path, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    worker = tmp_path / "w.py"
    worker.write_text(textwrap.dedent(
        f"""
        import os, pathlib, sys
        rid = os.environ["{REPLICA_GROUP_ID_ENV}"]
        (pathlib.Path(sys.argv[1]) / ("chips_" + rid)).write_text(
            os.environ["TPU_VISIBLE_CHIPS"] + " " +
            os.environ["JAX_COMPILATION_CACHE_DIR"])
        """
    ))
    code = launch_replica_groups(
        [sys.executable, str(worker), str(tmp_path)], num_groups=2,
        chips_per_group=2, poll_interval=0.1,
    )
    assert code == 0
    got = [(tmp_path / f"chips_{i}").read_text().split() for i in range(2)]
    assert [g[0] for g in got] == ["0,1", "2,3"]
    # the parent exports ONE compile cache for every worker
    assert got[0][1] == got[1][1] == os.environ["JAX_COMPILATION_CACHE_DIR"]


def test_doctor_cli():
    """Every check reports, and the host-independent ones (native build,
    virtual CPU mesh, lighthouse round-trip) pass. The accelerator check
    reflects live host state (under the JAX_PLATFORMS=cpu pin below it
    reports cpu, a warn), so its verdict is deliberately not asserted."""
    proc = subprocess.run(
        [sys.executable, "-m", "torchft_tpu.doctor"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    lines = {
        line.split()[1]: line.split()[0]
        for line in proc.stdout.splitlines()
        if line.startswith(("ok", "warn", "FAIL"))
    }
    assert set(lines) == {"native", "accelerator", "virtual-mesh",
                          "lighthouse", "retry-env", "health-env",
                          "compress-env", "health-http", "heal"}, (
        proc.stdout + proc.stderr
    )
    for check in ("native", "virtual-mesh", "lighthouse", "retry-env",
                  "health-env", "compress-env", "health-http", "heal"):
        assert lines[check] == "ok", proc.stdout
    if lines["accelerator"] != "FAIL":
        assert proc.returncode == 0, proc.stdout


def test_lighthouse_cli_and_dashboard():
    """Boot the CLI in a subprocess, hit /status, then terminate. Flags use
    the reference CLI's underscore spellings (src/lighthouse.rs structopt
    longs) — both spellings must launch, so a torchft script ports as-is."""
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "torchft_tpu.lighthouse",
            "--bind", "127.0.0.1:0",
            "--min_replicas", "1", "--quorum_tick_ms", "50",
        ],
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        addr = None
        for _ in range(100):
            line = proc.stderr.readline()
            if "listening at" in line:
                addr = line.rsplit(" ", 1)[-1].strip()
                break
        assert addr, "lighthouse did not report its address"
        if not addr.startswith("http"):
            addr = f"http://{addr}"
        with urllib.request.urlopen(f"{addr}/status", timeout=10) as resp:
            status = json.loads(resp.read().decode())
        assert "participants" in status or "quorum_id" in status
    finally:
        proc.terminate()
        proc.wait(timeout=10)


class TestClusterRunners:
    """The GKE/slurm launch-path generators (reference slurm runner parity,
    examples/slurm/runner.py:23-60): manifests must be valid and carry the
    launcher env contract."""

    @staticmethod
    def _load_runner(name):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            name, os.path.join(REPO, f"examples/cluster/{name}.py")
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_gke_manifests_valid_yaml_with_env_contract(self):
        import yaml

        mod = self._load_runner("gke_runner")
        import argparse

        args = argparse.Namespace(
            replica_groups=3, min_replicas=2,
            image="img:latest", tpu_type="tpu-v5p-slice",
            tpu_topology="2x2x1", chips_per_slice=4,
            fsdp=0, sp=1, tp=1,
            model_config="llama3_8b", local_batch_size=2, steps=10000,
            semi_sync_method="none",
        )
        docs = list(yaml.safe_load_all(mod.build_manifests(args)))
        # lighthouse Deployment + Service + 3 Jobs
        kinds = [d["kind"] for d in docs]
        assert kinds.count("Job") == 3 and "Deployment" in kinds
        job = next(d for d in docs if d["kind"] == "Job")
        env = {
            e["name"]: e["value"]
            for e in job["spec"]["template"]["spec"]["containers"][0]["env"]
        }
        assert env["NUM_REPLICA_GROUPS"] == "3"
        assert env["TORCHFT_LIGHTHOUSE"].startswith("torchft-lighthouse:")
        assert "REPLICA_GROUP_ID" in env
        res = job["spec"]["template"]["spec"]["containers"][0]["resources"]
        assert res["limits"]["google.com/tpu"] == 4

    def test_gke_diloco_variant_keeps_llama_trainer(self):
        import argparse

        mod = self._load_runner("gke_runner")
        args = argparse.Namespace(
            replica_groups=2, min_replicas=1,
            image="img", tpu_type="t", tpu_topology="2x2",
            chips_per_slice=4, fsdp=0, sp=1, tp=1,
            model_config="llama3_8b",
            local_batch_size=2, steps=100, semi_sync_method="diloco",
        )
        text = mod.build_manifests(args)
        # semi-sync still trains the Llama target — same trainer, DiLoCo mode
        assert "train_llama_hsdp.py" in text and "train_diloco.py" not in text
        assert "--diloco" in text and "--config=llama3_8b" in text
        assert "--sync-every=20" in text and "--num-fragments=2" in text

    def test_slurm_scripts_have_env_contract(self):
        mod = self._load_runner("slurm_runner")
        import argparse

        args = argparse.Namespace(
            replica_groups=2, min_replicas=2, lighthouse_host="lh-host",
            port=29510, model_config="llama3_8b", local_batch_size=2,
            chips_per_node=4, fsdp=0, sp=1, tp=1,
            steps=10000, semi_sync_method="none",
        )
        scripts = dict(mod.build_scripts(args))
        assert "lighthouse.sbatch" in scripts
        body = scripts["replica_1.sbatch"]
        for needle in (
            "export TORCHFT_LIGHTHOUSE=lh-host:29510",
            "export REPLICA_GROUP_ID=1",
            "export NUM_REPLICA_GROUPS=2",
            "--config=llama3_8b",
            "#SBATCH --requeue",
        ):
            assert needle in body, needle
