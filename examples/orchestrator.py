"""Actor-style orchestration of a fault-tolerant job
(reference: examples/monarch/train_distributed.py:27-442 — LighthouseActor,
TrainingActor/ReplicaActor, OrchestrationManager, FailureController).

Instead of Monarch's actor runtime, plain threads play the actor roles:

- ``LighthouseActor``  — owns the in-process lighthouse server
- ``ReplicaActor``     — supervises one replica group's worker subprocess;
  restarts it per the retry policy and reports state transitions
- ``FailureController``— injects failures (kill via the lighthouse HTTP
  endpoint) on a schedule to prove recovery
- ``OrchestrationManager`` — wires the actors, waits for completion, and
  reports a summary (restarts per replica, final status)

Demo (2 replica groups training the DDP example, one injected kill). The
workers are started with ``--virtual-chips 1``: this is a CPU demonstration
of supervision and restart and never touches an accelerator.

    python examples/orchestrator.py --replicas 2 --steps 40 --inject-kill-after 12
"""

import argparse
import os
import subprocess
import sys
import threading
import time
import urllib.request
from dataclasses import dataclass, field

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from torchft_tpu.coordination import LighthouseServer  # noqa: E402


class LighthouseActor:
    def __init__(self, min_replicas: int) -> None:
        self.server = LighthouseServer(
            bind="127.0.0.1:0", min_replicas=min_replicas, join_timeout_ms=500,
            quorum_tick_ms=50, heartbeat_timeout_ms=2000,
        )
        self.addr = f"127.0.0.1:{self.server.port}"

    def stop(self) -> None:
        self.server.shutdown()


@dataclass
class RetryPolicy:
    max_restarts: int = 3
    backoff_s: float = 1.0


class ReplicaActor:
    """Supervises one replica group's worker process (reference
    ReplicaActor + its restart loop)."""

    def __init__(self, rid: int, cmd: list, env: dict, policy: RetryPolicy) -> None:
        self.rid = rid
        self.cmd = cmd
        self.env = env
        self.policy = policy
        self.restarts = 0
        self.status = "pending"
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"replica_actor_{rid}")

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.is_set():
            self.status = "running"
            proc = subprocess.Popen(self.cmd, env=self.env)
            while proc.poll() is None:
                if self._stop.wait(0.5):
                    proc.terminate()
                    try:
                        proc.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        # SIGTERM-deaf worker: escalate so the actor thread
                        # reaches a terminal status and no orphan survives
                        proc.kill()
                        proc.wait()
                    self.status = "stopped"
                    return
            if proc.returncode == 0:
                self.status = "succeeded"
                return
            if self.restarts >= self.policy.max_restarts:
                self.status = "failed"
                print(f"[actor {self.rid}] out of restarts", flush=True)
                return
            self.restarts += 1
            self.status = "restarting"
            print(f"[actor {self.rid}] worker died rc={proc.returncode}; "
                  f"restart {self.restarts}/{self.policy.max_restarts}", flush=True)
            time.sleep(self.policy.backoff_s)

    def stop(self) -> None:
        self._stop.set()

    def join(self, timeout: float) -> None:
        self._thread.join(timeout)


class FailureController:
    """Injects failures through the lighthouse kill endpoint
    (reference FailureController)."""

    def __init__(self, lighthouse_addr: str, after_s: float) -> None:
        self._addr = lighthouse_addr
        self._after = after_s
        self._thread = threading.Thread(target=self._run, daemon=True)
        self.killed: list = []

    def start(self) -> None:
        self._thread.start()

    def _members(self) -> list:
        # one copy of the /status parsing: punisher.list_replicas handles
        # both participant shapes (bare ids vs member objects)
        from punisher import list_replicas

        members = list_replicas(self._addr)
        return sorted(set(members))

    def _run(self) -> None:
        time.sleep(self._after)
        try:
            members = []
            for _ in range(60):  # replicas may still be starting up
                members = self._members()
                if members:
                    break
                time.sleep(1)
            if not members:
                print("[chaos] no participants to kill", flush=True)
                return
            victim = members[-1]
            req = urllib.request.Request(
                f"http://{self._addr}/replica/{victim}/kill", method="POST"
            )
            with urllib.request.urlopen(req, timeout=10):
                pass
            self.killed.append(victim)
            print(f"[chaos] killed {victim}", flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"[chaos] injection failed: {e}", flush=True)


@dataclass
class OrchestrationManager:
    """Wires the actors and owns the job lifecycle (reference
    OrchestrationManager)."""

    replicas: int
    steps: int
    policy: RetryPolicy = field(default_factory=RetryPolicy)
    inject_kill_after: float = 0.0

    def run(self) -> int:
        lighthouse = LighthouseActor(min_replicas=1)
        print(f"[orchestrator] lighthouse at http://{lighthouse.addr}/", flush=True)

        script = os.path.join(os.path.dirname(__file__), "train_ddp.py")
        actors = [
            ReplicaActor(
                rid,
                [sys.executable, script, "--steps", str(self.steps),
                 "--virtual-chips", "1"],
                dict(os.environ, TORCHFT_LIGHTHOUSE=lighthouse.addr,
                     REPLICA_GROUP_ID=str(rid)),
                self.policy,
            )
            for rid in range(self.replicas)
        ]
        chaos = None
        if self.inject_kill_after > 0:
            chaos = FailureController(lighthouse.addr, self.inject_kill_after)

        for a in actors:
            a.start()
        if chaos:
            chaos.start()

        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if all(a.status in ("succeeded", "failed", "stopped") for a in actors):
                break
            time.sleep(1)
        for a in actors:
            a.stop()
            a.join(timeout=30)
        lighthouse.stop()

        print("[orchestrator] summary:", flush=True)
        rc = 0
        for a in actors:
            print(f"  replica {a.rid}: {a.status} after {a.restarts} restart(s)",
                  flush=True)
            rc |= 0 if a.status == "succeeded" else 1
        if chaos and not chaos.killed:
            print("  (chaos injection did not fire)", flush=True)
        return rc


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--max-restarts", type=int, default=3)
    parser.add_argument("--inject-kill-after", type=float, default=0.0)
    args = parser.parse_args()
    rc = OrchestrationManager(
        replicas=args.replicas,
        steps=args.steps,
        policy=RetryPolicy(max_restarts=args.max_restarts),
        inject_kill_after=args.inject_kill_after,
    ).run()
    sys.exit(rc)


if __name__ == "__main__":
    main()
