"""Multi-replica-group job launcher (reference: torchft/torchx.py:17-89).

The reference exposes a TorchX component that materialises one
``torchrun``-managed role per replica group with the env contract
``REPLICA_GROUP_ID`` / ``NUM_REPLICA_GROUPS`` / ``TORCHFT_LIGHTHOUSE``.
This launcher provides the same contract for local/multi-process TPU jobs —
and additionally *supervises*: failed replica groups are restarted up to
``--max-restarts`` times, which is the piece torchelastic provided in the
reference stack (a replica group that dies rejoins the quorum and live-heals
from a peer).

CLI::

    python -m torchft_tpu.launcher train.py --replica-groups 2 \
        --workers-per-replica 1 --max-restarts 3 -- --train-arg ...

or programmatic: ``launch_replica_groups(cmd, num_groups, ...)``.

A TPU chip belongs to one process at a time, so on a host whose chips are
shared between replica groups pass ``--chips-per-group N``: every worker is
handed its own chips through the environment (``chip_env``) and sees only
those as ``jax.devices()``. The launcher itself never initialises a JAX
backend, so it holds no chip.
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from torchft_tpu.coordination import LighthouseServer
from torchft_tpu.utils import compilation_cache_dir

logger = logging.getLogger(__name__)

__all__ = ["ReplicaGroupSpec", "chip_env", "launch_replica_groups", "main"]

LIGHTHOUSE_ENV = "TORCHFT_LIGHTHOUSE"
REPLICA_GROUP_ID_ENV = "REPLICA_GROUP_ID"
NUM_REPLICA_GROUPS_ENV = "NUM_REPLICA_GROUPS"
GROUP_RANK_ENV = "GROUP_RANK"
GROUP_WORLD_SIZE_ENV = "GROUP_WORLD_SIZE"
# how long groups too few to form a quorum may run on after the others have
# finished (their own last step and epilogue end within seconds of the
# others'), before launch_replica_groups stops them
ORPHAN_GRACE_S = 30.0

# libtpu's chip-grid shape for a process that owns n chips of one host
# (x,y,z; the table jax's own multi-process TPU tests use)
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def chip_env(
    group: int, rank: int, chips_per_group: int, workers_per_group: int = 1
) -> Dict[str, str]:
    """Environment that hands worker ``rank`` of replica group ``group``
    its own chips of this host: a pure function of its arguments.

    Chips are numbered as libtpu numbers them (``TPU_VISIBLE_CHIPS``
    ordinals); group g owns ``[g * chips_per_group, (g + 1) *
    chips_per_group)`` and its workers split that range in rank order.
    Each worker is a libtpu world of its own (``TPU_PROCESS_BOUNDS``
    1,1,1): replica groups talk over the Manager's process group, not over
    a shared runtime. Honoured by libtpu 0.0.34 on v5e 2x2 hosts (chip
    runs, PR 21): one chip per worker always; two chips per worker on
    three hosts of five — on the other two every such worker exited 1
    before reaching the chip, silently (consecutive chip ordinals are
    presumably not neighbours in y on every host; PERF.md, open question).
    """
    if chips_per_group % workers_per_group:
        raise ValueError(
            f"chips_per_group={chips_per_group} does not split over "
            f"{workers_per_group} workers"
        )
    per_worker = chips_per_group // workers_per_group
    if per_worker not in _CHIP_BOUNDS:
        raise ValueError(
            f"no chip-grid shape for {per_worker} chips per worker "
            f"(known: {sorted(_CHIP_BOUNDS)})"
        )
    first = group * chips_per_group + rank * per_worker
    return {
        "TPU_VISIBLE_CHIPS": ",".join(
            str(c) for c in range(first, first + per_worker)
        ),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _CHIP_BOUNDS[per_worker],
        "TPU_PROCESS_BOUNDS": "1,1,1",
        # several processes of one host each load libtpu for their own chips
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


@dataclass
class ReplicaGroupSpec:
    """One replica group's process set (reference role, torchx.py:55-85)."""

    cmd: List[str]
    replica_group_id: int
    num_replica_groups: int
    workers_per_replica: int = 1
    env: Dict[str, str] = field(default_factory=dict)
    chips_per_group: int = 0  # 0: no chip assignment (workers see every chip)

    def spawn(self, lighthouse_addr: str) -> List[subprocess.Popen]:
        procs = []
        for group_rank in range(self.workers_per_replica):
            chips = (
                chip_env(self.replica_group_id, group_rank,
                         self.chips_per_group, self.workers_per_replica)
                if self.chips_per_group else {}
            )
            env = {
                **os.environ,
                **self.env,
                **chips,
                LIGHTHOUSE_ENV: lighthouse_addr,
                REPLICA_GROUP_ID_ENV: str(self.replica_group_id),
                NUM_REPLICA_GROUPS_ENV: str(self.num_replica_groups),
                GROUP_RANK_ENV: str(group_rank),
                GROUP_WORLD_SIZE_ENV: str(self.workers_per_replica),
            }
            procs.append(subprocess.Popen(self.cmd, env=env))
        return procs


def launch_replica_groups(
    cmd: List[str],
    num_groups: int,
    workers_per_replica: int = 1,
    lighthouse_addr: Optional[str] = None,
    min_replicas: Optional[int] = None,
    max_restarts: int = 0,
    poll_interval: float = 1.0,
    chips_per_group: int = 0,
) -> int:
    """Run ``cmd`` as ``num_groups`` replica groups; supervise + restart.

    Returns the exit code: 0 iff every group eventually exited cleanly, or
    was stopped because the job had ended without it (below).
    Starts an in-process lighthouse when ``lighthouse_addr`` is None.
    ``chips_per_group > 0`` hands each worker its own chips (``chip_env``).

    Under its own lighthouse every group is this launcher's, so once groups
    have finished and fewer than ``min_replicas`` are left, no quorum can
    form again: the job is over, and a replacement that came up after the
    others' last step would wait out its quorum timeout. Where all that is
    left are groups this launcher restarted, they are given
    ``ORPHAN_GRACE_S`` to finish by themselves, then stopped.
    """
    # one persistent compile cache for every worker and every restart
    compilation_cache_dir()
    own_lighthouse = None
    quorum_size = 0  # known only of a lighthouse of our own
    if lighthouse_addr is None:
        quorum_size = min_replicas if min_replicas is not None else num_groups
        own_lighthouse = LighthouseServer(
            bind="0.0.0.0:0", min_replicas=quorum_size,
        )
        lighthouse_addr = own_lighthouse.address()
        logger.info("launcher lighthouse at %s", lighthouse_addr)

    specs = [
        ReplicaGroupSpec(
            cmd=cmd,
            replica_group_id=i,
            num_replica_groups=num_groups,
            workers_per_replica=workers_per_replica,
            chips_per_group=chips_per_group,
        )
        for i in range(num_groups)
    ]
    groups: List[List[subprocess.Popen]] = [s.spawn(lighthouse_addr) for s in specs]
    restarts = [0] * num_groups
    done = [False] * num_groups
    failed = False
    orphaned_at: Optional[float] = None

    stop = threading.Event()
    prev_handlers = {}
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            prev_handlers[sig] = signal.signal(sig, lambda *_: stop.set())
        except ValueError:  # not on the main thread (tests)
            pass

    try:
        while not stop.is_set() and not all(done):
            time.sleep(poll_interval)
            for i, procs in enumerate(groups):
                if done[i]:
                    continue
                codes = [p.poll() for p in procs]
                if all(c == 0 for c in codes):
                    done[i] = True
                    logger.info("replica group %d finished", i)
                elif any(c is not None and c != 0 for c in codes):
                    # kill stragglers of the dead group, then restart or fail
                    for p in procs:
                        if p.poll() is None:
                            p.terminate()
                    for p in procs:
                        try:
                            p.wait(timeout=30)
                        except subprocess.TimeoutExpired:
                            # a straggler trapping SIGTERM must not crash
                            # the supervisor; escalate like the final
                            # teardown does
                            p.kill()
                            try:
                                p.wait(timeout=30)
                            except subprocess.TimeoutExpired:
                                # even SIGKILL can stall on D-state I/O;
                                # carry on supervising rather than dying
                                logger.warning(
                                    "worker pid %s unkillable; continuing",
                                    p.pid,
                                )
                    if restarts[i] < max_restarts:
                        restarts[i] += 1
                        # the epoch stamp is this clock's reading of what
                        # the replacement reads of itself from /proc
                        # (tracing.process_start_us: startup/spawn_to_main)
                        logger.warning(
                            "replica group %d died (codes=%s); restart %d/%d "
                            "spawned at epoch %.3f",
                            i, codes, restarts[i], max_restarts, time.time(),
                        )
                        groups[i] = specs[i].spawn(lighthouse_addr)
                    else:
                        logger.error(
                            "replica group %d died (codes=%s); out of restarts",
                            i, codes,
                        )
                        done[i] = True
                        failed = True
            left = [i for i in range(num_groups) if not done[i]]
            if (not left or len(left) >= quorum_size or failed
                    or not all(restarts[i] for i in left)):
                orphaned_at = None
            elif orphaned_at is None:
                orphaned_at = time.monotonic()
            elif time.monotonic() - orphaned_at > ORPHAN_GRACE_S:
                logger.warning(
                    "replica group(s) %s stopped: the others have finished "
                    "and fewer than %d are left, so no quorum can form",
                    left, quorum_size,
                )
                for i in left:
                    done[i] = True  # the finally below stops its workers
    finally:
        for procs in groups:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
        for procs in groups:
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        if own_lighthouse is not None:
            own_lighthouse.shutdown()
        for sig, h in prev_handlers.items():
            signal.signal(sig, h)

    return 1 if (failed or stop.is_set()) else 0


def main(argv: "list[str] | None" = None) -> None:
    parser = argparse.ArgumentParser(prog="torchft_tpu_launcher", description=__doc__)
    parser.add_argument("script", help="worker script (run with this python)")
    parser.add_argument("--replica-groups", type=int, default=2)
    parser.add_argument("--workers-per-replica", type=int, default=1)
    parser.add_argument("--lighthouse", default=None,
                        help="existing lighthouse addr; else start one")
    parser.add_argument("--min-replicas", type=int, default=None)
    parser.add_argument("--max-restarts", type=int, default=0)
    parser.add_argument("--chips-per-group", type=int, default=0,
                        help="TPU chips of this host each replica group "
                             "owns; 0 (default) assigns none, right for one "
                             "group that takes the whole host")

    # everything after a literal `--` goes verbatim to the worker script
    if argv is None:
        argv = sys.argv[1:]
    if "--" in argv:
        split = argv.index("--")
        argv, worker_args = argv[:split], argv[split + 1:]
    else:
        worker_args = []
    ns = parser.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    code = launch_replica_groups(
        [sys.executable, ns.script, *worker_args],
        num_groups=ns.replica_groups,
        workers_per_replica=ns.workers_per_replica,
        lighthouse_addr=ns.lighthouse,
        min_replicas=ns.min_replicas,
        max_restarts=ns.max_restarts,
        chips_per_group=ns.chips_per_group,
    )
    sys.exit(code)


if __name__ == "__main__":
    main()
