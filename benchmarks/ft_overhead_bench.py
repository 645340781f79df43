"""Steady-state fault-tolerance overhead on the real example trainer.

The framework's pitch is fault tolerance at ~zero steady-state cost; this
harness measures that number instead of asserting it. It runs the SAME
trainer the shipped example trains (examples/train_ddp.py ``build_trainer``:
tiny CNN, sgd+momentum, jitted value_and_grad) two ways:

- **bare**: the plain training loop — forward/backward + update, no
  fault-tolerance machinery at all;
- **managed**: the example's actual FT loop — per-step ``start_quorum``
  (async, overlapped with the forward pass), managed allreduce of the grad
  pytree, and a real two-phase ``should_commit`` vote against a live
  lighthouse + manager server.

``ft_overhead_pct`` is the relative per-step cost of the managed loop, and
the per-phase splits (``allreduce_s``, ``should_commit_rpc_s``,
``bookkeeping_s``) from ``Manager.timings()`` say where the paid time went.
Medians throughout: the 1-vCPU bench hosts have scheduler noise that a mean
would launder into the answer.

    python benchmarks/ft_overhead_bench.py

Prints one JSON line; ``bench.py --ft-overhead`` runs it in a CPU-pinned
subprocess and merges the row into the bench artifact, and
``bench.py --ft-overhead --smoke`` is the fast-tier CI gate
(tests/test_bench_smoke.py).
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _median(xs):
    return statistics.median(xs) if xs else 0.0


def run(steps: int = 30, warmup: int = 5, batch_size: int = 8) -> dict:
    """Time the example trainer bare vs. under a live Manager.

    Returns ``ft_overhead_pct`` (managed vs bare median step), the raw
    medians, and the per-phase steady-state splits from
    ``Manager.timings()``.
    """
    import jax
    import optax

    from train_ddp import build_trainer

    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.manager import Manager
    from torchft_tpu.observability import log_timing_event
    from torchft_tpu.process_group import ProcessGroupHost

    total = warmup + steps

    def apply_update(state, optimizer, grads):
        updates, new_opt_state = optimizer.update(
            grads, state["opt_state"], state["params"]
        )
        state["params"] = optax.apply_updates(state["params"], updates)
        state["opt_state"] = new_opt_state

    # -- bare loop ---------------------------------------------------------
    state, grad_fn, optimizer, make_batch = build_trainer(0, batch_size)
    bare_times = []
    for _ in range(total):
        x, y = make_batch()
        t0 = time.perf_counter()
        loss, grads = grad_fn(state["params"], x, y)
        apply_update(state, optimizer, grads)
        float(loss)  # host value fetch = true execution barrier
        bare_times.append(time.perf_counter() - t0)
    bare_step_s = _median(bare_times[warmup:])

    # -- managed loop: real lighthouse, real per-step vote -----------------
    state, grad_fn, optimizer, make_batch = build_trainer(0, batch_size)
    lh = LighthouseServer(
        bind="127.0.0.1:0", min_replicas=1, join_timeout_ms=200,
        quorum_tick_ms=20, heartbeat_timeout_ms=2000,
    )
    manager = Manager(
        pg=ProcessGroupHost(timeout=30.0),
        load_state_dict=lambda sd: None,
        state_dict=lambda: {"params": state["params"]},
        min_replica_size=1,
        replica_id="ft_overhead",
        lighthouse_addr=f"127.0.0.1:{lh.port}",
        timeout=30.0,
    )
    ft_times = []
    splits = {
        "allreduce_s": [],
        "should_commit_rpc_s": [],
        "bookkeeping_s": [],
        # the bucket pipeline's stage splits
        # (see bucketing.BucketPipeline.record_timings)
        "allreduce_wire_s": [],
        "overlap_efficiency": [],
        "allreduce_buckets": [],
    }
    committed = 0
    try:
        for i in range(total):
            x, y = make_batch()
            t0 = time.perf_counter()
            manager.start_quorum()
            loss, grads = grad_fn(state["params"], x, y)
            reduced = manager.allreduce(grads).get_future().wait(timeout=60)
            if manager.should_commit():
                apply_update(state, optimizer, reduced)
                committed += 1
            float(loss)
            ft_times.append(time.perf_counter() - t0)
            if i >= warmup:
                t = manager.timings()
                for k in splits:
                    if t.get(k) is not None:
                        splits[k].append(t[k])
    finally:
        manager.shutdown(wait=False)
        lh.shutdown()
    ft_step_s = _median(ft_times[warmup:])

    result = {
        "ft_overhead_pct": round(
            (ft_step_s - bare_step_s) / bare_step_s * 100.0, 2
        )
        if bare_step_s > 0
        else None,
        "bare_step_s": round(bare_step_s, 6),
        "ft_step_s": round(ft_step_s, 6),
        "allreduce_s": round(_median(splits["allreduce_s"]), 6),
        "should_commit_rpc_s": round(_median(splits["should_commit_rpc_s"]), 6),
        "bookkeeping_s": round(_median(splits["bookkeeping_s"]), 6),
        "allreduce_wire_s": round(_median(splits["allreduce_wire_s"]), 6),
        "overlap_efficiency": round(_median(splits["overlap_efficiency"]), 4),
        "allreduce_buckets": _median(splits["allreduce_buckets"]),
        "steps": steps,
        "committed": committed,
        "batch_size": batch_size,
    }
    # the same row rides the observability stream so fleet tooling sees the
    # measured overhead next to the per-phase timing snapshots
    log_timing_event(phase="ft_overhead", replica_id="ft_overhead", **result)
    return result


if __name__ == "__main__":
    print(json.dumps(run()))
