"""Job kind ``managed``: replica groups of the managed trainer under the
launcher, steady state. ``python -m torchft_tpu.launcher chipbench/worker.py
... -- ...`` is the user's line with the cell's configuration registered by
the worker. Measures whole committed steps after the warm-up step, stamped
by this process as it reads each step's line (the trainer prints it after a
``float(loss)`` fetch). ``tok_s_chip`` is the tokens of a step over the median
interval between consecutive step lines: with four to eight steps in a window
one slow step (the host pipeline has them) moves a mean by percents and a
median not at all; the mean is kept in the notes."""

import os
import time
from statistics import median

from chipbench import launch, trainer_job


def run(cell, seed: int, seconds: float, trace: bool, out_dir: str,
        cache_dir: str, t_start: float) -> dict:
    tr, recipe = cell.traffic, cell.config["recipe"]
    cal = trainer_job.Calibration(cell, cache_dir)
    warm = tr["warmup_steps"]
    # a checkout's first run has seen no step: it measures min_steps of them
    known = cal.data.get("step_s")
    steps = warm + max(tr["min_steps"], int(seconds / known) if known else 0)
    trace_dir = os.path.join(out_dir, "trace") if trace else None
    with launch.Launch(trainer_job.launcher_args(cell),
                       trainer_job.worker_args(cell, steps, trace_dir),
                       os.path.join(out_dir, "launch.log")) as run_:
        t_launch = time.monotonic()
        t_pid = run_.wait_for(lambda ls: (launch.pids(ls, 0) or [None])[0],
                              300, "group 0 reaching its chip")[0]
        summaries = run_.finish(tr["timeout_s"] + steps * 4 * (known or 0.0))
        lines = run_.lines
    device = trainer_job.device_of(summaries, tr["groups"])
    per_group = {g: launch.steps(lines, g) for g in range(tr["groups"])}
    mine = per_group[0]
    if len(mine) <= warm:
        raise launch.Failed(f"only {len(mine)} committed steps")
    t_first = mine[warm - 1][0]  # warm-up's line: the first measured step starts
    measured = mine[warm:]
    stamps = [t_first] + [m[0] for m in measured]
    intervals = [b - a for a, b in zip(stamps, stamps[1:])]
    step_tokens = recipe["batch_size"] * recipe["seq_len"] * tr["groups"]
    sums = [summaries[g][-1] for g in range(tr["groups"])]
    bad = [b for s in sums for b in trainer_job.check_summary(s, cell, steps)]
    bad += [f"group {s['replica']}: {s['discarded_after_first']} step(s) discarded "
            "after the first" for s in sums if s["discarded_after_first"]]
    if len({s["param_checksum"] for s in sums}) != 1:
        bad.append("parameter checksums differ between groups")
    peak = max(s["peak_hbm_bytes"] or 0 for s in sums)  # None off the chip
    step_s = median(intervals)
    cal.save(step_s=step_s)
    obs = {
        "device": device, "memory_peak_bytes": peak, "correct": not bad,
        "attempted": sum(s["committed"] + s["discarded"] for s in sums),
        "failed": sum(s["discarded"] for s in sums),
        "e2e": {"tok_s_chip": step_tokens / step_s / cell.chips,
                "peak_hbm_gib": peak / 2**30, "setup_s": t_first - t_start},
        "phases": {"launcher.reach_chip_s": t_pid - t_launch},
        "summaries": summaries, "steps": per_group,
        "steps_in_window": len(measured),
        "notes": {"bad": bad, "seed": seed, "steps": steps, "intervals_s": intervals,
                  "tok_s_chip_by_mean": step_tokens * len(intervals)
                  / sum(intervals) / cell.chips,
                  "cache": [s["cache"] for s in sums]},
    }
    return trainer_job.with_trace(obs, trace_dir)
