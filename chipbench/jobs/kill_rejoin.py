"""Job kind ``kill_rejoin``: one scripted failure. N replica groups x 1 chip
under the launcher; the victim is SIGKILLed the moment this process has read
its commit line for step 2, the first step after the warm-up step (every
group is at a step boundary and has compiled; where the lighthouse formed
its first quorum from three groups, the fourth healed in during that step).
The victim is held at that boundary until the kill lands: its worker stops
itself once the line is out (worker.py, ``--chipbench-freeze``), because a
victim that runs on may have asked for the next quorum by the time a signal
from here reaches it, and a quorum formed with a dead member costs the
survivors a discarded step and a timeout, and this run its result;
the survivors commit on, the launcher restarts the victim on its chip, it
heals over the HTTP in-place transport and rejoins; the job ends by itself
and every group prints its SUMMARY. The event runs to its end
whatever ``--seconds`` says: one event is the unit of work. The arithmetic of
the phases is chipbench/phases.py; the victim is fixed in the traffic file,
not drawn from the seed, so a seed cannot change the shape of the event."""

import math
import os
import shutil
import signal
import time
from statistics import median

from chipbench import launch, phases, trainer_job

KILL_STEP = 2


def plan_steps(cal: dict, ready_s: float, after_heal: int) -> int:
    """--steps so that the job ends at least ``after_heal`` steps after the
    heal step: warm-up, the step before the kill, the survivors' steps that
    start before the replacement is ready, the heal step, and those after.
    ``ready_s`` (kill -> replacement ready) is the slowest the traffic file
    allows for, not the last one seen: it varies two to one from run to run
    (PERF.md section 6), and a job sized for a fast restart ends before a slow
    one has rejoined. A fast restart costs one more step at the end."""
    solo_step = cal.get("solo_step_s") or cal["step_s"]
    stall = cal.get("stall_s") or solo_step + 5.0
    solo = 1 + max(0, math.ceil((ready_s - stall) / solo_step))
    return KILL_STEP + solo + 1 + after_heal


def steps_for_another_try(lines, survivors, t_kill: float, ready_s: float,
                          after_heal: int) -> "int | None":
    """--steps for one more attempt, when the job ended before the replacement
    had rejoined: the survivors' steps after the kill were faster than the
    step the job was sized from (a loaded host slows a step with every group
    in it far more than a solo one; seen in the CPU rehearsal, never on the
    chip). Sized from those solo steps as the log has them, for twice
    ``ready_s``; None where the log has fewer than three of them."""
    t = [s[0] for s in launch.steps(lines, survivors[0]) if s[1] > KILL_STEP]
    if len(t) < 3:
        return None
    solo = (t[-1] - t[1]) / (len(t) - 2)  # t[0] ends the stall step
    return plan_steps({"step_s": solo, "stall_s": t[0] - t_kill},
                      2 * ready_s, after_heal)


def _step_seen(lines, groups: int, t_commit: float) -> float:
    """The step that ended at ``t_commit`` (the victim's commit of
    KILL_STEP), timed from the groups' lines for the step before it."""
    began = [s[0] for g in range(groups) for s in launch.steps(lines, g)
             if s[1] == KILL_STEP - 1]
    return t_commit - median(began)


def run(cell, seed: int, seconds: float, trace: bool, out_dir: str,
        cache_dir: str, t_start: float) -> dict:
    tr = cell.traffic
    groups, victim = tr["groups"], tr["victim"]
    survivors = [g for g in range(groups) if g != victim]
    cal = trainer_job.Calibration(cell, cache_dir)
    # a checkout's first run has seen no step: it is sized for a short one
    # (too many steps, never too few) and checked against the first step timed
    unsized = not cal.data
    known = cal.data or {"step_s": tr["uncalibrated_step_s"]}

    def size(seen: dict) -> int:
        return plan_steps(seen, tr["ready_after_kill_s"], tr["steps_after_heal"])

    steps = size(known)
    trace_dir = os.path.join(out_dir, "trace") if trace else None
    log = os.path.join(out_dir, "launch.log")

    frozen = os.path.join(out_dir, "frozen")  # made by the victim as it stops
    stale = None  # what the log of an attempt that is started again is kept as
    while True:
        if stale:
            os.replace(log, log + stale)
            if trace_dir:
                shutil.rmtree(trace_dir, ignore_errors=True)
        if os.path.exists(frozen):
            os.remove(frozen)
        with launch.Launch(trainer_job.launcher_args(cell),
                           trainer_job.worker_args(cell, steps, trace_dir)
                           + ["--chipbench-freeze", f"{victim}:{KILL_STEP}:{frozen}"],
                           log) as run_:
            t_launch = time.monotonic()
            t_pids = [run_.wait_for(
                lambda ls, g=g: (launch.pids(ls, g) or [None])[0], 300,
                f"group {g} reaching its chip") for g in range(groups)]
            old_pid = t_pids[victim][1]
            commit = run_.wait_for(
                lambda ls: next((s for s in launch.steps(ls, victim)
                                 if s[1] >= KILL_STEP), None),
                tr["timeout_s"], f"the victim's commit of step {KILL_STEP}")
            if unsized:
                unsized = False
                seen = {"step_s": _step_seen(run_.lines, groups, commit[0])}
                if size(seen) > steps:  # the job would end before the rejoin
                    known, steps, stale = seen, size(seen), ".too_short"
                    continue  # leaving the block stops every process of it
            open(frozen, "a").close()  # whatever happens, the replacement runs on
            os.kill(old_pid, signal.SIGKILL)
            t_kill = time.monotonic()
            summaries = run_.finish(tr["timeout_s"] + steps * 3 * known["step_s"])
            lines = run_.lines
        try:
            ph = phases.rejoin(lines, victim, survivors, t_kill, KILL_STEP)
        except ValueError:  # the job ended before the rejoin: once more, longer
            more = stale != ".ended_early" and steps_for_another_try(
                lines, survivors, t_kill, tr["ready_after_kill_s"],
                tr["steps_after_heal"])
            if not more or more <= steps:
                raise
            steps, stale = more, ".ended_early"
            continue
        break
    device = trainer_job.device_of(summaries, groups)
    ph["rejoin.work_s"] = sum(ph[k] for k in tr["work_phases"])
    ph["launcher.reach_chip_s"] = max(t for t, _ in t_pids) - t_launch
    sums = {g: summaries[g][-1] for g in range(groups)}
    rejoiner = sums[victim]
    heal = {k: v for k, v in rejoiner["timings"].items() if k.startswith("heal_")}
    if "heal_recv_s" in heal:
        ph["heal.recv_s"] = heal["heal_recv_s"]
        ph["rejoin.first_step_rest_s"] = ph["rejoin.heal_step_s"] - heal["heal_recv_s"]
    ph["heal.mb_s"] = heal.get("heal_mb_per_s")
    ph["rejoin.peak_hbm_gib"] = (rejoiner["peak_hbm_bytes"] or 0) / 2**30 or None

    bad = [f"group {g}: {b}" for g, s in sums.items()
           for b in trainer_job.check_summary(s, cell, steps)]
    if rejoiner["healed"] < 1:
        bad.append("the restarted group never healed")
    if rejoiner["pid"] == old_pid or rejoiner["pid"] != ph["new_pid"]:
        bad.append(f"the victim did not restart: pids {old_pid} {rejoiner['pid']}")
    chips = [s["visible_chips"] for s in sums.values()]
    if len(set(chips)) != groups:
        bad.append(f"replica groups share chips: {chips}")
    if len({s["param_checksum"] for s in sums.values()}) != 1:
        bad.append("parameter checksums differ: "
                   f"{[s['param_checksum'] for s in sums.values()]}")
    # the step the kill broke may be discarded once per survivor, no other
    over = {g: s["discarded_after_first"] for g, s in sums.items()
            if s["discarded_after_first"] > (g != victim)}
    if over:
        bad.append(f"steps discarded beyond the one the kill broke: {over}")
    theirs = launch.steps(lines, survivors[0])
    full = [s[4] for s in theirs[1:] if s[3] == groups and s[1] != ph["heal_step"]]
    ph["trainer.step_4g_s"] = median(full) if full else None
    cal.save(step_s=ph["trainer.step_4g_s"], stall_s=ph["recover.stall_s"],
             solo_step_s=ph["recover.solo_step_s"])
    peak_all = max(s["peak_hbm_bytes"] or 0 for s in sums.values())
    obs = {
        "device": device, "memory_peak_bytes": peak_all, "correct": not bad,
        "attempted": sum(s["committed"] + s["discarded"] for s in sums.values()),
        "failed": sum(s["discarded"] for s in sums.values()),
        # no time after the kill decides (ISSUE 23 rule 3, PERF.md section 6):
        # every phase is a per-layer metric of the traced run
        "e2e": {"peak_hbm_gib": peak_all / 2**30,
                "setup_s": t_kill - t_start},
        "phases": ph, "summaries": summaries,
        "steps": {g: launch.steps(lines, g) for g in range(groups)},
        "steps_in_window": None,
        "notes": {"bad": bad, "seed": seed, "steps": steps,
                  "ended_with_every_group_in": theirs[-1][3] == groups,
                  "cache": {g: s["cache"] for g, s in sums.items()}},
    }
    return trainer_job.with_trace(obs, trace_dir)
