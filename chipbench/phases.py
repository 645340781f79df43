"""The arithmetic of the kill -> rejoin event, from stamped lines alone (so
it comes out of an untraced run). Everything here is a pure function of the
lines; tests/chipbench/test_phases.py runs it on a canned log.

    rejoin.restart_s = t_pid - t_spawn      rejoin.init_s = t_ready - t_pid
    rejoin.heal_step_s = J                  rejoin.boundary_wait_s = (t_first - t_ready) - J

t_spawn  the launcher's "replica group V died ... restart" line, written just
         before it spawns the replacement: a clock started here holds no
         launcher poll (up to 1 s) and no heartbeat timeout (5 s)
t_pid    the replacement's ``pid=`` line (interpreter, imports, libtpu up)
t_ready  its ``mesh fsdp=`` line (state on the chip, Manager up)
t_first  the replacement's first committed-step line; its ``step=`` number
         names the heal step
J        the heal step as the job pays for it: on each survivor the interval
         between its commit lines for the step before the heal step and for
         the heal step, the median over the survivors. A survivor's iteration
         starts where its last one ended, so no wait for the boundary (a draw
         from 0..one step) is inside it. Every time here is this process's
         stamp of a line as it read it; the ``iter_s`` the program prints on
         those lines decides nothing (a later PR could move where it starts
         and stops): it feeds per-layer metrics only.

Which of these phases ``rejoin.work_s`` sums is data: ``work_phases`` in the
traffic file. It is a per-layer metric and decides nothing: PERF.md section 6
says which phases were dropped as noise, and why the sum that is left is
still not steady enough between machines to be an end-to-end metric.
"""

from statistics import median

from chipbench.launch import DIED_LINE, pids, steps


def rejoin(lines, victim: int, survivors, t_kill: float, kill_step: int) -> dict:
    """Phases of one scripted kill (``kill_step``: the last step the victim
    committed); ValueError when a line the arithmetic needs is missing."""
    spawn = [ln.t for ln in lines if ln.replica is None
             and (m := DIED_LINE.search(ln.text)) and int(m[1]) == victim]
    vp = pids(lines, victim)
    if not spawn or len(vp) < 2:
        raise ValueError("no restart of the victim in the log")
    t_spawn, (t_pid, new_pid) = spawn[0], vp[1]
    ready = [ln.t for ln in lines if ln.replica == victim and ln.t > t_pid
             and ln.text.startswith("mesh fsdp=")]
    mine = steps(lines, victim, after_t=t_pid)
    if not ready or not mine:
        raise ValueError("the replacement never committed a step")
    t_ready = ready[0]
    t_first, heal_step = mine[0][:2]
    took = []
    for g in survivors:
        at = {s[1]: s[0] for s in steps(lines, g)}
        if heal_step not in at or heal_step - 1 not in at:
            raise ValueError(f"survivor {g} has no lines for steps "
                             f"{heal_step - 1} and {heal_step}")
        took.append(at[heal_step] - at[heal_step - 1])
    J = median(took)
    # a survivor between the kill and the heal step: the stall step (it holds
    # the lighthouse's heartbeat timeout), then steps without the victim
    after = [s for s in steps(lines, survivors[0]) if s[1] > kill_step]
    solo = [s for s in after if s[1] < heal_step]
    return {
        "rejoin.restart_s": t_pid - t_spawn,
        "rejoin.init_s": t_ready - t_pid,
        "rejoin.boundary_wait_s": (t_first - t_ready) - J,
        "rejoin.heal_step_s": J,
        "recover.stall_s": after[0][0] - t_kill,
        "recover.solo_step_s": median(s[4] for s in solo[1:]) if solo[1:] else None,
        "heal_step": heal_step, "new_pid": new_pid,
        "solo_steps": len(solo),
    }
