"""The phase arithmetic of the kill -> rejoin event (``rejoin.work_s`` and
its phases) on a canned log."""

import pytest

from chipbench_helpers import ROOT  # noqa: F401

from chipbench import phases
from chipbench.launch import Line, parse_step, pids, steps


def canned(poll_phase: float, ready_early: float = 0.0):
    """A four-group job, group 1 killed at t=100 right after step 2. The
    launcher polls once a second: it notices ``poll_phase`` seconds after
    the kill. The replacement takes 21 s to its pid= line and 3 s more to
    be ready, whenever it was spawned; survivors step every 18 s after a
    5 s heartbeat timeout, and the heal step lasts 35 s."""
    L = []

    def step(t, g, n, p, it):
        L.append(Line(t, g, f"step={n} inner={n} loss=11.4355 participants={p} "
                               f"iter_s={it:.2f} tok/s=100"))

    for g in range(4):
        L.append(Line(10.0 + g, g, f"pid={1000 + g} platform=tpu device_kind='TPU v5 lite'"))
        L.append(Line(20.0 + g, g, "mesh fsdp=1 sp=1 tp=1 diloco=False starting at step 0"))
        step(82.0, g, 1, 4, 60.0)
        step(100.0, g, 2, 4, 18.0)
    t_spawn = 100.0 + poll_phase
    L.append(Line(t_spawn, None, "WARNING:__main__:replica group 1 died "
                                 "(codes=[-9]); restart 1/1"))
    L.append(Line(t_spawn + 21.0, 1, "pid=2001 platform=tpu device_kind='TPU v5 lite'"))
    t_ready = t_spawn + 24.0 - ready_early
    L.append(Line(t_ready, 1, "mesh fsdp=1 sp=1 tp=1 diloco=False starting at step 0"))
    for g in (0, 2, 3):
        step(123.0, g, 3, 3, 23.0)       # 5 s heartbeat timeout + 18 s
        step(141.0, g, 4, 3, 18.0)       # the replacement became ready in here
        step(176.0, g, 5, 3, 35.0)       # the heal step: still reads 3
        step(194.0, g, 6, 4, 18.0)
    step(176.2, 1, 5, 3, 176.2 - t_ready)
    step(194.0, 1, 6, 4, 17.8)
    return sorted(L, key=lambda ln: ln.t)


def work(ph):
    """The work as ISSUE 23 defines it: (t_ready - t_spawn) + J."""
    return ph["rejoin.restart_s"] + ph["rejoin.init_s"] + ph["rejoin.heal_step_s"]


def test_line_readers():
    L = canned(0.5)
    assert parse_step(L[-1].text) == (6, 11.4355, 4, pytest.approx(18.0, abs=0.3))
    assert [p for _, p in pids(L, 1)] == [1001, 2001]
    assert [s[1] for s in steps(L, 0)] == [1, 2, 3, 4, 5, 6]


def test_phases_of_the_canned_kill():
    ph = phases.rejoin(canned(0.5), 1, [0, 2, 3], 100.0, 2)
    assert ph["heal_step"] == 5 and ph["new_pid"] == 2001
    assert ph["rejoin.restart_s"] == pytest.approx(21.0)
    assert ph["rejoin.init_s"] == pytest.approx(3.0)
    assert ph["rejoin.heal_step_s"] == 35.0
    assert work(ph) == pytest.approx(24.0 + 35.0)
    # ready at 124.5, the boundary at 141: it waited 16.5 s, reported apart
    assert ph["rejoin.boundary_wait_s"] == pytest.approx(16.5 + 0.2, abs=0.02)
    assert ph["recover.stall_s"] == pytest.approx(23.0)
    assert ph["solo_steps"] == 2 and ph["recover.solo_step_s"] == 18.0


def test_a_kill_just_before_or_after_a_launcher_poll_gives_the_same_number():
    """The poll second moves the spawn and everything the replacement does,
    never the survivors: a clock started at the spawn does not see it."""
    early = phases.rejoin(canned(0.02), 1, [0, 2, 3], 100.0, 2)
    late = phases.rejoin(canned(0.98), 1, [0, 2, 3], 100.0, 2)
    assert work(early) == pytest.approx(work(late), abs=1e-9)
    # what a clock started at the kill would have carried
    assert early["rejoin.boundary_wait_s"] - late["rejoin.boundary_wait_s"] \
        == pytest.approx(0.96)


def test_a_survivor_line_is_matched_by_step_number_not_participants():
    """The heal step reads participants=3 on every line (a healing replica
    sits its step out); J must still be found."""
    L = canned(0.3)
    assert all(s[3] == 3 for s in steps(L, 0) if s[1] == 5)
    assert phases.rejoin(L, 1, [0, 2, 3], 100.0, 2)["rejoin.heal_step_s"] == 35.0


def test_the_heal_step_is_timed_by_the_stamps_not_by_the_programs_iter_s():
    """J is the interval between the survivors' commit lines as this process
    stamped them: whatever the program prints as iter_s (a later PR may move
    where that clock starts and stops) moves nothing."""
    import re

    L = canned(0.3)
    want = phases.rejoin(L, 1, [0, 2, 3], 100.0, 2)
    assert want["rejoin.heal_step_s"] == pytest.approx(35.0)  # 141 -> 176
    lied = [Line(ln.t, ln.replica, re.sub(r"iter_s=\S+", "iter_s=0.01", ln.text))
            for ln in L]
    got = phases.rejoin(lied, 1, [0, 2, 3], 100.0, 2)
    assert got["rejoin.heal_step_s"] == want["rejoin.heal_step_s"]
    assert got["rejoin.boundary_wait_s"] == want["rejoin.boundary_wait_s"]
    # one survivor's line read late: the median over the survivors holds
    late = [Line(ln.t + 0.4 if (ln.replica == 2 and "step=5 " in ln.text) else ln.t,
                 ln.replica, ln.text) for ln in L]
    assert phases.rejoin(late, 1, [0, 2, 3], 100.0, 2)["rejoin.heal_step_s"] \
        == pytest.approx(35.0)


def test_missing_lines_are_an_error():
    L = [ln for ln in canned(0.3) if ln.replica is not None]  # no restart line
    with pytest.raises(ValueError, match="no restart"):
        phases.rejoin(L, 1, [0, 2, 3], 100.0, 2)
    L = [ln for ln in canned(0.3) if not (ln.replica == 1 and ln.t > 150)]
    with pytest.raises(ValueError, match="never committed"):
        phases.rejoin(L, 1, [0, 2, 3], 100.0, 2)
    L = [ln for ln in canned(0.3) if not (ln.replica == 3 and "step=4 " in ln.text)]
    with pytest.raises(ValueError, match="survivor 3 has no lines"):
        phases.rejoin(L, 1, [0, 2, 3], 100.0, 2)


def test_plan_steps():
    from chipbench import manifest

    job = manifest.load_module(ROOT, "jobs", "kill_rejoin")
    # stall 23 s, ready 28 s after the kill: one more solo step starts
    assert job.plan_steps({"step_s": 18.0, "stall_s": 23.0}, 28.0, 1) == 6
    # ready before the stall step ends: the heal step follows it at once
    assert job.plan_steps({"step_s": 18.0, "stall_s": 23.0}, 17.0, 1) == 5
    assert job.plan_steps({"step_s": 2.0}, 32.0, 1) == 2 + 14 + 2
    # the chip cell's own numbers (PERF.md): stall 19.5, solo step 13.8
    assert job.plan_steps({"step_s": 15.9, "stall_s": 19.5, "solo_step_s": 13.8}, 30.0, 1) == 6
    # a checkout's first run (traffic/kill-rejoin-4g.json: sized as for 4 s
    # steps): more steps than any slower step needs, so never too few
    first = job.plan_steps({"step_s": 4.0}, 30.0, 1)
    assert first == 11
    assert all(job.plan_steps({"step_s": s}, 30.0, 1) <= first for s in (4.0, 6.8, 15.9, 40.0))
    assert job.plan_steps({"step_s": 1.5}, 30.0, 1) > first  # seen at the kill: start again


def test_a_job_that_ended_before_the_rejoin_is_sized_again_from_its_solo_steps():
    """A loaded host slows a step with every group in it far more than a solo
    one (seen in the CPU rehearsal): the survivors, sized from the step before
    the kill, were done before the replacement was ready, which then trained
    alone from step 0. One more attempt is sized from the solo steps the log
    has, for twice the restart the traffic file allows for."""
    from chipbench import manifest

    job = manifest.load_module(ROOT, "jobs", "kill_rejoin")
    L = []
    for g in (0, 1):
        L.append(Line(18.0 + g, g, f"pid={100 + g} platform=cpu"))
        L.append(Line(35.0, g, "step=1 inner=1 loss=7.4 participants=2 iter_s=6.50"))
        L.append(Line(41.0, g, "step=2 inner=2 loss=7.4 participants=2 iter_s=6.00"))
    L.append(Line(42.1, None, "WARNING:__main__:replica group 1 died (codes=[-9]); restart 1/1"))
    L.append(Line(45.4, 1, "pid=201 platform=cpu"))
    for n, t in enumerate((47.0, 48.5, 50.0, 51.5, 53.0, 54.5, 56.0), start=3):
        L.append(Line(t, 0, f"step={n} inner={n} loss=7.4 participants=1 iter_s=1.50"))
    L.append(Line(55.9, 1, "mesh fsdp=1 sp=1 tp=1 diloco=False starting at step 0"))
    L.append(Line(62.5, 1, "step=1 inner=1 loss=7.4 participants=1 iter_s=6.50"))
    L.sort(key=lambda ln: ln.t)
    with pytest.raises(ValueError, match="survivor 0 has no lines for steps 0 and 1"):
        phases.rejoin(L, 1, [0], 41.0, 2)
    # stall step 6 s, then 1.5 s a step: 1 + ceil((50 - 6) / 1.5) solo steps
    assert job.steps_for_another_try(L, [0], 41.0, 25.0, 1) == 2 + 31 + 2
    # a log with no solo steps to size from: no other attempt
    assert job.steps_for_another_try(L[:8], [0], 41.0, 25.0, 1) is None
