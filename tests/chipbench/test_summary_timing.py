"""The ``summary_timing`` reducer on a recorded SUMMARY object
(tests/chipbench/record_ring.py): a key of ``Manager.timings()`` of one
group's last process, the rejoiner being the group whose last pid is the
replacement's; a program that prints no such key (the parent commit)
leaves the metric out."""

import copy
import os

from chipbench_helpers import DATA, ROOT, read

from chipbench import manifest, run


def cell(name):
    return manifest.Cell(ROOT, manifest.load(ROOT), name)


def reduce(obs, **args):
    return manifest.load_module(ROOT, "reducers", "summary_timing").reduce(
        obs, cell("mistral-7b.managed-1g"), **args)


def summary(pid=None, **timings):
    s = copy.deepcopy(read(os.path.join(DATA, "ring.summary.json")))
    s["pid"] = pid or s["pid"]
    s["timings"].update(timings)
    return s


def test_a_group_by_number_and_the_rejoiner_by_pid():
    rec = summary()
    assert rec["timings"]["trace_dropped"] == 0.0
    first, second = summary(pid=11, heal_fetch_s=1.0), summary(pid=22, heal_fetch_s=9.5)
    obs = {"summaries": {0: [rec], 1: [first, second], 2: []},
           "phases": {"new_pid": 22}}
    t = rec["timings"]
    assert reduce(obs, key="startup_imports_s") == t["startup_imports_s"] > 0
    assert reduce(obs, key="first_step_compile_s", group=0) == t["first_step_compile_s"]
    assert reduce(obs, key="heal_fetch_s", group="rejoiner") == 9.5   # the last process
    assert reduce(obs, key="heal_fetch_s", group=1) == 9.5
    assert reduce(obs, key="heal_fetch_s", group=0) is None           # never healed
    assert reduce(obs, key="startup_imports_s", group=2) is None      # printed nothing
    assert reduce(obs, key="startup_imports_s", group=3) is None
    # nobody restarted (the managed cells), or the restart's pid matches no group
    assert reduce({"summaries": {0: [rec]}, "phases": {}},
                  key="heal_fetch_s", group="rejoiner") is None
    obs["phases"]["new_pid"] = 33
    assert reduce(obs, key="heal_fetch_s", group="rejoiner") is None
    assert reduce({}, key="startup_imports_s") is None


def test_the_parent_commits_summary_leaves_the_metrics_out():
    """A program without these timings (what the driver runs this benchmark
    on, on the parent's side): None, no KeyError."""
    old = summary()
    old["timings"] = {k: v for k, v in old["timings"].items()
                      if not k.startswith(("startup_", "first_step_", "heal_fetch",
                                           "heal_place"))}
    obs = {"summaries": {0: [old]}, "phases": {"new_pid": old["pid"]}}
    c = cell("internlm2-1.8b.kill-rejoin-4g")
    c.per_layer = [m for m in c.per_layer
                   if c.layer_metric(m["name"])["reducer"] == "summary_timing"]
    assert len(c.per_layer) == 8
    assert set(run.layer_values(c, obs).values()) == {None}


def test_every_new_timing_metric_reads_the_recorded_summary():
    rec = summary(heal_fetch_s=3.0, heal_place_s=0.5)
    obs = {"summaries": {0: [rec], 1: [rec]}, "phases": {"new_pid": rec["pid"]}}
    for name, n in (("mistral-7b.managed-1g", 3), ("internlm2-1.8b.managed-1g", 3),
                    ("internlm2-1.8b.kill-rejoin-4g", 8)):
        c = cell(name)
        c.per_layer = [m for m in c.per_layer
                       if c.layer_metric(m["name"])["reducer"] == "summary_timing"]
        got = run.layer_values(c, obs)
        assert len(got) == n and all(isinstance(v, float) for v in got.values()), got
