"""The ``summary_counter`` reducer on a recorded SUMMARY object: a counter
or share of ``Manager.timings()`` of one group's last process; a program
that prints no such key (the parent commit) leaves the metric out."""

import copy
import os

import pytest
from chipbench_helpers import DATA, ROOT, read

from chipbench import manifest, run

MANAGED = ("mistral-7b.managed-1g", "internlm2-1.8b.managed-1g",
           "olmoe-1b-7b.managed-1g")


def summary(**timings):
    s = copy.deepcopy(read(os.path.join(DATA, "ring.summary.json")))
    s["timings"].update(timings)
    return s


def counters(name):
    c = manifest.Cell(ROOT, manifest.load(ROOT), name)
    c.per_layer = [m for m in c.per_layer
                   if c.layer_metric(m["name"])["reducer"] == "summary_counter"]
    return c


@pytest.mark.parametrize("name", MANAGED)
def test_the_hit_share_is_read_from_group_0s_last_summary(name):
    first, last = summary(stage_pool_hit_share=0.0), summary(stage_pool_hit_share=1.0)
    obs = {"summaries": {0: [first, last], 1: [first]}, "phases": {}}
    assert run.layer_values(counters(name), obs) == {
        "allreduce.stage_pool_hit_share": 1.0}


@pytest.mark.parametrize("name", MANAGED)
def test_the_parent_commits_summary_leaves_it_out(name):
    """The recorded summary is a program's from before the counter: None,
    no KeyError, also where a group printed nothing at all."""
    assert "stage_pool_hit_share" not in summary()["timings"]
    for obs in ({"summaries": {0: [summary()]}}, {"summaries": {0: []}}, {}):
        assert run.layer_values(counters(name), obs) == {
            "allreduce.stage_pool_hit_share": None}


def test_only_the_managed_cells_report_it():
    for w in manifest.load(ROOT)["workloads"]:
        got = [m["name"] for m in counters(w["name"]).per_layer]
        assert got == (["allreduce.stage_pool_hit_share"]
                       if w["name"] in MANAGED else []), w["name"]
