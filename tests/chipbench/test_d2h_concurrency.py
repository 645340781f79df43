"""``allreduce.d2h_concurrency`` (PR 36): one more counter of
``Manager.timings()`` on the ``summary_counter`` reducer, a data file and an
appended entry. Read from group 0's last SUMMARY in the three managed cells;
left out, with no KeyError, by a program that prints no such key (the parent
commit, on which the driver lays this file too)."""

import copy
import os

import pytest
from chipbench_helpers import DATA, ROOT, read

from chipbench import manifest, run

NAME = "allreduce.d2h_concurrency"
MANAGED = ("mistral-7b.managed-1g", "internlm2-1.8b.managed-1g",
           "olmoe-1b-7b.managed-1g")


def summary(**timings):
    s = copy.deepcopy(read(os.path.join(DATA, "ring.summary.json")))
    s["timings"].update(timings)
    return s


def only(name):
    c = manifest.Cell(ROOT, manifest.load(ROOT), name)
    c.per_layer = [m for m in c.per_layer if m["name"] == NAME]
    return c


@pytest.mark.parametrize("name", MANAGED)
def test_it_is_read_from_group_0s_last_summary(name):
    first = summary(d2h_concurrency=0.98, d2h_gb_s=6.1)
    last = summary(d2h_concurrency=3.412, d2h_gb_s=10.4)
    obs = {"summaries": {0: [first, last], 1: [first]}, "phases": {}}
    assert run.layer_values(only(name), obs) == {NAME: 3.412}


@pytest.mark.parametrize("name", MANAGED)
def test_the_parent_commits_summary_leaves_it_out(name):
    assert "d2h_concurrency" not in summary()["timings"]
    for obs in ({"summaries": {0: [summary()]}}, {"summaries": {0: []}}, {}):
        assert run.layer_values(only(name), obs) == {NAME: None}


def test_only_the_managed_cells_report_it():
    bench = manifest.load(ROOT)
    [entry] = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == list(MANAGED)
    assert (entry["moves"], entry["source"], entry["better"], entry["unit"]) == (
        "tok_s_chip", "program_counter", "higher", "x")
    pool = next(m for m in bench["per_layer"]
                if m["name"] == "allreduce.stage_pool_hit_share")
    assert entry["layer"] == pool["layer"]  # the layer's name as it stands
    for w in bench["workloads"]:
        assert bool(only(w["name"]).per_layer) == (w["name"] in MANAGED)
    assert manifest.problems(ROOT) == []


def test_it_is_appended_after_everything_that_was_there():
    names = [m["name"] for m in manifest.load(ROOT)["per_layer"]]
    assert names.index(NAME) > names.index("attn.mixer_s")
    assert names.index(NAME) > names.index("allreduce.d2h_under_backward_share")


def test_its_file_is_data_on_the_reducer_that_is_there():
    spec = read(os.path.join(ROOT, "chipbench", "layer_metrics", NAME + ".json"))
    assert sorted(spec) == ["args", "reducer", "what"]
    assert spec["reducer"] == "summary_counter"
    assert spec["args"] == {"key": "d2h_concurrency", "group": 0}
