"""The ``span_groups`` reducer on hand-made spans of four traced processes
(a span's per-step sum in each group, ``over`` the groups that have the
step, median over steps), the eight ``ring.*`` layer metrics through
``run.layer_values``, and their entries in the repo's manifest: appended,
for the four-group steady cell alone."""

import copy
import os

import pytest

from chipbench_helpers import DATA, ROOT, read

from chipbench import manifest, run

S = 1_000_000_000
RUN, ENTRY, STREAM = ("manager.allreduce." + n for n in
                      ("wire_run", "ring_entry_wait", "ring_stream"))
CELL = "internlm2-1.8b.managed-4g"
SPAN_METRICS = {"ring.span_s": (RUN, "max"),
                "ring.entry_wait_s": (ENTRY, "max"),
                "ring.stream_s": (STREAM, "min")}
TIMING_METRICS = {f"ring.{t}_s": f"ring_{t}_s"
                  for t in ("recv_wait", "recv", "fold", "send", "handoff")}


def cell(name=CELL):
    return manifest.Cell(ROOT, manifest.load(ROOT), name)


def reduce(obs, **args):
    return manifest.load_module(ROOT, "reducers", "span_groups").reduce(
        obs, cell(), **args)


def proc(replica, spans, pid=None, window=(0, 100 * S)):
    return {"replica": replica, "pid": pid or 10 + replica, "window": window,
            "spans": [(n, int(a * S), int(b * S), step) for n, a, b, step in spans]}


def ring(t0, entry, stream, step, pieces=2):
    """A step's ``pieces`` runs from ``t0``: each a wire_run with its two
    children, ``entry`` and ``stream`` seconds in all."""
    out, e, s = [], entry / pieces, stream / pieces
    for k in range(pieces):
        a = t0 + k * (e + s)
        out += [(RUN, a, a + e + s, step), (ENTRY, a, a + e, step),
                (STREAM, a + e, a + e + s, step)]
    return out


def four_groups():
    # step 5: the groups enter 0 / 0.1 / 0.2 / 0.3 s apart, the ring itself
    # takes 0.4 s; step 6: all at once, 0.5 s; step 7: 0.6 s, group 3 away
    return {"procs": [
        proc(0, ring(1.0, 0.3, 0.4, 5) + ring(3, 0.0, 0.5, 6) + ring(5, 0.0, 0.6, 7)),
        proc(1, ring(1.1, 0.2, 0.4, 5) + ring(3, 0.0, 0.5, 6) + ring(5, 0.1, 0.6, 7)),
        proc(2, ring(1.2, 0.1, 0.4, 5) + ring(3, 0.0, 0.5, 6) + ring(5, 0.2, 0.6, 7)),
        proc(3, ring(1.3, 0.0, 0.4, 5) + ring(3, 0.0, 0.5, 6)),
    ]}


def test_max_and_min_across_the_groups_then_the_median_over_steps():
    obs = four_groups()
    # per step, over the groups: wire_run max 0.7, 0.5, 0.8; stream min
    # 0.4, 0.5, 0.6; entry wait max 0.3, 0.0, 0.2
    assert reduce(obs, name=RUN, over="max") == pytest.approx(0.7)
    assert reduce(obs, name=RUN) == pytest.approx(0.7)            # the default
    assert reduce(obs, name=RUN, over="min") == pytest.approx(0.5)
    assert reduce(obs, name=STREAM, over="min") == pytest.approx(0.5)
    assert reduce(obs, name=ENTRY, over="max") == pytest.approx(0.2)
    assert reduce(obs, name=ENTRY, over="min") == pytest.approx(0.0)
    with pytest.raises(KeyError):
        reduce(obs, name=RUN, over="mean")


def test_a_group_missing_a_step_is_left_out_of_that_step_alone():
    obs = four_groups()
    # step 7 has three groups: its max is theirs, and the step still counts
    assert reduce({"procs": obs["procs"][:1]}, name=RUN) == pytest.approx(0.6)
    only7 = {"procs": [proc(p["replica"], [
        (n, a / S, b / S, st) for n, a, b, st in p["spans"] if st == 7])
        for p in obs["procs"]]}
    assert reduce(only7, name=RUN, over="max") == pytest.approx(0.8)
    assert reduce(only7, name=RUN, over="min") == pytest.approx(0.6)


def test_a_rejoiners_two_processes_are_one_group():
    """Group 1's first process has steps 5 and 6, its replacement 6 and 7:
    step 6 is read from the one that was traced longer, never summed."""
    obs = {"procs": [
        proc(0, ring(1, 0, 0.4, 5) + ring(3, 0, 0.4, 6) + ring(5, 0, 0.4, 7)),
        proc(1, ring(1, 0, 0.6, 5) + ring(3, 0, 0.9, 6), pid=21,
             window=(0, 4 * S)),
        proc(1, ring(3, 0, 0.7, 6) + ring(5, 0, 0.5, 7), pid=22,
             window=(2 * S, 100 * S)),
    ]}
    # max over groups: 0.6, 0.7 (not 0.9, not 1.6), 0.5
    assert reduce(obs, name=RUN, over="max") == pytest.approx(0.6)
    assert reduce(obs, name=RUN, over="min") == pytest.approx(0.4)


def test_only_spans_inside_a_processs_own_window_count():
    obs = {"procs": [
        proc(0, ring(1, 0, 0.4, 5) + ring(50, 0, 9.0, 6), window=(0, 10 * S)),
        proc(1, ring(1, 0, 0.5, 5) + ring(50, 0, 0.3, 6)),
    ]}
    assert reduce(obs, name=RUN, over="max") == pytest.approx(0.4)  # 0.5, 0.3


def test_none_where_no_process_has_the_span():
    obs = four_groups()
    parent = {"procs": [dict(p, spans=[s for s in p["spans"] if s[0] == RUN])
                        for p in obs["procs"]]}
    assert reduce(parent, name=RUN) == pytest.approx(0.7)
    assert reduce(parent, name=ENTRY) is None      # the parent commit's ring
    assert reduce(parent, name=STREAM, over="min") is None
    assert reduce({"procs": []}, name=RUN) is None
    assert reduce({}, name=RUN) is None


def summary(**timings):
    s = copy.deepcopy(read(os.path.join(DATA, "ring.summary.json")))
    s["timings"].update(timings)
    return s


@pytest.mark.parametrize("name", [*SPAN_METRICS, *TIMING_METRICS])
def test_each_new_layer_metric_reads_what_the_program_writes(name):
    keys = {key: 0.01 * (i + 1) for i, key in enumerate(TIMING_METRICS.values())}
    obs = {**four_groups(), "summaries": {0: [summary(**keys)], 1: [summary()]},
           "phases": {}}
    c = cell()
    spec = c.layer_metric(name)
    assert set(spec) == {"reducer", "args", "what"}
    c.per_layer = [m for m in c.per_layer if m["name"] == name]
    assert len(c.per_layer) == 1
    (got,) = run.layer_values(c, obs).values()
    if name in SPAN_METRICS:
        span, over = SPAN_METRICS[name]
        assert spec["reducer"] == "span_groups"
        assert spec["args"] == {"name": span, "over": over}
        assert got == pytest.approx(reduce(obs, name=span, over=over))
    else:
        assert spec["reducer"] == "summary_timing"
        assert spec["args"] == {"key": TIMING_METRICS[name], "group": 0}
        assert got == keys[TIMING_METRICS[name]]
    # the parent commit's program: the same spans and SUMMARY without what
    # this metric reads: left out of the line, nothing raised
    parent = {"procs": [dict(p, spans=[s for s in p["spans"] if s[0] == RUN])
                        for p in obs["procs"]],
              "summaries": {0: [summary()]}, "phases": {}}
    (got,) = run.layer_values(c, parent).values()
    assert (got is None) == (name != "ring.span_s")


def test_the_readings_order_as_the_ring_does():
    """The stream with everyone present is no longer than the ring from its
    first entrant, and no group waited longer for its first byte than the
    entries were spread."""
    c = cell()
    c.per_layer = [m for m in c.per_layer if m["name"] in SPAN_METRICS]
    got = run.layer_values(c, four_groups())
    assert got["ring.stream_s"] <= got["ring.span_s"]
    assert got["ring.entry_wait_s"] <= (
        got["ring.span_s"] - got["ring.stream_s"] + 1e-6)


def test_the_eight_are_appended_entries_of_the_four_group_steady_cell_alone():
    bench = manifest.load(ROOT)
    names = [*SPAN_METRICS, *TIMING_METRICS]
    mine = [m for m in bench["per_layer"] if m["name"].startswith("ring.")]
    assert [m["name"] for m in mine] == names
    at = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][at:at + 8] == mine     # one block, in order
    layer = next(m["layer"] for m in bench["per_layer"]
                 if m["name"] == "allreduce.wire_run_s")
    for m in mine:
        assert m == {"name": m["name"], "unit": "s", "better": "lower",
                     "source": ("program_span" if m["name"] in SPAN_METRICS
                                else "program_counter"),
                     "layer": layer, "moves": "tok_s_chip", "workloads": [CELL]}
    assert manifest.problems(ROOT) == []
