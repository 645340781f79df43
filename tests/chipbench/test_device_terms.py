"""The managed step as four terms on the device's clock (PR 37): the
watcher's ``device/forward``, ``/backward`` and ``/update`` spans through the
``span`` reducer that is there, the exposed exchange as the gap between the
last backward and the update (reducer ``span_gap``, new), and the ring's own
honesty counter ``trace_dropped`` through ``summary_counter``: five data
files and five appended entries. (The three spans go through ``device_span``,
``span`` under a name of its own: test_span_wait.py holds every ``span``
metric of a managed cell to a number on rings recorded before these spans
were.) Read from a ring recorded on the CPU through
the real trainer (``python3 tests/chipbench/record_ring.py ring37``: the
times are a CPU's and mean nothing, the tests read structure and
arithmetic), left out with no error by a program that has no such span."""

import copy
import os
from statistics import median

import pytest
from chipbench_helpers import DATA, ROOT, read

from chipbench import manifest, run

S = 1_000_000_000
MANAGED = ("mistral-7b.managed-1g", "internlm2-1.8b.managed-1g",
           "olmoe-1b-7b.managed-1g")
FWD, BWD, UPD, LANDED = ("manager.device." + n for n in
                         ("forward", "backward", "update", "landed"))
TERMS = {"trainer.forward_device_s": FWD, "trainer.backward_device_s": BWD,
         "trainer.update_device_s": UPD}
NEW = [*TERMS, "allreduce.exposed_s", "manager.trace_dropped"]


def only(cell_name, *names):
    c = manifest.Cell(ROOT, manifest.load(ROOT), cell_name)
    c.per_layer = [m for m in c.per_layer if m["name"] in names]
    return c


def gap(obs, **args):
    spec = read(os.path.join(ROOT, "chipbench", "layer_metrics",
                             "allreduce.exposed_s.json"))["args"]
    return manifest.load_module(ROOT, "reducers", "span_gap").reduce(
        obs, only(MANAGED[0]), **{**spec, **args})


def ring(name="ring37"):
    return read(os.path.join(DATA, f"{name}.spans.json"))["spans"]


def proc(spans, window=None):
    """A recorded ring as hostspans.collect names it; default window: all."""
    spans = [(f"manager.{s['cat']}.{s['name']}", s["ts_us"] * 1000,
              (s["ts_us"] + s["dur_us"]) * 1000, s["step"]) for s in spans]
    window = window or (min(s[1] for s in spans), max(s[2] for s in spans))
    return {"replica": 0, "pid": 1, "spans": spans, "window": window}


# ------------------------------------------------------------ the recording
def test_the_recorded_trainer_leaves_one_chain_a_step():
    """Through examples/train_llama_hsdp.py on the CPU (two ops a step at
    the tiny preset, nine buckets): one forward, one backward, a landed a
    bucket, one update, in the device's order, each inside its
    ``trainer/step`` by its ancestors."""
    spans = ring()
    by_id = {s["id"]: s for s in spans}
    dev = sorted((s for s in spans if s["cat"] == "device"),
                 key=lambda s: s["ts_us"] + s["dur_us"])
    unpacks = [s for s in spans if s["name"] == "unpack"]
    # the recording keeps the spans numbered 2 and 3: an update carries the
    # number after its commit, so the first belongs to the step cut away
    assert dev[0]["name"] == "update" and dev[0]["step"] == 2
    per_step = len(unpacks) // 2
    chain = ["forward", "backward"] + ["landed"] * per_step + ["update"]
    assert [s["name"] for s in dev[1:]] == chain + chain[:-1]
    assert [s["step"] for s in dev[1:]] == \
        [2] * (2 + per_step) + [3] * (3 + per_step)

    def step_of(s):
        while s is not None and (s["cat"], s["name"]) != ("trainer", "step"):
            s = by_id.get(s["parent"])
        return s

    steps = [step_of(s) for s in dev[1:]]
    assert all(s is not None for s in steps)
    assert len({s["id"] for s in steps}) == 2
    for s in dev:
        assert set(s["args"]) >= {"waited_us", "late"}
    landed = [s for s in dev if s["name"] == "landed"]
    assert sorted((s["step"], s["args"]["segment"], s["args"]["bucket"])
                  for s in landed) == \
        sorted((s["step"], s["args"]["segment"], s["args"]["bucket"])
               for s in unpacks)
    # no more recycle tokens than landings: the instants ride on them
    assert len([s for s in spans if s["name"] == "recycle"]) == len(landed)


def test_the_terms_and_the_gap_of_the_recording():
    p = proc(ring())
    obs = {"procs": [p]}
    values = run.layer_values(only(MANAGED[0], *TERMS, "allreduce.exposed_s"), obs)
    dev = {n: sorted((a, b) for name, a, b, _ in p["spans"] if name == n)
           for n in (FWD, BWD, UPD)}
    for metric, name in TERMS.items():
        assert values[metric] == pytest.approx(
            median((b - a) / 1e9 for a, b in dev[name]))
    # two updates follow a backward inside the recording; the first one's
    # step was cut away and it has nothing before it
    want = [(u[0] - max(b[1] for b in dev[BWD] if b[1] <= u[0])) / 1e9
            for u in dev[UPD][1:]]
    assert len(want) == 1 and want[0] > 0
    assert values["allreduce.exposed_s"] == pytest.approx(median(want))
    # the four terms tile the step: forward starts where the update before
    # it ended or later, and ends where backward starts
    f, b = dev[FWD][-1], dev[BWD][-1]
    assert f[1] == b[0] and f[0] >= dev[UPD][0][1]


# ----------------------------------------------------- span_gap, arithmetic
def test_the_gap_is_from_the_latest_end_before_each_start():
    spans = [
        # step 5: two backward segments, the update 3 s after the second
        (FWD, 0, 1 * S, 5), (BWD, 1 * S, 2 * S, 5), (BWD, 2 * S, 4 * S, 5),
        (LANDED, 6 * S, 6 * S + 1000, 5), (UPD, 7 * S, 8 * S, 6),
        # step 6: the update 1 s after
        (FWD, 8 * S, 9 * S, 6), (BWD, 9 * S, 10 * S, 6), (UPD, 11 * S, 12 * S, 7),
        # step 7: discarded, no update; step 8: 5 s
        (FWD, 12 * S, 13 * S, 7), (BWD, 13 * S, 14 * S, 7),
        (FWD, 14 * S, 15 * S, 8), (BWD, 15 * S, 16 * S, 8), (UPD, 21 * S, 22 * S, 9),
    ]
    obs = {"procs": [{"replica": 0, "pid": 1, "window": (0, 30 * S), "spans": spans}]}
    assert gap(obs) == pytest.approx(median([3.0, 1.0, 5.0]))
    assert gap(obs, replica=1) is None
    assert gap({}) is None


def test_a_window_that_cuts_a_step_leaves_its_update_out():
    spans = [(FWD, 0, 1 * S, 5), (BWD, 1 * S, 2 * S, 5), (UPD, 4 * S, 5 * S, 6),
             (FWD, 5 * S, 6 * S, 6), (BWD, 6 * S, 7 * S, 6), (UPD, 8 * S, 9 * S, 7),
             (FWD, 9 * S, 10 * S, 7), (BWD, 10 * S, 11 * S, 7), (UPD, 14 * S, 15 * S, 8)]
    cut = lambda t0, t1: {"procs": [{  # noqa: E731
        "replica": 0, "pid": 1, "window": (t0, t1), "spans": spans}]}
    assert gap(cut(0, 20 * S)) == pytest.approx(median([2.0, 1.0, 3.0]))
    # the window opens inside step 5's backward: its update has nothing of
    # ``frm`` before it inside the window
    assert gap(cut(S + S // 2, 20 * S)) == pytest.approx(median([1.0, 3.0]))
    # and closes inside the last update
    assert gap(cut(S + S // 2, 14 * S + S // 2)) == pytest.approx(1.0)
    assert gap(cut(11 * S, 13 * S)) is None
    # on the recording: the last update's step is whole, the first one's not
    whole = gap({"procs": [proc(ring())]})
    p = proc(ring())
    first_fwd = min(a for n, a, _, _ in p["spans"] if n == FWD)
    assert gap({"procs": [proc(ring(), (first_fwd, p["window"][1]))]}) == whole


def test_a_one_op_step_ends_its_gradients_with_forward():
    """The hybrid, LocalSGD: one allreduce a step, no ``device/backward``."""
    spans = [(FWD, 0, 2 * S, 5), (UPD, 5 * S, 6 * S, 6),
             (FWD, 6 * S, 8 * S, 6), (UPD, 9 * S, 10 * S, 7)]
    obs = {"procs": [{"replica": 0, "pid": 1, "window": (0, 20 * S), "spans": spans}]}
    assert gap(obs) == pytest.approx(2.0)
    one_op = [s for s in ring() if (s["cat"], s["name"]) != ("device", "backward")]
    p = proc(one_op)
    upd = max(a for n, a, _, _ in p["spans"] if n == UPD)
    end = max(b for n, _, b, _ in p["spans"] if n == FWD and b <= upd)
    assert gap({"procs": [p]}) == pytest.approx((upd - end) / 1e9)
    values = run.layer_values(only(MANAGED[0], "trainer.backward_device_s"),
                              {"procs": [p]})
    assert values == {"trainer.backward_device_s": None}


# ------------------------------------------------- the parent, the manifest
@pytest.mark.parametrize("name", MANAGED)
def test_a_program_without_the_spans_leaves_all_four_out(name):
    """The parent commit's ring (``ring25``: PR 27's recording) has no
    ``device/*`` span; its SUMMARY prints ``trace_dropped`` already."""
    obs = {"procs": [proc(ring("ring25"))],
           "summaries": {0: [read(os.path.join(DATA, "ring.summary.json"))]}}
    values = run.layer_values(only(name, *NEW), obs)
    assert values == {**dict.fromkeys(NEW[:4]), "manager.trace_dropped": 0.0}
    assert run.layer_values(only(name, *NEW), {}) == dict.fromkeys(NEW)


@pytest.mark.parametrize("name", MANAGED)
def test_trace_dropped_is_group_0s_last_summary(name):
    s = read(os.path.join(DATA, "ring37.summary.json"))
    assert s["timings"]["trace_dropped"] == 0.0
    lost = copy.deepcopy(s)
    lost["timings"]["trace_dropped"] = 2204.0
    obs = {"summaries": {0: [s, lost], 1: [s]}}
    assert run.layer_values(only(name, "manager.trace_dropped"), obs) == {
        "manager.trace_dropped": 2204.0}


def test_the_five_are_appended_entries_of_the_three_managed_cells():
    bench = manifest.load(ROOT)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-5:] == NEW
    like = {"trainer.forward_device_s": "trainer.step_span_s",
            "trainer.backward_device_s": "trainer.step_span_s",
            "trainer.update_device_s": "trainer.step_span_s",
            "allreduce.exposed_s": "allreduce.grad_wait_s",
            "manager.trace_dropped": "manager.quorum_s"}
    for entry in bench["per_layer"][-5:]:
        assert entry["workloads"] == list(MANAGED)
        assert (entry["moves"], entry["better"]) == ("tok_s_chip", "lower")
        sibling = next(m for m in bench["per_layer"] if m["name"] == like[entry["name"]])
        assert entry["layer"] == sibling["layer"]  # the layer's name as it stands
        counter = entry["name"] == "manager.trace_dropped"
        assert entry["source"] == ("program_counter" if counter else "program_span")
        assert entry["unit"] == ("spans" if counter else "s")
    for w in bench["workloads"]:
        assert bool(only(w["name"], *NEW).per_layer) == (w["name"] in MANAGED)
    assert manifest.problems(ROOT) == []


def test_their_files_are_data_on_reducers_that_were_there_and_two_new():
    reducers = {}
    for name in NEW:
        spec = read(os.path.join(ROOT, "chipbench", "layer_metrics", name + ".json"))
        assert sorted(spec) == ["args", "reducer", "what"]
        reducers[name] = spec["reducer"]
        manifest.load_module(ROOT, "reducers", spec["reducer"])
    assert reducers == {**dict.fromkeys(TERMS, "device_span"),
                        "allreduce.exposed_s": "span_gap",
                        "manager.trace_dropped": "summary_counter"}
    for name, span in TERMS.items():
        args = read(os.path.join(ROOT, "chipbench", "layer_metrics",
                                 name + ".json"))["args"]
        assert args == {"name": span, "replica": 0}  # per step, median over steps
    # ``device_span`` is ``span``: the same numbers from the same arguments
    obs = {"procs": [proc(ring())]}
    by_span = manifest.load_module(ROOT, "reducers", "span")
    for metric, span in TERMS.items():
        assert run.layer_values(only(MANAGED[0], metric), obs)[metric] == \
            by_span.reduce(obs, only(MANAGED[0]), name=span, replica=0)
