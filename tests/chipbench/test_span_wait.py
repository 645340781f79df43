"""The ``span_wait`` reducer: exact arithmetic on hand-made spans, and on a
recorded span ring (tests/chipbench/record_ring.py) that pairing the k-th
span of one stage with the k-th of the next, as the reducer must (the
benchmark's span tuples carry no bucket number), pairs the same buckets as
the ring's own ``bucket`` args do."""

import os
from statistics import median

import pytest

from chipbench_helpers import DATA, ROOT, read

from chipbench import manifest, run

S = 1_000_000_000
D, W, DEC, DIV, H = ("manager.allreduce." + n for n in
                     ("dispatch", "wire_run", "decode", "divide", "h2d"))


def cell(name="mistral-7b.managed-1g"):
    return manifest.Cell(ROOT, manifest.load(ROOT), name)


def reduce(obs, **args):
    return manifest.load_module(ROOT, "reducers", "span_wait").reduce(
        obs, cell(), **args)


def ring_proc(ring="ring"):
    """The recorded ring as hostspans.collect names it, window = all of it."""
    spans = [(f"manager.{s['cat']}.{s['name']}", s["ts_us"] * 1000,
              (s["ts_us"] + s["dur_us"]) * 1000, s["step"])
             for s in read(os.path.join(DATA, f"{ring}.spans.json"))["spans"]]
    return {"replica": 0, "pid": 1, "spans": spans,
            "window": (min(s[1] for s in spans), max(s[2] for s in spans))}


def test_waits_are_paired_in_order_and_summed_per_step():
    spans = [
        # step 7: two buckets, waits 1 s and 3 s
        (D, 0, 1 * S, 7), (D, 1 * S, 2 * S, 7),
        (W, 2 * S, 3 * S, 7), (W, 5 * S, 6 * S, 7),
        # step 8: one bucket, wait 2 s
        (D, 10 * S, 11 * S, 8), (W, 13 * S, 14 * S, 8),
        # step 9: the window cut it (a dispatch without its wire_run): left out
        (D, 20 * S, 21 * S, 9), (D, 21 * S, 22 * S, 9), (W, 23 * S, 24 * S, 9),
        (D, -5 * S, -4 * S, 6), (W, -3 * S, -2 * S, 6),    # before the window
    ]
    obs = {"procs": [{"replica": 0, "pid": 1, "window": (0, 30 * S), "spans": spans}]}
    assert reduce(obs, frm=D, to=[W]) == pytest.approx(median([4.0, 2.0]))
    assert reduce(obs, frm=D, to=[W], replica=1) is None
    assert reduce({}, frm=D, to=[W]) is None
    assert reduce(obs, frm=W, to=[DEC, DIV, H]) is None     # no such spans at all


def test_the_first_name_with_spans_in_the_step_is_the_next_stage():
    spans = [(W, 0, 1 * S, 3), (DIV, 2 * S, 3 * S, 3), (H, 3 * S, 4 * S, 3),
             # a compressed step: decode comes first, divide is not the start
             (W, 10 * S, 11 * S, 4), (DEC, 11 * S + S // 2, 12 * S, 4),
             (DIV, 12 * S, 13 * S, 4), (H, 13 * S, 14 * S, 4)]
    obs = {"procs": [{"replica": 0, "pid": 1, "window": (0, 20 * S), "spans": spans}]}
    assert reduce(obs, frm=W, to=[DEC, DIV, H]) == pytest.approx(median([1.0, 0.5]))


def test_order_pairing_is_bucket_pairing_on_a_recorded_ring():
    ring = read(os.path.join(DATA, "ring.spans.json"))
    assert ring["dropped"] == 0
    by = {}   # (step, name, bucket) -> (start_us, end_us)
    for s in ring["spans"]:
        if s["cat"] == "allreduce" and "bucket" in s.get("args", {}):
            by[s["step"], s["name"], s["args"]["bucket"]] = (
                s["ts_us"], s["ts_us"] + s["dur_us"])
    steps = sorted({k[0] for k in by})
    buckets = sorted({k[2] for k in by})
    assert len(steps) == 2 and len(buckets) == 8
    obs = {"procs": [ring_proc()]}
    for frm, to, names in (("dispatch", "wire_run", [W]),
                           ("wire_run", "divide", [DEC, DIV, H])):
        want = median(sum(by[st, to, b][0] - by[st, frm, b][1] for b in buckets) / 1e6
                      for st in steps)
        got = reduce(obs, frm="manager.allreduce." + frm, to=names)
        assert got == pytest.approx(want, abs=1e-9)
        # a wait, not noise around zero: FIFO workers start a bucket after
        # the stage before it ended (re-anchored clocks: to 50 us)
        assert all(by[st, to, b][0] - by[st, frm, b][1] > -50
                   for st in steps for b in buckets)


@pytest.mark.parametrize("metric", ["allreduce.unpack_wait_s",
                                    "allreduce.unpack_wait_4g_s"])
def test_the_files_list_pairs_wire_run_with_the_buckets_first_unpack_child(metric):
    """Since PR 25 the unpack worker lands a bucket (``h2d``) and then
    averages what landed (``divide``); before, it divided first. The
    layer-metric file's ``to`` list, in its order, finds the first child on
    the ring recorded since (ring25), so the metric is the wait alone; in
    the order it had before PR 27 it read the wait plus ``h2d``."""
    args = read(f"{ROOT}/chipbench/layer_metrics/{metric}.json")["args"]
    assert args["frm"] == W and args["to"] == [DEC, H, DIV]
    ring = read(os.path.join(DATA, "ring25.spans.json"))
    assert ring["dropped"] == 0
    kids, ran = {}, {}  # (step, bucket) -> the unpack children / wire_run's end
    for s in ring["spans"]:
        b = s.get("args", {}).get("bucket")
        if s["cat"] == "allreduce" and s["name"] in ("decode", "h2d", "divide"):
            kids.setdefault((s["step"], b), []).append((s["ts_us"], s["name"], s["dur_us"]))
        elif s["cat"] == "allreduce" and s["name"] == "wire_run":
            ran[s["step"], b] = s["ts_us"] + s["dur_us"]
    assert len(kids) == len(ran) == 16
    assert {min(k)[1] for k in kids.values()} == {"h2d"}   # lands, then averages
    steps = sorted({k[0] for k in kids})
    wait = median(sum(min(kids[k])[0] - ran[k] for k in kids if k[0] == st) / 1e6
                  for st in steps)
    obs = {"procs": [ring_proc("ring25")]}
    assert reduce(obs, **args) == pytest.approx(wait, abs=1e-9) and wait > 0
    # the order before: the second child, so the wait plus what h2d took
    # (and the little between the two spans)
    before = reduce(obs, frm=W, to=[DEC, DIV, H])
    h2d = median(sum(min(kids[k])[2] for k in kids if k[0] == st) / 1e6 for st in steps)
    assert before >= wait + h2d and before - wait < 1.5 * h2d
    # on PR 24's ring the old order was the right one; a program is read by
    # the list that fits it, and the benchmark reads the program that is there
    old = {"procs": [ring_proc("ring")]}
    assert reduce(old, frm=W, to=[DEC, DIV, H]) < reduce(old, **args)


@pytest.mark.parametrize("ring", ["ring", "ring25"])
@pytest.mark.parametrize("cell_name", ["mistral-7b.managed-1g",
                                       "internlm2-1.8b.kill-rejoin-4g"])
def test_every_new_span_metric_reads_the_recorded_ring(cell_name, ring):
    """Through run.layer_values, like a traced run: the metrics this ring can
    feed (reducers ``span`` and ``span_wait``) all come out as numbers."""
    c = cell(cell_name)
    obs = {"procs": [ring_proc(ring)], "summaries": {}, "phases": {}, "e2e": {},
           "steps": {}}
    mine = [m for m in c.per_layer
            if c.layer_metric(m["name"])["reducer"] in ("span", "span_wait")]
    c.per_layer = mine
    got = run.layer_values(c, obs)
    assert len(got) >= 10 and all(isinstance(v, float) for v in got.values()), got
    tail = "_4g_s" if cell_name.endswith("4g") else "_s"
    pack, parts = got["allreduce.pack" + tail], ("d2h", "capture", "wire_run", "h2d")
    assert all(0 < got[f"allreduce.{p}{tail}"] for p in parts)
    assert got["allreduce.d2h" + tail] < pack    # a child's sum inside its parent's
