"""Seconds per step that buckets waited between two stages of the bucket
pipeline. Both stages run FIFO on a single worker each, so within a step the
k-th span named ``frm`` and the k-th span of ``to`` belong to the same
bucket: the wait is the start of the second less the end of the first,
summed over the buckets of a step, median over steps. ``to`` is a list in
order of preference: the first name that has spans in the step is the
stage (the unpack worker starts with ``decode`` only where a bucket rode
the wire compressed, else with ``h2d``, and before PR 25 with ``divide``:
the layer-metric file's list is in that order). A step the window cut (the two
counts differ) is left out. A wait is arithmetic on the work's spans and
not a span of its own, so the idle-gap attribution (xplane.attribute) keeps
giving a gap to the work that causes it."""

from statistics import median


def reduce(obs, cell, frm, to, replica=0):
    procs = [p for p in obs.get("procs", []) if p["replica"] == replica]
    if not procs:
        return None
    p = max(procs, key=lambda p: p["window"][1] - p["window"][0])
    t0, t1 = p["window"]
    by_step = {}
    for name, a, b, step in p["spans"]:
        if a >= t0 and b <= t1 and (name == frm or name in to):
            by_step.setdefault(step, {}).setdefault(name, []).append((a, b))
    vals = []
    for names in by_step.values():
        first = sorted(names.get(frm, []))
        then = next((sorted(names[n]) for n in to if n in names), [])
        if first and len(first) == len(then):
            vals.append(sum(b[0] - a[1] for a, b in zip(first, then)) / 1e9)
    return median(vals) if vals else None
