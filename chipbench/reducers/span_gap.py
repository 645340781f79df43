"""Seconds between two kinds of span that follow each other: for every span
named ``to`` in the traced window, its start less the end of the latest span
of ``frm`` that ended before it; the median over those. ``frm`` is a list in
order of preference: the first name that has spans in the window is the one
(a trainer that hands its gradients over in one allreduce has no
``device/backward``; its ``device/forward`` ends where the gradients do). A
``to`` span with nothing of ``frm`` before it (the window cut its step) is
left out. The gap is arithmetic on the spans and no span of its own, so the
idle-gap attribution (xplane.attribute) keeps giving a gap to the host work
that runs in it."""

from bisect import bisect_right
from statistics import median


def reduce(obs, cell, frm, to, replica=0):
    procs = [p for p in obs.get("procs", []) if p["replica"] == replica]
    if not procs:
        return None
    p = max(procs, key=lambda p: p["window"][1] - p["window"][0])
    t0, t1 = p["window"]
    inside = [s for s in p["spans"] if s[1] >= t0 and s[2] <= t1]
    ends = next((e for e in (sorted(s[2] for s in inside if s[0] == n)
                             for n in frm) if e), [])
    gaps = []
    for name, start, _end, _step in inside:
        before = bisect_right(ends, start) if name == to else 0
        if before:
            gaps.append((start - ends[before - 1]) / 1e9)
    return median(gaps) if gaps else None
