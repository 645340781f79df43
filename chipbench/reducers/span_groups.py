"""A host span's seconds per step across the cell's replica groups: what no
group can see by itself. In every traced process the spans named ``name``
inside the traced window are summed within each step (the runs of one
allreduce), as ``span`` does for one group; a group's value of a step is
that of its process with the longest window among those that have the step
(a rejoiner's group has two); ``over`` ("max" or "min") is taken across the
groups that have the step, and the median over the steps. None where no
process has such a span (the parent commit's ring; a cell of one group
whose program records none)."""

from statistics import median


def reduce(obs, cell, name, over="max"):
    pick = {"max": max, "min": min}[over]
    by_step = {}  # step -> group -> (window length, seconds)
    for p in obs.get("procs", []):
        t0, t1 = p["window"]
        sums = {}
        for n, a, b, step in p["spans"]:
            if n == name and a >= t0 and b <= t1:
                sums[step] = sums.get(step, 0.0) + (b - a) / 1e9
        for step, seconds in sums.items():
            groups = by_step.setdefault(step, {})
            groups[p["replica"]] = max(
                groups.get(p["replica"], (-1, 0.0)), (t1 - t0, seconds))
    if not by_step:
        return None
    return median(pick(seconds for _, seconds in groups.values())
                  for groups in by_step.values())
