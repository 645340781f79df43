"""Host spans of one traced process (the worker's stamps of the trainer's
loop, the Manager's span ring), by exact name, inside the traced window.
``stat``: "per_step" sums a name's spans within each step (buckets of one
allreduce) and takes the median over steps; "median" and "max" are over the
single spans."""

from statistics import median


def reduce(obs, cell, name, stat="per_step", replica=0):
    procs = [p for p in obs.get("procs", []) if p["replica"] == replica]
    if not procs:
        return None
    p = max(procs, key=lambda p: p["window"][1] - p["window"][0])
    t0, t1 = p["window"]
    got = [(s[3], (s[2] - s[1]) / 1e9) for s in p["spans"]
           if s[0] == name and s[1] >= t0 and s[2] <= t1]
    if not got:
        return None
    if stat == "per_step":
        by_step = {}
        for step, d in got:
            by_step[step] = by_step.get(step, 0.0) + d
        vals = list(by_step.values())
    else:
        vals = [d for _, d in got]
    return max(vals) if stat == "max" else median(vals)
