"""One of the model's own per-step counters as a replica group's last SUMMARY
line printed them (``obs["summaries"]``, key ``model_stats``: name -> one
value per logged step), reduced over the steps (``stat``: "median", "max" or
"last"). None where the program prints no such counter (a dense model, or a
program from before the counters)."""

from statistics import median


def reduce(obs, cell, key, group=0, stat="median"):
    last = ((obs.get("summaries") or {}).get(group) or [None])[-1]
    values = ((last or {}).get("model_stats") or {}).get(key)
    if not values:
        return None
    return {"median": median, "max": max, "last": lambda v: v[-1]}[stat](values)
