"""One counter or share among ``Manager.timings()`` as a replica group's last
SUMMARY line printed it (``obs["summaries"]``, key ``timings``): what the
program counted, not what it timed (those are ``summary_timing``'s, which
also knows the rejoiner). None where the program prints no such key (the
parent commit of the PR that added the counter)."""


def reduce(obs, cell, key, group=0):
    last = ((obs.get("summaries") or {}).get(group) or [None])[-1]
    return ((last or {}).get("timings") or {}).get(key)
