"""Median ``iter_s`` of a replica's committed-step lines after the warm-up
step, optionally only those with ``participants`` groups in them."""

from statistics import median


def reduce(obs, cell, replica=0, participants=None, skip=1):
    vals = [s[4] for s in obs["steps"].get(replica, [])[skip:]
            if participants is None or s[3] == participants]
    return median(vals) if vals else None
