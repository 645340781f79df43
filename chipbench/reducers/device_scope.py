"""Seconds per step of the device operations that belong to a
``jax.named_scope`` of the program (self time, summed over the traced chips'
ops, divided by chips x steps). A device trace names instructions, not
scopes; the job hands out, under ``obs["scopes"]``, the compiled step's own
map from each traced instruction to its ``op_name`` (the scope path the
program gave it, backward and rematerialised copies included). An operation
counts where its ``op_name`` matches ``scope``, or its own name matches
``also`` (a kernel the profiler names by itself), and not where its own name
matches ``without``. A job that hands out no map, or a program that has no
such scope, leaves nothing to read."""

import re


def reduce(obs, cell, scope, also=None, without=None):
    t, n, scopes = obs.get("trace"), obs.get("steps_in_window"), obs.get("scopes")
    if not t or not n or not scopes:
        return None
    inside = re.compile(scope)
    extra, out = (re.compile(p) if p else None for p in (also, without))
    hit = [s for name, s in t["ops"].items()
           if not (out and out.search(name))
           and (inside.search(scopes.get(name, "")) or (extra and extra.search(name)))]
    if not hit:
        return None
    return sum(hit) / t["chips_traced"] / n
