"""Seconds per step of the device operations whose name matches ``pattern``
(self time, summed over the traced chips' ops, divided by chips x steps).
With ``roofline`` set it returns instead the kernel's share of its roofline
in percent: the least time the chip could take for the calls a step makes
over the time they took. ``roofline["kernel"]`` (absent: ``attention``) names
the kernel; its operations and bytes, and the layers that call it, are the
configuration's adapter's (chipbench/flops.py for ``llama``)."""

import re

from chipbench import flops


def reduce(obs, cell, pattern, roofline=None):
    t, n = obs.get("trace"), obs.get("steps_in_window")
    if not t or not n:
        return None
    rx = re.compile(pattern)
    hit = [v for k, v in t["ops"].items() if rx.search(k)]
    if not hit:
        return None
    per_step = sum(hit) / t["chips_traced"] / n
    if roofline is None:
        return per_step
    r, cfg, adapter = cell.config["recipe"], cell.config, cell.adapter()
    kernel = roofline.get("kernel", "attention")
    floor = 0.0
    for passes, calls in roofline["calls_per_layer"].items():
        cost = adapter.KERNEL_COSTS[kernel](cfg, r["batch_size"], r["seq_len"], passes)
        floor += calls * adapter.layers_with(cfg, kernel) * flops.roofline_floor_s(
            cost, obs["device"]["kind"])[0]
    return 100.0 * floor / per_step
