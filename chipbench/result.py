"""The last line of stdout: exactly the contract's keys."""

import json
import math


def build(cell, obs: dict, values: dict, trace: bool) -> dict:
    """``values``: metric name -> number or None (None: nothing to read,
    the metric is left out). ``obs``: what the job saw (see jobs/)."""
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        v = values.get(m["name"])
        if v is None:
            continue
        v = float(v)
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} is {v}")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = obs["device"]
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        raise RuntimeError(
            f"{cell.name} needs {cell.chips} TPU chip(s); JAX reported {dev}: "
            "no result is printed for a run off the chip")
    device = {"platform": str(dev["platform"]), "kind": str(dev["kind"]),
              "count": int(cell.chips),
              "memory_peak_bytes": int(obs["memory_peak_bytes"])}
    line = {"correct": bool(obs["correct"]), "attempted": int(obs["attempted"]),
            "failed": int(obs["failed"]), "metrics": metrics, "device": device}
    if trace:
        t = obs["trace"]
        device["busy_s"] = float(t["busy_s"])
        device["window_s"] = float(t["window_s"])
        line["breakdown"] = {
            "device_ops": [[n, float(s)] for n, s in t["device_ops"][:10]],
            "idle_gaps": [[n, float(s)] for n, s in t["idle_gaps"][:10]],
        }
    return line


def dumps(line: dict) -> str:
    return json.dumps(line)
