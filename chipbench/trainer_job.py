"""What the job kinds that drive the managed trainer share: the command
line, the per-checkout calibration of the step time, and the checks on what
the program shows of itself (its SUMMARY line)."""

import json
import math
import os

from chipbench import hostspans
from chipbench.launch import Failed


def worker_args(cell, steps: int, trace_dir: "str | None") -> "list[str]":
    r, tr = cell.config["recipe"], cell.traffic
    out = ["--chipbench-config", cell.config_path]
    if trace_dir:
        out += ["--chipbench-trace", trace_dir]
    return out + ["--batch-size", str(r["batch_size"]),
                  "--seq-len", str(r["seq_len"]), "--lr", str(r["lr"]),
                  "--steps", str(steps), "--timeout", str(tr["timeout_s"]),
                  *tr.get("trainer_args", [])]


def launcher_args(cell) -> "list[str]":
    tr = cell.traffic
    out = ["--replica-groups", str(tr["groups"])]
    if tr["chips_per_group"]:
        out += ["--chips-per-group", str(tr["chips_per_group"])]
    if "min_replicas" in tr:
        out += ["--min-replicas", str(tr["min_replicas"])]
    if tr.get("max_restarts"):
        out += ["--max-restarts", str(tr["max_restarts"])]
    return out


class Calibration:
    """Step times this checkout's earlier runs of the cell saw.
    The trainer takes ``--steps`` when it starts, before any step has been
    timed, so the number of steps that fill ``--seconds`` comes from the
    run before. ``data`` is empty in a checkout's first run of a cell: the
    job kind then sizes the run without a step time (jobs/*.py say how) and
    nothing is guessed per configuration."""

    def __init__(self, cell, cache_dir: str) -> None:
        self.path = os.path.join(cache_dir, f"calibration_{cell.name}.json")
        self.data = {}
        if os.path.exists(self.path):
            with open(self.path) as f:
                self.data = json.load(f)

    def save(self, **seen) -> None:
        self.data.update({k: v for k, v in seen.items() if v})
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        with open(self.path + ".tmp", "w") as f:
            json.dump(self.data, f)
        os.replace(self.path + ".tmp", self.path)


def check_summary(s: dict, cell, steps: int) -> "list[str]":
    """What must hold for a replica that ran to the end; the list of what
    does not."""
    bad = []
    vocab, band = cell.config["vocab_size"], cell.traffic["loss_band"]
    if s["device"]["platform"] != "tpu":
        bad.append(f"trainer ran on {s['device']}")
    if s["step"] < steps:
        bad.append(f"stopped at step {s['step']} of {steps}")
    if not s["losses"] or not all(math.isfinite(x) for x in s["losses"]):
        bad.append("loss not finite")
    # random tokens: the loss starts at ln(vocab) whatever the arithmetic
    # does; a first loss outside the band is a broken model, not training
    elif abs(s["losses"][0] - math.log(vocab)) > band:
        bad.append(f"first loss {s['losses'][0]} not within {band} of ln({vocab})")
    if s["attention"] != cell.config["recipe"]["attention"]:
        bad.append(f"attention dispatched to {s['attention']!r}")
    if not (s["state_on_device"] and s["reduced_on_device"]):
        bad.append("state or reduced gradients left the TPU")
    return bad


def device_of(summaries: dict, groups: int) -> dict:
    """The device as the trainers' JAX reported it, one chip per group;
    every group must have printed its SUMMARY."""
    if sorted(summaries) != list(range(groups)):
        raise Failed(f"SUMMARY from {sorted(summaries)}, expected {groups} groups")
    s = summaries[0][-1]["device"]
    return {"platform": s["platform"], "kind": s["kind"], "count": groups}


def with_trace(obs: dict, trace_dir: "str | None") -> dict:
    """``obs`` plus, in a traced run, what the workers left behind."""
    obs["procs"] = hostspans.collect(trace_dir) if trace_dir else []
    if trace_dir:
        obs["trace"] = hostspans.reduce(obs["procs"])
    return obs
