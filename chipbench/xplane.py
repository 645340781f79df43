"""From a profiler trace (``.xplane.pb``) to numbers: device busy time, time
per device operation, and the idle gaps attributed to what the host was
doing. Read with ``jax.profiler.ProfileData`` and nothing else; checked on a
small recorded trace in tests/chipbench/.

A trace's clock starts at 0 when the profiler starts. The worker writes one
``chipbench.anchor`` annotation at a known epoch time, so host stamps taken
with ``time.time_ns()`` (the worker's, the Manager's span ring) can be put on
the trace's clock: ``trace_ns = epoch_ns - anchor_epoch_ns + anchor_trace_ns``.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ANCHOR = "chipbench.anchor"
MIN_GAP_NS = 50_000  # shorter gaps are the device's own launch latency


def find(trace_dir: str) -> "str | None":
    got = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return got[-1] if got else None


def read(path: str) -> dict:
    """{"devices": {id: [(name, start_ns, end_ns)]}, "anchor_ns": float|None,
    "annotations": [(name, start_ns, end_ns)]} of one trace file."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "anchor_ns": None, "annotations": []}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out["devices"][int(m[1])] = sorted(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        out["anchor_ns"] = e.start_ns
                    elif not e.name.startswith(("$", "Thunk", "Threadpool")):
                        out["annotations"].append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[4,8]{1,0} fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ")[0].split(":")[0].strip().lstrip("%")


def busy_intervals(events) -> "list[tuple[float, float]]":
    """Union of the events' intervals (events sorted by start)."""
    out = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def self_times(events) -> "dict[str, float]":
    """Seconds per operation name, a parent's time less its children's (a
    ``while`` over the layers contains the layer's fusions)."""
    total: "dict[str, float]" = {}
    stack = []  # [name, start, end, child_ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, a, b, child = stack.pop()
            total[name] = total.get(name, 0.0) + (b - a - child) / 1e9
            if stack:
                stack[-1][3] += b - a

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        close(a)
        stack.append([op_name(name), a, b, 0.0])
    close(float("inf"))
    return total


def gaps(busy, t0: float, t1: float) -> "list[tuple[float, float]]":
    """Idle intervals of the window [t0, t1] given the busy union."""
    out, at = [], t0
    for a, b in busy:
        if b <= t0 or a >= t1:
            continue
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if at < t1:
        out.append((at, t1))
    return out


def attribute(idle, spans, min_gap_ns: float = MIN_GAP_NS) -> "dict[str, float]":
    """Seconds of idle time per host span name. A slice of a gap goes to the
    covering span that started last (the innermost); slices under no span go
    to ``(no host span)``, gaps shorter than ``min_gap_ns`` to
    ``(between ops)``. ``spans``: [(name, start_ns, end_ns)], same clock."""
    out: "dict[str, float]" = {}

    def add(name, ns):
        if ns > 0:
            out[name] = out.get(name, 0.0) + ns / 1e9

    spans = sorted(spans, key=lambda s: s[1])
    for a, b in idle:
        if b - a < min_gap_ns:
            add("(between ops)", b - a)
            continue
        over = [s for s in spans if s[1] < b and s[2] > a]
        cuts = sorted({a, b, *(min(max(t, a), b) for s in over for t in s[1:])})
        for x, y in zip(cuts, cuts[1:]):
            cover = [s for s in over if s[1] <= x and s[2] >= y]
            add(max(cover, key=lambda s: (s[1], -s[2]))[0] if cover
                else "(no host span)", y - x)
    return out


def reduce(trace: dict, spans, window=None) -> dict:
    """One process's trace -> busy_s, window_s, per-op seconds, idle seconds
    per host span. ``spans`` on the trace's clock; ``window`` (t0, t1) on it
    too, default: first to last device event."""
    per_dev = []
    for dev, events in sorted(trace["devices"].items()):
        if not events:
            continue
        t0, t1 = window or (events[0][1], max(e[2] for e in events))
        inside = [(n, max(a, t0), min(b, t1)) for n, a, b in events
                  if b > t0 and a < t1]
        busy = busy_intervals(inside)
        per_dev.append({
            "device": dev, "window_s": (t1 - t0) / 1e9,
            "busy_s": sum(b - a for a, b in busy) / 1e9,
            "ops": self_times(inside),
            "idle": attribute(gaps(busy, t0, t1), spans),
            "n_events": len(inside),
        })
    return {"devices": per_dev}


def merge(reduced: "list[dict]") -> dict:
    """Several processes' reductions -> what the result line carries: busy
    and window averaged over the chips, op and gap seconds summed."""
    devs = [d for r in reduced for d in r["devices"]]
    if not devs:
        raise ValueError("no device plane with an 'XLA Ops' line in any trace")
    ops: "dict[str, float]" = {}
    idle: "dict[str, float]" = {}
    for d in devs:
        for k, v in d["ops"].items():
            ops[k] = ops.get(k, 0.0) + v
        for k, v in d["idle"].items():
            idle[k] = idle.get(k, 0.0) + v
    top = lambda m: sorted(m.items(), key=lambda kv: -kv[1])  # noqa: E731
    return {"busy_s": sum(d["busy_s"] for d in devs) / len(devs),
            "window_s": sum(d["window_s"] for d in devs) / len(devs),
            "chips_traced": len(devs), "ops": ops,
            "device_ops": top(ops), "idle_gaps": top(idle)}


def main(argv) -> None:
    """``python3 -m chipbench.xplane <file.xplane.pb>``: what is in a trace
    (planes, lines, first events) — look at one by hand before trusting the
    reduction."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(argv[0]).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:int(argv[1]) if len(argv) > 1 else 5]:
                print(f"    {e.start_ns:14.0f} {e.duration_ns:12.0f} {e.name[:160]}")


if __name__ == "__main__":
    import sys

    main(sys.argv[1:])
