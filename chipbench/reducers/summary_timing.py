"""One key of ``Manager.timings()`` as a replica group's last SUMMARY line
printed it (``obs["summaries"]``): what the program timed of events that lie
outside the traced window (process start, the heal, the first step's
compiles). ``group``: a group's number, or "rejoiner" for the group whose
last process is the replacement the launcher started
(``obs["phases"]["new_pid"]``). None where the program prints no such key."""


def reduce(obs, cell, key, group=0):
    sums = obs.get("summaries") or {}
    if group == "rejoiner":
        pid = obs.get("phases", {}).get("new_pid")
        last = next((s[-1] for s in sums.values()
                     if s and pid is not None and s[-1]["pid"] == pid), None)
    else:
        last = (sums.get(group) or [None])[-1]
    return None if last is None else last.get("timings", {}).get(key)
