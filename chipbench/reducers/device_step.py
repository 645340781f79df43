"""Device busy seconds per step: the union of the intervals in which an
operation ran on the chip, over the steps of the traced window."""


def reduce(obs, cell):
    t, n = obs.get("trace"), obs.get("steps_in_window")
    return t["busy_s"] / n if t and n else None
