"""Tokens that arrived in the traced window over its time."""


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window["traced"]
    n = sum(1 for times in run.window["arrivals"] for t in times if lo <= t <= hi)
    return n / (hi - lo) if n else None
