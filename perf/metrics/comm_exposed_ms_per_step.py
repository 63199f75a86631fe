"""Collective operations' device time during which nothing else runs on
that device, per step, worst device. Nothing to read on one chip."""


def read(run):
    if run.trace is None or run.cell["chips"] < 2 or not run.window.get("steps"):
        return None
    worst = max(run.trace.exposed_collective_s(d) for d in run.trace.devices)
    return worst / run.window["steps"] * 1e3
