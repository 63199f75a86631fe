"""Collective operations per step in the device trace (start/done pairs
count once)."""


def read(run):
    if run.trace is None or run.cell["chips"] < 2 or not run.window.get("steps"):
        return None
    dev = run.trace.devices[0]
    calls = [e for e in run.trace.collectives(dev) if "-done" not in e[0]]
    return len(calls) / run.window["steps"]
