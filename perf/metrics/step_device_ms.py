"""Device busy time per step in the traced window, busiest device."""


def read(run):
    if run.trace is None or not run.window.get("steps"):
        return None
    busy = max(run.trace.busy_s(d) for d in run.trace.devices)
    return busy / run.window["steps"] * 1e3
