"""Host time blocked in next(feed), per step of the window (the benchmark's
own stamps round the call)."""


def read(run):
    if not run.window.get("steps"):
        return None
    return run.window["feed_wait_s"] / run.window["steps"] * 1e3
