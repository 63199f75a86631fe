"""1 - union of the device's operation intervals over the traced window,
worst device."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.worst_idle_share()
