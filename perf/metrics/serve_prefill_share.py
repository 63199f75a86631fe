"""Device time inside the prefill and KV-write programs over the device's
busy time, traced window."""

PROGRAMS = r"prefill_body|write_body"


def read(run):
    if run.trace is None:
        return None
    seconds, programs = run.trace.module_seconds(PROGRAMS)
    if not programs:
        return None
    return 100.0 * seconds / run.trace.busy_s(run.trace.devices[0])
