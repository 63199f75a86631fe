"""The attention kernels' time over the device's busy time."""

EVENTS = r"^(jvp_jit__flash_fwd__|transpose_jvp_jit__flash_bwd__)"


def read(run):
    if run.trace is None:
        return None
    seconds, events = run.trace.op_seconds(EVENTS)
    if not events:
        return None
    return 100.0 * seconds / run.trace.busy_s(run.trace.devices[0])
