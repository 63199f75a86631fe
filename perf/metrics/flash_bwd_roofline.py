"""Causal attention's backward, as flash_fwd_roofline."""

from perf.lib import manifest

EVENTS = r"^transpose_jvp_jit__flash_bwd__"


def read(run):
    if run.trace is None:
        return None
    seconds, events = run.trace.op_seconds(EVENTS)
    if not events:
        return None
    fwd = manifest.load_module(manifest.PERF / "metrics" / "flash_fwd_roofline.py")
    least, run.notes["flash_bwd_bound"] = fwd.least_seconds(run, "bwd")
    return 100.0 * least / seconds
