"""Causal attention's forward: the least time the chip could take for its
operations and bytes at the cell's shapes, over the forward kernel's time in
the trace."""

from perf.lib import counts

EVENTS = r"^jvp_jit__flash_fwd__"


def least_seconds(run, which):
    c, t = run.config, run.traffic
    fn = (counts.causal_attention_forward if which == "fwd"
          else counts.causal_attention_backward)
    ops, moved = fn(t["batch"], c["n_head"], c["n_positions"], c["head_dim"])
    per_layer, bound = counts.roofline_seconds(ops, moved, run.peaks())
    return per_layer * c["n_layer"] * run.window["steps"], bound


def read(run):
    if run.trace is None:
        return None
    seconds, events = run.trace.op_seconds(EVENTS)
    if not events:
        return None
    least, run.notes["flash_fwd_bound"] = least_seconds(run, "fwd")
    return 100.0 * least / seconds
