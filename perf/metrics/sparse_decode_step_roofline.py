"""The least time to read the dense weights and the head once, the experts
that got a token, the live index keys and the selected rows of K and V,
step by step, over the decode program's device time in the traced window.
Contexts, selected rows and experts hit are the program's counters; the
bytes are what the mathematics reads, not what the program gathers."""

from perf.lib import counts_keye as counts, keye_spans


def read(run):
    if run.trace is None:
        return None
    steps = keye_spans.decodes(run)
    seconds, programs = run.trace.module_seconds(keye_spans.DECODE_PROGRAM)
    if not steps or not programs:
        return None
    bw = run.peaks()["hbm_bytes_per_s"]
    least = sum(counts.decode_step_bytes(
        run.config, d["inflight"], d["ctx_tokens"], d["selected_tokens"],
        d["experts_hit"]) / bw for d in steps)
    run.notes["sparse_decode_steps_traced"] = [len(steps), programs]
    return 100.0 * least / seconds
