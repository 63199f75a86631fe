"""The least time to read the weights once and the live keys and values of
the in-flight sequences, step by step, over the decode program's device time
in the traced window. The bytes come from the sequences' real lengths, not
from what the program gathers."""

from perf.lib import counts

PROGRAM = r"decode_body"


def read(run):
    if run.trace is None:
        return None
    seconds, programs = run.trace.module_seconds(PROGRAM)
    lo, hi = run.window["traced"]
    steps = [s for s in run.record.series.get("engine_step", [])
             if s[2] > 0 and lo <= s[0] and s[1] <= hi]
    if not programs or not steps:
        return None
    bw = run.peaks()["hbm_bytes_per_s"]
    least = sum(counts.gpt2_decode_step_bytes(run.config, [s[4]]) / bw
                for s in steps)
    run.notes["decode_steps_traced"] = [len(steps), programs]
    return 100.0 * least / seconds
