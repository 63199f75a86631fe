"""In-flight sequences per decode step over max_batch, over the steps of
the window (the benchmark's loop counts them)."""


def read(run):
    steps = [s for s in run.record.series.get("engine_step", [])
             if s[1] <= run.window["seconds"] and s[2] > 0]
    if not steps:
        return None
    return 100.0 * sum(s[2] for s in steps) / len(steps) / run.window["max_batch"]
