"""The step record: one small file per run under perf/out/, so that a slow
run can be read afterwards (uniformly slower steps, or a few long ones, and
in which host span). None of it goes into the result line."""

import json
import pathlib
import time

from perf.lib import manifest


class Record:
    def __init__(self, workload, seed, trace):
        self.meta = {"workload": workload, "seed": seed, "trace": trace,
                     "wall_start": time.time()}
        self.series = {}

    def add(self, series, *values):
        self.series.setdefault(series, []).append(values)

    def note(self, **kw):
        self.meta.update(kw)

    def write(self, out_dir=None):
        out = pathlib.Path(out_dir or manifest.PERF / "out")
        out.mkdir(parents=True, exist_ok=True)
        stem = (f"{self.meta['workload']}.seed{self.meta['seed']}"
                f".trace{self.meta['trace']}")
        n = 0
        while (out / f"{stem}.{n}.json").exists():
            n += 1
        path = out / f"{stem}.{n}.json"
        with open(path, "w") as f:
            json.dump({"meta": self.meta, "series": self.series}, f)
        return path


def _spread(values_ms):
    import statistics

    vals = sorted(values_ms)
    return {"median": statistics.median(vals), "min": vals[0], "max": vals[-1]}


def summarize(record):
    """What a reader of a slow run looks at first, from one step record (the
    dict the file holds). Training: milliseconds a step over the whole
    window, the host's wait in ``next(feed)`` and in ``trainer.step``, the
    intervals between loss read-backs (the device's pace, a step at a time)
    and the blocked warm-up steps. Uniformly longer intervals are another
    steady state; a few long ones are a stall, and the column says in which
    host span. Serving: the engine's steps and how late submissions ran."""
    meta, series = record["meta"], record["series"]
    out = {"workload": meta["workload"], "seed": meta["seed"],
           "setup_s": meta.get("setup_s"), "correct": meta.get("correct"),
           "setup_phases": {p[0]: round(p[1], 2)
                            for p in series.get("setup_phase", [])}}
    steps = series.get("step")
    if steps:
        back = [s[3] for s in steps if len(s) > 3]
        every = max(1, round(len(steps) / max(1, len(back))))
        out.update(
            steps=meta["steps"],
            step_ms=meta["window_s"] / meta["steps"] * 1e3,
            feed_wait_ms=_spread([(s[1] - s[0]) * 1e3 for s in steps]),
            dispatch_ms=_spread([(s[2] - s[1]) * 1e3 for s in steps]),
            warmup_ms=[round(w[0] * 1e3, 2) for w in series["warmup_step_s"]])
        if len(back) > 1:
            out["read_back_interval_ms_per_step"] = _spread(
                [(b - a) / every * 1e3 for a, b in zip(back, back[1:])])
    engine = series.get("engine_step")
    if engine:
        out.update(
            engine_steps=len(engine),
            engine_step_ms=_spread([(s[1] - s[0]) * 1e3 for s in engine]),
            late_submit_ms=_spread([(s[2] - s[1]) * 1e3
                                    for s in series["submit"]]),
            drained_at_s=meta.get("drained_at_s"))
    return out


if __name__ == "__main__":
    import sys

    for path in sys.argv[1:]:
        with open(path) as f:
            print(json.dumps(summarize(json.load(f))))
