"""One traced run of a cell, its device time by the part of the model that
each operation computes: the ``mlsl_*`` scopes the programs write into their
operations (``perf/lib/scopes.py``).

    python perf/scope_table.py --workload <cell> --seed <n> [--seconds <s>]
                               [--tiny]

The run is ``perf/run.py --trace 1``'s own, result line and all. Before its
profile is deleted, the scope path of every device operation is read from it
and put beside the trace; then come a table of the twelve largest (phase,
scope) entries, each with its largest op family, on standard error, the
same table for each program (XLA module) that ran, the ten largest op
families with the entries that hold each, and the readings of the
scope readers under ``perf/metrics/``, as one JSON line on standard output
(after the run's own) and in ``perf/out/<cell>.seed<n>.scopes.json``. The
benchmark's own runs never run this: ``perf/lib/trace.py`` does not keep
the paths (PERF.md section 7).
"""

import argparse
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf import run as run_lib  # noqa: E402
from perf.lib import keye_spans, manifest, scopes, trace as trace_lib  # noqa: E402

READERS = ("step_backward_share", "step_update_share", "head_share_of_step",
           "norm_share_of_step", "prefill_expert_share", "device_scoped_share",
           "serve_device_scoped_share")


def reading_paths(held):
    """-> the ``prepare`` hook that reads the scope paths from the profile
    as soon as the profiler has stopped, into ``held``."""
    def prepare(ctx):
        held["ctx"] = ctx
        stop = ctx.tracer.stop

        def stop_and_read():
            stop()
            t = time.perf_counter()
            held["paths"] = scopes.op_paths(trace_lib.find_xplane(ctx.trace_dir))
            held["read_s"] = time.perf_counter() - t
        ctx.tracer.stop = stop_and_read
    return prepare


def by_program(ctx, n=12):
    """The scope table inside the runs of each program of the traced window."""
    out = {}
    for module in ctx.trace.module_names():
        runs = keye_spans.program_runs(ctx, "^" + re.escape(module))
        rows = scopes.table(scopes.by_scope(ctx.trace, within=runs), n)
        if rows:
            out[module] = {"runs": len(runs), "table": rows}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    argv = ["--workload", args.workload, "--seed", str(args.seed), "--trace", "1"]
    if args.seconds is not None:
        argv += ["--seconds", str(args.seconds)]
    held = {}
    run_lib.main(argv + (["--tiny"] if args.tiny else []),
                 prepare=reading_paths(held))
    ctx = held["ctx"]
    out = {"workload": args.workload, "seed": args.seed,
           "read_s": held.get("read_s")}
    if ctx.trace is not None:
        out["attached"] = scopes.attach(ctx.trace.data, held.get("paths", {}))
        parts = scopes.by_scope(ctx.trace)
        out["table"] = scopes.table(parts)
        out["families"] = scopes.family_table(parts)
        out["programs"] = by_program(ctx)
        out["metrics"] = {name: manifest.metric_reader(name)(ctx)
                          for name in READERS}
        print(scopes.format_table(out["table"]), file=sys.stderr)
    path = manifest.PERF / "out" / f"{args.workload}.seed{args.seed}.scopes.json"
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
