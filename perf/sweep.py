"""The one sweep that finds a serving cell's knee: the cell's own traffic at
other rates, one process a rate. Not part of any run of the benchmark; the
rate it finds is then a number in the traffic file.

    python perf/sweep.py --workload <name> --rate <requests/s> --seconds <s>
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf import run as run_lib  # noqa: E402
from perf.lib import device, manifest  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    args.trace, args.tiny = 0, False
    ctx = run_lib.Context(args, manifest.load(), run_lib.T0)
    ctx.traffic["rate_per_s"] = args.rate
    import jax

    device.require_chip(jax, ctx.cell["chips"])
    ctx.ready()
    run_lib.measure(ctx)
    steps = [s for s in ctx.record.series["engine_step"]
             if s[1] <= args.seconds and s[2] > 0]
    tokens = sum(len(a) for a in ctx.window["arrivals"])
    print(json.dumps({
        "rate_per_s": args.rate, "seconds": args.seconds,
        "requests": ctx.attempted, "failed": ctx.failed,
        **ctx.end_to_end,
        "drained_at_s": ctx.window["drained_at_s"],
        "tokens_per_s_to_drain": tokens / ctx.window["drained_at_s"],
        "occupancy": sum(s[2] for s in steps) / max(1, len(steps))
        / ctx.window["max_batch"],
        "late_submit_ms_max": ctx.window["late_submit_ms_max"],
        "sheds": ctx.record.meta.get("failure_counters"),
        "checks": {c["name"]: c["value"] for c in ctx.checks}}), flush=True)


if __name__ == "__main__":
    main()
