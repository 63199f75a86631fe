"""The sweep that finds a serving cell's knee: the cell's own requests, in the
cell's own order, offered at another rate, one process a rate. Not part of
any run of the benchmark; the rate it finds is then a number in the traffic
file.

    python perf/sweep.py --workload <name> --rate <requests/s> [--seconds <s>]

Without ``--seconds`` the window is as long as the requests of one run of the
cell take at that rate. The order of a window's requests is drawn for their
number, so a window of another length is another arrangement, with heavy
stretches of its own, and the knee follows the heaviest (PERF.md section 6,
PR 28).
"""

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf import run as run_lib  # noqa: E402
from perf.lib import (device, manifest, program_spans,  # noqa: E402
                      traffic as traffic_lib)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args(argv)
    args.trace, args.tiny = 0, False
    man = manifest.load()
    ctx = run_lib.Context(args, man, run_lib.T0)
    if args.seconds is None:
        own = traffic_lib.request_count(ctx.traffic, man["run_seconds"])
        args.seconds = (own + 0.5) / args.rate
    ctx.traffic["rate_per_s"] = args.rate
    import jax

    device.require_chip(jax, ctx.cell["chips"])
    ctx.ready()
    run_lib.measure(ctx)
    ctx.record.write()
    steps = [s for s in ctx.record.series["engine_step"]
             if s[1] <= args.seconds and s[2] > 0]
    tokens = sum(len(a) for a in ctx.window["arrivals"])
    ring = ctx.record.meta.get("ring_summary") or {}
    held = [c["pages_held"] for c in program_spans.decode_counts(ctx)]
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
        "kv_pool_peak_pages": max(held, default=None),
        "gap_share_admitting": ring.get("gap_share_admitting"),
        "step_ms_by_admissions": {
            k: [v["n"], v.get("median_ms"), v.get("p95_ms")]
            for k, v in ring.get("steps_by_admissions", {}).items()},
        "checks": {c["name"]: c["value"] for c in ctx.checks}}), flush=True)


if __name__ == "__main__":
    main()
