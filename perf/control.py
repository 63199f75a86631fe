"""The control of "how correct is decided": the reference put in the
program's place, computed in the precision below the one the configuration
states (float8 operands for bfloat16), and the faults a training cell can
have planted in the reference. It has to come out as NOT correct. The
benchmark's own runs never run this; the builder runs it on the chip at the
cell's own size and tests/perf keeps it at a size a test can hold.

    python perf/control.py --workload <name> --seed <n> [--tiny]

One JSON line per reading on standard output: what was put in the program's
place, each number compared, and whether the limits fail it.
"""

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf import run as run_lib  # noqa: E402
from perf.lib import compare, manifest, train_cell  # noqa: E402


def as_program(out):
    return {"losses": out["losses"],
            "grad": dict(zip(out["paths"], out["grad_norms"])),
            "delta": dict(zip(out["paths"], out["delta_norms"]))}


def training(ctx, emit):
    """No program, no window: the reference in float32 against itself in
    the lower precision, and against itself with half of the batch left out
    (the mean taken over the rest)."""
    ref = manifest.reference(ctx.config)
    seed, config, traffic = ctx.args.seed, ctx.config, ctx.traffic
    batches = train_cell.first_batches(
        train_cell.host_batches(seed, config, traffic), 3)
    want = ref.train(seed, config, traffic, batches)
    shards = traffic.get("shards", 1)
    stands_in = {
        "control_" + config["control_precision"]:
            dict(precision=config["control_precision"]),
        "fault_half_batch": dict(keep_rows=traffic["batch"] // shards // 2),
        # no control: the reference in the precision the configuration
        # states, a witness of what rounding alone reads
        "stated_bf16": dict(precision="bf16"),
    }
    if shards > 1:
        stands_in["fault_no_exchange"] = dict(no_exchange=True)
    if ctx.args.only:
        stands_in = {k: v for k, v in stands_in.items() if k in ctx.args.only}
    readings = {}
    for name, kw in stands_in.items():
        got = ref.train(seed, config, traffic, batches, **kw)
        checks, notes = compare.training(as_program(got), want, ctx.limits)
        readings[name] = emit(name, checks, notes)
    return readings


def serving(ctx, emit):
    """A short window at the cell's own load; then, at each position of the
    prompts and tokens the program served, the gap of the token that the
    lower precision puts first."""
    ctx.control = ctx.config["control_precision"]
    run_lib.measure(ctx)
    emit("program", ctx.checks, {})
    return {"control_" + ctx.control: emit(
        "control_" + ctx.control,
        compare.serving(ctx.notes["control_gaps"], ctx.limits), {})}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)
    args.trace = 0
    man = manifest.load()
    ctx = run_lib.Context(args, man, run_lib.T0)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    if not args.tiny:
        from perf.lib import device

        device.require_chip(jax, ctx.cell["chips"])
    ctx.ready()

    def emit(name, checks, notes):
        line = {"workload": args.workload, "seed": args.seed, "in_place": name,
                "correct": compare.verdict(checks), "notes": notes,
                "checks": compare.as_pairs(checks)}
        print(json.dumps(line), flush=True)
        return line

    kind = ctx.traffic["kind"]
    return training(ctx, emit) if kind == "train" else serving(ctx, emit)


if __name__ == "__main__":
    main()
