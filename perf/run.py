"""One process, one cell, one run.

    python perf/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Fails without a TPU that holds the chips the cell asks for. The last line of
standard output is the result (correct, attempted, failed, metrics, device,
and with --trace 1 a breakdown), with the numbers that decided ``correct``,
each beside its limit, under ``checks``, which comes last.

``--tiny`` is the CPU dry run of the tests: toy sizes from
perf/configs/tiny/, no number under a metric's name.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf.lib import (compare, device, manifest, peaks, record as record_lib,  # noqa: E402
                      spans as spans_lib, trace as trace_lib)


class Context:
    """What one run knows; the cell runners fill it in."""

    def __init__(self, args, man, t0):
        self.args, self.manifest, self.t0 = args, man, t0
        self.cell = manifest.cell(man, args.workload)
        self.config = manifest.config_file(man, self.cell["config"])
        if args.tiny:
            self.config = {**self.config, **manifest.read_json(
                manifest.PERF / "configs" / "tiny" / f"{self.cell['config']}.json")}
        self.traffic = manifest.traffic_file(self.cell["traffic"])
        if args.tiny:
            self.traffic = {**self.traffic, **self.traffic.get("tiny", {})}
        limits = manifest.read_json(
            manifest.PERF / "limits" / f"{args.workload}.json")
        self.limits = limits["tiny" if args.tiny else "limits"]
        self.record = record_lib.Record(args.workload, args.seed, args.trace)
        self.spans = spans_lib.Spans(bool(args.trace))
        # one a process: two traced runs side by side (the tests' workers)
        # would delete each other's profile
        self.trace_dir = manifest.PERF / "out" / f"trace.{os.getpid()}"
        self.tracer = spans_lib.Tracer(self.trace_dir) if args.trace else None
        self.t_ready = t0        # ready() moves it to when the chip was up
        self.setup_s = None
        self.end_to_end, self.window, self.checks = {}, {}, []
        self.attempted = self.failed = 0
        self.device = None
        self.trace = None
        self.control = None      # perf/control.py sets the lower precision
        self.notes = {}          # what a reader wants said beside its number
        # the tests plant faults under the timed path through these
        self.wrap_trainer = self.wrap_engine = lambda x: x

    def phase(self, name):
        """Stamp the end of a set-up phase into the step record."""
        self.record.add("setup_phase", name, time.perf_counter() - self.t0)

    def ready(self):
        """JAX is imported and the chip is up: ``setup_s`` counts from here.
        What lies before is the interpreter, JAX's import and the TPU
        runtime's start, 10 to 15 s that drift with the machine's state and
        hold nothing of the program (PERF.md section 2); the step record
        keeps them as the phase ``chip_up``."""
        self.t_ready = time.perf_counter()
        self.phase("chip_up")

    def describe_device(self):
        import jax

        return device.describe(jax, self.cell["chips"])

    def peaks(self):
        return peaks.of(self.device["kind"])


def measure(ctx):
    kind = ctx.traffic["kind"]
    if kind == "train":
        from perf.lib import train_cell as runner
    elif kind == "serve":
        from perf.lib import serve_cell as runner
    else:
        raise manifest.ManifestError(f"traffic kind {kind!r}")
    if ctx.args.trace:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    runner.run(ctx)
    ctx.end_to_end["setup_s"] = ctx.setup_s
    if ctx.args.trace:
        data = trace_lib.from_xplane(trace_lib.find_xplane(ctx.trace_dir))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        if data["devices"]:
            ctx.trace = trace_lib.Trace(data)
        elif not ctx.args.tiny:
            raise RuntimeError("the trace holds no operation of a TPU")


def result(ctx):
    """The last line. With --trace 0 the cell's end-to-end metrics, with
    --trace 1 its per-layer metrics, each read by the reader of its name; a
    reader that finds nothing to read returns None and is left out."""
    man, name = ctx.manifest, ctx.args.workload
    metrics = {}
    if ctx.args.trace:
        for m in manifest.metrics_of(man, name, "per_layer"):
            value = manifest.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in manifest.metrics_of(man, name, "end_to_end"):
            metrics[m["name"]] = {"value": ctx.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    dev = dict(ctx.device)
    out = {"correct": compare.verdict(ctx.checks, ctx.failed),
           "attempted": ctx.attempted, "failed": ctx.failed,
           "metrics": metrics, "device": dev}
    if ctx.trace is not None:
        dev["busy_s"] = ctx.trace.mean_busy_s()
        dev["window_s"] = ctx.trace.window_s
        out["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                            "idle_gaps": ctx.trace.idle_gaps(10)}
    if ctx.args.tiny:
        # a CPU run names no device metric: the numbers stand apart
        out["dry_run"] = {k: v["value"] for k, v in metrics.items()}
        out["metrics"] = {}
    out["checks"] = compare.as_pairs(ctx.checks)
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU dry run at toy sizes (tests)")
    return ap.parse_args(argv)


def main(argv=None, t0=None, prepare=None):
    """``prepare(ctx)``: the tests' hook to plant a fault under the timed
    path. -> the result dict (also printed as the last line)."""
    args = parse(argv)
    man = manifest.load()
    if args.seconds is None:
        args.seconds = float(man["run_seconds"])
    ctx = Context(args, man, T0 if t0 is None else t0)
    if args.tiny:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{ctx.cell['chips']}").strip()
    elif "tpu" not in os.environ.get("JAX_PLATFORMS", "tpu"):
        raise SystemExit(f"perf/run.py: JAX_PLATFORMS="
                         f"{os.environ['JAX_PLATFORMS']} names no TPU")
    import jax

    if not args.tiny:
        device.require_chip(jax, ctx.cell["chips"])
    ctx.ready()
    if prepare is not None:
        prepare(ctx)
    measure(ctx)
    out = result(ctx)
    ctx.record.note(correct=out["correct"], checks=out["checks"],
                    device=out["device"])
    ctx.record.write()
    for name, (value, limit) in out["checks"].items():
        print(f"check {name}: {value:.6g} (limit {limit:.6g})", file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
