"""The planted faults that the limit of ``keye-vl2-30b-a3b-serve-longctx`` has
to refuse: a run of the cell with a wrong computation put under the timed
path through ``ctx.wrap_engine`` (perf/adapters/keye.py ``plant``). Each has
to come out as NOT correct. The benchmark's own runs never run this; the
builder runs it on the chip at the cell's own size, and tests/perf keeps it at
a size a test can hold.

    python perf/keye_faults.py --fault recent_window|experts_top7 --seed <n>
                               [--seconds <s>] [--tiny]

``recent_window``: the selection replaced by the most recent ``topk``
positions. ``experts_top7``: a token's least probable chosen expert left
out, the other seven renormalised. The last line is the run's result line.
"""

import argparse
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from perf import run as run_lib  # noqa: E402
from perf.lib import manifest  # noqa: E402

WORKLOAD = "keye-vl2-30b-a3b-serve-longctx"


_PLANTED = []      # the adapters that hold the program's own functions


def planting(fault):
    """-> the ``prepare`` hook that plants ``fault`` under a run's engine."""
    def prepare(ctx):
        adapter = manifest.adapter(ctx.config)
        _PLANTED.append(adapter)
        ctx.wrap_engine = lambda engine: adapter.plant(engine, fault)
    return prepare


def restore():
    """The program's own functions back (a process that goes on to sound
    runs: the tests)."""
    while _PLANTED:
        _PLANTED.pop().restore()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    argv = ["--workload", WORKLOAD, "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", "0"]
    return run_lib.main(argv + (["--tiny"] if args.tiny else []),
                        prepare=planting(args.fault))


if __name__ == "__main__":
    main()
