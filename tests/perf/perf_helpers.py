"""Shared by the benchmark's run tests: one tiny run in this process."""

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def tiny_run(capsys, workload, seed, seconds=1.0, trace=0, prepare=None):
    """perf/run.py --tiny in this process (the look for a chip skipped, the
    rest of a run driven) -> (the dict main returns, the last line parsed)."""
    import time

    from perf import run as run_lib

    out = run_lib.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace), "--tiny"], t0=time.perf_counter(),
        prepare=prepare)
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    return out, last, captured.err
