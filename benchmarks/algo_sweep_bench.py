"""Algorithm-sweep benchmark: algbw per collective algorithm x size x shape.

Runs the tuner sweep (mlsl_tpu.tuner.run_sweep — the SAME measurement the
MLSL_TUNE=1 init path uses) on the attached backend and prints one JSON row
per selection-table cell, so the per-algorithm algbw curves land in the
capture record next to the allreduce/quant curves. Then exercises the full
profile lifecycle: write the profile, reload it, verify the reloaded table
reproduces every recorded selection, and pin the chosen program of one
non-default cell bit-for-bit against the lax baseline on integer payloads
(the acceptance row: tuned path bit-identical to baseline for sum
allreduce).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/algo_sweep_bench.py [--smoke] [--quant] \\
              [--profile-out PATH]

--smoke trims sizes/iters for tests/test_algos.py (which holds the profile's
round trip and the parity row, never a time) and scripts/run_tune.sh. Full
sweeps (default sizes up to 8 MiB plus the quant-block cell) are for the
chip: the per-lowering curve ROADMAP S8 runs on four chips.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# smoke stays small on purpose: the tier-1 budget is tight, and the
# bandwidth tail belongs to the full run on the chip
SMOKE_SIZES = (4 * 1024, 64 * 1024)
FULL_SIZES = (16 * 1024, 128 * 1024, 1024 * 1024, 8 * 1024 * 1024)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", action="store_true",
                    help="also sweep the quant-ring block palette")
    ap.add_argument("--profile-out", default="",
                    help="write the profile here (default: a temp file)")
    ap.add_argument("--iters", type=int, default=0)
    args = ap.parse_args()

    import numpy as np
    import jax

    from mlsl_tpu import tuner
    from mlsl_tpu.comm import algos
    from mlsl_tpu.comm.mesh import ProcessGroup, Topology
    from mlsl_tpu.types import ReductionType

    sizes = SMOKE_SIZES if args.smoke else FULL_SIZES
    iters = args.iters or (3 if args.smoke else 7)
    # an explicit --quant is honored even in smoke mode (run_tune.sh passes
    # it through)
    quant = args.quant

    prof = tuner.run_sweep(sizes=sizes, iters=iters, quant=quant)

    non_default = 0
    for c in prof.cells:
        best_us = c["us"][c["algo"]]
        payload = c.get("payload_bytes") or 0
        if c["algo"] != algos.DEFAULT:
            non_default += 1
        print(json.dumps({
            "metric": "algo_sweep",
            "kind": c["kind"],
            "shape": c["shape"],
            "bytes": payload,
            "chosen": c["algo"],
            "algbw_gbps": round(payload / (best_us / 1e6) / 1e9, 4)
            if best_us else None,
            "us": c["us"],
        }), flush=True)
    print(json.dumps({
        "metric": "algo_sweep_selection",
        "cells": len(prof.cells),
        "non_default": non_default,
        "knobs": {k: v for k, v in prof.knobs.items()
                  if not k.startswith("_")},
    }), flush=True)

    # -- profile round-trip + parity (the acceptance row) -------------------
    path = args.profile_out or os.path.join(
        tempfile.gettempdir(), f"mlsl_tune_profile.{os.getpid()}.json"
    )
    prof.save(path)
    back = tuner.load_profile(path)
    ok = back.matches(prof.fingerprint)
    for c in prof.cells:
        pb = c.get("payload_bytes") or 1
        if back.select(c["kind"], tuple(c["shape"]), "none", pb) != c["algo"]:
            ok = False

    # pin one cell's chosen program bit-for-bit against the baseline on
    # integer-valued payloads (every summation order exact); prefer a
    # non-default cell so the parity covers a genuinely different program
    cell = next((c for c in prof.cells if c["algo"] != algos.DEFAULT),
                prof.cells[0])
    devices = tuple(jax.devices())
    n_dev = len(devices)
    shape = tuple(cell["shape"])
    if len(shape) == 1:
        topo = Topology(n_dev, 1, devices=devices)
        group = ProcessGroup(topo, ("data",))
    else:
        topo = Topology(shape[0], shape[1], devices=devices)
        group = ProcessGroup(topo, ("data", "model"))
    g = group.size
    elems = max(((cell.get("payload_bytes") or 4096) // 4) // g * g, g)
    kw = {"op": ReductionType.SUM}
    if cell["kind"] == "reduce_scatter":
        kw["recv_count"] = elems // g
    rng = np.random.default_rng(0)
    vals = rng.integers(-8, 8, size=(*topo.grid_shape, elems)).astype(np.float32)
    buf = topo.shard_buffer(vals)
    base = algos.build(cell["kind"], group, np.float32, "lax", **kw)
    tuned = algos.build(cell["kind"], group, np.float32, cell["algo"], **kw)
    want = np.asarray(jax.block_until_ready(base(buf)))
    got = np.asarray(jax.block_until_ready(tuned(buf)))
    parity_exact = bool(np.array_equal(got, want))

    print(json.dumps({
        "metric": "algo_profile_roundtrip",
        "ok": bool(ok),
        "profile": path,
        "parity_cell": {"kind": cell["kind"], "shape": cell["shape"],
                        "algo": cell["algo"]},
        "parity_exact": parity_exact,
    }), flush=True)
    if not args.profile_out:
        os.unlink(path)
    return 0 if ok and parity_exact else 1


if __name__ == "__main__":
    sys.exit(main())
