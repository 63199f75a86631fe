"""Small-message latency bench: the kernel family's latency-class curve.

The acceptance instrument for the ``pallas_rhd`` lowering (the recursive
halving/doubling allreduce) and the ``pallas_a2a`` fused MoE exchange:

- **latency curve** (<= 512 KiB, the ``msg_priority_threshold`` class):
  best-of-N wall time per payload for {``lax``, ``rhd``, ``pallas_ring``,
  ``pallas_rhd``} — the regime where hop count (2*log2(G) vs 2*(G-1)),
  not algbw, decides. The ``crossover`` row reports the smallest swept
  payload where the ring overtakes rhd (None = rhd won the whole band).
- **MoE row**: the fused quantized alltoall against the inline ``lax``
  exchange on a dispatch-shaped payload, with the analytic wire-bytes
  ratio (int8 blockwise codec vs f32 inline — <= 1/3 by construction).
- **parity rows**: integer-sum bit-exactness of every timed kernel against
  its lax oracle (the exit code; timing never gates).

Off-TPU the kernels run under the Pallas interpreter (armed here when no
TPU is attached): parity rows are real, timing rows are tagged ``backend:
interpret`` and are NOT a performance signal — interpreter DMAs are
simulated with world gathers. Not measured on the chip (ROADMAP S8).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/latency_bench.py [--smoke]

--smoke trims sizes/iters for the tier-1 wiring (tests/test_pallas_rhd.py,
the ``bench_smoke`` marker). The full grid belongs to the capture run.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# the latency class: everything at or below 512 KiB (the band boundary the
# MLSL_PALLAS_RHD_MAX_BYTES knob carves); smoke keeps interpret-mode wall
# time inside the tier-1 budget
SMOKE_SIZES = (4 * 1024, 32 * 1024)
FULL_SIZES = (4 * 1024, 16 * 1024, 64 * 1024, 256 * 1024, 512 * 1024)
QUANT_BLOCK = 256
MOE_ROWS_PER_DEST = 4          # dispatch-shaped: a few capacity rows/dest


def _time(fn, args, iters, warmup=1):
    import jax

    fn = getattr(fn, "_mlsl_inner", fn)
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--iters", type=int, default=0)
    args = ap.parse_args()

    from mlsl_tpu import sysinfo

    import numpy as np
    import jax

    if not sysinfo.on_tpu():
        os.environ.setdefault("MLSL_PALLAS_INTERPRET", "1")

    from mlsl_tpu.comm import algos
    from mlsl_tpu.comm.mesh import ProcessGroup, Topology
    from mlsl_tpu.ops import a2a_kernels, rhd_kernels
    from mlsl_tpu.ops import ring_kernels as rk
    from mlsl_tpu.types import ReductionType

    backend = "tpu" if sysinfo.on_tpu() else (
        "interpret" if rk.interpret_mode() else "cpu")
    sizes = SMOKE_SIZES if args.smoke else FULL_SIZES
    iters = args.iters or (2 if args.smoke else 9)

    n = jax.device_count()
    topo = Topology(n, 1)
    group = ProcessGroup(topo, ("data",))
    if not (rhd_kernels.eligible("allreduce", group)
            and a2a_kernels.eligible("alltoall", group)):
        print(json.dumps({"metric": "latency_bench",
                          "error": "kernel family not runnable on this "
                                   "backend/group", "backend": backend}))
        return 1
    rng = np.random.default_rng(0)

    def buf(elems, vals=None):
        a = vals if vals is not None else np.zeros(
            (*topo.grid_shape, elems), np.float32)
        return topo.shard_buffer(a)

    # -- latency curve ------------------------------------------------------
    curve = []
    for size_b in sizes:
        elems = max(-(-(size_b // 4) // n) * n, n)
        payload = elems * 4
        row = {"metric": "latency_bench", "bytes": payload,
               "backend": backend, "devices": n, "us": {}}
        for algo in ("lax", "rhd", "pallas_ring", "pallas_rhd"):
            fn = algos.build("allreduce", group, np.float32, algo,
                             op=ReductionType.SUM)
            row["us"][algo] = round(
                _time(fn, (buf(elems),), iters) * 1e6, 1)
        curve.append(row)
        print(json.dumps(row), flush=True)

    # crossover: the smallest payload where the bandwidth-class ring
    # overtakes the latency-class rhd (None = rhd won the whole band, the
    # expected shape when the band boundary sits above the sweep)
    cross = None
    for row in curve:
        if row["us"]["pallas_ring"] < row["us"]["pallas_rhd"]:
            cross = row["bytes"]
            break
    print(json.dumps({
        "metric": "latency_crossover", "backend": backend, "devices": n,
        "rhd_beats_ring_below_bytes": cross,
        "rhd_wins_band": [r["bytes"] for r in curve
                          if r["us"]["pallas_rhd"] <= r["us"]["pallas_ring"]],
    }), flush=True)

    # -- MoE dispatch row: fused quantized alltoall vs the inline lax wire --
    rc = n * QUANT_BLOCK * MOE_ROWS_PER_DEST // n * n  # per-dest, block grid
    count = n * rc
    fn_lax = algos.build("alltoall", group, np.float32, "lax",
                         send_count=rc)
    fn_a2a = algos.build("alltoall", group, np.float32, "pallas_a2a",
                         block=QUANT_BLOCK, quantized=True)
    moe = {"metric": "latency_bench_moe", "backend": backend, "devices": n,
           "bytes": count * 4, "us": {}}
    moe["us"]["inline_lax/f32"] = round(
        _time(fn_lax, (buf(count),), iters) * 1e6, 1)
    moe["us"]["pallas_a2a/int8"] = round(
        _time(fn_a2a, (buf(count),), iters) * 1e6, 1)
    wire_q = a2a_kernels.wire_bytes(n, count, QUANT_BLOCK, True)
    wire_f = a2a_kernels.wire_bytes(n, count, QUANT_BLOCK, False)
    moe["wire_bytes"] = {"pallas_a2a/int8": wire_q, "inline_lax/f32": wire_f,
                         "ratio": round(wire_q / wire_f, 4)}
    print(json.dumps(moe), flush=True)

    # -- parity acceptance rows (integer sums: exact in both codecs) --------
    elems = max(-(-(sizes[0] // 4) // n) * n, n)
    ivals = rng.integers(-8, 8,
                         size=(*topo.grid_shape, elems)).astype(np.float32)
    base = algos.build("allreduce", group, np.float32, "lax",
                       op=ReductionType.SUM)
    fused = algos.build("allreduce", group, np.float32, "pallas_rhd",
                        op=ReductionType.SUM)
    want = np.asarray(jax.block_until_ready(base(buf(elems, ivals))))
    got = np.asarray(jax.block_until_ready(fused(buf(elems, ivals))))
    rhd_ok = bool(np.array_equal(got, want))

    # integer payload with the +-127 sentinel at every block start on every
    # member: the blockwise scale is exactly 1.0, so the int8 round trip is
    # the identity and the fused wire must match the raw lax exchange
    qv = rng.integers(-10, 10, size=(n, count)).astype(np.float32)
    qv[:, ::QUANT_BLOCK] = 127.0
    qbuf = buf(count, qv.reshape(*topo.grid_shape, count))
    want = np.asarray(jax.block_until_ready(fn_lax(qbuf)))
    got = np.asarray(jax.block_until_ready(fn_a2a(qbuf)))
    a2a_ok = bool(np.array_equal(got, want))

    print(json.dumps({
        "metric": "latency_bench_parity",
        "backend": backend,
        "rhd_int_bitexact_vs_lax": rhd_ok,
        "a2a_int_bitexact_vs_lax": a2a_ok,
        "a2a_wire_ratio_le_third": bool(wire_q * 3 <= wire_f),
    }), flush=True)
    return 0 if rhd_ok and a2a_ok and wire_q * 3 <= wire_f else 1


if __name__ == "__main__":
    sys.exit(main())
