"""Pallas fused-ring benchmark: algbw curve per lowering x wire x size.

The on-chip acceptance for the ``pallas_ring`` lowering (ROADMAP #1): the
measured allreduce algbw curve of the fused kernel against the composed
``lax`` lowerings — dense f32 wire vs ``lax``/``rhd``, int8 wire vs the
composed ``quant_ring`` — plus the parity acceptance rows (dense bit-exact
vs ``lax`` on integer sums; quantized bit-exact vs the ``quant_ring`` oracle
on an exact-scale payload, where every per-hop scale is exactly 1.0 so both
hop engines' arithmetic is exactly representable).

Off-TPU the kernel runs under the Pallas interpreter (armed here via
MLSL_PALLAS_INTERPRET=1 when no TPU is attached): the parity rows are real,
the timing rows are tagged ``backend: interpret`` and are NOT a performance
signal — the interpreter simulates every DMA with gathers. Not measured on
the chip (ROADMAP S8).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/pallas_ring_bench.py [--smoke]

--smoke trims sizes/iters for the tier-1 wiring (tests/test_pallas_ring.py,
the ``bench_smoke`` marker). The full grid belongs to the capture run.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# interpret-mode DMAs are simulated with world gathers: smoke sizes must be
# tiny for the tier-1 budget; the full grid assumes a real chip
SMOKE_SIZES = (16 * 1024, 64 * 1024)
FULL_SIZES = (256 * 1024, 2 * 1024 * 1024, 16 * 1024 * 1024,
              64 * 1024 * 1024)
QUANT_BLOCK = 256


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
    ap.add_argument("--bidir", action="store_true",
                    help="also time the bidirectional dense variant")
    args = ap.parse_args()

    from mlsl_tpu import sysinfo

    import numpy as np
    import jax

    if not sysinfo.on_tpu():
        # arm the interpreter BEFORE any kernel build: parity is real, the
        # timing rows are tagged
        os.environ.setdefault("MLSL_PALLAS_INTERPRET", "1")

    from mlsl_tpu.comm import algos, quant_ring
    from mlsl_tpu.comm.mesh import ProcessGroup, Topology
    from mlsl_tpu.ops import ring_kernels as rk
    from mlsl_tpu.types import ReductionType

    backend = "tpu" if sysinfo.on_tpu() else (
        "interpret" if rk.interpret_mode() else "cpu")
    sizes = SMOKE_SIZES if args.smoke else FULL_SIZES
    iters = args.iters or (2 if args.smoke else 7)

    n = jax.device_count()
    topo = Topology(n, 1)
    group = ProcessGroup(topo, ("data",))
    if not rk.eligible_quant(group, QUANT_BLOCK):
        print(json.dumps({"metric": "pallas_ring_bench",
                          "error": "pallas_ring not runnable on this "
                                   "backend/group", "backend": backend}))
        return 1
    rng = np.random.default_rng(0)

    def buf(elems, vals=None):
        a = vals if vals is not None else np.zeros(
            (*topo.grid_shape, elems), np.float32)
        return topo.shard_buffer(a)

    def zerr(el):
        return topo.shard_buffer(
            np.zeros((*topo.grid_shape, el), np.float32))

    # -- algbw curve --------------------------------------------------------
    for size_b in sizes:
        elems = max(-(-(size_b // 4) // n) * n, n)
        payload = elems * 4
        row = {"metric": "pallas_ring_bench", "bytes": payload,
               "backend": backend, "devices": n, "us": {}}
        dense_algos = ["lax", "rhd", "pallas_ring"]
        for algo in dense_algos:
            fn = algos.build("allreduce", group, np.float32, algo,
                             op=ReductionType.SUM)
            row["us"][f"dense/{algo}"] = round(
                _time(fn, (buf(elems),), iters) * 1e6, 1)
        if args.bidir:
            from mlsl_tpu.comm.algos import pallas_ring as pr

            fnb = pr.build("allreduce", group, op=ReductionType.SUM,
                           bidir=True)
            row["us"]["dense/pallas_ring+bidir"] = round(
                _time(fnb, (buf(elems),), iters) * 1e6, 1)
        for ring, name in (("lax", "quant_ring"), ("pallas", "pallas_ring")):
            fn, el = quant_ring.build_quantized_collective(
                "allreduce", group, elems, QUANT_BLOCK, ring=ring)
            row["us"][f"int8/{name}"] = round(
                _time(fn, (buf(elems), zerr(el)), iters) * 1e6, 1)
        row["algbw_gbps"] = {
            k: round(payload / (v / 1e6) / 1e9, 4)
            for k, v in row["us"].items() if v
        }
        print(json.dumps(row), flush=True)

    # -- parity acceptance rows --------------------------------------------
    elems = max(-(-(sizes[0] // 4) // n) * n, n)
    ivals = rng.integers(-8, 8,
                         size=(*topo.grid_shape, elems)).astype(np.float32)
    base = algos.build("allreduce", group, np.float32, "lax",
                       op=ReductionType.SUM)
    fused = algos.build("allreduce", group, np.float32, "pallas_ring",
                        op=ReductionType.SUM)
    want = np.asarray(jax.block_until_ready(base(buf(elems, ivals))))
    got = np.asarray(jax.block_until_ready(fused(buf(elems, ivals))))
    dense_ok = bool(np.array_equal(got, want))

    # exact-scale construction: sentinel +-127 at block position 0 on rank
    # 0, small ints elsewhere -> every entry/hop scale is exactly 1.0 and
    # both hop engines' arithmetic is exactly representable
    qelems = n * QUANT_BLOCK * 32
    v = rng.integers(-3, 3, size=(n, qelems)).astype(np.float32)
    v[:, ::QUANT_BLOCK] = 0.0
    v[0, ::QUANT_BLOCK] = 127.0
    qbuf = buf(qelems, v.reshape(*topo.grid_shape, qelems))
    ofn, oel = quant_ring.build_quantized_collective(
        "allreduce", group, qelems, QUANT_BLOCK, ring="lax")
    pfn, pel = quant_ring.build_quantized_collective(
        "allreduce", group, qelems, QUANT_BLOCK, ring="pallas")
    oo, oe = ofn(qbuf, zerr(oel))
    po, pe = pfn(qbuf, zerr(pel))
    oo, oe, po, pe = [np.asarray(jax.block_until_ready(a))
                      for a in (oo, oe, po, pe)]
    quant_ok = bool(np.array_equal(po, oo) and np.array_equal(pe, oe)
                    and oel == pel)

    print(json.dumps({
        "metric": "pallas_ring_parity",
        "backend": backend,
        "dense_int_bitexact_vs_lax": dense_ok,
        "quant_bitexact_vs_quant_ring": quant_ok,
    }), flush=True)
    return 0 if dense_ok and quant_ok else 1


if __name__ == "__main__":
    sys.exit(main())
