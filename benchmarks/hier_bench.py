"""Hierarchical-collective benchmark: size x codec x (flat vs hier) algbw on
the synthetic two-tier mesh, with a DCN bandwidth-delay simulator.

The CPU proof mesh has no slow tier — every virtual device shares one
memory bus — so raw wall clock cannot show WHY the two-tier decomposition
wins. This bench separates the two effects:

- **wall_us** is the measured program time (compute + every hop at local
  speed): what the flat-vs-hier schedule itself costs.
- **sim_us** adds the modeled DCN cost of the bytes each lowering puts on
  the slow tier (``--dcn-gbps`` link bandwidth, ``--dcn-lat-us`` per-hop
  latency — the bandwidth-delay knob): flat lowerings carry the FULL
  payload across the tier boundary 2(G-1)/G times (every ring hop crosses
  it), hier carries the 1/L shard at the DCN codec's wire width once per
  tier peer (comm/algos/hier.dcn_wire_bytes). On a real pod the DCN link
  decides; the simulator makes the CPU mesh show the same ordering.

Rows: per (size x lowering) algbw curve, a ResNet-50-shaped gradient-stream
total (the acceptance workload), and the ``hier_vs_flat`` summary ratio =
best flat simulated stream time / hier-int8 simulated stream time.

Usage: python benchmarks/hier_bench.py [--smoke] [--tiers 2x4]
       [--dcn-gbps 6.25] [--dcn-lat-us 50] [--no-dcn-sim]

--smoke trims sizes/iters for the tier-1 wiring (tests/test_hier.py, the
``bench_smoke`` marker).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

SMOKE_SIZES = (64 * 1024, 1024 * 1024)
FULL_SIZES = (64 * 1024, 512 * 1024, 4 * 1024 * 1024, 16 * 1024 * 1024)


def _time_fn(fn, args, iters):
    import jax

    fn = getattr(fn, "_mlsl_inner", fn)
    for _ in range(2):
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
    ap.add_argument("--tiers", default="2x4",
                    help="synthetic TxL split (sets MLSL_MESH_TIERS when the "
                         "env var is unset; on real multislice leave both "
                         "alone and slice_index drives the tier map)")
    ap.add_argument("--dcn-gbps", type=float, default=6.25,
                    help="simulated DCN link bandwidth (GB/s); the "
                         "bandwidth half of the bandwidth-delay knob")
    ap.add_argument("--dcn-lat-us", type=float, default=50.0,
                    help="simulated per-DCN-hop latency (us)")
    ap.add_argument("--no-dcn-sim", action="store_true",
                    help="report raw wall time only (real-pod runs, where "
                         "the DCN is physically in the measurement)")
    ap.add_argument("--iters", type=int, default=0)
    ap.add_argument("--block", type=int, default=256)
    args = ap.parse_args()

    if not os.environ.get("MLSL_MESH_TIERS"):
        os.environ["MLSL_MESH_TIERS"] = args.tiers

    import numpy as np
    import jax

    from mlsl_tpu.comm import algos, quant_ring
    from mlsl_tpu.comm.algos import hier
    from mlsl_tpu.comm.mesh import ProcessGroup, Topology, world_tiers
    from mlsl_tpu.types import ReductionType

    devices = tuple(jax.devices())
    n_dev = len(devices)
    if n_dev < 2:
        print(json.dumps({"metric": "hier_vs_flat", "value": None,
                          "reason": "single-device world"}), flush=True)
        return 0
    tiers = world_tiers(devices)
    if tiers is None:
        print(json.dumps({"metric": "hier_vs_flat", "value": None,
                          "reason": "no tier structure"}), flush=True)
        return 0
    t_cnt, l_cnt = tiers
    topo = Topology(n_dev, 1, devices=devices)
    group = ProcessGroup(topo, ("data",))
    iters = args.iters or (3 if args.smoke else 7)
    block = args.block
    sim = not args.no_dcn_sim
    bw = args.dcn_gbps * 1e9
    lat = args.dcn_lat_us * 1e-6

    def buf(elems):
        return topo.shard_buffer(
            np.zeros((*topo.grid_shape, elems), dtype=np.float32)
        )

    def err(el):
        return topo.shard_buffer(
            np.zeros((*topo.grid_shape, el), dtype=np.float32)
        )

    def flat_dcn(elems, codec):
        """Modeled DCN cost (s) of a FLAT lowering: every ring hop crosses
        the tier boundary, so the full 2(G-1)/G payload rides the slow link
        at the codec's wire width."""
        wpe = 4.0 if codec == "none" else 1.0 + 4.0 / block
        return (2 * (n_dev - 1) / n_dev * elems * wpe / bw
                + 2 * (n_dev - 1) * lat)

    def hier_dcn(elems, codec):
        return (hier.dcn_wire_bytes(elems, tiers, codec, block) / bw
                + hier.dcn_phases(tiers, codec if codec != "none" else "f32")
                * lat)

    # -- contenders: (label, codec, build(elems) -> (fn, extra args fn)) ----
    def dense(algo):
        def make(elems):
            fn = algos.build("allreduce", group, np.float32, algo,
                             op=ReductionType.SUM)
            return fn, (buf(elems),)
        return make

    def quant(ring, **kw):
        def make(elems):
            fn, el = quant_ring.build_quantized_collective(
                "allreduce", group, elems, block, ring=ring, **kw
            )
            return fn, (buf(elems), err(el))
        return make

    contenders = [
        ("lax", "flat", "none", dense("lax")),
        ("rhd", "flat", "none", dense("rhd")),
        ("quant_ring", "flat", "int8", quant("lax")),
        ("hier", "hier", "none", dense("hier")),
        # dcn_codec pinned: the row label must mean int8 even when the
        # caller's environment exports MLSL_HIER_DCN_CODEC
        ("hier+int8", "hier", "int8", quant("hier", dcn_codec="int8")),
    ]

    def sim_s(shape, codec, elems, wall):
        if not sim:
            return wall
        dcn = hier_dcn(elems, codec) if shape == "hier" \
            else flat_dcn(elems, codec)
        return wall + dcn

    # -- size curve ---------------------------------------------------------
    sizes = SMOKE_SIZES if args.smoke else FULL_SIZES
    walls = {}  # (label, elems) -> wall seconds
    for size_b in sizes:
        elems = max(-(-(size_b // 4) // n_dev) * n_dev, n_dev)
        for label, shape, codec, make in contenders:
            fn, fargs = make(elems)
            w = _time_fn(fn, fargs, iters)
            walls[(label, elems)] = w
            s = sim_s(shape, codec, elems, w)
            print(json.dumps({
                "metric": "hier_curve",
                "bytes": elems * 4,
                "lowering": label,
                "tiers": f"{t_cnt}x{l_cnt}",
                "wall_us": round(w * 1e6, 1),
                "sim_us": round(s * 1e6, 1),
                "algbw_gbps": round(elems * 4 / s / 1e9, 4),
            }), flush=True)

    # -- ResNet-50-shaped gradient stream (the acceptance workload) ---------
    from benchmarks.quant_bucket_bench import resnet50_counts

    stream = resnet50_counts(scale=16 if args.smoke else 1)
    stream = [max(-(-c // n_dev) * n_dev, n_dev) for c in stream]
    distinct = sorted(set(stream))
    per_size_counts = {c: stream.count(c) for c in distinct}
    totals = {}
    for label, shape, codec, make in contenders:
        total = 0.0
        for elems in distinct:
            fn, fargs = make(elems)
            w = _time_fn(fn, fargs, max(2, iters - 1))
            total += per_size_counts[elems] * sim_s(shape, codec, elems, w)
        totals[label] = total
        print(json.dumps({
            "metric": "hier_resnet50_stream",
            "lowering": label,
            "tensors": len(stream),
            "sim_ms": round(total * 1e3, 3),
        }), flush=True)

    best_flat = min(
        (lbl for lbl, shape, _, _ in contenders if shape == "flat"),
        key=lambda lbl: totals[lbl],
    )
    ratio = totals[best_flat] / totals["hier+int8"]
    print(json.dumps({
        "metric": "hier_vs_flat",
        "value": round(ratio, 4),
        "best_flat": best_flat,
        "tiers": f"{t_cnt}x{l_cnt}",
        "dcn_sim": {"gbps": args.dcn_gbps, "lat_us": args.dcn_lat_us}
        if sim else None,
        "stream_ms": {k: round(v * 1e3, 3) for k, v in totals.items()},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
