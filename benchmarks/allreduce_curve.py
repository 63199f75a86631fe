"""AllReduce algorithmic-bandwidth curve: the isolation-benchmark harness.

Produces the algbw-vs-message-size table that is the BASELINE metric (SURVEY.md §6:
"allreduce algbw (GB/s) vs msg size"), using the Statistics isolation methodology
(10 iterations, 4 warm-up skipped — reference src/mlsl_impl_stats.cpp:48-49).

algbw for an allreduce of S bytes over n ranks uses the standard convention
busbw = algbw * 2(n-1)/n. On a single real chip the group is degenerate (the curve
then measures framework dispatch floor); on a v5p slice this is the ≥90%-of-ICI-peak
north-star measurement. Run with JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=8 for the virtual-mesh curve.

Output: one row per size, plus a JSON summary line.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def measure_dispatch_floor(env, dist):
    """Host-side cost of driving one already-compiled request, in µs.

    Three numbers (the knob VERDICT r4 item 3 demands be tracked so host
    dispatch can never silently eat the overlap budget):
      - start_us:      async Start() enqueue alone (the per-layer hot path —
                       the reference's analog is queuing one cached CommRequest
                       on the eplib command queue, eplib/cqueue.c:1906-2026)
      - start_wait_us: full Start()+Wait() round trip on a tiny payload — the
                       smallest achievable per-request latency
      - test_us:       one non-blocking Test() poll on a completed request
    """
    import time

    import numpy as np

    from mlsl_tpu.comm.request import CommDesc, CommRequest
    from mlsl_tpu.types import DataType, ReductionType

    count = 256  # tiny payload: device time ~0, what remains is host dispatch
    req = CommRequest(
        CommDesc("allreduce", dist.data_group, count, DataType.FLOAT,
                 op=ReductionType.SUM),
        env.dispatcher,
    )
    req.setup()
    buf = dist.make_buffer(lambda p: np.zeros(count, dtype=np.float64), count)
    import jax

    bare = req._fns[0]  # the raw compiled XLA program behind the request
    for _ in range(10):  # warm: compile + caches
        req.start(buf)
        req.wait()
    iters, blocks = 150, 3
    # All loops keep in-flight depth at 1 (a free-running start loop starves
    # the CPU backend's in-process collective rendezvous). Best-of-blocks:
    # the minimum over blocks is reported.
    start_us = start_wait_us = launch_us = float("inf")
    for _ in range(blocks):
        t_start = 0
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            a = time.perf_counter_ns()
            req.start(buf)
            t_start += time.perf_counter_ns() - a
            req.wait()
        start_wait_us = min(
            start_wait_us, (time.perf_counter_ns() - t0) / iters / 1e3
        )
        start_us = min(start_us, t_start / iters / 1e3)
        t_call = 0
        for _ in range(iters):
            a = time.perf_counter_ns()
            out = bare(buf)
            t_call += time.perf_counter_ns() - a
            jax.block_until_ready(out)
        launch_us = min(launch_us, t_call / iters / 1e3)
    t0 = time.perf_counter_ns()
    for _ in range(iters):
        req.test()
    test_us = (time.perf_counter_ns() - t0) / iters / 1e3
    return {
        "metric": "dispatch_floor",
        "start_us": round(start_us, 2),
        "launch_us": round(launch_us, 2),       # bare XLA async dispatch
        "overhead_us": round(start_us - launch_us, 2),  # the framework's slice
        "start_wait_us": round(start_wait_us, 2),
        "test_us": round(test_us, 2),
        "unit": "us",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--min-kb", type=int, default=4)
    ap.add_argument("--max-mb", type=int, default=64)
    ap.add_argument("--quant", action="store_true", help="also run int8 ring")
    args = ap.parse_args()

    import numpy as np

    import mlsl_tpu as mlsl
    from mlsl_tpu.comm.request import CommDesc, CommRequest
    from mlsl_tpu.core.stats import isolation_time_request
    from mlsl_tpu.types import CompressionType, DataType, ReductionType

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    dist = env.create_distribution(world, 1)
    n_ranks = dist.get_process_count_data()
    bus_factor = 2 * (n_ranks - 1) / n_ranks if n_ranks > 1 else 1.0

    sizes = []
    s = args.min_kb * 1024
    while s <= args.max_mb * 1024 * 1024:
        sizes.append(s)
        s *= 4

    modes = [("fp32", CompressionType.NONE)]
    if args.quant:
        modes.append(("int8", CompressionType.QUANTIZATION))

    print(f"{'bytes':>12} {'mode':>6} {'us/iter':>10} {'algbw GB/s':>11} {'busbw GB/s':>11}")
    best = 0.0
    for nbytes in sizes:
        count = nbytes // 4
        for name, comp in modes:
            req = CommRequest(
                CommDesc(
                    "allreduce", dist.data_group, count, DataType.FLOAT,
                    op=ReductionType.SUM, compression=comp,
                ),
                env.dispatcher,
            )
            req.setup()
            ns, _ = isolation_time_request(req)
            algbw = nbytes / max(ns, 1)  # bytes/ns == GB/s
            # the headline busbw uses uncompressed fp32 only: int8's algbw is
            # computed from the uncompressed payload, so folding it in would
            # overstate the physical bus bandwidth ~4x
            if comp == CompressionType.NONE:
                best = max(best, algbw * bus_factor)
            print(
                f"{nbytes:>12} {name:>6} {ns / 1e3:>10.1f} {algbw:>11.2f} "
                f"{algbw * bus_factor:>11.2f}"
            )
    floor = measure_dispatch_floor(env, dist)
    print(json.dumps(floor))
    print(json.dumps({
        "metric": "allreduce_busbw_peak",
        "value": round(best, 3),
        "unit": "GB/s",
        "ranks": n_ranks,
        "dispatch_floor_start_us": floor["start_us"],
    }))


if __name__ == "__main__":
    main()
