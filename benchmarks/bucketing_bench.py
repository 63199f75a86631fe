"""Gradient-bucketing benchmark: per-layer vs coalesced grad sync.

Measures a backward-pass-shaped stream of N small gradient allreduces through
the ParameterSet engine, individually vs bucketed (core/bucketing.py), at a
launch-bound size and a bandwidth-entering size. The bucket's win is the
amortized host dispatch + wire latency; its cost is one jitted pack/unpack.
Round-5 CPU-mesh numbers: 12 x 8 KiB grads ~1.5x faster bucketed (1.49x in
the committed harness row; up to 1.9x on an unloaded box); 12 x 64 KiB about
par (the CPU backend's in-process reduce is uniquely cheap relative to its
dispatch). Not measured on the chip.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python benchmarks/bucketing_bench.py
Prints one JSON line per configuration.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    import numpy as np

    import mlsl_tpu as mlsl
    from mlsl_tpu.types import OpType

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    dist = env.create_distribution(world, 1)
    # on one device the grad group is degenerate: neither path communicates,
    # so rows measure dispatch noise only — tag them like grid_collectives does
    degenerate = {"note": "degenerate group: dispatch floor"} if world == 1 else {}

    def build(nlayers, count, bucket_mb, du=False):
        env.config.grad_bucket_mb = bucket_mb
        s = env.create_session()
        s.set_global_minibatch_size(8)
        ops = []
        for _ in range(nlayers):
            r = s.create_operation_reg_info(OpType.CC)
            r.add_input(8, 4)
            r.add_output(8, 4)
            r.add_parameter_set(count, 1, distributed_update=du)
            ops.append(s.get_operation(s.add_operation(r, dist)))
        s.commit()
        env.config.grad_bucket_mb = 0
        return [op.get_parameter_set(0) for op in ops]

    # 12 stays under the CPU backend's concurrent in-flight collective limit
    NL = 12
    for cnt in (2048, 16384):
        bufs = [
            dist.make_buffer(
                lambda p: p + np.arange(cnt, dtype=np.float64), cnt
            )
            for _ in range(NL)
        ]

        from benchmarks._common import device_sync

        def step(pss):
            for ps, b in zip(reversed(pss), reversed(bufs)):
                ps.start_gradient_comm(b)
            outs = [ps.wait_gradient_comm() for ps in pss]
            device_sync(outs[-1])

        times = {}
        for label, mb in (("individual_ms", 0), ("bucketed_ms", 4)):
            pss = build(NL, cnt, mb)
            for _ in range(3):
                step(pss)
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(5):
                    step(pss)
                best = min(best, (time.perf_counter() - t0) / 5)
            times[label] = round(best * 1e3, 3)
        print(json.dumps({
            "metric": "grad_bucketing_step",
            "layers": NL,
            "grad_kib": cnt * 4 // 1024,
            **times,
            "speedup": round(times["individual_ms"] / times["bucketed_ms"], 3),
            "unit": "ms",
            **degenerate,
        }))

    # ZeRO-1: both phases (grad reduce_scatter + increment all_gather) bucket
    cnt = 2048
    bufs = [dist.make_buffer(lambda p: p + np.arange(cnt, dtype=np.float64), cnt)
            for _ in range(NL)]
    from benchmarks._common import device_sync

    def du_step(pss):
        owned = {}
        for ps, b in zip(reversed(pss), reversed(bufs)):
            ps.start_gradient_comm(b)
        for ps in pss:
            owned[ps] = ps.wait_gradient_comm()
        for ps in pss:
            ps.start_increment_comm(owned[ps])
        outs = [ps.wait_increment_comm() for ps in pss]
        device_sync(outs[-1])

    times = {}
    for label, mb in (("individual_ms", 0), ("bucketed_ms", 4)):
        pss = build(NL, cnt, mb, du=True)
        for _ in range(3):
            du_step(pss)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(5):
                du_step(pss)
            best = min(best, (time.perf_counter() - t0) / 5)
        times[label] = round(best * 1e3, 3)
    print(json.dumps({
        "metric": "zero1_bucketing_step",
        "layers": NL,
        "grad_kib": cnt * 4 // 1024,
        **times,
        "speedup": round(times["individual_ms"] / times["bucketed_ms"], 3),
        "unit": "ms",
        **degenerate,
    }))


if __name__ == "__main__":
    main()
