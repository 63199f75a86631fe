"""BASELINE config 2: the Distribution(data x model) grid collective set.

Times AllReduce + AllGather + Bcast + ReduceScatter over both the data and
model groups of a hybrid grid (the reference's four grid collectives,
BASELINE.json configs[1]) with the isolation methodology (best-of-blocks,
d2h-synced). On one real chip the groups degenerate to the dispatch floor;
on a mesh (virtual CPU or a real slice) the rows are group-wise algbw.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python benchmarks/grid_collectives.py
Prints one JSON line per (collective, group).
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    import numpy as np

    import mlsl_tpu as mlsl
    from mlsl_tpu.types import DataType, GroupType, ReductionType

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    model = 2 if world % 2 == 0 and world > 1 else 1
    dist = env.create_distribution(max(world // model, 1), model)
    nbytes = 4 * 1024 * 1024  # 4 MiB fp32 per rank
    count = nbytes // 4
    buf = dist.make_buffer(
        lambda p: p * 1.0 + np.arange(count, dtype=np.float64) % 977, count
    )

    from benchmarks._common import timed  # paired-block estimate, 4-byte d2h sync
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    def run(kind, gt):
        gsize = {GroupType.DATA: dist.get_process_count_data(),
                 GroupType.MODEL: dist.get_process_count_model()}[gt]
        group = dist._group(gt)
        # one prebuilt, reused request per row — the same steady-state the
        # committed dispatch_floor metric measures (allreduce_curve.py), so
        # degenerate-group rows stay comparable to it
        kw = {}
        if kind in ("allreduce", "reduce_scatter"):
            kw["op"] = ReductionType.SUM
        if kind == "bcast":
            kw["root"] = 0
        if kind == "reduce_scatter":
            kw["recv_count"] = max(count // max(gsize, 1), 1)
        req = CommRequest(
            CommDesc(kind, group, count, DataType.FLOAT, **kw), env.dispatcher
        )
        req.setup()

        def one():
            req.start(buf)
            return req.wait()

        ms = timed(one, iters=9, warmup=2, blocks=3)
        row = {"metric": f"grid_{kind}", "group": gt.name.lower(),
               "group_size": gsize, "us_per_op": round(ms * 1e3, 1),
               "bytes": nbytes}
        if gsize > 1:
            row["algbw_gbs"] = round(nbytes / (ms / 1e3) / 1e9, 3)
        else:
            # one-member group: the request is the identity program — the row
            # is the per-collective dispatch floor, not bandwidth
            row["note"] = "degenerate group: dispatch floor"
        return row

    for kind in ("allreduce", "allgather", "bcast", "reduce_scatter"):
        for gt in (GroupType.DATA, GroupType.MODEL):
            print(json.dumps(run(kind, gt)))


if __name__ == "__main__":
    main()
