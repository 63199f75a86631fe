"""A documented end-to-end walkthrough of mlsl_tpu (the analog of the reference's
tests/examples/mlsl_example/mlsl_example.cpp): create the environment, lay out a
data x model grid, register a small operation graph, and run training-loop phases
with asynchronous gradient synchronization.

Run on the 8-device CPU mesh (simulating a TPU slice):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/mlsl_example.py
or on real TPU hardware with no extra flags.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mlsl_tpu as mlsl
from mlsl_tpu.types import DataType, GroupType, OpType, ReductionType


def main():
    # 1. Bootstrap (reference: Environment::GetEnv().Init(&argc, &argv))
    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    print(f"process count: {world}")

    # 2. Parallelism layout: a data x model grid over the device world
    #    (reference: Environment::CreateDistribution(dataParts, modelParts))
    model_parts = 2 if world % 2 == 0 else 1
    data_parts = world // model_parts
    dist = env.create_distribution(data_parts, model_parts)
    print(f"grid: data={data_parts} x model={model_parts}")

    # 3. A raw collective through the Distribution (returns an async request;
    #    Environment.wait completes it — reference CommReq* + Environment::Wait)
    buf = dist.make_buffer(lambda p: np.full(4, float(p + 1)), 4)
    req = dist.AllReduce(buf, 4, DataType.FLOAT, ReductionType.SUM, GroupType.GLOBAL)
    out = env.wait(req)
    print("global allreduce:", dist.local_part(out, 0))

    # 4. Register a two-layer operation graph (reference: Session::AddOperation
    #    from OperationRegInfo, SetNext to wire edges, Commit to build comms)
    session = env.create_session()
    session.SetGlobalMinibatchSize(4 * data_parts)
    reg1 = session.CreateOperationRegInfo(OpType.CC)
    reg1.AddInput(8, 16, DataType.FLOAT)
    reg1.AddOutput(16, 16, DataType.FLOAT)
    reg1.AddParameterSet(8 * 16, 1, DataType.FLOAT)
    op1 = session.GetOperation(session.AddOperation(reg1, dist))

    reg2 = session.CreateOperationRegInfo(OpType.CC)
    reg2.AddInput(16, 16, DataType.FLOAT)
    reg2.AddOutput(4, 16, DataType.FLOAT)
    reg2.AddParameterSet(16 * 4, 1, DataType.FLOAT, distributed_update=True)
    op2 = session.GetOperation(session.AddOperation(reg2, dist))

    op1.SetNext(op2, 0, 0)
    session.Commit()

    # 5. Training-loop phases (reference mlsl_test loop :660-698): start the
    #    gradient collectives newest-first, overlap, then wait + update
    for it in range(3):
        for op in (op2, op1):  # backward order
            ps = op.GetParameterSet(0)
            n = ps.GetLocalKernelCount() * ps.GetKernelSize()
            grads = dist.make_buffer(lambda p: np.full(n, float(it + 1)), n)
            ps.StartGradientComm(grads)
        for op in (op1, op2):
            ps = op.GetParameterSet(0)
            reduced = ps.WaitGradientComm()
            kind = "owned shard" if ps.IsDistributedUpdate() else "full"
            if reduced is not None:
                print(
                    f"iter {it} {op.GetName()}: {kind} reduced[0] = "
                    f"{float(np.asarray(dist.local_part(reduced, 0))[0])}"
                )

    # 6. Statistics (reference Statistics::Print -> mlsl_stats.log)
    print(session.GetStats().Print("/tmp/mlsl_stats_example.log")[:200] or "(stats disabled; set MLSL_STATS=1)")

    env.finalize()
    print("example OK")


if __name__ == "__main__":
    main()
