"""Compiled-overlap benchmark: in-graph vs host per-layer gradient schedule.

The acceptance measurement for the compiled overlap engine (comm/overlap.py):
a ResNet-50-shaped per-layer gradient stream — one registered layer per
conv+BN group plus the fc head, ~54 layers with real ResNet-50 parameter
counts — trained through the SAME DataParallelTrainer twice:

- **host per-layer** (``force_graph_path=True``): the Session/Operation
  Start/Wait engine, one XLA dispatch per layer collective plus the barrier
  update program — the schedule BENCH_r05 showed gains nothing on chip
  (``per_layer_vs_fused: 1.0``).
- **compiled** (``overlap_compiled=True``): ONE donation-enabled step
  program with every layer's collective emitted in-graph, newest-first,
  staged over ``--stages`` unit starts.

The model's compute is deliberately negligible (per-tensor elementwise
loss): the rows measure the dispatch/communication schedule itself, which is
what the engine replaces. A fused monolithic raw-JAX jit of the same math
provides the ``compiled_vs_fused`` context ratio bench.py tracks on chip.

Layer count stays ~54 (the real bench.py per-layer trainer's count): the CPU
proof backend deadlocks past a few dozen concurrent in-flight collectives
(the PR 2 hazard), and the host twin keeps all layers in flight per step.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/overlap_compiled_bench.py [--smoke]
--smoke scales tensor sizes down (~1/16, same layer count — the per-layer
dispatch floor being beaten is per layer) and trims iters; the tier-1 wiring
(tests/test_overlap_compiled.py, ``bench_smoke``) runs this mode. Prints one
JSON row per configuration (the standard capture-row shape).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def resnet50_layer_counts(scale: int = 1):
    """Per-LAYER parameter counts of a ResNet-50 at conv+BN granularity:
    53 conv+BN groups + the fc head = 54 layers (the same granularity the
    real bench.py per-layer trainer registers). ``scale`` divides counts
    (smoke) without changing the LAYER count — the per-layer host dispatch
    floor is per layer."""
    counts = []

    def conv(cin, cout, k):
        counts.append(cin * cout * k * k + 2 * cout)  # conv + BN gamma/beta

    conv(3, 64, 7)
    cin = 64
    for blocks, mid in [(3, 64), (4, 128), (6, 256), (3, 512)]:
        for b in range(blocks):
            conv(cin, mid, 1)
            conv(mid, mid, 3)
            conv(mid, mid * 4, 1)
            if b == 0:
                conv(cin, mid * 4, 1)
            cin = mid * 4
    counts.append(2048 * 1000 + 1000)  # fc
    return [max(c // scale, 64) for c in counts]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast tier-1 mode: scaled-down tensors, fewer iters")
    ap.add_argument("--stages", type=int, default=None,
                    help="overlap staging depth (default: config)")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    import mlsl_tpu as mlsl
    from benchmarks._common import device_sync
    from mlsl_tpu.models.train import DataParallelTrainer
    from mlsl_tpu.types import CompressionType

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    dist0 = env.create_distribution(world, 1)
    degenerate = (
        {"note": "degenerate group: schedule structure only"}
        if dist0.get_process_count_data() == 1 else {}
    )

    counts = resnet50_layer_counts(scale=16 if args.smoke else 1)
    layers = [f"l{i}" for i in range(len(counts))]
    rng = np.random.default_rng(0)
    params = {
        n: {"w": jnp.asarray(rng.normal(size=(c,)).astype(np.float32) * 0.1)}
        for n, c in zip(layers, counts)
    }

    def loss_fn(p, batch):
        x, _ = batch
        s = jnp.mean(x)
        tot = 0.0
        for n in layers:
            w = p[n]["w"]
            tot = tot + jnp.sum(w * s + 0.005 * w * w) / w.shape[0]
        return tot / len(layers)

    def get_layer(p, name):
        return p[name]

    batch = 32
    x = rng.normal(size=(batch, 16)).astype(np.float32)
    y = rng.integers(0, 4, size=(batch,)).astype(np.int32)

    def build(overlap, compression=CompressionType.NONE, stages=None):
        if stages is not None:
            env.config.overlap_stages = stages
        dist = env.create_distribution(world, 1)
        s = env.create_session()
        s.set_global_minibatch_size(batch)
        # force_graph_path on BOTH twins: the host twin must take the
        # Start/Wait engine, and on a single-device world the compiled twin
        # would otherwise lose to the fused no-comm shortcut and never build
        # the engine (the `degenerate` rows measure schedule structure)
        t = DataParallelTrainer(
            env, dist, s, params, loss_fn, layers, get_layer, lr=0.05,
            compression=compression, overlap_compiled=overlap,
            force_graph_path=True,
        )
        return t, t.shard_batch(x, y)

    warmup, blocks, per_block = (2, 3, 2) if args.smoke else (3, 5, 5)

    def timed(t, b):
        for _ in range(warmup):
            t.step(b)
        device_sync(t.params)
        best = float("inf")
        for _ in range(blocks):
            t0 = time.perf_counter()
            for _ in range(per_block):
                t.step(b)
            device_sync(t.params)
            best = min(best, (time.perf_counter() - t0) / per_block)
        return best * 1e3  # ms

    # fused monolithic raw-JAX reference (the compiled_vs_fused anchor):
    # batch sharded over the mesh, params replicated, XLA/GSPMD owns the
    # gradient collectives — bench.py's raw-baseline methodology
    from jax.sharding import NamedSharding, PartitionSpec as P

    lr = 0.05
    mesh = dist0.topology.mesh

    @jax.jit
    def fused_step(p, bx, by):
        loss, grads = jax.value_and_grad(loss_fn)(p, (bx, by))
        return loss, jax.tree.map(lambda w, g: w - lr * g, p, grads)

    raw_p = jax.device_put(params, NamedSharding(mesh, P()))
    data_spec = P(("replica", "data", "seq", "model"))
    bx = jax.device_put(jnp.asarray(x), NamedSharding(mesh, data_spec))
    by = jax.device_put(jnp.asarray(y), NamedSharding(mesh, data_spec))

    def timed_fused():
        nonlocal raw_p
        for _ in range(warmup):
            _, raw_p = fused_step(raw_p, bx, by)
        device_sync(raw_p)
        best = float("inf")
        for _ in range(blocks):
            t0 = time.perf_counter()
            for _ in range(per_block):
                _, raw_p = fused_step(raw_p, bx, by)
            device_sync(raw_p)
            best = min(best, (time.perf_counter() - t0) / per_block)
        return best * 1e3

    fused_ms = timed_fused()

    rows = [("plain", CompressionType.NONE)]
    if not args.smoke:
        rows.append(("quant", CompressionType.QUANTIZATION))
    for tag, comp in rows:
        th, bh = build(False, comp)
        host_ms = timed(th, bh)
        tc, bc = build(True, comp, stages=args.stages)
        assert tc._overlap is not None, "compiled overlap did not engage"
        compiled_ms = timed(tc, bc)
        print(json.dumps({
            "metric": "overlap_compiled_resnet50_stream",
            "compression": tag,
            "layers": len(layers),
            "params": sum(counts),
            "stages": tc._overlap.plan.stages,
            "units": len(tc._overlap.plan.units),
            "host_per_layer_ms": round(host_ms, 3),
            "compiled_ms": round(compiled_ms, 3),
            "speedup": round(host_ms / compiled_ms, 3),
            "fused_ms": round(fused_ms, 3),
            "compiled_vs_fused": round(fused_ms / compiled_ms, 4),
            "accept": host_ms / compiled_ms >= 1.1,
            "unit": "ms",
            **degenerate,
        }))

    if not args.smoke:
        # staging-depth curve: how the interleave window moves the number
        # (on sim meshes usually flat — the backend serializes collectives)
        for stages in (1, 2, 4):
            tc, bc = build(True, stages=stages)
            ms = timed(tc, bc)
            print(json.dumps({
                "metric": "overlap_compiled_stages",
                "stages": stages,
                "compiled_ms": round(ms, 3),
                "unit": "ms",
                **degenerate,
            }))


if __name__ == "__main__":
    main()
