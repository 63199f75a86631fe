"""Input-pipeline benchmark: the wire-dtype x depth x cache feed grid.

Streams a fixed synthetic image dataset through the device feed pipeline
(mlsl_tpu.data: DeviceFeed + AsyncLoader) for every cell of
{wire dtype} x {prefetch depth} x {cache on/off}, with a small jitted
consumer forcing materialization of each decoded batch. Reports effective
images/s, achieved H2D MB/s, wire MB/batch, and per-batch input stall — the
numbers that say whether a training job on this machine should ship uint8,
bf16, or full-width batches, how deep to prefetch, and whether its dataset
should pin in HBM.

Epoch 0 of every cell is warmup (staging + decode compiles); the timed
window covers the REPLAY epochs, where the cache pays off (or doesn't).

The closing ``input_pipeline_best`` row names the winning cell — its
``feed_depth`` is the value an operator (or a tuned profile,
tuner.KNOB_RANGES) would carry as ``MLSL_FEED_DEPTH`` on this machine
(docs/TUNING.md §12).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/input_pipeline_bench.py [--smoke]
--smoke trims the grid and shapes for the tier-1 wiring
(tests/test_feed.py, ``bench_smoke`` marker). Prints one JSON row per cell
(the standard capture-row shape: a "metric" field per line).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast tier-1 mode: tiny shapes, trimmed grid")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    import mlsl_tpu as mlsl
    from mlsl_tpu.core import stats as core_stats
    from mlsl_tpu.data import AsyncLoader, DeviceFeed

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    dist = env.create_distribution(world, 1)
    topo = dist.topology

    if args.smoke:
        batch, hw, n_batches, epochs = 16, 16, 3, 2
        wires = ("none", "uint8")
        depths = (2,)
        caches = (0, 64)
    else:
        batch, hw, n_batches, epochs = 64, 64, 6, 3
        wires = ("none", "bf16", "uint8", "int8")
        depths = (1, 2, 4)
        caches = (0, 512)

    rng = np.random.default_rng(0)
    dataset = [
        (rng.normal(size=(batch, hw, hw, 3)).astype(np.float32),
         rng.integers(0, 100, size=(batch,)).astype(np.int32))
        for _ in range(n_batches)
    ]

    @jax.jit
    def consume(b):
        # forces materialization of the decoded batch; tiny on purpose —
        # this bench measures the FEED, bench.py measures feed-under-train
        return sum(jnp.sum(l.astype(jnp.float32)) for l in jax.tree.leaves(b))

    def run_cell(wire, depth, cache_mb):
        core_stats.reset_feed_counters()
        feed = DeviceFeed(dataset, topo, wire=wire, cache_mb=cache_mb,
                          epochs=epochs + 1)
        loader = AsyncLoader(feed, depth=depth)
        it = iter(loader)
        for _ in range(n_batches):  # warm epoch: staging + decode compiles
            float(consume(next(it)))
        f0 = dict(core_stats.FEED_COUNTERS)
        st0 = loader.stats()
        t0 = time.perf_counter()
        count = 0
        for b in it:
            float(consume(b))
            count += 1
        dt = time.perf_counter() - t0
        f1 = dict(core_stats.FEED_COUNTERS)
        st1 = loader.stats()
        loader.close()
        staged = int(f1["batches_staged"] - f0["batches_staged"])
        wire_bytes = f1["wire_bytes"] - f0["wire_bytes"]
        return {
            "metric": "input_pipeline",
            "wire": wire,
            "depth": depth,
            "cache_mb": cache_mb,
            "images_per_s": round(count * batch / dt, 1),
            "h2d_mbps": round(wire_bytes / 1e6 / dt, 2),
            "wire_mb_per_batch": (
                round(wire_bytes / 1e6 / staged, 3) if staged else 0.0
            ),
            "stall_ms_per_batch": round(
                (st1["stall_ms"] - st0["stall_ms"]) / max(count, 1), 3
            ),
            "cache_hits": int(f1["cache_hits"] - f0["cache_hits"]),
            "batch": batch,
            "hw": hw,
            "epochs_timed": epochs,
        }

    rows = []
    for wire in wires:
        for depth in depths:
            for cache_mb in caches:
                row = run_cell(wire, depth, cache_mb)
                rows.append(row)
                print(json.dumps(row), flush=True)

    best = max(rows, key=lambda r: r["images_per_s"])
    print(json.dumps({
        "metric": "input_pipeline_best",
        "wire": best["wire"],
        "feed_depth": best["depth"],
        "cache_mb": best["cache_mb"],
        "images_per_s": best["images_per_s"],
        "device": jax.devices()[0].device_kind,
    }), flush=True)


if __name__ == "__main__":
    main()
