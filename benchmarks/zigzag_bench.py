"""Causal ring attention: contiguous vs zigzag-sharded schedule.

The contiguous causal ring computes the full (2c x 2c) score block every hop
and masks ~half away; zigzag (parallel/sequence.py zigzag_ring_attention)
does exactly two unmasked (c x c) updates per hop — ~2x fewer block-FLOPs,
uniformly across devices. Round-5 committed CPU-mesh row (B1 H4 S4096 D64,
ring of 8): 1.69x (1.7-1.75x across runs of this harness on the shared box).
Needs a multi-device mesh (virtual CPU mesh or a real slice); on a single
chip the ring degenerates and this prints a skip note.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
       python benchmarks/zigzag_bench.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    import jax
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import mlsl_tpu as mlsl
    from mlsl_tpu.models.train import smap
    from mlsl_tpu.parallel.sequence import (
        ring_attention, zigzag_perm, zigzag_ring_attention,
    )

    env = mlsl.Environment.get_env().init()
    ndev = env.get_process_count()
    if ndev < 2:
        print(json.dumps({"metric": "zigzag_ring_speedup",
                          "skipped": "needs a multi-device mesh"}))
        return
    B, H, S, D = 1, 4, 4096, 64
    SP = ndev
    dist = env.create_distribution(1, 1, seq_parts=SP)
    mesh = dist.topology.mesh
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(B, H, S, D)).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    perm = zigzag_perm(S, SP)
    spec = P(None, None, "seq", None)

    ring = jax.jit(smap(
        lambda q, k, v: ring_attention(q, k, v, "seq", SP, causal=True,
                                       use_flash=False),
        mesh, in_specs=(spec,) * 3, out_specs=spec,
    ))
    zig = jax.jit(smap(
        lambda q, k, v: zigzag_ring_attention(q, k, v, "seq", SP),
        mesh, in_specs=(spec,) * 3, out_specs=spec,
    ))
    qz, kz, vz = q[:, :, perm], k[:, :, perm], v[:, :, perm]

    from benchmarks._common import device_sync

    def best_ms(f, *a, n=10):
        device_sync(f(*a))
        device_sync(f(*a))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(n):
                r = f(*a)
            device_sync(r)
            best = min(best, (time.perf_counter() - t0) / n)
        return best * 1e3

    contig = best_ms(ring, q, k, v)
    zigzag = best_ms(zig, qz, kz, vz)
    print(json.dumps({
        "metric": "zigzag_ring_speedup",
        "value": round(contig / zigzag, 3),
        "unit": "x",
        "contiguous_ms": round(contig, 2),
        "zigzag_ms": round(zigzag, 2),
        "shape": f"B{B} H{H} S{S} D{D} ring{SP}",
    }))


if __name__ == "__main__":
    main()
