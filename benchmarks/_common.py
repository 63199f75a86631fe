"""Shared helpers for the measurement tools kept beside the benchmark
(``perf/``): one synchronisation primitive and two host-clock timers.

A bench process imports JAX itself and fails loudly if the chip cannot be had:
a chip belongs to one process, so nothing here probes it from a child first.
The platform comes from ``JAX_PLATFORMS`` and the compile cache from
``Environment.init`` (mlsl_tpu.sysinfo.resolve_compile_cache).
"""

import os
import sys

REPO_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO_ROOT)


def setup_chip():
    """Import JAX in THIS process and arm the one compile cache, for the
    raw-JAX scripts that build no Environment (which does it in init)."""
    import jax

    from mlsl_tpu.sysinfo import resolve_compile_cache

    resolve_compile_cache()
    return jax


def device_sync(tree):
    """Wait for a result tree by reading ONE element of its first leaf back to
    the host. The element is sliced device-side first so only 4 bytes cross
    the link (np.asarray of a full leaf would ship the whole array inside the
    timed window). Whether this is needed over ``jax.block_until_ready`` on
    this machine is recorded in PERF.md (chip_smoke.py times one step both
    ways)."""
    import numpy as np
    import jax

    leaves = jax.tree.leaves(tree)
    if not leaves:
        # degenerate result (e.g. wait on a 1-member group returns None): no
        # output to read back, so this is only a host round trip — it does NOT
        # order against in-flight device work; callers timing real work must
        # sync on a tree that depends on it
        leaves = [jax.numpy.zeros((1,))]
    return float(np.asarray(jax.numpy.ravel(leaves[0])[0]))


def timed_scan(step, carry0, iters=100, blocks=5):
    """Per-iteration ms for a carry→carry `step`, executed as a lax.scan
    inside ONE device computation, using a PAIRED-length estimate: best time
    at 2*iters minus best time at iters, divided by iters. The difference
    removes whatever one launch plus one ``device_sync`` costs, whatever
    that is, from a sub-millisecond kernel's time. Blocks alternate
    short/long so slow drift hits both arms equally. The carry dependency
    serializes iterations and defeats CSE; callers must make `step` keep its
    values bounded."""
    import time

    import jax
    from jax import lax

    def make(n):
        return jax.jit(
            lambda c: lax.scan(lambda c, _: (step(c), None), c, None, length=n)[0]
        )

    run1, run2 = make(iters), make(2 * iters)
    device_sync(run1(carry0))  # compile + warm
    device_sync(run2(carry0))
    best1 = best2 = float("inf")
    for _ in range(blocks):
        t0 = time.perf_counter()
        device_sync(run1(carry0))
        best1 = min(best1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        device_sync(run2(carry0))
        best2 = min(best2, time.perf_counter() - t0)
    return max((best2 - best1) / iters * 1e3, 1e-3)


def timed(fn, *args, iters=30, warmup=5, blocks=3):
    """Best-of-blocks per-call ms ending in ``device_sync``, as a PAIRED-block
    estimate: each round times a block of K calls and a block of 2K calls;
    the reported value is (best_2K - best_K) / K, which removes the fixed
    cost of one sync from the per-call time (it does NOT remove per-call
    dispatch cost: both arms pay it per call). Minima across rounds are
    taken per arm."""
    import time

    r = fn(*args)  # also covers warmup=0: r must exist for the first sync
    for _ in range(max(0, warmup - 1)):
        r = fn(*args)
    device_sync(r)
    # each round runs K + 2K calls; keep the TOTAL near the caller's iters
    # budget so existing call sites don't silently triple their wall time —
    # but never below 2 calls per arm, where the paired difference would ride
    # on a single dispatch's jitter
    per_block = max(2, iters // (3 * blocks))
    best1 = best2 = float("inf")
    for _ in range(blocks):
        t0 = time.perf_counter()
        for _ in range(per_block):
            r = fn(*args)
        device_sync(r)
        best1 = min(best1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(2 * per_block):
            r = fn(*args)
        device_sync(r)
        best2 = min(best2, time.perf_counter() - t0)
    # floor at 1 µs: callers derive rates by dividing by this
    return max((best2 - best1) / per_block * 1e3, 1e-3)
