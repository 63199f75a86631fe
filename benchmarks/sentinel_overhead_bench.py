"""Integrity-sentinel overhead bench: the <2% contract at the default interval.

Measures the three costs the sentinel adds to a training loop on the 8-dev
CPU mesh, against the per-layer trainer's measured step floor:

- ``gate_ms``   — one quality-gate screen (the fused nonfinite/norm program +
  the single host sync for the verdict), paid EVERY step when
  ``MLSL_SENTINEL_GATE`` is armed;
- ``audit_ms``  — one cross-replica consistency audit (blockwise fingerprint
  + on-device pmin/pmax + the digest readback), paid every
  ``MLSL_SENTINEL_EVERY`` steps;
- the comparative armed-vs-off step delta (reported, but the CPU mesh
  carries +-15% run-to-run noise — the accounted model is the contract,
  same reasoning as trace_overhead_bench.py).

The acceptance row (ISSUE 9): ``overhead_frac_default`` =
``(gate_ms + audit_ms / DEFAULT_INTERVAL) / step_ms`` < 0.02 at the default
interval. The full run also prints the overhead-vs-interval curve so TUNING
§13's sizing guidance is measured, not guessed.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/sentinel_overhead_bench.py [--smoke]
Prints one JSON row (capture-row shape, metric=sentinel_overhead).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

#: the interval TUNING §13 recommends as the starting point: audits amortize
#: to noise while a silent corruption is still caught within ~1 minute of
#: steps on a real pod
DEFAULT_INTERVAL = 50


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast tier-1 mode: fewer iters")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    import mlsl_tpu as mlsl
    from mlsl_tpu import sentinel
    from mlsl_tpu.models.train import DataParallelTrainer

    warmup, iters = (3, 8) if args.smoke else (5, 20)
    intervals = (1, 10, DEFAULT_INTERVAL) if args.smoke else (
        1, 5, 10, DEFAULT_INTERVAL, 200
    )

    # A REPRESENTATIVE compute:params ratio is what makes this row honest:
    # the gate's cost scales with the gradient footprint, the step's with
    # batch x FLOPs — a toy batch would overstate the gate fraction by an
    # order of magnitude vs any real workload (ResNet-50 does ~100x more
    # compute per parameter than even this config; 256 examples per replica
    # is an ordinary data-parallel shard). The distortion to beware on the
    # CPU proof mesh: memory-bound elementwise work (the gate's scan) runs
    # ~100x closer to the matmul rate than on a real TPU, so the measured
    # fraction here is an UPPER bound on hardware.
    K, D, B = 6, 512, 8192
    layers = [f"l{i}" for i in range(K)]

    def init_params(key):
        ks = jax.random.split(key, K)
        return {
            f"l{i}": {
                "w": jax.random.normal(k, (D, D)) * 0.05,
                "b": jnp.zeros((D,)),
            }
            for i, k in enumerate(ks)
        }

    def loss_fn(params, batch):
        x, y = batch
        h = x
        for i in range(K):
            h = jnp.tanh(h @ params[f"l{i}"]["w"] + params[f"l{i}"]["b"])
        return jnp.mean((h[:, 0] - y) ** 2)

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    dist = env.create_distribution(world, 1)
    sess = env.create_session()
    sess.set_global_minibatch_size(B)
    trainer = DataParallelTrainer(
        env, dist, sess, init_params(jax.random.PRNGKey(0)), loss_fn,
        layers, lambda p, n: p[n], lr=0.05,
    )
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, D)).astype(np.float32)
    y = rng.normal(size=(B,)).astype(np.float32)
    batch = trainer.shard_batch(x, y)

    def timed(fn, n, blocks=3):
        # best-of-blocks: the min is each path's noise-free floor (load
        # spikes only ever ADD time on this shared box — the same reasoning
        # as trace_overhead_bench.py)
        best = float("inf")
        per = max(1, n // blocks)
        for _ in range(blocks):
            t0 = time.perf_counter()
            for _ in range(per):
                fn()
            best = min(best, (time.perf_counter() - t0) / per * 1e3)
        return best

    # -- the step floor (sentinel off) ------------------------------------
    for _ in range(warmup):
        jax.block_until_ready(trainer.step(batch))
    step_ms = timed(lambda: jax.block_until_ready(trainer.step(batch)), iters)

    # -- isolated gate + audit cost (the accounted model) ------------------
    s = sentinel.Sentinel(trainer.mesh, gate="warn",
                          every=DEFAULT_INTERVAL)
    loss, grads = trainer._grad_fn(trainer.params, batch)
    jax.block_until_ready(loss)
    for _ in range(warmup):
        s.gate(loss, grads, trainer.params, step=0)
    gate_ms = timed(lambda: s.gate(loss, grads, trainer.params, step=0),
                    iters)
    for _ in range(warmup):
        s.audit_now(trainer, step=0)
    audit_ms = timed(lambda: s.audit_now(trainer, step=0), iters)

    # -- comparative armed-vs-off delta (noisy; reported, not the contract)
    trainer.sentinel = sentinel.Sentinel(trainer.mesh, gate="warn",
                                         every=DEFAULT_INTERVAL)
    for _ in range(warmup):
        jax.block_until_ready(trainer.step(batch))
    armed_ms = timed(lambda: jax.block_until_ready(trainer.step(batch)),
                     iters)
    trainer.sentinel = None

    curve = {
        str(k): round((gate_ms + audit_ms / k) / step_ms, 4)
        for k in intervals
    }
    row = {
        "metric": "sentinel_overhead",
        "devices": world,
        "iters": iters,
        "step_ms": round(step_ms, 3),
        "gate_ms": round(gate_ms, 3),
        "audit_ms": round(audit_ms, 3),
        "interval_default": DEFAULT_INTERVAL,
        "overhead_frac_default": curve[str(DEFAULT_INTERVAL)],
        "overhead_frac_by_interval": curve,
        "armed_step_ms": round(armed_ms, 3),
        "delta_frac": round((armed_ms - step_ms) / step_ms, 4),
        "smoke": bool(args.smoke),
    }
    print(json.dumps(row))
    env.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
