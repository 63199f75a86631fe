"""Telemetry-plane overhead bench: the zero-alloc disabled path and the <2%
armed-path contract at the default cadence (ISSUE 15 acceptance).

Three measurements against the per-layer trainer's step floor on the 8-dev
CPU proof mesh:

- **disabled_zero_alloc** — tracemalloc-asserted (the tracer precedent):
  with the registry disarmed, a full training step must attribute ZERO
  allocations to ``obs/metrics.py`` — the disabled path is one module-attr
  load and a None test per site. The bench FAILS (exit 1) if this does not
  hold; it is a correctness gate, not a number.
- **accounted model** (the contract, trace_overhead_bench reasoning: the
  CPU mesh carries ±15% comparative noise, so the per-event costs are
  measured in isolation and composed):
  ``overhead_frac = (observe_ns x events_per_step + tick_ms / EVERY) /
  step_ms`` where events_per_step = one step_ms observe + one
  dispatch-wait + algbw observe per layer, and tick_ms is one full cadence
  tick (loss readback + family snapshot + ring sample + JSONL append).
  Acceptance: < 0.02 at the default ``MLSL_METRICS_EVERY`` (asserted in
  --smoke via the bench_smoke tier-1 test).
- **comparative delta** — armed-vs-off step time, reported but not the
  contract (noise).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/metrics_overhead_bench.py [--smoke]
Prints one JSON row (capture-row shape, metric=metrics_overhead).
"""

import argparse
import json
import os
import sys
import time
import tracemalloc

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

#: the default sampler cadence (obs/metrics.py DEFAULT_EVERY): one cadence
#: tick per this many steps pays the loss readback + snapshot + JSONL append
DEFAULT_EVERY = 20


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast tier-1 mode: fewer iters")
    args = ap.parse_args()

    import numpy as np
    import jax
    import jax.numpy as jnp

    import mlsl_tpu as mlsl
    from mlsl_tpu.obs import metrics as obs_metrics

    # the bench owns the registry lifecycle end to end
    obs_metrics.disable()

    warmup, iters = (3, 8) if args.smoke else (5, 20)
    cadences = (1, 5, DEFAULT_EVERY) if args.smoke else (
        1, 5, 10, DEFAULT_EVERY, 100
    )

    # representative shape: same reasoning as sentinel_overhead_bench — the
    # per-step telemetry cost is per LAYER (one dispatch-wait observe each)
    # plus per STEP, so a multi-layer model with a real batch keeps the
    # measured fraction honest
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

    from mlsl_tpu.models.train import DataParallelTrainer

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    dist = env.create_distribution(world, 1)
    sess = env.create_session()
    sess.set_global_minibatch_size(B)
    trainer = DataParallelTrainer(
        env, dist, sess, init_params(jax.random.PRNGKey(0)), loss_fn,
        layers, lambda p, n: p[n], lr=0.05, force_graph_path=True,
    )
    rng = np.random.default_rng(0)
    x = rng.normal(size=(B, D)).astype(np.float32)
    y = rng.normal(size=(B,)).astype(np.float32)
    batch = trainer.shard_batch(x, y)

    def timed(fn, n, blocks=3):
        best = float("inf")
        per = max(1, n // blocks)
        for _ in range(blocks):
            t0 = time.perf_counter()
            for _ in range(per):
                fn()
            best = min(best, (time.perf_counter() - t0) / per * 1e3)
        return best

    # -- the step floor (metrics off) --------------------------------------
    assert obs_metrics._registry is None
    for _ in range(warmup):
        jax.block_until_ready(trainer.step(batch))
    step_ms = timed(lambda: jax.block_until_ready(trainer.step(batch)), iters)

    # -- disabled path: zero allocations attributed to obs/metrics.py ------
    obs_dir = os.path.dirname(os.path.abspath(obs_metrics.__file__))
    metrics_file = os.path.join(obs_dir, "metrics.py")
    tracemalloc.start()
    try:
        jax.block_until_ready(trainer.step(batch))
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    leaks = snap.filter_traces(
        [tracemalloc.Filter(True, metrics_file)]
    ).statistics("filename")
    disabled_zero_alloc = not leaks
    if not disabled_zero_alloc:
        print(f"metrics_overhead: DISABLED PATH ALLOCATED: {leaks}",
              file=sys.stderr)

    # -- accounted per-event costs -----------------------------------------
    reg = obs_metrics.enable(every=DEFAULT_EVERY)
    h = reg.histogram("mlsl_step_ms")
    n_obs = 20000 if not args.smoke else 5000
    t0 = time.perf_counter_ns()
    for _ in range(n_obs):
        h.observe(7.5)
    observe_ns = (time.perf_counter_ns() - t0) / n_obs

    # one full cadence tick: loss readback (device sync) + family snapshot
    # + ring sample + JSONL append — measured through the trainer's own
    # tick path so the model prices what production pays
    loss = trainer.step(batch)
    jax.block_until_ready(loss)
    for _ in range(2):
        trainer._sample_telemetry(reg, loss)
    tick_ms = timed(lambda: trainer._sample_telemetry(reg, loss),
                    8 if args.smoke else 16)

    # events per step on this trainer: one step_ms observe + per-layer
    # (dispatch_wait + algbw) observes from the request layer
    events_per_step = 1 + 2 * K
    curve = {
        str(k): round(
            (observe_ns * events_per_step / 1e6 + tick_ms / k) / step_ms, 5
        )
        for k in cadences
    }

    # -- comparative delta (reported, not the contract) --------------------
    for _ in range(warmup):
        jax.block_until_ready(trainer.step(batch))
    armed_ms = timed(lambda: jax.block_until_ready(trainer.step(batch)),
                     iters)
    obs_metrics.disable()

    row = {
        "metric": "metrics_overhead",
        "devices": world,
        "iters": iters,
        "step_ms": round(step_ms, 3),
        "disabled_zero_alloc": disabled_zero_alloc,
        "observe_ns": round(observe_ns, 1),
        "tick_ms": round(tick_ms, 3),
        "events_per_step": events_per_step,
        "cadence_default": DEFAULT_EVERY,
        "overhead_frac_default": curve[str(DEFAULT_EVERY)],
        "overhead_frac_by_cadence": curve,
        "armed_step_ms": round(armed_ms, 3),
        "delta_frac": round((armed_ms - step_ms) / step_ms, 4),
        "smoke": bool(args.smoke),
    }
    print(json.dumps(row))
    env.finalize()
    if not disabled_zero_alloc:
        return 1
    if row["overhead_frac_default"] >= 0.02:
        print(f"metrics_overhead: armed path {row['overhead_frac_default']}"
              " >= 0.02 of the step at the default cadence", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
