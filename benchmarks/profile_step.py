"""Capture an XLA op-level profile of the ResNet-50 train step on the chip.

Writes a jax.profiler trace of a few steps to --logdir, then (if the
tensorboard profile plugin is importable) prints the top-k ops by self time —
the ground truth for where the step's milliseconds go (TUNING.md step 6.3).
"""

import argparse
import glob
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from benchmarks._common import device_sync, setup_chip

jax = setup_chip()

import jax.numpy as jnp

from mlsl_tpu.models import resnet


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--logdir", default="/tmp/mlsl_profile")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args()

    params = jax.device_put(resnet.init_resnet50(jax.random.PRNGKey(0), 1000))
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(
        rng.normal(size=(args.batch, 224, 224, 3)), jnp.float32))
    y = jax.device_put(jnp.asarray(
        rng.integers(0, 1000, size=(args.batch,)), jnp.int32))
    lr = 0.05

    @jax.jit
    def sgd(p, b):
        loss, g = jax.value_and_grad(resnet.loss_fn)(p, b)
        return loss, jax.tree.map(lambda w, gg: w - lr * gg, p, g)

    _, p2 = sgd(params, (x, y))  # compile + warm
    device_sync(p2)

    jax.profiler.start_trace(args.logdir)
    p = params
    for _ in range(args.steps):
        _, p = sgd(p, (x, y))
    device_sync(p)
    jax.profiler.stop_trace()
    print("trace written to", args.logdir)

    xplanes = sorted(glob.glob(
        os.path.join(args.logdir, "**", "*.xplane.pb"), recursive=True))
    if not xplanes:
        print("no xplane.pb found")
        return
    xp = xplanes[-1]
    try:
        from tensorboard_plugin_profile.convert import raw_to_tool_data
        data, _ = raw_to_tool_data.xspace_to_tool_data(
            [xp], "framework_op_stats^", {})
    except Exception as e:
        print(f"op-stats conversion unavailable ({e}); raw trace at {xp}")
        return
    import csv
    import io
    rows = list(csv.DictReader(io.StringIO(
        data.decode() if isinstance(data, bytes) else data)))
    key = None
    for cand in ("total_self_time_in_us", "self_time_us", "Total self-time (us)"):
        if rows and cand in rows[0]:
            key = cand
            break
    if key is None:
        print("columns:", list(rows[0].keys()) if rows else "none")
        return
    rows.sort(key=lambda r: float(r[key] or 0), reverse=True)
    tot = sum(float(r[key] or 0) for r in rows)
    if tot <= 0:
        print("no nonzero self-time rows")
        return
    print(f"total self time: {tot/1e3:.2f} ms over {args.steps} steps")
    for r in rows[: args.top]:
        us = float(r[key] or 0)
        name = (r.get("operation") or r.get("Operation")
                or r.get("op_name") or "?")[:80]
        cat = r.get("category") or r.get("Type") or ""
        print(f"{us/tot*100:5.1f}%  {us/1e3/args.steps:8.3f} ms/step  "
              f"{cat:<18} {name}")


if __name__ == "__main__":
    main()
