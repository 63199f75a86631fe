"""Probe: how much of the ResNet-50 step is batch-norm statistics?

Compares the real model against (a) single-pass E[x^2]-E[x]^2 variance and
(b) a no-stats affine-only variant (identity stats — NOT valid training, just
an upper bound on what BN tuning could ever recover).

Measured (v5e, batch 32, round 2): two-pass ~16.5 ms, one-pass ~17.1 ms —
at small batch the rewrite did not pay. Re-measured at batch 128 (round 5,
BN_PROBE_BATCH=128): two-pass 58.8 ms, one-pass 49.2-54.2 ms, no-stats
40.8 ms — at the MXU-saturating batch the two-pass form's second activation
read dominates, so the model now uses the one-pass form with a clamped
variance (see mlsl_tpu/models/resnet.py _bn).
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from benchmarks._common import device_sync, setup_chip

jax = setup_chip()

import jax.numpy as jnp
from jax import lax

from mlsl_tpu.models import resnet


def bn_onepass(x, p, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=(0, 1, 2), keepdims=True)
    msq = jnp.mean(jnp.square(xf), axis=(0, 1, 2), keepdims=True)
    var = msq - jnp.square(mean)
    inv = lax.rsqrt(var + eps)
    return ((xf - mean) * inv * p["scale"] + p["bias"]).astype(x.dtype)


def bn_nostats(x, p, eps=1e-5):
    return (x.astype(jnp.float32) * p["scale"] + p["bias"]).astype(x.dtype)


def timed_step(bn_impl, params, batch, tag):
    orig = resnet._bn
    resnet._bn = bn_impl
    try:
        lr = 0.05

        @jax.jit
        def sgd(p, b):
            loss, g = jax.value_and_grad(resnet.loss_fn)(p, b)
            return loss, jax.tree.map(lambda w, gg: w - lr * gg, p, g)

        p = jax.tree.map(jnp.copy, params)
        for _ in range(4):
            _, p = sgd(p, batch)
        device_sync(p)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(8):
                _, p = sgd(p, batch)
            device_sync(p)
            best = min(best, (time.perf_counter() - t0) / 8 * 1e3)
        loss, _ = sgd(p, batch)
        print(f"{tag:12s}: best {best:6.2f} ms   loss {float(loss):.4f}")
        return best
    finally:
        resnet._bn = orig


def bn_twopass(x, p, eps=1e-5):
    # the pre-round-5 model form (resnet._bn is one-pass now): centered
    # variance, second full read of the activation
    mean = jnp.mean(x, axis=(0, 1, 2), dtype=jnp.float32)
    var = jnp.mean(lax.square(x.astype(jnp.float32) - mean), axis=(0, 1, 2))
    a = lax.rsqrt(var + eps) * p["scale"]
    b = p["bias"] - mean * a
    return (x * a + b).astype(x.dtype)


def main():
    BATCH = int(os.environ.get("BN_PROBE_BATCH", "32"))
    print("batch:", BATCH)
    params = jax.device_put(resnet.init_resnet50(jax.random.PRNGKey(0), 1000))
    rng = np.random.default_rng(0)
    x = jax.device_put(jnp.asarray(rng.normal(size=(BATCH, 224, 224, 3)), jnp.float32))
    y = jax.device_put(jnp.asarray(rng.integers(0, 1000, size=(BATCH,)), jnp.int32))
    timed_step(bn_twopass, params, (x, y), "two-pass")
    timed_step(resnet._bn, params, (x, y), "model(1p)")
    timed_step(bn_onepass, params, (x, y), "one-pass")
    timed_step(bn_nostats, params, (x, y), "no-stats")


if __name__ == "__main__":
    main()
