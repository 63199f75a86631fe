"""Bisection experiments for the ResNet-50 step time (run on a real chip).

Times fwd-only vs fwd+bwd (value_and_grad), the SGD update, and donation, at
several batch sizes, plus bare dispatch latency — so tuning effort goes where
the milliseconds are.
"""

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from benchmarks._common import device_sync, setup_chip, timed

jax = setup_chip()

import jax.numpy as jnp

from mlsl_tpu.models import resnet


def timeit(fn, *args, iters=20, warmup=4):
    return timed(fn, *args, iters=iters, warmup=warmup)


def main():
    dev = jax.devices()[0]
    print("device:", dev.device_kind)

    # bare dispatch latency: tiny no-op jit, timed the same way
    tiny = jax.jit(lambda x: x + 1)
    z = jax.device_put(jnp.zeros((8, 8)))
    print(f"tiny-op round trip: {timeit(tiny, z, iters=50):7.3f} ms")

    params = jax.device_put(resnet.init_resnet50(jax.random.PRNGKey(0), 1000))
    rng = np.random.default_rng(0)

    lr = 0.05
    fwd = jax.jit(resnet.apply_resnet50)
    vg = jax.jit(lambda p, b: jax.value_and_grad(resnet.loss_fn)(p, b))

    @jax.jit
    def sgd(p, b):
        loss, g = jax.value_and_grad(resnet.loss_fn)(p, b)
        return loss, jax.tree.map(lambda w, gg: w - lr * gg, p, g)

    @functools.partial(jax.jit, donate_argnums=0)
    def sgd_donate(p, b):
        loss, g = jax.value_and_grad(resnet.loss_fn)(p, b)
        return loss, jax.tree.map(lambda w, gg: w - lr * gg, p, g)

    def timeit_state(fn, p, b, iters=10, warmup=4):
        # threads params through (for donated variants)
        for _ in range(warmup):
            _, p = fn(p, b)
        device_sync(p)
        t0 = time.perf_counter()
        for _ in range(iters):
            _, p = fn(p, b)
        device_sync(p)
        return (time.perf_counter() - t0) / iters * 1e3

    for batch in (32, 64, 128):
        x = jax.device_put(
            jnp.asarray(rng.normal(size=(batch, 224, 224, 3)), jnp.float32)
        )
        y = jax.device_put(jnp.asarray(rng.integers(0, 1000, size=(batch,)), jnp.int32))
        f_ms = timeit(fwd, params, x, iters=10)
        v_ms = timeit(vg, params, (x, y), iters=10)
        s_ms = timeit_state(sgd, params, (x, y))
        d_ms = timeit_state(sgd_donate, jax.tree.map(jnp.copy, params), (x, y))
        print(
            f"batch {batch:4d}: fwd {f_ms:6.2f}  vg {v_ms:6.2f}  "
            f"sgd {s_ms:6.2f}  sgd+donate {d_ms:6.2f} ms "
            f"({batch/d_ms*1e3:6.0f} img/s)"
        )


if __name__ == "__main__":
    main()
