"""What the plain references share: products in the control's lower
precision, and the names of a tree's leaves. ``jax`` alone, nothing of the
program."""

import jax
import jax.numpy as jnp


def to_low(x, precision, dtype=None):
    """Round to the lower precision and back: bfloat16 as it is, float8 with
    one scale a tensor (e4m3 for operands, e5m2 for cotangents, as float8
    training does)."""
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        dtype = dtype or jnp.float8_e4m3fn
        scale = jnp.max(jnp.abs(x)) / float(jnp.finfo(dtype).max) + 1e-30
        return (x / scale).astype(dtype).astype(jnp.float32) * scale
    raise ValueError(precision)


def product(fn, a, b, precision):
    """``fn(a, b)``, a matrix product or convolution at ``highest``. In a
    lower precision both operands are rounded on the way in, and in the
    backward pass the cotangent is rounded too, so that the control computes
    its gradients in the lower precision and not only its forward pass."""
    if precision == "f32":
        return fn(a, b)

    @jax.custom_vjp
    def low(a, b):
        return fn(to_low(a, precision), to_low(b, precision))

    def fwd(a, b):
        ra, rb = to_low(a, precision), to_low(b, precision)
        return fn(ra, rb), (ra, rb)

    def bwd(saved, ct):
        ct = to_low(ct, precision, jnp.float8_e5m2 if precision == "fp8" else None)
        return jax.vjp(fn, *saved)[1](ct)

    low.defvjp(fwd, bwd)
    return low(a, b)


def leaf_paths(tree):
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
