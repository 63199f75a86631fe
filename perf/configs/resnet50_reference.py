"""Plain reference of ResNet-50 (He et al. 2015, table 1, 50-layer column)
as the configuration states it: bottleneck blocks (3, 4, 6, 3), the stride
of a stage's first block on its 3x3 convolution, batch normalisation with the
statistics of the rows at hand, a 7x7/2 stem, 3x3/2 max pooling, global
average pooling and one classifier. ``jax.numpy``/``lax`` in float32 with
every product at ``highest`` precision; the two-pass variance. It imports
nothing of the program and makes its own weights from the seed, in the tree
the program's entry takes (``stem.conv``, ``stage2[3].bn1.scale`` ...).

``precision``: 'f32' is the reference; 'fp8' the control (every convolution
and the classifier take their operands rounded to float8_e4m3 with one scale
a tensor); 'bf16' rounds them to bfloat16.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perf.lib.lowprec import leaf_paths, product  # noqa: F401 (leaf_paths: the adapters' interface)

HI = lax.Precision.HIGHEST


def _he(key, kh, kw, cin, cout):
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) \
        * np.sqrt(2.0 / (kh * kw * cin))


def _bn_init(c, scale=1.0):
    return {"scale": jnp.full((c,), scale, jnp.float32),
            "bias": jnp.zeros((c,), jnp.float32)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _init(key, blocks, widths, stem, classes, residual_scale):
    keys = iter(jax.random.split(key, 128))
    params = {"stem": {"conv": _he(next(keys), 7, 7, 3, stem),
                       "bn": _bn_init(stem)}}
    cin = stem
    for si, (n, width) in enumerate(zip(blocks, widths)):
        mid = width // 4
        stage = []
        for bi in range(n):
            block = {"conv1": _he(next(keys), 1, 1, cin, mid), "bn1": _bn_init(mid),
                     "conv2": _he(next(keys), 3, 3, mid, mid), "bn2": _bn_init(mid),
                     "conv3": _he(next(keys), 1, 1, mid, width),
                     "bn3": _bn_init(width, residual_scale)}
            if bi == 0:
                block["proj"] = _he(next(keys), 1, 1, cin, width)
                block["bn_proj"] = _bn_init(width)
            stage.append(block)
            cin = width
        params[f"stage{si}"] = stage
    params["fc"] = {"w": jax.random.normal(next(keys), (cin, classes),
                                           jnp.float32) * 0.01,
                    "b": jnp.zeros((classes,), jnp.float32)}
    return params


def init_params(seed, config):
    """Weights from the seed, on the device, in one jitted call, float32."""
    key = jax.random.PRNGKey(int(seed) % (2**31 - 1))
    return _init(key, tuple(config["stage_blocks"]),
                 tuple(config["stage_widths"]), config["stem_width"],
                 config["num_classes"], config["residual_bn_scale_init"])


def _conv(x, w, stride, precision):
    return product(lambda a, b: lax.conv_general_dilated(
        a, b, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI),
        x, w, precision)


def _bn(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _bottleneck(x, block, stride, eps, precision):
    y = jax.nn.relu(_bn(_conv(x, block["conv1"], 1, precision), block["bn1"], eps))
    y = jax.nn.relu(_bn(_conv(y, block["conv2"], stride, precision),
                        block["bn2"], eps))
    y = _bn(_conv(y, block["conv3"], 1, precision), block["bn3"], eps)
    if "proj" in block:
        x = _bn(_conv(x, block["proj"], stride, precision), block["bn_proj"], eps)
    return jax.nn.relu(x + y)


def logits(params, x, config, precision="f32", remat=False):
    """x (N, H, W, 3) float32, already decoded -> logits (N, classes)."""
    eps = config["batch_norm_epsilon"]
    x = _conv(x, params["stem"]["conv"], 2, precision)
    x = jax.nn.relu(_bn(x, params["stem"]["bn"], eps))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for si, n in enumerate(config["stage_blocks"]):
        for bi in range(n):
            stride = 2 if (bi == 0 and si > 0) else 1
            blk = functools.partial(_bottleneck, stride=stride, eps=eps,
                                    precision=precision)
            if remat:
                blk = jax.checkpoint(blk)
            x = blk(x, params[f"stage{si}"][bi])
    x = jnp.mean(x, axis=(1, 2))
    return product(lambda a, b: jnp.einsum("nc,ck->nk", a, b, precision=HI),
                    x, params["fc"]["w"], precision) + params["fc"]["b"]


def decode(images, traffic):
    """The feed's decode: uint8 -> float32, (x - mean) * (1 / std)."""
    n = traffic["normalize"]
    inv = np.float32(1.0) / np.float32(n["std"])
    return (jnp.asarray(images).astype(jnp.float32) - np.float32(n["mean"])) * inv


def mean_ce(params, x, y, config, precision):
    lg = logits(params, x, config, precision, remat=True)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def host_norms(tree):
    return np.asarray([np.linalg.norm(np.asarray(l, np.float64).ravel())
                       for l in jax.tree.leaves(jax.device_get(tree))])


def host_diff_norms(a, b):
    a, b = jax.device_get(a), jax.device_get(b)
    return np.asarray([
        np.linalg.norm((np.asarray(x, np.float64) - np.asarray(y, np.float64)).ravel())
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def train(seed, config, traffic, batches, precision="f32", keep_rows=None,
          steps=3, no_exchange=False):
    """Follow the first ``steps`` steps of plain SGD on ``batches`` [(uint8
    images, labels)]. With ``shards`` in the traffic file the rows are split
    over that many chips: each takes the batch statistics and the gradient of
    its own rows and the gradients are averaged. Planted faults:
    ``keep_rows`` (the mean over the first rows only of each shard) and
    ``no_exchange`` (the first chip's gradient alone)."""
    lr = traffic["optimizer"]["learning_rate"]
    shards = traffic.get("shards", 1)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, x, y: mean_ce(p, x, y, config, precision)))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add(a, b):
        return jax.tree.map(jnp.add, a, b)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def sgd(p, g, k):
        return jax.tree.map(lambda p, g: p - lr * k * g, p, g)

    p = init_params(seed, config)
    paths = leaf_paths(p)
    losses, grad_norms = [], None
    for step in range(steps):
        images, labels = batches[step]
        rows = images.shape[0] // shards
        used = 1 if no_exchange else shards
        total, grads = 0.0, None
        for s in range(used):
            lo, hi = s * rows, (s + 1) * rows
            if keep_rows is not None:
                hi = lo + keep_rows
            loss, g = grad_fn(p, decode(images[lo:hi], traffic),
                              jnp.asarray(labels[lo:hi]))
            total += float(loss)
            grads = g if grads is None else add(grads, g)
        losses.append(total / used)
        if step == 0:
            grad_norms = host_norms(grads) / used
        p = sgd(p, grads, jnp.float32(1.0 / used))
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": host_diff_norms(p, init_params(seed, config)),
            "paths": paths}
