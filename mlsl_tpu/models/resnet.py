"""ResNet-50 in pure JAX (no flax/haiku) — the flagship benchmark model.

The reference's headline workload is Caffe ResNet-50 data-parallel training with
per-layer gradient sync through the Session/Operation graph (BASELINE.json config 5).
This is a from-scratch TPU-idiomatic implementation: NHWC layout (TPU-native),
bfloat16 activations with float32 params, lax.conv_general_dilated on the MXU, and a
flat per-layer parameter list that maps 1:1 onto MLSL Operations.

Train-mode batch norm computes batch statistics on the local shard (per-device BN, the
standard data-parallel practice; the reference likewise keeps BN local to each worker).
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

Params = Dict[str, Any]

STAGES = (3, 4, 6, 3)          # ResNet-50 bottleneck counts
WIDTHS = (256, 512, 1024, 2048)


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    std = np.sqrt(2.0 / fan_in)
    return jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) * std


def _bn_init(c):
    return {"scale": jnp.ones((c,), jnp.float32), "bias": jnp.zeros((c,), jnp.float32)}


def init_resnet50(key, num_classes: int = 1000) -> Params:
    keys = iter(jax.random.split(key, 128))
    params: Params = {"stem": {"conv": _conv_init(next(keys), 7, 7, 3, 64), "bn": _bn_init(64)}}
    cin = 64
    for si, (blocks, width) in enumerate(zip(STAGES, WIDTHS)):
        mid = width // 4
        stage = []
        for bi in range(blocks):
            block = {
                "conv1": _conv_init(next(keys), 1, 1, cin, mid),
                "bn1": _bn_init(mid),
                "conv2": _conv_init(next(keys), 3, 3, mid, mid),
                "bn2": _bn_init(mid),
                "conv3": _conv_init(next(keys), 1, 1, mid, width),
                "bn3": _bn_init(width),
            }
            if bi == 0:
                block["proj"] = _conv_init(next(keys), 1, 1, cin, width)
                block["bn_proj"] = _bn_init(width)
            stage.append(block)
            cin = width
        params[f"stage{si}"] = stage
    params["fc"] = {
        "w": jax.random.normal(next(keys), (2048, num_classes), jnp.float32) * 0.01,
        "b": jnp.zeros((num_classes,), jnp.float32),
    }
    return params


def _bn(x, p, eps=1e-5):
    # Folded BN, one-pass statistics: mean and E[x^2] accumulate in f32 off
    # the bf16 input in a SINGLE read of the activation (XLA fuses both
    # reductions into one convert_reduce pass). The centered two-pass form
    # read every activation twice (the step's device-op table is
    # `perf/run.py --workload resnet50-1chip --trace 1`). E[x^2]-E[x]^2 can
    # cancel to a small negative on near-constant channels, so the variance
    # is clamped at 0 — normalization then degrades to rsqrt(eps)-scaling,
    # exactly what true-variance BN does on such channels (flax BatchNorm's
    # use_fast_variance default takes the same trade). Normalization folds
    # into per-channel (a, b) so the apply is one fused multiply-add; output
    # returns to the compute dtype so downstream convs stay on the MXU's
    # bf16 path.
    mean = jnp.mean(x, axis=(0, 1, 2), dtype=jnp.float32)
    msq = jnp.mean(lax.square(x.astype(jnp.float32)), axis=(0, 1, 2))
    var = jnp.maximum(msq - lax.square(mean), 0.0)
    a = lax.rsqrt(var + eps) * p["scale"]
    b = p["bias"] - mean * a
    return (x * a + b).astype(x.dtype)


def _conv(x, w, stride=1):
    return lax.conv_general_dilated(
        x,
        w.astype(x.dtype),
        window_strides=(stride, stride),
        padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _stem_conv(x, w):
    """7x7-stride-2 'SAME' stem conv, optionally in space-to-depth form.

    The direct form contracts over 7*7*3 = 147 input taps — poor MXU lane
    utilization at 3 input channels (MLPerf ResNet submissions on TPU use
    the same space-to-depth rewrite). With MLSL_RESNET_S2D=1 the input is
    rearranged to (H/2, W/2, 12) 2x2 phases and the kernel zero-padded to
    8x8 and resampled into 2x2 phases of 4x4x12, giving a stride-1 conv
    with identical outputs for even H, W:
        y[i,j] = sum_u x[2i+u-2] w[u]   (u in [0,7), SAME pad (2,3))
      = sum_{k,a} x2[i+k-1, a] w[2k+a]  (k in [0,4), a in {0,1}, pad (1,2))
    Parameters stay in the canonical (7,7,3,64) shape — the rewrite is a
    trace-time reparametrization, so checkpoints and grad sync see the
    same tree either way.
    """
    n, h, wd, c = x.shape
    if not _use_s2d_stem() or h % 2 or wd % 2:
        return _conv(x, w, stride=2)
    x2 = x.reshape(n, h // 2, 2, wd // 2, 2, c)
    x2 = x2.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, wd // 2, 4 * c)
    wp = jnp.pad(w, ((0, 1), (0, 1), (0, 0), (0, 0)))
    kh, kw, cin, co = wp.shape
    w2 = wp.reshape(kh // 2, 2, kw // 2, 2, cin, co)
    w2 = w2.transpose(0, 2, 1, 3, 4, 5).reshape(kh // 2, kw // 2, 4 * cin, co)
    return lax.conv_general_dilated(
        x2,
        w2.astype(x.dtype),
        window_strides=(1, 1),
        padding=((1, 2), (1, 2)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _use_s2d_stem() -> bool:
    """MLSL_RESNET_S2D: '1' forces the space-to-depth stem, '0' forces the
    direct conv; unset defaults to on for TPU backends (identical math,
    pinned by test_s2d_stem_matches_direct_conv). Any other value raises."""
    import os

    from mlsl_tpu.log import mlsl_assert
    from mlsl_tpu.sysinfo import on_tpu

    v = os.environ.get("MLSL_RESNET_S2D", "").strip().lower()
    if v in ("0", "false", "off"):
        return False
    if v in ("1", "true", "on"):
        return True
    mlsl_assert(v == "", "MLSL_RESNET_S2D must be 0/false/off, 1/true/on or "
                "unset (got %r)", v)
    return on_tpu()


def _bottleneck(x, block, stride):
    y = jax.nn.relu(_bn(_conv(x, block["conv1"]), block["bn1"]))
    y = jax.nn.relu(_bn(_conv(y, block["conv2"], stride), block["bn2"]))
    y = _bn(_conv(y, block["conv3"]), block["bn3"])
    if "proj" in block:
        x = _bn(_conv(x, block["proj"], stride), block["bn_proj"])
    return jax.nn.relu(x + y)


def apply_resnet50(params: Params, x: jax.Array) -> jax.Array:
    """x: (N, H, W, 3) -> logits (N, num_classes). Compute in bf16, params f32."""
    x = x.astype(jnp.bfloat16)
    x = _stem_conv(x, params["stem"]["conv"])
    x = jax.nn.relu(_bn(x, params["stem"]["bn"]))
    x = lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    for si, blocks in enumerate(STAGES):
        stage = params[f"stage{si}"]
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            x = _bottleneck(x, stage[bi], stride)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))  # pool accumulates in f32
    return x @ params["fc"]["w"] + params["fc"]["b"]


def loss_fn(params: Params, batch: Tuple[jax.Array, jax.Array]) -> jax.Array:
    x, labels = batch
    logits = apply_resnet50(params, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def layer_names(params: Params) -> List[str]:
    """Flat per-layer names in forward order — one MLSL Operation per entry."""
    names = ["stem"]
    for si, blocks in enumerate(STAGES):
        names += [f"stage{si}.{bi}" for bi in range(blocks)]
    names.append("fc")
    return names


def layer_param_counts(params: Params) -> Dict[str, int]:
    """name -> total parameter element count (the Operation's kernel count)."""
    counts = {}
    counts["stem"] = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params["stem"]))
    for si, blocks in enumerate(STAGES):
        for bi in range(blocks):
            counts[f"stage{si}.{bi}"] = sum(
                int(np.prod(l.shape)) for l in jax.tree.leaves(params[f"stage{si}"][bi])
            )
    counts["fc"] = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params["fc"]))
    return counts


def layer_subtree(params: Params, name: str):
    if name in ("stem", "fc"):
        return params[name]
    stage, block = name.split(".")
    return params[stage][int(block)]
