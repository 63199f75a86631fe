"""User-pluggable gradient compression, both plug-in forms (the analog of the
reference's dlopen'd quantization library, quant/quant.c:96-133, registered via
Environment::SetQuantizationParams, src/mlsl.cpp:798).

Form 1 — jittable Python callables (the TPU-native form): compress/decompress
(and optionally reduce) trace straight into the compressed allreduce ring, so
the codec runs on-device with no host round-trips.

Form 2 — the reference's exact shared-library contract: a .so exposing
compress/decompress/reduce_sum symbols is dlopen'd and bridged with host
callbacks. Geometry is calibrated at registration: a declared block_size the
codec does not honor fails loudly instead of corrupting memory.

Run on the 8-device CPU mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/custom_codec.py
"""

import os
import subprocess
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mlsl_tpu as mlsl
from mlsl_tpu.types import (
    CompressionType, DataType, GroupType, QuantParams, ReductionType,
)


def quantized_allreduce(env, dist, n, vals):
    req = dist.all_reduce(
        dist.make_buffer(lambda p: vals[p], n), n, DataType.FLOAT,
        ReductionType.SUM, GroupType.DATA,
        compression=CompressionType.QUANTIZATION,
    )
    return env.wait(req)


def main():
    import jax.numpy as jnp

    env = mlsl.Environment.get_env().init()
    dist = env.create_distribution(len(env.devices), 1)
    world = len(env.devices)
    n = 1024
    rng = np.random.default_rng(7)
    vals = {p: (rng.normal(size=n) * 3).astype(np.float32) for p in range(world)}
    want = np.sum([vals[p] for p in range(world)], axis=0)

    # --- Form 1: jittable callables (f16 truncation, on-device) ------------
    env.set_quantization_params(QuantParams(
        compress_fn=lambda v: v.astype(jnp.float16),
        decompress_fn=lambda payload, count: payload.astype(jnp.float32),
        reduce_sum_fn=lambda a, b: a + b,   # reduce in the compressed domain
    ))
    out = quantized_allreduce(env, dist, n, vals)
    got = np.asarray(dist.local_part(out, 0))
    err = float(np.median(np.abs(got - want) / (np.abs(want) + 1e-3)))
    print(f"jittable f16 codec: median relative error {err:.5f}")
    assert err < 0.01

    # --- Form 2: the reference's shared-library contract -------------------
    with tempfile.TemporaryDirectory() as td:
        so = os.path.join(td, "libsample_codec.so")
        subprocess.run(
            ["gcc", "-shared", "-fPIC", "-O2", "-o", so,
             os.path.join(os.path.dirname(__file__), "..", "native",
                          "sample_codec.c")],
            check=True,
        )
        env.set_quantization_params(QuantParams(
            lib_path=so,
            quant_buffer_func_name="sample_compress",
            dequant_buffer_func_name="sample_decompress",
            reduce_sum_func_name="sample_reduce_sum",
            elem_in_block=128, block_size=256,  # 128 f32 in -> 256 B of f16 out
        ))
        out = quantized_allreduce(env, dist, n, vals)
        got = np.asarray(dist.local_part(out, 0))
        err = float(np.median(np.abs(got - want) / (np.abs(want) + 1e-3)))
        print(f"dlopen'd library codec: median relative error {err:.5f}")
        assert err < 0.01

        # A geometry the codec does not honor is rejected at registration.
        try:
            env.set_quantization_params(QuantParams(
                lib_path=so,
                quant_buffer_func_name="sample_compress",
                dequant_buffer_func_name="sample_decompress",
                reduce_sum_func_name="sample_reduce_sum",
                elem_in_block=256, block_size=256,  # codec writes 512 B/block
            ))
        except mlsl.MLSLError as e:
            print(f"inconsistent geometry rejected: {e}")
        else:
            raise AssertionError(
                "geometry mismatch was accepted — the calibration probe "
                "regressed"
            )

    # Back to the built-in Pallas int8 block codec.
    env.set_quantization_params(QuantParams(elem_in_block=256))
    out = quantized_allreduce(env, dist, n, vals)
    got = np.asarray(dist.local_part(out, 0))
    err = float(np.median(np.abs(got - want) / (np.abs(want) + 1e-3)))
    print(f"built-in int8 block codec: median relative error {err:.5f}")
    assert err < 0.05

    env.finalize()
    print("custom codec example OK")


if __name__ == "__main__":
    main()
