"""Codec-lab benchmark: wire bytes per codec, and calibrated vs uniform int8.

Two measurement families through the registry (mlsl_tpu.codecs) and the
calibration autotuner (tuner/calibrate.py):

1. **wire curve** — per registered codec x payload size: the compressed
   wire image of one full payload (``Codec.wire_len``) and the measured
   encode/decode noise-to-signal on the standard calibration sample. One
   JSON row per (codec, size): where each codec's byte cost sits against
   its noise cost on this machine's numerics.

2. **calibrated-vs-uniform-int8 acceptance row** — a ResNet-50-shaped
   quantized gradient stream (the 161-tensor list quant_bucket_bench.py
   measures) committed twice on the live 8-device mesh: once under
   ``MLSL_TUNE_CODEC``-style calibration (per-set codec x block against the
   NSR budget) and once on the uniform int8 seed wire. The acceptance
   contract: the calibrated assignment carries FEWER total wire bytes per
   round while every calibrated cell's NSR stays under the same budget the
   uniform wire comfortably meets (matched averaged-tail convergence, by
   construction of the budget constraint).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/codec_lab_bench.py [--smoke]
--smoke trims the size grid and scales the stream (~1/16 elements, same 161
tensors) — the tier-1 wiring (tests/test_codec_lab.py, the ``bench_smoke``
marker) runs this mode. Prints one JSON row per measurement.
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

# calibration persists its profile to MLSL_STATS_DIR (default CWD) — route
# it to a scratch dir so a bench run never drops files at the repo root
os.environ.setdefault(
    "MLSL_STATS_DIR", tempfile.mkdtemp(prefix="codec_lab_bench_")
)

from quant_bucket_bench import resnet50_counts  # noqa: E402  (sibling module)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast tier-1 mode: trimmed sizes, scaled stream")
    args = ap.parse_args()

    import mlsl_tpu as mlsl
    from mlsl_tpu import codecs
    from mlsl_tpu.tuner import calibrate
    from mlsl_tpu.types import CompressionType, OpType

    # --- 1. wire-bytes x codec x size curve -----------------------------
    sizes = (4096, 65536) if args.smoke else (4096, 65536, 1048576, 4194304)
    for n in sizes:
        x = calibrate.gradient_sample(f"bench/{n}", n)
        for name in codecs.names():
            codec = codecs.get(name)
            print(json.dumps({
                "metric": "codec_wire_bytes",
                "codec": name,
                "elems": n,
                "wire_bytes": int(codec.wire_len(n)),
                "f32_bytes": 4 * n,
                "ratio": round(codec.wire_len(n) / (4 * n), 4),
                "nsr": round(calibrate.measure_nsr(codec, x), 6),
            }))

    # --- 2. calibrated vs uniform int8 on the ResNet-50 stream ----------
    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    dist = env.create_distribution(world, 1)
    counts = resnet50_counts(scale=16 if args.smoke else 1)
    budget = env.config.codec_nsr_budget

    def build(tune):
        env.config.tune_codec = tune
        env.config.codec_assignment = {}
        s = env.create_session()
        s.set_global_minibatch_size(8)
        reqs = []
        for c in counts:
            r = s.create_operation_reg_info(OpType.CC)
            r.add_output(8, 4)
            r.add_parameter_set(
                c, 1, compression_type=CompressionType.QUANTIZATION
            )
            op = s.get_operation(s.add_operation(r, dist))
            reqs.append(op.get_parameter_set(0).grad_req)
        s.commit()
        env.config.tune_codec = False
        return reqs

    def wire_bytes(reqs):
        # each request pins its per-round compressed image at setup
        # (request._wire_rec — the same figure stats.record_codec_wire
        # accounts per started round)
        return sum(int(r._wire_rec[1]) for r in reqs if r._wire_rec)

    uniform = wire_bytes(build(tune=False))
    calibrated_reqs = build(tune=True)
    calibrated = wire_bytes(calibrated_reqs)
    cells = env.config.codec_assignment
    worst_nsr = max((c["nsr"] for c in cells.values()), default=0.0)
    by_codec: dict = {}
    for r in calibrated_reqs:
        by_codec[r.codec_name] = by_codec.get(r.codec_name, 0) + 1
    print(json.dumps({
        "metric": "codec_lab_calibrated_vs_int8",
        "tensors": len(counts),
        "params": sum(counts),
        "uniform_int8_bytes": uniform,
        "calibrated_bytes": calibrated,
        "saving": round(1.0 - calibrated / max(uniform, 1), 4),
        "nsr_budget": budget,
        "worst_cell_nsr": round(worst_nsr, 6),
        "assignment": by_codec,
    }))
    env.finalize()


if __name__ == "__main__":
    main()
