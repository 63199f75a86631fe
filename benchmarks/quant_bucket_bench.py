"""Quantized-bucket benchmark: coalesced vs per-layer rings, quant vs plain.

Two measurements through the ParameterSet engine (core/bucketing.py +
comm/quant_ring.py):

1. **algbw curve** — a backward-shaped stream of NL same-size gradient
   allreduces at several message sizes, in all four corners of
   {individual, bucketed} x {plain f32, int8 quantized}. EQuARX/THC both show
   quantized collectives only reach peak algbw at coalesced, bandwidth-sized
   messages — this row set is where that shows up (or doesn't) on the
   attached backend.

2. **ResNet-50-shaped stream** — the full 161-tensor per-layer gradient list
   of a ResNet-50 (conv + BN + fc shapes), quantized, individual vs bucketed:
   aggregate per-step comm time. This is the acceptance row — the coalesced
   compressed ring must beat 161 per-layer compressed rings, which pay the
   host dispatch floor per tensor at latency-bound sizes.

Tensor counts are rounded UP to a small size palette so the per-layer path
compiles a handful of distinct ring programs instead of ~50 (the coalesced
path is insensitive; the palette preserves the size distribution).

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
       python benchmarks/quant_bucket_bench.py [--smoke]
--smoke scales the tensor list down (~1/16 the elements, same 161 tensors)
and trims sizes/iters — the tier-1 wiring (tests/test_quant_bucket.py, the
``bench_smoke`` marker) runs this mode. Prints one JSON row per
configuration (the standard capture-row shape: a "metric" field per line).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

#: size palette (elements): counts round UP to the nearest entry so the
#: individual path shares ring programs across same-palette tensors
PALETTE = (1024, 4096, 16384, 65536, 262144, 1048576, 4194304)


def _palette(c: int) -> int:
    for p in PALETTE:
        if c <= p:
            return p
    return c


def resnet50_counts(scale: int = 1):
    """Per-tensor parameter counts of a ResNet-50: 53 convs + 53 BN
    (gamma, beta) pairs + fc weight/bias = 161 tensors, palette-rounded.
    ``scale`` divides every count (smoke mode) without changing the tensor
    COUNT — the per-layer dispatch floor being measured is per tensor."""
    counts = []

    def conv(cin, cout, k):
        counts.append(cin * cout * k * k)
        counts.extend([cout, cout])  # BN gamma, beta

    conv(3, 64, 7)
    cin = 64
    for stage, (blocks, mid) in enumerate(
        [(3, 64), (4, 128), (6, 256), (3, 512)]
    ):
        for b in range(blocks):
            conv(cin, mid, 1)
            conv(mid, mid, 3)
            conv(mid, mid * 4, 1)
            if b == 0:  # downsample projection
                conv(cin, mid * 4, 1)
            cin = mid * 4
    counts.extend([2048 * 1000, 1000])  # fc weight, bias
    return [_palette(max(c // scale, 256)) for c in counts]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fast tier-1 mode: scaled-down tensors, fewer iters")
    args = ap.parse_args()

    import numpy as np

    import mlsl_tpu as mlsl
    from benchmarks._common import device_sync
    from mlsl_tpu.types import CompressionType, OpType

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    dist = env.create_distribution(world, 1)
    g = dist.get_process_count_data()
    degenerate = {"note": "degenerate group: dispatch floor"} if world == 1 else {}

    def build(counts, bucket_mb, compression):
        env.config.grad_bucket_mb = bucket_mb
        s = env.create_session()
        s.set_global_minibatch_size(8)
        ops = []
        for c in counts:
            r = s.create_operation_reg_info(OpType.CC)
            r.add_input(8, 4)
            r.add_output(8, 4)
            r.add_parameter_set(c, 1, compression_type=compression)
            ops.append(s.get_operation(s.add_operation(r, dist)))
        s.commit()
        env.config.grad_bucket_mb = 0
        return [op.get_parameter_set(0) for op in ops]

    def make_bufs(counts, seed=0):
        rng = np.random.default_rng(seed)
        vals = [rng.normal(size=c).astype(np.float64) for c in counts]
        return [
            dist.make_buffer(lambda p, v=v: v + p, c)
            for c, v in zip(counts, vals)
        ]

    # The CPU proof backend DEADLOCKS past a few dozen concurrent in-flight
    # collectives (a thread-pool rendezvous starves; bucketing_bench.py caps
    # NL=12 for the same reason), so a 161-tensor stream must bound its
    # outstanding requests. The window is BUCKET-AWARE: members of one bucket
    # start together (waiting any member before its bucket fills would trigger
    # the early-Wait fallback and silently measure the individual path), and
    # the window counts in-flight REQUESTS — one per bucket, one per
    # unbucketed member. This is also the realistic backward schedule: a
    # trainer drains old layers' collectives while new ones start.
    WINDOW = 8

    def step(pss, bufs):
        groups = []  # contiguous-by-bucket member index groups, start order
        cur_bucket = object()
        for i in range(len(pss) - 1, -1, -1):  # backward start order
            b = pss[i].bucket
            if b is None or b is not cur_bucket:
                groups.append([])
                cur_bucket = b
            groups[-1].append(i)
        outs = [None] * len(pss)
        inflight = []
        for idxs in groups:
            for i in idxs:
                pss[i].start_gradient_comm(bufs[i])
            inflight.append(idxs)
            if len(inflight) > WINDOW:
                for j in inflight.pop(0):
                    outs[j] = pss[j].wait_gradient_comm()
        for idxs in inflight:
            for j in idxs:
                outs[j] = pss[j].wait_gradient_comm()
        device_sync(outs[-1])

    def timed_step(pss, bufs, warmup, blocks, per_block):
        for _ in range(warmup):
            step(pss, bufs)
        best = float("inf")
        for _ in range(blocks):
            t0 = time.perf_counter()
            for _ in range(per_block):
                step(pss, bufs)
            best = min(best, (time.perf_counter() - t0) / per_block)
        return best * 1e3  # ms

    warmup, blocks, per_block = (1, 2, 1) if args.smoke else (3, 5, 5)

    # --- 1. algbw curve: size x {individual,bucketed} x {plain,quant} ---
    NL = 12  # stays under the CPU backend's in-flight collective limit
    sizes = (2048,) if args.smoke else (2048, 16384, 131072)
    for cnt in sizes:
        counts = [cnt] * NL
        bufs = make_bufs(counts, seed=cnt)
        total_bytes = NL * cnt * 4
        for comp, tag in (
            (CompressionType.NONE, "plain"),
            (CompressionType.QUANTIZATION, "quant"),
        ):
            times = {}
            for label, mb in (("individual_ms", 0), ("bucketed_ms", 4)):
                pss = build(counts, mb, comp)
                times[label] = round(
                    timed_step(pss, bufs, warmup, blocks, per_block), 3
                )
            # allreduce algorithm bandwidth over the aggregate stream
            algbw = {
                k.replace("_ms", "_gbps"): round(
                    2 * (g - 1) / max(g, 1) * total_bytes / (v / 1e3) / 1e9, 3
                )
                for k, v in times.items()
            }
            print(json.dumps({
                "metric": "quant_bucket_algbw",
                "compression": tag,
                "layers": NL,
                "grad_kib": cnt * 4 // 1024,
                **times,
                **algbw,
                "speedup": round(times["individual_ms"] / times["bucketed_ms"], 3),
                "unit": "ms",
                **degenerate,
            }))

    # --- 2. ResNet-50-shaped quantized stream (the acceptance row) ---
    counts = resnet50_counts(scale=16 if args.smoke else 1)
    bufs = make_bufs(counts, seed=50)
    rows = [("quant", CompressionType.QUANTIZATION)]
    if not args.smoke:
        rows.append(("plain", CompressionType.NONE))
    for tag, comp in rows:
        times = {}
        for label, mb in (("individual_ms", 0), ("bucketed_ms", 4)):
            pss = build(counts, mb, comp)
            n_bucketed = sum(ps.bucket is not None for ps in pss)
            times[label] = round(
                timed_step(pss, bufs, warmup, blocks, per_block), 3
            )
        print(json.dumps({
            "metric": "quant_bucket_resnet50_stream",
            "compression": tag,
            "tensors": len(counts),
            "params": sum(counts),
            "bucketed_members": n_bucketed,
            **times,
            "speedup": round(times["individual_ms"] / times["bucketed_ms"], 3),
            "unit": "ms",
            **degenerate,
        }))


if __name__ == "__main__":
    main()
