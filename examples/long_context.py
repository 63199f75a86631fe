"""Long-context training walkthrough: zigzag context parallelism.

Shows the sequence-parallel menu for causal attention over sequences that
don't fit one device, and why zigzag is the default choice for causal
training:

1. contiguous ring (`ring_attention`): k/v blocks rotate over the ICI ring;
   causal masking wastes ~half the computed score blocks;
2. zigzag ring (`zigzag_ring_attention` / `attention="zigzag"`): each device
   holds global chunks r and 2G-1-r, so every hop is two UNMASKED chunk
   updates — same math, ~2x fewer attention FLOPs (docs/DESIGN.md);
3. Ulysses (`ulysses_attention`): two all-to-alls trade sequence sharding
   for head sharding when heads are plentiful.

Run on the 8-device CPU mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/long_context.py
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mlsl_tpu as mlsl


def main():
    from mlsl_tpu.models import transformer as tfm

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()
    sp = world  # the whole mesh shards the sequence (context parallelism)

    # a sequence this long lives only as shards of seq_len/sp per device
    cfg = dict(vocab=128, d_model=64, n_heads=8, head_dim=8, n_blocks=2,
               seq_len=64 * sp, dtype="float32")
    rng = np.random.default_rng(0)
    toks = rng.integers(0, 128, size=(2, cfg["seq_len"])).astype(np.int32)
    labels = rng.integers(0, 128, size=(2, cfg["seq_len"])).astype(np.int32)

    losses = {}
    times = {}
    for mode in ("ring", "zigzag"):
        c = tfm.TransformerConfig(attention=mode, **cfg)
        trainer = tfm.HybridTrainer(env, c, dp=1, sp=sp, tp=1, batch=2, lr=0.3)
        # shard_tokens handles the zigzag data permutation transparently;
        # callers always pass sequences in natural order
        st, sl = trainer.shard_tokens(toks, labels)
        float(trainer.step(st, sl))  # compile + d2h sync before timing
        t0 = time.perf_counter()
        losses[mode] = [float(trainer.step(st, sl)) for _ in range(3)]
        times[mode] = (time.perf_counter() - t0) / 3
        print(f"{mode:7s}: losses {['%.4f' % x for x in losses[mode]]}  "
              f"({times[mode] * 1e3:.0f} ms/step)")

    # identical math, different schedule: trajectories agree to rounding
    np.testing.assert_allclose(losses["zigzag"], losses["ring"], rtol=1e-4)
    print("zigzag == ring trajectory (to rounding): OK")
    print("long-context example OK")
    env.finalize()


if __name__ == "__main__":
    main()
