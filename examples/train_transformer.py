"""Hybrid-parallel transformer training walkthrough: dp x sp x tp with MLSL-driven
gradient sync, async data loading and checkpointing.

Run on the 8-device CPU mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_transformer.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import mlsl_tpu as mlsl


def main():
    from mlsl_tpu.checkpoint import CheckpointManager, restore_trainer, save_trainer
    from mlsl_tpu.data import AsyncLoader
    from mlsl_tpu.models import transformer as tfm

    env = mlsl.Environment.get_env().init()
    world = env.get_process_count()

    # Factor the world into data x seq x model parallelism so dp*sp*tp == world
    # for ANY device count: peel a factor of 2 for tensor parallelism, another
    # for sequence sharding (ring attention), and give the rest to the batch.
    tp = 2 if world % 2 == 0 else 1
    sp = 2 if (world // tp) % 2 == 0 and world // tp > 1 else 1
    dp = world // (tp * sp)

    cfg = tfm.TransformerConfig(
        vocab=128, d_model=64, n_heads=8, head_dim=8, n_blocks=2, seq_len=64,
        attention="ring",
    )
    batch = 4 * dp
    trainer = tfm.HybridTrainer(env, cfg, dp, sp, tp, batch=batch, lr=0.3)
    print(f"world={world}: dp={dp} sp={sp} tp={tp}; "
          f"{sum(trainer.local_counts.values())} params/device")

    # a fixed synthetic corpus: memorize next-token prediction on 4 sequences
    rng = np.random.default_rng(0)
    corpus = rng.integers(0, cfg.vocab, size=(batch, cfg.seq_len)).astype(np.int32)

    def batches():
        while True:
            yield corpus, np.roll(corpus, -1, axis=1)

    loader = AsyncLoader(batches(), trainer.shard_tokens, depth=2)
    mgr = CheckpointManager("/tmp/mlsl_tpu_tfm_ckpt")

    for step, (toks, labels) in enumerate(loader):
        loss = float(np.asarray(trainer.step(toks, labels)))
        if step % 5 == 0:
            print(f"step {step:3d}  loss {loss:.4f}")
        if step == 10:
            save_trainer(mgr, trainer, step=step, wait=True)
        if step >= 20:
            break
    loader.close()

    restored = restore_trainer(mgr, trainer)
    print(f"checkpoint restored from step {restored}")
    mgr.close()
    env.finalize()
    print("transformer example OK")


if __name__ == "__main__":
    main()
