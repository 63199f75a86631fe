"""ZeRO-1 Adam with gradient accumulation and checkpoint resume.

Demonstrates the trainer-side framework features on top of the MLSL graph:
- optax optimizer with state sharded on each rank's OWNED gradient shard
  (ZeRO-1: the distributed-update path, reference src/mlsl_impl.cpp:401-435,
  with Adam moments instead of plain SGD);
- gradient accumulation (the Caffe iter_size pattern): k local fwd/bwd passes,
  one gradient sync;
- checkpointing that persists the optimizer state, so a resumed run continues
  the Adam trajectory instead of restarting from zero moments.

Run on the 8-device CPU mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/train_zero1_adam.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np
import jax
import optax

import mlsl_tpu as mlsl
from mlsl_tpu.checkpoint import CheckpointManager, restore_trainer, save_trainer
from mlsl_tpu.models.mlp import LAYERS, get_layer, init as mlp_init, loss_fn
from mlsl_tpu.models.train import DataParallelTrainer


def main():
    env = mlsl.Environment.get_env().init()
    n = len(env.devices)
    dist = env.create_distribution(n, 1)
    sess = env.create_session()
    sess.set_global_minibatch_size(2 * n)

    trainer = DataParallelTrainer(
        env, dist, sess, mlp_init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
        get_layer,
        distributed_update=True,          # ZeRO-1: ReduceScatter + owned update
        optimizer=optax.adam(5e-3),       # moments live on the owned shard only
    )

    rng = np.random.default_rng(0)

    def micro_batch():
        x = rng.normal(size=(2 * n, 8)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int32) + 2 * (x[:, 1] > 0).astype(np.int32)
        return trainer.shard_batch(x, y)

    ckpt_dir = os.path.join(tempfile.mkdtemp(prefix="mlsl_zero1_"), "ckpt")
    mgr = CheckpointManager(ckpt_dir)

    for step in range(6):
        # 2x gradient accumulation: effective batch 4n, one sync per step
        loss = trainer.step_accum([micro_batch(), micro_batch()])
        lv = float(np.asarray(loss).mean())
        print(f"step {step}: loss {lv:.4f}")
        if step == 2:
            save_trainer(mgr, trainer, step, wait=True)

    # Resume: a fresh trainer restored from step 2 continues the Adam
    # trajectory (moments + count come back with the params).
    sess2 = env.create_session()
    sess2.set_global_minibatch_size(2 * n)
    trainer2 = DataParallelTrainer(
        env, dist, sess2, mlp_init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
        get_layer, distributed_update=True, optimizer=optax.adam(5e-3),
    )
    mgr2 = CheckpointManager(ckpt_dir)
    restored = restore_trainer(mgr2, trainer2)
    print(f"resumed from step {restored}")
    loss = trainer2.step_accum([micro_batch(), micro_batch()])
    print(f"post-resume loss {float(np.asarray(loss).mean()):.4f}")
    mgr.close()
    mgr2.close()
    env.finalize()
    print("OK")


if __name__ == "__main__":
    main()
