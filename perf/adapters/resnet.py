"""How the benchmark builds and drives the program's ResNet-50: through
``DataParallelTrainer`` and its ``feed()`` (uint8 wire, HBM cache), as
chip_smoke.py P1 and M1 do. On one chip the trainer takes its fused step; on
four, the per-layer gradient graph over the data group."""

import jax
import numpy as np


def environment():
    import mlsl_tpu as mlsl

    return mlsl.Environment.get_env().init()


class Trainer:
    def __init__(self, env, config, traffic, params, chips):
        from mlsl_tpu.models import resnet
        from mlsl_tpu.models.train import DataParallelTrainer

        self.traffic = traffic
        self.lr = traffic["optimizer"]["learning_rate"]
        dist = env.create_distribution(chips, 1, devices=env.devices[:chips])
        session = env.create_session()
        session.set_global_minibatch_size(traffic["batch"])
        self.trainer = DataParallelTrainer(
            env, dist, session, params, resnet.loss_fn,
            resnet.layer_names(params), resnet.layer_subtree, lr=self.lr)
        self.items_per_step = traffic["batch"]
        self.loader = None

    def feed(self, batches):
        """The device feed over a list of host batches, replayed for ever:
        after the first pass every batch decodes out of the HBM cache."""
        t = self.traffic
        n = t["normalize"]
        self.loader = self.trainer.feed(
            list(batches), wire=t["wire"], cache_mb=t["cache_mb"],
            epochs=None, depth=t["feed_depth"],
            normalize=(n["mean"], n["std"]))
        return iter(self.loader)

    def step(self, batch):
        return self.trainer.step(batch)

    def params(self):
        return self.trainer.params

    def first_gradient(self, ref, p0):
        """Plain SGD keeps no state: after one step the gradient as the
        update got it is (p0 - p1) / lr."""
        norms = ref.host_diff_norms(self.trainer.params, p0()) / self.lr
        return dict(zip(ref.leaf_paths(self.trainer.params), norms))

    def delta(self, ref, p0):
        return dict(zip(ref.leaf_paths(self.trainer.params),
                        ref.host_diff_norms(self.trainer.params, p0())))

    def close(self):
        if self.loader is not None:
            self.loader.close()
            self.loader = None

    def free(self):
        self.close()
        for leaf in jax.tree.leaves(self.trainer.params):
            leaf.delete()
        self.trainer.params = None
