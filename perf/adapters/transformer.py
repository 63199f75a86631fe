"""How the benchmark builds and drives the program's transformer: through
``HybridTrainer`` (training) and ``InferenceEngine`` (serving), as
chip_smoke.py P2 and P3 do. This file and its siblings are the only ones of
the benchmark that import the program."""

import copy
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np


def environment(ring_events=None):
    """The program's environment, its span ring holding at least
    ``ring_events`` events (the serving runner's size; a training cell asks
    for nothing and runs the program's own 65,536). The ring is armed when
    the program is imported; a smaller one is made anew at that size here,
    and nothing is left in the process's environment."""
    from mlsl_tpu import obs

    if ring_events and obs.enabled() \
            and obs.get_tracer().capacity < ring_events:
        obs.disable()
        obs.enable(ring_events)
    import mlsl_tpu as mlsl

    return mlsl.Environment.get_env().init()


def program_config(config):
    from mlsl_tpu.models import transformer as tfm

    return tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], head_dim=config["head_dim"],
        n_blocks=config["n_layer"], seq_len=config["n_positions"],
        mlp_ratio=config["mlp_ratio"], dtype=config["compute_dtype"])


def _place_like(params, like):
    """Hand the benchmark's weights to the program in the program's own
    placement, and free what the program had made for itself."""
    placed = jax.tree.map(lambda x, old: jax.device_put(x, old.sharding),
                          params, like)
    for leaf in jax.tree.leaves(like):
        leaf.delete()
    return placed


class Trainer:
    """``HybridTrainer`` on one chip (dp = sp = tp = 1), AdamW from the
    traffic file through optax, as the trainer's ``optimizer=`` takes it."""

    def __init__(self, env, config, traffic, params, chips):
        import optax

        from mlsl_tpu.models import transformer as tfm

        if chips != 1:
            raise ValueError("the transformer's training cells take one chip")
        o = traffic["optimizer"]
        self.b1 = o["b1"]
        self.cfg = program_config(config)
        self.trainer = tfm.HybridTrainer(
            env, self.cfg, 1, 1, 1, batch=traffic["batch"],
            devices=env.devices[:1],
            optimizer=optax.adamw(
                learning_rate=o["learning_rate"], b1=o["b1"], b2=o["b2"],
                eps=o["eps"], weight_decay=o["weight_decay"]))
        self.trainer.params = _place_like(params, self.trainer.params)
        self.items_per_step = traffic["batch"] * config["n_positions"]

    def feed(self, host_batch):
        """``HybridTrainer`` has no feed(): each step's tokens are placed by
        ``shard_tokens`` when the loop asks for them."""
        for step in itertools.count():
            yield self.trainer.shard_tokens(*host_batch(step))

    def step(self, batch):
        return self.trainer.step(*batch)

    def params(self):
        return self.trainer.params

    def first_gradient(self, ref, p0):
        """Per-leaf norms of the first gradient as the optimizer got it:
        after one step Adam's first moment is (1 - b1) * g. The state is one
        flat vector a layer, in the order of the layer's leaves."""
        tr = self.trainer
        out = {}
        for name in tr.layers:
            leaves = sorted(tr.params[name].items())
            sizes = tuple(int(np.prod(leaf.shape)) for _, leaf in leaves)
            norms = np.asarray(_segment_norms(
                _first_moment(tr._opt_state[name]), sizes), np.float64)
            for (key, _), norm in zip(leaves, norms):
                out[f"{name}/{key}"] = float(norm) / (1.0 - self.b1)
        return out

    def delta(self, ref, p0):
        return dict(zip(ref.leaf_paths(self.trainer.params),
                        ref.diff_norms(self.trainer.params, p0())))

    def close(self):
        pass

    def free(self):
        tr = self.trainer
        for leaf in jax.tree.leaves((tr.params, tr._opt_state)):
            leaf.delete()
        tr.params = tr._opt_state = None


@functools.partial(jax.jit, static_argnums=(1,))
def _segment_norms(flat, sizes):
    flat = flat.reshape(-1).astype(jnp.float32)
    out, at = [], 0
    for n in sizes:
        out.append(jnp.sqrt(jnp.sum(jnp.square(flat[at:at + n]))))
        at += n
    return jnp.stack(out)


def _first_moment(state):
    for part in jax.tree.leaves(state, is_leaf=lambda s: hasattr(s, "mu")):
        if hasattr(part, "mu"):
            return part.mu
    raise ValueError("no Adam state among the optimizer's parts")


class Engine:
    """``InferenceEngine`` with its own arguments and Config for the batch,
    the pool and the queue, as chip_smoke.py P3 sets them."""

    def __init__(self, env, config, traffic, params, chips):
        from mlsl_tpu.serve import InferenceEngine

        if chips != 1:
            raise ValueError("the serving cells take one chip")
        self.cfg = program_config(config)
        cfg = copy.copy(env.config)
        cfg.serve_kv_cache_mb = traffic["kv_cache_mb"]
        cfg.serve_kv_page_elems = traffic["kv_page_tokens"]
        self.engine = InferenceEngine(
            env, self.cfg, tp=1, params=params, devices=env.devices[:1],
            config=cfg, max_batch=traffic["max_batch"],
            queue_depth=traffic["queue_depth"])
        self.max_batch = traffic["max_batch"]

    def submit(self, prompt, max_new):
        return self.engine.submit(prompt, max_new)

    def step(self):
        return self.engine.step()

    def failure_counters(self):
        from mlsl_tpu.core import stats

        return {k: v for k, v in stats.SERVE_COUNTERS.items() if v and (
            k in ("failed", "rejected", "retries", "kv_evictions",
                  "kv_rejects") or k.startswith("shed_"))}

    def close(self):
        self.engine.close()

    def free(self):
        e = self.engine
        for leaf in jax.tree.leaves((e.params, e.kpool, e.vpool)):
            leaf.delete()
        e.params = e.kpool = e.vpool = None
