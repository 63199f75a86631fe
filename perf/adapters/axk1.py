"""How the benchmark builds and drives the program's serving engine for a
configuration with latent attention, a leading dense layer and held
group-routed experts beside a shared one (``ax-k1-ep16-pp10``):
``InferenceEngine`` with ``prefill_chunk``, as the Keye adapter drives its
configuration. With its siblings the only files of the benchmark that import
the program; the faults that prove the cell's limit (``plant``) are here for
that reason."""

import copy
import pathlib

import jax

from perf.lib import manifest

_transformer = manifest.load_module(
    pathlib.Path(__file__).with_name("transformer.py"))


def environment(ring_events=None):
    """As the transformer adapter's; and a program that cannot serve this
    configuration (an older commit) fails here, before any weight is made."""
    from mlsl_tpu.models import transformer as tfm

    if not hasattr(tfm, "latent_scale"):
        raise ImportError(
            "this program has no latent attention "
            "(mlsl_tpu.models.transformer.latent_scale): it cannot serve "
            "a configuration with a latent cache")
    return _transformer.environment(ring_events)


def program_config(config, traffic):
    from mlsl_tpu.models import transformer as tfm

    y = config["rope_scaling"]
    return tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        head_dim=config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        n_blocks=config["num_hidden_layers"],
        seq_len=traffic["max_total_tokens"], dtype=config["compute_dtype"],
        norm="rms", norm_eps=config["rms_norm_eps"], positions="rope",
        rope_theta=float(config["rope_theta"]), mlp="experts",
        n_experts=config["n_routed_experts"],
        moe_top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        q_lora_rank=config["q_lora_rank"], kv_lora_rank=config["kv_lora_rank"],
        qk_nope_dim=config["qk_nope_head_dim"],
        qk_rope_dim=config["qk_rope_head_dim"], v_head_dim=config["v_head_dim"],
        yarn=(float(y["factor"]), int(y["original_max_position_embeddings"]),
              float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
              float(y["mscale_all_dim"])),
        first_k_dense=config["first_k_dense_replace"],
        dense_width=config["intermediate_size"],
        shared_experts=config["n_shared_experts"], n_groups=config["n_group"],
        route_groups=config["topk_group"],
        routed_scale=float(config["routed_scaling_factor"]),
        router_experts=config["published"]["n_routed_experts"],
        held_from=config["held_from"], weights_dtype=config["weights_dtype"],
        kv_dtype=config["kv_dtype"], residual_dtype="float32")


class Engine(_transformer.Engine):
    """The transformer adapter's ``Engine`` (submit, step, failure counters,
    close) around an ``InferenceEngine`` that prefills by chunks into one
    pool of latent rows; the batch, the pool, the page and the chunk from
    the traffic file."""

    def __init__(self, env, config, traffic, params, chips):
        from mlsl_tpu.serve import InferenceEngine

        if chips != 1:
            raise ValueError("the serving cells take one chip")
        self.cfg = program_config(config, traffic)
        cfg = copy.copy(env.config)
        cfg.serve_kv_cache_mb = traffic["kv_cache_mb"]
        cfg.serve_kv_page_elems = traffic["kv_page_tokens"]
        self.engine = InferenceEngine(
            env, self.cfg, tp=1, params=params, devices=env.devices[:1],
            config=cfg, max_batch=traffic["max_batch"],
            queue_depth=traffic["queue_depth"],
            prefill_chunk=traffic["prefill_chunk_tokens"])
        self.max_batch = traffic["max_batch"]

    def free(self):
        e = self.engine
        for leaf in jax.tree.leaves((e.params, e.lpool)):
            leaf.delete()
        e.params = e.lpool = None


# -- the faults that the cell's limit has to refuse ---------------------------

FAULTS = ("experts_top7", "no_shared_expert", "no_k_pe")
_SOUND = {}     # the program's own functions, kept while a fault stands in


def plant(engine, fault):
    """Put a wrong computation under the timed path of ``engine`` (an
    ``Engine`` of this file, before its programs have run) and hand it back.
    ``experts_top7``: a token's least probable of its 8 chosen experts left
    out, the rest renormalised. ``no_shared_expert``: the shared expert left
    out. ``no_k_pe``: the rotary key zeroed where the cache stores it. The
    program's functions are replaced in the running process (``restore``
    puts them back) and the engine's programs built anew."""
    import jax.numpy as jnp

    from mlsl_tpu.models import transformer as tfm

    _SOUND.setdefault("route", tfm.route_group_sigmoid)
    _SOUND.setdefault("swiglu", tfm._swiglu)
    _SOUND.setdefault("project", tfm._latent_project)
    if fault == "experts_top7":
        route = _SOUND["route"]

        def top7(y, wr, top_k, **kw):
            topi, gates = route(y, wr, top_k, **kw)
            # the weights are in order of score: the last is the least
            total = jnp.sum(gates, axis=-1, keepdims=True)
            gates = gates.at[:, -1].set(0.0)
            return topi, gates * total / jnp.sum(gates, axis=-1, keepdims=True)

        tfm.route_group_sigmoid = top7
    elif fault == "no_shared_expert":
        swiglu = _SOUND["swiglu"]

        def without_shared(a, wgu, wd, cdt):
            out = swiglu(a, wgu, wd, cdt)
            # the dense layer is d x 2F: only the shared expert is 2F = 2f
            return out * 0.0 if wgu.shape[1] == 2 * engine.cfg.expert_width \
                * engine.cfg.shared_experts else out

        tfm._swiglu = without_shared
    elif fault == "no_k_pe":
        project = _SOUND["project"]

        def without_k_pe(a, ap, cfg, positions):
            q_nope, q_pe, row = project(a, ap, cfg, positions)
            lat = cfg.kv_lora_rank
            return q_nope, q_pe, row.at[:, lat:].set(0.0)

        tfm._latent_project = without_k_pe
    else:
        raise ValueError(f"fault {fault!r}; known: {FAULTS}")
    engine.engine._build_programs()
    return engine


def restore():
    """The program's own functions back in the place of a planted fault (a
    process that goes on to sound runs: the tests)."""
    from mlsl_tpu.models import transformer as tfm

    if _SOUND:
        tfm.route_group_sigmoid = _SOUND.pop("route")
        tfm._swiglu = _SOUND.pop("swiglu")
        tfm._latent_project = _SOUND.pop("project")
