"""How the benchmark builds and drives the program's serving engine for a
configuration with grouped-query heads, an indexer and dropless experts
(``keye-vl2-30b-a3b-pp8``): ``InferenceEngine`` with ``prefill_chunk``, as the
transformer adapter drives it for GPT-2. With its sibling the only file of
the benchmark that imports the program; the faults that prove the cell's
limit (``plant``) are here for that reason."""

import copy
import os
import pathlib

import jax

from perf.lib import manifest

_transformer = manifest.load_module(
    pathlib.Path(__file__).with_name("transformer.py"))


def environment(ring_events=None):
    """As the transformer adapter's; and a program that cannot serve this
    configuration (an older commit) fails here, before any weight is made."""
    if ring_events:
        os.environ.setdefault("MLSL_TRACE_CAPACITY", str(ring_events))
    import mlsl_tpu as mlsl
    from mlsl_tpu.models import transformer as tfm

    if not hasattr(tfm, "chunk_local"):
        raise ImportError(
            "this program has no chunked prefill over the paged cache "
            "(mlsl_tpu.models.transformer.chunk_local): it cannot serve "
            "a configuration with an indexer")
    return mlsl.Environment.get_env().init()


def program_config(config, traffic):
    from mlsl_tpu.models import transformer as tfm

    sa = config["sa_config"]
    return tfm.TransformerConfig(
        vocab=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_blocks=config["num_hidden_layers"],
        seq_len=traffic["max_total_tokens"], dtype=config["compute_dtype"],
        norm="rms", norm_eps=config["rms_norm_eps"], positions="rope",
        rope_theta=float(config["rope_theta"]), qk_norm=True, mlp="experts",
        n_experts=config["num_experts"],
        moe_top_k=config["num_experts_per_tok"],
        expert_width=config["moe_intermediate_size"],
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], weights_dtype=config["weights_dtype"],
        kv_dtype=config["kv_dtype"], residual_dtype="float32")


class Engine(_transformer.Engine):
    """The transformer adapter's ``Engine`` (submit, step, failure counters,
    close) around an ``InferenceEngine`` that prefills by chunks; the batch,
    the pool, the page and the chunk from the traffic file."""

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
        for leaf in jax.tree.leaves((e.params, e.kpool, e.vpool, e.ipool)):
            leaf.delete()
        e.params = e.kpool = e.vpool = e.ipool = None


# -- the faults that the cell's limit has to refuse ---------------------------

FAULTS = ("recent_window", "experts_top7")
_SOUND = {}     # the program's own functions, kept while a fault stands in


def plant(engine, fault):
    """Put a wrong computation under the timed path of ``engine`` (an
    ``Engine`` of this file, before its programs have run) and hand it back.
    ``recent_window``: the selection replaced by the most recent ``topk``
    positions. ``experts_top7``: a token's least probable chosen expert left
    out, the rest renormalised. The program's functions are replaced in the
    running process (``restore`` puts them back) and the engine's programs
    built anew."""
    import jax.numpy as jnp

    from mlsl_tpu.models import moe
    from mlsl_tpu.ops import paged_attention

    _SOUND.setdefault("top_k", paged_attention.exact_top_k_mask)
    _SOUND.setdefault("route", moe.route_top_k)
    if fault == "recent_window":
        def recent(scores, k):
            may = scores > -jnp.inf
            left = jnp.sum(may, axis=1, keepdims=True) \
                - jnp.cumsum(may, axis=1)           # allowed after this one
            return may & (left < k[:, None])

        paged_attention.exact_top_k_mask = recent
    elif fault == "experts_top7":
        route = _SOUND["route"]

        def top7(y, wr, top_k):
            topi, gates = route(y, wr, top_k)
            gates = gates.at[:, -1].set(0.0)
            return topi, gates / jnp.sum(gates, axis=-1, keepdims=True)

        moe.route_top_k = top7
    else:
        raise ValueError(f"fault {fault!r}; known: {FAULTS}")
    engine.engine._build_programs()
    return engine


def restore():
    """The program's own functions back in the place of a planted fault (a
    process that goes on to sound runs: the tests)."""
    from mlsl_tpu.models import moe
    from mlsl_tpu.ops import paged_attention

    if _SOUND:
        paged_attention.exact_top_k_mask = _SOUND.pop("top_k")
        moe.route_top_k = _SOUND.pop("route")
