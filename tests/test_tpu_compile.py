"""Programs of the main path compiled for the TPU v5e at real widths, with no
chip attached (the TPU's compiler is installed; /opt/skills/guides/
on-chip-measurement section 2): what interpret mode and the CPU cannot show,
at no chip time. The topology is described inside a fixture, never at import:
only the worker that runs this file may load the TPU's library. Keep such
tests in this one file."""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def tokens_and_pools_only(lowered, mem, slots, vocab):
    """What a serving program returns beside the pools it updates in place
    does not follow the vocabulary: int32 tokens first (``slots`` of them, a
    scalar for a chunk), no float32 result of the logits' size anywhere: a
    (32, 50257) one is 6.4 MB a step for the host to read back."""
    first, *rest = jax.tree.leaves(lowered.out_info)
    assert first.dtype == jnp.int32 and first.shape in ((slots,), ())
    assert all(vocab not in leaf.shape for leaf in rest)
    assert mem.output_size_in_bytes - mem.alias_size_in_bytes < 4096, mem


@pytest.mark.parametrize("quant", [False, True], ids=["f32_pool", "int8_pool"])
def test_decode_program_reads_and_writes_the_pool_in_place(
        one_chip, no_compile_cache, quant):
    """GPT-2 medium's decode step over a 2 GiB pool, as the serving cell
    runs it (the engine's own body): the pools alias their outputs, nothing
    the program keeps beside them is of the pool's size (the layer's slice
    made a buffer, the relayout before a gather and the gathered context of
    the dense form were 6.5 GB here), and what it returns beside them is a
    token a slot, not 6.4 MB of logits for the host to read back."""
    from mlsl_tpu.models import transformer as tfm
    from mlsl_tpu.ops import paged_attention
    from mlsl_tpu.serve import engine

    cfg = tfm.TransformerConfig(
        vocab=50257, d_model=1024, n_heads=16, head_dim=64, n_blocks=24,
        seq_len=1024, dtype="bfloat16")
    page, batch = 16, 32
    pages = (2048 << 20) // (cfg.n_blocks * 2 * page * 1024 * 4) + 1
    cap = -(-pages // paged_attention.PAGES_PER_CHUNK) \
        * paged_attention.PAGES_PER_CHUNK

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: shape(x.shape, jnp.float32),
        jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)))
    pool = shape((cfg.n_blocks, pages, page, cfg.n_heads * cfg.head_dim),
                 jnp.int8 if quant else jnp.float32)
    scale = shape((cfg.n_blocks, pages, cfg.n_heads * page), jnp.float32)
    pools = (pool, pool) + ((scale, scale) if quant else ())

    lowered = jax.jit(
        engine.decode_body_of(cfg, 1, None, None,
                              ("kscale", "vscale") if quant else ()),
        donate_argnums=tuple(range(3, 3 + len(pools))),
    ).lower(params, shape((3, batch), jnp.int32), shape((3, cap), jnp.int32),
            *pools)
    mem = lowered.compile().memory_analysis()
    pool_bytes = sum(jnp.dtype(p.dtype).itemsize * math.prod(p.shape)
                     for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 16, mem
    tokens_and_pools_only(lowered, mem, batch, cfg.vocab)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_indexer_programs_keep_three_pools_in_place(
        one_chip, no_compile_cache, program):
    """The decode step and the prefill chunk (the engine's own bodies) of
    the configuration with an indexer (perf/configs/keye-vl2-30b-a3b-pp8.json:
    32 query heads over 4 key-value heads, 16 x 64 indexer with topk 2,048,
    128 experts, bfloat16 weights and pools) at the serving cell's sizes: K,
    V and the index keys alias their outputs, the temporaries do not grow
    with the pool (1 and 3 GiB compile to the same), weights, pool and
    temporaries fit the chip, and beside the pools come tokens and two
    counts, no logits. The first trace of PR 29 showed what a 64-lane index
    pool costs: the compiler laid the whole pool out anew around every
    layer's gather."""
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from perf.lib import manifest

    from mlsl_tpu.models import transformer as tfm
    from mlsl_tpu.serve import engine

    config = manifest.read_json(
        manifest.PERF / "configs" / "keye-vl2-30b-a3b-pp8.json")
    traffic = manifest.traffic_file("longctx-open-loop")
    cfg = manifest.load_module(
        manifest.PERF / "adapters" / "keye.py").program_config(config, traffic)
    page, batch = traffic["kv_page_tokens"], traffic["max_batch"]
    chunk = traffic["prefill_chunk_tokens"]
    table = cfg.seq_len // page

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda x: shape(x.shape, x.dtype),
        jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)))
    weights = sum(jnp.dtype(x.dtype).itemsize * math.prod(x.shape)
                  for x in jax.tree.leaves(params))
    assert 8.7e9 < weights < 8.8e9          # 4,375 M parameters in bfloat16

    def compiled(pool_mb):
        token = 2 * cfg.kv_heads * cfg.head_dim + cfg.index_row
        pages = (pool_mb << 20) // (cfg.n_blocks * page * token * 2) + 1
        kv = shape((cfg.n_blocks, pages, page, cfg.kv_heads * cfg.head_dim),
                   jnp.bfloat16)
        pools = (kv, kv, shape((cfg.n_blocks, pages, page, cfg.index_row),
                               jnp.bfloat16))
        if program == "decode":
            body = engine.decode_body_of(cfg, 1, None, None, ("ipool",))
            args = (shape((3, batch), jnp.int32),
                    shape((batch, table), jnp.int32))
        else:
            body = engine.chunk_body_of(cfg, 1, None)
            args = (shape((chunk,), jnp.int32), shape((), jnp.int32),
                    shape((), jnp.int32), shape((table,), jnp.int32))
        n = 1 + len(args)
        lowered = jax.jit(body, donate_argnums=(n, n + 1, n + 2)).lower(
            params, *args, *pools)
        mem = lowered.compile().memory_analysis()
        tokens_and_pools_only(lowered, mem, batch, cfg.vocab)
        return mem, sum(jnp.dtype(p.dtype).itemsize * math.prod(p.shape)
                        for p in pools)

    small, small_pool = compiled(1024)
    large, large_pool = compiled(3072)
    assert large.alias_size_in_bytes >= large_pool > 2.9 * small_pool
    assert small.alias_size_in_bytes >= small_pool
    assert abs(large.temp_size_in_bytes - small.temp_size_in_bytes) < 64 << 20
    assert large.temp_size_in_bytes < large_pool
    assert weights + large_pool + large.temp_size_in_bytes < 15.75 * 2**30
