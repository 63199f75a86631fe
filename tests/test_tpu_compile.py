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


@pytest.mark.parametrize("quant", [False, True], ids=["f32_pool", "int8_pool"])
def test_decode_program_reads_and_writes_the_pool_in_place(
        one_chip, no_compile_cache, quant):
    """GPT-2 medium's decode step over a 2 GiB pool, as the serving cell
    runs it: the pools alias their outputs, and nothing the program keeps
    beside them is of the pool's size (the layer's slice made a buffer, the
    relayout before a gather and the gathered context of the dense form
    were 6.5 GB here)."""
    from mlsl_tpu.models import transformer as tfm
    from mlsl_tpu.ops import paged_attention

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

    def decode_body(params, slots, live, kpool, vpool, *scales):
        return tfm.decode_local(params, slots, live, kpool, vpool, cfg, 1,
                                **dict(zip(("kscale", "vscale"), scales)))

    compiled = jax.jit(
        decode_body, donate_argnums=tuple(range(3, 3 + len(pools))),
    ).lower(params, shape((3, batch), jnp.int32), shape((3, cap), jnp.int32),
            *pools).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(jnp.dtype(p.dtype).itemsize * math.prod(p.shape)
                     for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 16, mem
