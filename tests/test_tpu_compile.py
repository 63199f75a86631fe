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


_INDEXER = {}      # (program, pool MB) -> what ``indexer_program`` compiled


def indexer_program(one_chip, program, pool_mb):
    """The decode step or the prefill chunk (the engine's own bodies) of
    the configuration with an indexer (perf/configs/keye-vl2-30b-a3b-pp8.json:
    32 query heads over 4 key-value heads, 16 x 64 indexer with topk 2,048,
    128 experts, bfloat16 weights and pools) at the serving cell's sizes,
    compiled once a pool size: -> (cfg, traffic, weights' bytes, pools'
    bytes, lowered, compiled)."""
    import pathlib
    import sys

    if (program, pool_mb) in _INDEXER:
        return _INDEXER[program, pool_mb]
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
    pool = sum(jnp.dtype(p.dtype).itemsize * math.prod(p.shape)
               for p in pools)
    _INDEXER[program, pool_mb] = (cfg, traffic, weights, pool, lowered,
                                  lowered.compile())
    return _INDEXER[program, pool_mb]


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_indexer_programs_keep_three_pools_in_place(
        one_chip, no_compile_cache, program):
    """The decode step and the prefill chunk of the configuration with an
    indexer: K, V and the index keys alias their outputs, the temporaries do
    not grow with the pool (1 and 3 GiB compile to the same), weights, pool
    and temporaries fit the chip, and beside the pools come tokens and two
    counts, no logits. The first trace of PR 29 showed what a 64-lane index
    pool costs: the compiler laid the whole pool out anew around every
    layer's gather."""
    def compiled(pool_mb):
        cfg, traffic, weights, pool, lowered, exe = indexer_program(
            one_chip, program, pool_mb)
        assert 8.7e9 < weights < 8.8e9      # 4,375 M parameters in bfloat16
        mem = exe.memory_analysis()
        tokens_and_pools_only(lowered, mem, traffic["max_batch"], cfg.vocab)
        return mem, pool, weights

    small, small_pool, weights = compiled(1024)
    large, large_pool, _ = compiled(3072)
    assert large.alias_size_in_bytes >= large_pool > 2.9 * small_pool
    assert small.alias_size_in_bytes >= small_pool
    assert abs(large.temp_size_in_bytes - small.temp_size_in_bytes) < 64 << 20
    assert large.temp_size_in_bytes < large_pool
    assert weights + large_pool + large.temp_size_in_bytes < 15.75 * 2**30


def _computations(hlo):
    """Compiled HLO text -> {computation's name: its lines}, the entry under
    ``"ENTRY"``."""
    out, name = {}, None
    for line in hlo.splitlines():
        if line.startswith(("ENTRY ", "%")) and line.rstrip().endswith("{"):
            name = "ENTRY" if line.startswith("ENTRY") \
                else line.split()[0].lstrip("%")
            out[name] = []
        elif name is not None:
            out[name].append(line)
    return out


def _passes_loops(comps, rows, width):
    """The ``while`` loops that carry (rows, width) ordered scores: -> a
    list of (the loop's line, trips from its condition, the instructions
    of its body that read the scores)."""
    import re

    carried = re.compile(rf"u32\[{rows},{width}\]\{{[^}}]*\}}")
    found = []
    for lines in comps.values():
        for line in lines:
            if " while(" not in line or not carried.search(line):
                continue
            cond = re.search(r"condition=%([\w.\-]+)", line).group(1)
            body = re.search(r"body=%([\w.\-]+)", line).group(1)
            trips, = [int(n) for c in comps[cond]
                      for n in re.findall(r" constant\((\d+)\)", c)]
            scores, = [re.match(r"\s*%([\w.\-]+) = ", c).group(1)
                       for c in comps[body]
                       if "get-tuple-element(" in c and carried.search(
                           c.split(" get-tuple-element(")[0])]
            readers = [c for c in comps[body]
                       if re.search(rf"%{re.escape(scores)}[,)]", c)
                       and not c.lstrip().startswith("ROOT")]
            found.append((line, trips, readers))
    return found


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_indexer_programs_keep_the_loops_the_readers_count(
        one_chip, no_compile_cache, program):
    """What perf/lib/keye_spans.py cuts the device time by, and what a
    counting pass costs. The decode program: two loops a layer at the top
    level (the passes over 16 x 33,280, then the experts' walk), no
    conditional. The chunk program: three a layer (index scores, attention,
    experts) and the selection's conditional between the first two, and in
    it one search a layer, whatever the context held: units of 1,024 tile
    rows of 8,320, a unit's 32 passes, the unit's ordered scores in fast
    memory (``S(1)``) across them, and one fusion a trip that reads them."""
    from mlsl_tpu.ops import paged_attention

    cfg, traffic, _, _, _, exe = indexer_program(one_chip, program, 1024)
    comps = _computations(exe.as_text())
    top = [line for line in comps["ENTRY"] if " while(" in line]
    conditionals = [line for line in comps["ENTRY"] if " conditional(" in line]
    s_max = cfg.seq_len
    if program == "decode":
        rows, width = traffic["max_batch"], s_max
        assert len(top) == 2 * cfg.n_blocks and not conditionals
    else:
        rows, width = paged_attention.GROUP_BYTES // s_max, s_max // 4
        assert (rows, width) == (1024, 8320)
        assert len(top) == 3 * cfg.n_blocks
        assert len(conditionals) == cfg.n_blocks
    loops = _passes_loops(comps, rows, width)
    assert len(loops) == cfg.n_blocks
    for line, trips, readers in loops:
        assert trips == 32
        assert len(readers) == 1 and " fusion(" in readers[0], readers
        if program == "chunk":
            assert f"u32[{rows},{width}]{{1,0:T(8,128)S(1)}}" in line
        else:
            assert line in top
