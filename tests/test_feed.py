"""Device feed pipeline tests (mlsl_tpu/data): wire-codec decode parity,
HBM cache epoch parity, backpressure/exception behavior, chaos threading.

The contract under test: enabling a wire dtype or the feed cache is a pure
TRANSPORT optimization — decoded batches are pinned bit-exact against the
same math done host-side (uint8) or tolerance-pinned against the original
(int8 block codec), and an epoch replay produces the identical batch stream
with the cache on or off.
"""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mlsl_tpu import chaos
from mlsl_tpu.core import stats as core_stats
from mlsl_tpu.log import MLSLError


@pytest.fixture(autouse=True)
def _clean_feed_state():
    core_stats.reset_feed_counters()
    yield
    chaos.clear()
    core_stats.reset_feed_counters()


def _topo(env, n=8):
    dist = env.create_distribution(n, 1)
    return dist, dist.topology


def _batches(k=4, b=16, shape=(8,), classes=4, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        x = rng.normal(size=(b, *shape)).astype(dtype)
        y = rng.integers(0, classes, size=(b,)).astype(np.int32)
        out.append((x, y))
    return out


def _flat(buf, shape):
    """Distributed buffer (R,D,S,M,localB,...) -> host array (B, ...)."""
    a = np.asarray(buf)
    return a.reshape(-1, *shape[1:])[: shape[0] * 1].reshape(shape)


# -- wire spec grammar -------------------------------------------------------


def test_parse_wire_spec_grammar():
    from mlsl_tpu.data import parse_wire_spec

    assert parse_wire_spec(None) == ("none", {})
    assert parse_wire_spec("") == ("none", {})
    assert parse_wire_spec("f32") == ("none", {})
    assert parse_wire_spec("uint8") == ("uint8", {})
    assert parse_wire_spec("bfloat16") == ("bf16", {})
    # per-leaf overrides keep the user's name (alias resolution is at
    # lookup, against positional keys only)
    assert parse_wire_spec("uint8,y=none") == ("uint8", {"y": "none"})
    assert parse_wire_spec("x=int8") == ("none", {"x": "int8"})
    assert parse_wire_spec("img.raw=u8") == ("none", {"img.raw": "uint8"})
    with pytest.raises(ValueError, match="unknown feed wire dtype"):
        parse_wire_spec("float8")


def test_leaf_override_aliases_and_dict_keys(env):
    """x/y alias the canonical tuple's positional leaves at LOOKUP time; a
    dict leaf literally named 'x' matches its own name, not the alias."""
    from mlsl_tpu.data import FeedCodec

    _, topo = _topo(env)
    rng = np.random.default_rng(17)
    xf = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int32)
    # tuple batch: 'x' alias hits leaf 0
    codec = FeedCodec(topo, "x=uint8")
    codec.stage((xf, y))
    assert [l.kind for l in codec._layout] == ["uint8", "none"]
    # dict batch with literal 'x'/'y' keys: names match directly
    codec = FeedCodec(topo, "x=bf16,y=none")
    codec.stage({"x": xf, "y": y})
    kinds = {l.key: l.kind for l in codec._layout}
    assert kinds == {"x": "bf16", "y": "none"}


def test_config_validates_feed_knobs():
    from mlsl_tpu.config import Config

    c = Config()
    c.feed_wire_dtype = "uint8,y=none"
    c.validate()  # fine
    c.feed_wire_dtype = "garbage"
    with pytest.raises(MLSLError, match="MLSL_FEED_WIRE_DTYPE"):
        c.validate()
    c = Config()
    c.feed_depth = 0
    with pytest.raises(MLSLError, match="MLSL_FEED_DEPTH"):
        c.validate()
    c = Config()
    c.feed_cache_mb = -1
    with pytest.raises(MLSLError, match="MLSL_FEED_CACHE_MB"):
        c.validate()


# -- decode parity -----------------------------------------------------------


def test_uint8_raw_decode_parity_bitexact(env):
    """A uint8 source leaf ships raw; on-device (cast + normalize) must be
    BIT-EXACT against the same f32 math done host-side."""
    from mlsl_tpu.data import FeedCodec

    _, topo = _topo(env)
    rng = np.random.default_rng(0)
    x = rng.integers(0, 256, size=(16, 4, 3)).astype(np.uint8)
    y = rng.integers(0, 4, size=(16,)).astype(np.int32)
    mean = np.array([125.3, 122.9, 113.8], np.float32)
    std = np.array([63.0, 62.1, 66.7], np.float32)
    codec = FeedCodec(topo, "uint8", normalize=(mean, std))
    wire, wire_bytes, full_bytes = codec.stage((x, y))
    dx, dy = codec.decode(wire)
    # the canonical decode formulation: subtract mean, multiply by the
    # host-computed reciprocal (see FeedCodec.normalize)
    ref = (x.astype(np.float32) - mean) * (np.float32(1.0) / std)
    np.testing.assert_array_equal(_flat(dx, ref.shape), ref)
    np.testing.assert_array_equal(_flat(dy, y.shape), y)
    # raw uint8 ships 4x fewer bytes than the decoded f32 form would
    assert wire_bytes < (x.size * 4 + y.nbytes) / 3.0


def test_uint8_affine_decode_parity(env):
    """A f32 leaf under uint8 wire: device decode must be bit-exact against
    the host-side affine dequant of the same payload, and within scale/2 of
    the original values."""
    from mlsl_tpu.data import FeedCodec
    from mlsl_tpu.data.wire import _encode_uint8

    _, topo = _topo(env)
    (x, y), = _batches(1, 16, (8, 3), seed=1)
    codec = FeedCodec(topo, "uint8")
    wire, wire_bytes, full_bytes = codec.stage((x, y))
    assert wire_bytes < full_bytes / 3.0  # ~4x byte cut for f32 images
    dx, _ = codec.decode(wire)
    got = _flat(dx, x.shape)
    # host reference, per shard slice exactly as the codec encodes; the
    # decode contract is (q + off) * scale (FMA-proof — see _encode_uint8)
    local_b = 16 // 8
    worst_scale = 0.0
    for d in range(8):
        sl = x[d * local_b : (d + 1) * local_b]
        q, meta = _encode_uint8(sl)
        ref = (q.astype(np.float32) + meta[0]) * meta[1]
        np.testing.assert_array_equal(got[d * local_b : (d + 1) * local_b], ref)
        worst_scale = max(worst_scale, float(meta[1]))
    assert np.abs(got - x).max() <= worst_scale * 0.51 + 1e-6


def test_int8_block_codec_parity(env):
    """int8 wire rides the SAME blockwise codec as the quantized collectives
    (ops/quant_kernels): decode must match dequantize_blocks_ref bit-exactly
    and sit within the per-block scale bound of the original."""
    from mlsl_tpu.data import FeedCodec
    from mlsl_tpu.data.wire import _encode_int8
    from mlsl_tpu.ops import quant_kernels

    _, topo = _topo(env)
    rng = np.random.default_rng(2)
    x = rng.normal(size=(16, 64)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int32)
    block = 128
    codec = FeedCodec(topo, "int8", quant_block=block)
    wire, _, _ = codec.stage((x, y))
    dx, dy = codec.decode(wire)
    got = _flat(dx, x.shape)
    local_b = 16 // 8
    n = local_b * 64
    for d in range(8):
        sl = x[d * local_b : (d + 1) * local_b]
        q, scales = _encode_int8(sl, block)
        ref = np.asarray(
            quant_kernels.dequantize_blocks_ref(
                jnp.asarray(q.reshape(-1, block)), jnp.asarray(scales)
            )
        ).reshape(-1)[:n].reshape(sl.shape)
        np.testing.assert_array_equal(
            got[d * local_b : (d + 1) * local_b], ref
        )
    # per-element error bounded by half the worst block scale
    assert np.abs(got - x).max() <= np.abs(x).max() / 127.0
    np.testing.assert_array_equal(_flat(dy, y.shape), y)


def test_uint8_affine_rejects_extreme_dc_offset(env):
    """A leaf whose DC offset dwarfs its spread cannot ride the uint8 affine
    wire faithfully (float32 ulp(off) would eat the payload bits): encode
    fails LOUDLY with per-leaf guidance instead of decoding to a constant."""
    from mlsl_tpu.data import FeedCodec

    _, topo = _topo(env)
    x = (1e7 + np.linspace(0, 1, 16 * 8).reshape(16, 8)).astype(np.float32)
    y = np.zeros((16,), np.int32)
    codec = FeedCodec(topo, "uint8")
    with pytest.raises(MLSLError, match="DC offset"):
        codec.stage((x, y))


def test_bf16_wire_and_labels_untouched(env):
    from mlsl_tpu.data import FeedCodec

    _, topo = _topo(env)
    (x, y), = _batches(1, 16, (8,), seed=3)
    codec = FeedCodec(topo, "bf16")
    wire, wire_bytes, full_bytes = codec.stage((x, y))
    dx, dy = codec.decode(wire)
    ref = x.astype(jnp.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(_flat(dx, x.shape), ref)
    # int labels never get a lossy wire dtype, even under a default kind
    np.testing.assert_array_equal(_flat(dy, y.shape), y)
    assert wire_bytes == x.size * 2 + y.nbytes  # bf16 x, untouched y


# -- cache ------------------------------------------------------------------


def test_cache_epoch_parity_fixed_shuffle(env):
    """Cache on vs off under a fixed shuffle seed: identical decoded batch
    stream, and the cached run stages each batch exactly once."""
    from mlsl_tpu.data import DeviceFeed

    _, topo = _topo(env)
    batches = _batches(4, 16, (8,), seed=4)

    def run(cache_mb):
        core_stats.reset_feed_counters()
        feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=cache_mb,
                          epochs=3, shuffle_seed=11)
        out = [
            tuple(np.asarray(l) for l in jax.tree.leaves(b)) for b in feed
        ]
        return out, dict(core_stats.FEED_COUNTERS)

    cached, c_on = run(64)
    streamed, c_off = run(0)
    assert len(cached) == 12
    for a, b in zip(cached, streamed):
        for la, lb in zip(a, b):
            np.testing.assert_array_equal(la, lb)
    assert c_on["batches_staged"] == 4          # first epoch only
    assert c_on["cache_hits"] == 8              # epochs 2-3 entirely from HBM
    assert c_off["batches_staged"] == 12        # every epoch over the wire
    assert c_off["cache_hits"] == 0
    # shuffle actually shuffled (some epoch deviates from insertion order)
    xs = [a[0] for a in cached]
    assert any(
        not np.array_equal(xs[e * 4], batches[0][0]) for e in range(3)
    )


@pytest.mark.parametrize("wire,share", [("none", 1.0), ("bf16", 0.55),
                                        ("uint8", 0.3), ("int8", 0.3)])
def test_feed_accounts_the_codecs_wire_bytes_per_batch(env, wire, share):
    """The feed as a training loop holds it (DeviceFeed behind an
    AsyncLoader, HBM cache on, three epochs): what FEED_COUNTERS says crossed
    the link is what the wire's codec says one staging of each batch ships,
    once a batch (epochs two and three come from the cache), and a narrower
    wire ships no more than its share of the float32 it decodes to."""
    from mlsl_tpu.data import AsyncLoader, DeviceFeed, FeedCodec

    _, topo = _topo(env)
    batches = _batches(4, 16, (64, 64, 3), seed=9)
    shipped = [FeedCodec(topo, wire).stage(b)[1:] for b in batches]
    full = sum(x.nbytes + y.nbytes for x, y in batches)
    assert sum(f for _, f in shipped) == full
    core_stats.reset_feed_counters()
    loader = AsyncLoader(
        DeviceFeed(batches, topo, wire=wire, cache_mb=64, epochs=3), depth=2)
    seen = sum(1 for _ in loader)
    loader.close()
    c = dict(core_stats.FEED_COUNTERS)
    assert seen == 12
    assert c["batches_staged"] == 4 and c["cache_hits"] == 8
    assert c["wire_bytes"] == sum(w for w, _ in shipped)
    assert c["wire_bytes"] <= share * full
    assert wire != "none" or c["wire_bytes"] == full


def test_cache_budget_rejects_but_streams(env):
    from mlsl_tpu.data import DeviceFeed

    _, topo = _topo(env)
    batches = _batches(3, 16, (64,), seed=5)
    feed = DeviceFeed(batches, topo, wire="none", cache_mb=0.004, epochs=2)
    out = list(feed)
    assert len(out) == 6
    assert feed.cache.rejects > 0
    assert core_stats.FEED_COUNTERS["cache_rejects"] > 0
    # nothing (or almost nothing) fit: most batches streamed twice
    assert core_stats.FEED_COUNTERS["batches_staged"] >= 4


def test_cached_batch_decodes_stably(env):
    """Cache hits must decode with donate=False: the pinned wire buffers
    survive arbitrarily many replays."""
    from mlsl_tpu.data import DeviceFeed

    _, topo = _topo(env)
    batches = _batches(1, 16, (8,), seed=6)
    feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=64, epochs=4)
    outs = [np.asarray(jax.tree.leaves(b)[0]) for b in feed]
    for o in outs[1:]:
        np.testing.assert_array_equal(outs[0], o)


def test_one_shot_iterator_replay_contract(env):
    from mlsl_tpu.data import DeviceFeed

    _, topo = _topo(env)
    batches = _batches(3, 16, (8,), seed=7)
    # full cache: epoch 1+ replays from HBM without touching the source
    feed = DeviceFeed(iter(batches), topo, wire="bf16", cache_mb=64, epochs=2)
    assert len(list(feed)) == 6
    # cache off: a one-shot iterator cannot replay — loud error, no hang
    feed = DeviceFeed(iter(batches), topo, wire="bf16", cache_mb=0, epochs=2)
    with pytest.raises(MLSLError, match="one-shot iterator"):
        list(feed)
    # shuffle needs random access
    with pytest.raises(MLSLError, match="sequence source"):
        DeviceFeed(iter(batches), topo, shuffle_seed=1)


# -- trainer integration -----------------------------------------------------


def test_trainer_feed_matches_direct_shard_batch(env):
    """trainer.feed(wire='none') must land on the bit-identical trajectory as
    feeding shard_batch directly: the package's placement + decode is a pure
    transport change."""
    from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
    from mlsl_tpu.models.train import DataParallelTrainer

    batches = _batches(3, 16, (8,), seed=8)

    def build():
        dist = env.create_distribution(8, 1)
        sess = env.create_session()
        sess.set_global_minibatch_size(16)
        return DataParallelTrainer(
            env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
            get_layer,
        )

    tr1 = build()
    loader = tr1.feed(batches, wire="", cache_mb=0, epochs=2)
    n = 0
    for b in loader:
        tr1.step(b)
        n += 1
    loader.close()
    assert n == 6

    tr2 = build()
    for _ in range(2):
        for x, y in batches:
            tr2.step(tr2.shard_batch(x, y))
    for a, b in zip(jax.tree.leaves(tr1.params), jax.tree.leaves(tr2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_feed_uint8_cache_trains(env):
    """The full pipeline (uint8 wire + cache + prefetch) trains: losses are
    finite and the replayed epochs hit the cache."""
    from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
    from mlsl_tpu.models.train import DataParallelTrainer

    dist = env.create_distribution(8, 1)
    sess = env.create_session()
    sess.set_global_minibatch_size(16)
    trainer = DataParallelTrainer(
        env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
        get_layer,
    )
    batches = _batches(2, 16, (8,), seed=9)
    loader = trainer.feed(batches, wire="uint8", cache_mb=64, epochs=3,
                          shuffle_seed=3)
    losses = [float(np.asarray(trainer.step(b)).reshape(-1)[0])
              for b in loader]
    loader.close()
    assert len(losses) == 6 and np.isfinite(losses).all()
    assert core_stats.FEED_COUNTERS["cache_hits"] == 4
    assert core_stats.FEED_COUNTERS["batches_staged"] == 2


# -- loader backpressure + failure contract ----------------------------------


def test_backpressure_and_stall_accounting(env):
    from mlsl_tpu.data import AsyncLoader

    # slow source -> consumer stalls are accounted
    def slow_source():
        for i in range(3):
            time.sleep(0.05)
            yield np.full((4,), i, np.float32)

    loader = AsyncLoader(slow_source(), place=lambda b: b, depth=2)
    got = list(loader)
    st = loader.stats()
    loader.close()
    assert len(got) == 3
    assert st["stall_ms"] > 0
    assert core_stats.FEED_COUNTERS["stall_ms"] > 0

    # fast source + slow consumer -> producer blocks on the full queue
    def fast_source():
        for i in range(6):
            yield np.full((4,), i, np.float32)

    loader = AsyncLoader(fast_source(), place=lambda b: b, depth=1)
    time.sleep(0.2)  # let the worker fill the queue and block
    st = loader.stats()
    assert st["in_flight"] <= 1  # depth bound respected
    out = list(loader)
    assert len(out) == 6
    assert loader.stats()["producer_wait_ms"] > 0
    loader.close()


def test_worker_death_surfaces_original_exception(env):
    """A worker that dies mid-epoch surfaces its ORIGINAL exception on the
    next __next__ — and stays exhausted — instead of hanging the consumer."""
    from mlsl_tpu.data import AsyncLoader

    def dying_source():
        yield np.zeros((4,), np.float32)
        yield np.ones((4,), np.float32)
        raise KeyError("backing store lost the shard")

    loader = AsyncLoader(dying_source(), place=lambda b: b, depth=2)
    it = iter(loader)
    assert next(it) is not None
    assert next(it) is not None
    with pytest.raises(KeyError, match="backing store"):
        next(it)
    with pytest.raises(KeyError, match="backing store"):
        next(it)  # still the original error, no empty-queue hang
    loader.close()


def test_transient_source_errors_retry(env):
    from mlsl_tpu.data import AsyncLoader

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] in (2, 3):
            raise OSError("nfs hiccup")  # TRANSIENT in the taxonomy
        if calls["n"] > 5:
            raise StopIteration
        return np.full((4,), calls["n"], np.float32)

    loader = AsyncLoader(flaky, place=lambda b: b, depth=1, retries=2,
                         retry_backoff_s=0.001)
    got = list(loader)
    loader.close()
    assert len(got) == 3  # reads 1, 4 (after two retries), 5
    assert core_stats.FEED_COUNTERS["retries"] == 2

    # retries exhausted -> the original exception surfaces
    calls["n"] = 0

    def always_bad():
        raise OSError("disk gone")

    loader = AsyncLoader(always_bad, place=lambda b: b, depth=1, retries=1,
                         retry_backoff_s=0.001)
    with pytest.raises(OSError, match="disk gone"):
        next(iter(loader))
    loader.close()


def test_dead_generator_error_surfaces_not_truncates(env):
    """Review regression: a TRANSIENT error from a GENERATOR source must
    surface immediately — retrying next() on the dead generator frame yields
    StopIteration, which would read as a clean (truncated!) end-of-stream."""
    from mlsl_tpu.data import AsyncLoader, DeviceFeed

    def gen():
        yield np.zeros((4,), np.float32)
        yield np.ones((4,), np.float32)
        raise OSError("nfs hiccup")  # TRANSIENT — but the frame is now dead

    loader = AsyncLoader(gen(), place=lambda b: b, depth=1, retries=3,
                         retry_backoff_s=0.001)
    it = iter(loader)
    got = [next(it), next(it)]
    assert len(got) == 2
    with pytest.raises(OSError, match="nfs hiccup"):
        next(it)  # the ORIGINAL error, not silent exhaustion
    loader.close()

    # DeviceFeed factory source: same contract, and _n must NOT pin to the
    # truncated length
    _, topo = _topo(env)
    good = _batches(1, 16, (8,), seed=18)[0]

    def factory():
        def g():
            yield good
            raise OSError("read failed")
        return g()

    feed = DeviceFeed(factory, topo, wire="none", cache_mb=0, retries=3)
    it = iter(feed)
    assert next(it) is not None
    with pytest.raises(OSError, match="read failed"):
        next(it)
    assert feed._n is None  # epoch length never learned from a dead stream


# -- chaos threading ---------------------------------------------------------


def test_chaos_error_and_delay_through_feed(env):
    from mlsl_tpu.data import DeviceFeed

    _, topo = _topo(env)
    batches = _batches(2, 16, (8,), seed=10)
    # error: PERSISTENT ChaosError surfaces (no silent retry-away)
    chaos.plan("data.prefetch", "error")
    feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=64)
    with pytest.raises(chaos.ChaosError):
        list(feed)
    chaos.clear()
    # TRANSIENT error: absorbed by the rung-2 retry, stream completes
    p = chaos.plan("data.prefetch", "error", exc=OSError)
    feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=64, retries=2)
    assert len(list(feed)) == 2
    assert p.fires == 1
    assert core_stats.FEED_COUNTERS["retries"] >= 1
    chaos.clear()
    # delay: slows, never corrupts
    chaos.plan("data.prefetch", "delay", seconds=0.01, times=None)
    feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=64, epochs=2)
    out = [np.asarray(jax.tree.leaves(b)[0]) for b in feed]
    assert len(out) == 4
    np.testing.assert_array_equal(out[0], out[2])  # cached replay identical


def test_chaos_bitrot_through_codec_and_cache(env):
    """bitrot rots the encoded wire payload: decode survives (shapes/dtypes
    intact, values differ) and the cache replays the rotted batch
    consistently — a bad read is bad data, not a crash."""
    from mlsl_tpu.data import DeviceFeed

    _, topo = _topo(env)
    batches = _batches(1, 16, (8,), seed=12)

    clean_feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=0)
    clean = np.asarray(jax.tree.leaves(next(iter(clean_feed)))[0])

    chaos.plan("data.prefetch", "bitrot")
    feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=64, epochs=2)
    it = iter(feed)
    rotted = np.asarray(jax.tree.leaves(next(it))[0])
    assert rotted.shape == clean.shape and rotted.dtype == clean.dtype
    assert not np.array_equal(rotted, clean)
    replay = np.asarray(jax.tree.leaves(next(it))[0])
    np.testing.assert_array_equal(rotted, replay)  # cache is consistent
    assert np.isfinite(rotted).all()


def test_loader_surfaces_feed_error_not_truncation(env):
    """A TRANSIENT error that exhausts the DeviceFeed's OWN retry budget must
    surface through the wrapping AsyncLoader — not be re-retried against the
    now-dead generator, which would read as clean exhaustion and silently
    truncate the epoch."""
    from mlsl_tpu.data import AsyncLoader, DeviceFeed

    _, topo = _topo(env)
    good = _batches(1, 16, (8,), seed=16)[0]

    def source():
        yield good
        raise OSError("source died")

    feed = DeviceFeed(source(), topo, wire="none", cache_mb=0, retries=0)
    loader = AsyncLoader(feed, depth=2)
    it = iter(loader)
    assert next(it) is not None
    with pytest.raises(OSError, match="source died"):
        next(it)
    loader.close()


def test_loader_rejects_place_with_devicefeed(env):
    """A DeviceFeed already places and decodes — passing a place callable
    (the old-API habit) must fail loudly at construction, not die with a
    shape error deep in the prefetch thread."""
    from mlsl_tpu.data import AsyncLoader, DeviceFeed

    _, topo = _topo(env)
    feed = DeviceFeed(_batches(1, 16, (8,), seed=20), topo, wire="none")
    with pytest.raises(MLSLError, match="place must be None"):
        AsyncLoader(feed, lambda x, y: (x, y), depth=1)


def test_loader_does_not_double_fire_chaos_over_devicefeed(env):
    """AsyncLoader must not fire data.prefetch again when its source is a
    DeviceFeed (which already injects per batch): an armed @after/xN budget
    would otherwise burn twice per batch."""
    from mlsl_tpu.data import AsyncLoader, DeviceFeed

    _, topo = _topo(env)
    batches = _batches(3, 16, (8,), seed=13)
    p = chaos.plan("data.prefetch", "delay", seconds=0.0, times=None)
    feed = DeviceFeed(batches, topo, wire="none", cache_mb=0)
    loader = AsyncLoader(feed, depth=2)
    assert len(list(loader)) == 3
    loader.close()
    assert p.hits == 3  # one per batch, not two


# -- observability -----------------------------------------------------------


def test_feed_spans_on_timeline(env):
    from mlsl_tpu import obs
    from mlsl_tpu.data import DeviceFeed

    _, topo = _topo(env)
    tr = obs.enable()
    try:
        tr.clear()
        batches = _batches(2, 16, (8,), seed=14)
        feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=64, epochs=2)
        list(feed)
        names = {(ev[obs.tracer.CAT], ev[obs.tracer.NAME])
                 for ev in tr.snapshot()}
        assert ("feed", "h2d.transfer") in names
        assert ("feed", "feed.decode") in names
        assert ("feed", "feed.cache_hit") in names
        assert len(tr.span_durations("h2d.transfer", "feed")) == 2
    finally:
        obs.disable()


def test_feed_line_surfaces_on_stall_alone(env, tmp_path, monkeypatch):
    """A plain AsyncLoader run (no wire path, no cache) that stalled the
    consumer must still print the FEED line — 'is this run input-bound' is
    exactly what the line answers."""
    from mlsl_tpu.data import AsyncLoader

    monkeypatch.setenv("MLSL_STATS_DIR", str(tmp_path))
    sess = env.create_session()

    def slow():
        for i in range(2):
            time.sleep(0.03)
            yield np.full((4,), i, np.float32)

    loader = AsyncLoader(slow(), place=lambda b: b, depth=1)
    list(loader)
    loader.close()
    assert core_stats.FEED_COUNTERS["batches_staged"] == 0
    assert core_stats.FEED_COUNTERS["stall_ms"] > 0
    assert "FEED" in sess.get_stats().print_()


def test_chaos_bitrot_not_swallowed_by_streaming_cache_hit(env):
    """Review regression: on a partially/fully cached STREAMING epoch a
    fired bitrot must corrupt what is served — not be silently discarded
    because the key happens to be cached."""
    from mlsl_tpu.data import DeviceFeed

    _, topo = _topo(env)
    # budget fits exactly ONE wire batch: the cache stays incomplete, so
    # epoch 1 must stream (and read) while key 0 is a cache hit
    batches = _batches(2, 16, (8,), seed=19)
    feed = DeviceFeed(lambda: iter(list(batches)), topo, wire="uint8",
                      cache_mb=0.0003, epochs=2)
    it = iter(feed)
    first_clean = np.asarray(jax.tree.leaves(next(it))[0])
    next(it)
    assert len(feed.cache) == 1 and feed.cache.rejects >= 1
    # after=1: the next site hit is epoch 0's END-OF-EPOCH probe read (the
    # next(it) that raises StopIteration also passes the chaos site); the
    # fire must land on epoch 1's first REAL read
    p = chaos.plan("data.prefetch", "bitrot", after=1)
    rotted = np.asarray(jax.tree.leaves(next(it))[0])
    assert p.fires == 1
    assert not np.array_equal(rotted, first_clean)  # served rot, not cache


def test_feed_line_in_stats_log(env, tmp_path, monkeypatch):
    from mlsl_tpu.data import DeviceFeed

    monkeypatch.setenv("MLSL_STATS_DIR", str(tmp_path))
    dist, topo = _topo(env)
    sess = env.create_session()
    batches = _batches(2, 16, (8,), seed=15)
    feed = DeviceFeed(batches, topo, wire="uint8", cache_mb=64, epochs=2)
    list(feed)
    text = sess.get_stats().print_()
    assert "FEED" in text
    assert "cache 2h/2m" in text
    with open(tmp_path / "mlsl_stats.log") as f:
        assert "FEED" in f.read()
