"""Quant-bucket parity suite (core/bucketing.py compressed path) and the
commit-time AOT precompilation plans (MLSL_PRECOMPILE).

The coalesced compressed ring is an approximation-preserving rearrangement of
the individual compressed rings: results are checked against the exact sum
with the reference's statistical oracle (rel L2 < 2%, mlsl_test.cpp:407-428)
and against the individual ring within error-feedback tolerance — never
bit-exactly (entry quantization sees a different block stream)."""

import os

import numpy as np
import pytest
import jax

from mlsl_tpu.types import CompressionType, DataType, OpType


def _quant_session(env, counts, bucket_mb, du=False, dtype=DataType.FLOAT,
                   compression=CompressionType.QUANTIZATION):
    env.config.grad_bucket_mb = bucket_mb
    dist = env.create_distribution(8, 1)
    s = env.create_session()
    s.set_global_minibatch_size(8)
    ops = []
    for c in counts:
        r = s.create_operation_reg_info(OpType.CC)
        r.add_input(8, 4)
        r.add_output(8, 4)
        r.add_parameter_set(c, 1, data_type=dtype, distributed_update=du,
                            compression_type=compression)
        ops.append(s.get_operation(s.add_operation(r, dist)))
    s.commit()
    env.config.grad_bucket_mb = 0
    return dist, s, [op.get_parameter_set(0) for op in ops]


def _bufs(dist, counts, vals):
    return [
        dist.make_buffer(lambda p, v=v: v[p], c)
        for c, v in zip(counts, vals)
    ]


def _vals(counts, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {p: rng.normal(size=c).astype(np.float32) for p in range(8)}
        for c in counts
    ]


def _round(pss, bufs):
    for ps, b in zip(reversed(pss), reversed(bufs)):
        ps.start_gradient_comm(b)
    return [ps.wait_gradient_comm() for ps in pss]


def _rel(got, exact):
    return np.linalg.norm(got - exact) / (np.linalg.norm(exact) + 1e-9)


@pytest.mark.parametrize("bucket_mb,n_buckets", [(4, 1), (1, 2)])
def test_quant_bucket_matches_individual_within_tolerance(env, bucket_mb,
                                                          n_buckets):
    """Bucketed compressed ring vs individual compressed ring vs exact sum,
    across bucket sizes (one bucket / several buckets), over several rounds
    (error feedback engaged on both paths)."""
    counts = [65536] * 6  # 256 KiB each: 1 MiB limit splits, 4 MiB coalesces
    vals = _vals(counts)
    dist_i, _, ind = _quant_session(env, counts, 0)
    dist_b, _, buck = _quant_session(env, counts, bucket_mb)
    assert all(ps.bucket is None for ps in ind)
    buckets = {id(ps.bucket) for ps in buck}
    assert all(ps.bucket is not None for ps in buck)
    assert len(buckets) == n_buckets
    assert all(ps.bucket.compression == CompressionType.QUANTIZATION
               for ps in buck)

    for _ in range(3):  # rounds: residuals carry on both paths
        outs_i = _round(ind, _bufs(dist_i, counts, vals))
        outs_b = _round(buck, _bufs(dist_b, counts, vals))
    assert all(ps._bucket_round for ps in buck)  # bucket served, no fallback
    for c, v, oi, ob in zip(counts, vals, outs_i, outs_b):
        exact = sum(v.values())
        got_i = np.asarray(dist_i.local_part(oi, 0))[:c]
        got_b = np.asarray(dist_b.local_part(ob, 0))[:c]
        assert _rel(got_i, exact) < 0.02
        assert _rel(got_b, exact) < 0.02
        # error-feedback tolerance between the two compressed paths: each is
        # within one quant error of exact, so within two of each other
        assert _rel(got_b, got_i) < 0.04


def test_resnet50_shaped_stream_coalesces(env):
    """A ragged stream as a real model registers it (ResNet-50's 161
    gradient tensors, counts divided by 16): under a 4 MiB limit the stream
    rides a few coalesced compressed rings, every tensor in exactly one, and
    no bucket holds more than the limit."""
    from conftest import resnet50_counts

    counts = resnet50_counts(scale=16, floor=256)
    assert len(counts) == 161
    _, _, pss = _quant_session(env, counts, 4)
    bucketed = [ps for ps in pss if ps.bucket is not None]
    assert len(bucketed) == 161
    buckets = {id(ps.bucket): ps.bucket for ps in bucketed}.values()
    assert sum(len(b.members) for b in buckets) == len(bucketed)
    for b in buckets:
        assert b.compression == CompressionType.QUANTIZATION
        assert len(b.members) > 1 and 4 * sum(b.counts) <= 4 << 20


def test_quant_bucket_dtype_and_compression_mixing(env):
    """Same-dtype quantized sets share a bucket; uncompressed, other-dtype,
    and TOPK sets never mix into it (TOPK stays individual entirely)."""
    env.config.grad_bucket_mb = 4
    try:
        dist = env.create_distribution(8, 1)
        s = env.create_session()
        s.set_global_minibatch_size(8)

        def add(dtype, comp, n=2):
            out = []
            for _ in range(n):
                r = s.create_operation_reg_info(OpType.CC)
                r.add_input(8, 4)
                r.add_output(8, 4)
                r.add_parameter_set(512, 1, data_type=dtype,
                                    compression_type=comp)
                out.append(s.get_operation(s.add_operation(r, dist)))
            return out

        q32 = add(DataType.FLOAT, CompressionType.QUANTIZATION)
        plain = add(DataType.FLOAT, CompressionType.NONE)
        qbf = add(DataType.BFLOAT16, CompressionType.QUANTIZATION)
        topk = add(DataType.FLOAT, CompressionType.TOPK)
        s.commit()

        ps = lambda ops: [op.get_parameter_set(0) for op in ops]
        q32b = {id(p.bucket) for p in ps(q32)}
        plainb = {id(p.bucket) for p in ps(plain)}
        qbfb = {id(p.bucket) for p in ps(qbf)}
        assert len(q32b) == 1 and None not in {p.bucket for p in ps(q32)}
        assert len(plainb) == 1 and None not in {p.bucket for p in ps(plain)}
        assert len(qbfb) == 1 and None not in {p.bucket for p in ps(qbf)}
        assert q32b.isdisjoint(plainb) and q32b.isdisjoint(qbfb)
        assert ps(q32)[0].bucket.compression == CompressionType.QUANTIZATION
        assert ps(plain)[0].bucket.compression == CompressionType.NONE
        assert all(p.bucket is None for p in ps(topk))
    finally:
        env.config.grad_bucket_mb = 0


def test_quant_bucket_early_wait_fallback(env):
    """A Wait before the quant bucket fills degrades to the members'
    individual compressed requests (correctness never depends on co-arrival);
    the next complete round is bucket-served again."""
    counts = [1024] * 3
    vals = _vals(counts, seed=1)
    dist, _, pss = _quant_session(env, counts, 4)
    assert all(ps.bucket is not None for ps in pss)
    bufs = _bufs(dist, counts, vals)

    pss[0].start_gradient_comm(bufs[0])
    pss[1].start_gradient_comm(bufs[1])
    out0 = pss[0].wait_gradient_comm()  # partial round -> fallback
    out1 = pss[1].wait_gradient_comm()
    assert not pss[0]._bucket_round and not pss[1]._bucket_round
    for i, out in ((0, out0), (1, out1)):
        exact = sum(vals[i].values())
        assert _rel(np.asarray(dist.local_part(out, 0))[: counts[i]], exact) < 0.02

    outs = _round(pss, bufs)  # complete round: bucket serves again
    assert all(ps._bucket_round for ps in pss)
    for i, out in enumerate(outs):
        exact = sum(vals[i].values())
        assert _rel(np.asarray(dist.local_part(out, 0))[: counts[i]], exact) < 0.02


@pytest.mark.chaos
def test_quant_bucket_chaos_roundtrip_recovers(env):
    """A fault at the quant_ring chaos site ('codec.roundtrip') during the
    bucket's coalesced dispatch surfaces at the starting member, the already-
    registered members degrade to their individual compressed rings, and the
    next round is clean."""
    from mlsl_tpu import chaos

    counts = [1024] * 2
    vals = _vals(counts, seed=2)
    dist, _, pss = _quant_session(env, counts, 4)
    assert all(ps.bucket is not None for ps in pss)
    bufs = _bufs(dist, counts, vals)

    with chaos.injected("codec.roundtrip", "error", times=1):
        pss[1].start_gradient_comm(bufs[1])
        # the LAST member's start fires the coalesced ring -> chaos raises
        with pytest.raises(chaos.ChaosError):
            pss[0].start_gradient_comm(bufs[0])
    # member 1 is still registered in the un-dispatched round: its wait runs
    # the fallback (individual compressed ring); member 0 never started
    out1 = pss[1].wait_gradient_comm()
    assert _rel(np.asarray(dist.local_part(out1, 0))[: counts[1]],
                sum(vals[1].values())) < 0.02
    # next complete round is bucket-served
    outs = _round(pss, bufs)
    assert all(ps._bucket_round for ps in pss)
    for i, out in enumerate(outs):
        assert _rel(np.asarray(dist.local_part(out, 0))[: counts[i]],
                    sum(vals[i].values())) < 0.02


def test_quant_bucket_error_feedback_improves_repeated_sums(env):
    """The bucket residual (one buffer, per-member slices) preserves the
    error-feedback contract: the time-averaged bucketed result converges on
    repeated identical sums, like the individual ring's."""
    counts = [1024, 512]
    dist, _, pss = _quant_session(env, counts, 4)
    assert all(ps.bucket is not None for ps in pss)
    x = np.linspace(-3, 3, counts[0]).astype(np.float32) + 0.0317
    vals = [{p: x for p in range(8)},
            {p: x[: counts[1]] for p in range(8)}]
    exact = 8.0 * x
    outs = []
    for _ in range(16):
        outs.append(np.asarray(dist.local_part(
            _round(pss, _bufs(dist, counts, vals))[0], 0))[: counts[0]])
    err_single = np.abs(outs[0] - exact).mean()
    err_avg = np.abs(np.mean(outs, axis=0) - exact).mean()
    assert err_avg <= err_single * 0.51 or err_avg < 1e-4


def test_zero1_quant_bucket_both_phases(env):
    """ZeRO-1 quantized sets coalesce the gradient phase on the compressed
    ring (reduce_scatter kind) and the increment all_gather on the plain
    bucket; owned shards match the exact reduction's slices."""
    counts = [1024] * 3
    vals = _vals(counts, seed=3)
    dist, _, pss = _quant_session(env, counts, 4, du=True)
    assert all(ps.bucket is not None and ps.bucket.kind == "reduce_scatter"
               for ps in pss)
    assert pss[0].bucket.compression == CompressionType.QUANTIZATION
    assert all(ps.inc_bucket is not None and ps.inc_bucket.kind == "allgather"
               for ps in pss)
    assert pss[0].inc_bucket.compression == CompressionType.NONE

    bufs = _bufs(dist, counts, vals)
    outs = _round(pss, bufs)
    assert all(ps._bucket_round for ps in pss)
    for i, (ps, out) in enumerate(zip(pss, outs)):
        n_owned = ps.owned_kernel_count * ps.kernel_size
        exact = sum(vals[i].values())
        for p in range(8):
            got = np.asarray(dist.local_part(out, p))[:n_owned]
            want = exact[p * n_owned:(p + 1) * n_owned]
            assert _rel(got, want) < 0.02, f"member {i} rank {p}"


def test_bucket_round_counters(env):
    """The stats ring tracks dispatched / fallback / abandon rounds, coalesced
    bytes, and the compression wire-savings estimate; print_ emits the BUCKET
    line into mlsl_stats.log."""
    from mlsl_tpu.core import stats as stats_mod

    counts = [1024] * 2
    vals = _vals(counts, seed=4)
    dist, sess, pss = _quant_session(env, counts, 4)
    bufs = _bufs(dist, counts, vals)
    stats_mod.reset_bucket_counters()
    try:
        _round(pss, bufs)  # dispatched round
        c = stats_mod.BUCKET_COUNTERS
        assert c["rounds_dispatched"] == 1
        assert c["bytes_coalesced"] == sum(counts) * 4
        assert c["wire_bytes_saved"] > 0  # int8 wire vs f32
        pss[0].start_gradient_comm(bufs[0])
        pss[0].wait_gradient_comm()  # partial -> fallback round
        assert c["rounds_fallback"] == 1
        # restart while in flight -> abandon
        pss[0].start_gradient_comm(bufs[0])
        pss[1].start_gradient_comm(bufs[1])  # dispatches (round 2)
        pss[1].start_gradient_comm(bufs[1])  # restart mid-flight: abandons
        assert c["member_abandons"] == 1
        for ps in pss:
            ps.wait_gradient_comm()
        text = sess.get_stats().print_(path=os.devnull)
        assert "BUCKET" in text and "dispatched" in text
        assert stats_mod.BUCKET_EVENTS  # per-round detail ring populated
    finally:
        stats_mod.reset_bucket_counters()


def test_precompile_first_round_has_no_compiles(env):
    """MLSL_PRECOMPILE contract at the request layer: after Commit warms the
    plans, the first full start/wait round — bucketed quant ring, pack,
    unpack — triggers zero XLA backend compilations."""
    from mlsl_tpu.comm import collectives
    from mlsl_tpu.core import stats as stats_mod

    env.config.precompile = True
    try:
        counts = [3072] * 3
        vals = _vals(counts, seed=5)
        dist, sess, pss = _quant_session(env, counts, 4)
        assert all(ps.bucket is not None for ps in pss)
        assert len(collectives._plan_cache) > 0
        bufs = _bufs(dist, counts, vals)
        with stats_mod.count_backend_compiles() as n:
            outs = _round(pss, bufs)
        assert n[0] == 0, f"{n[0]} compiles leaked into the first round"
        assert _rel(np.asarray(dist.local_part(outs[0], 0))[: counts[0]],
                    sum(vals[0].values())) < 0.02
        # idempotent: a second commit-equivalent walk warms nothing new
        assert sess.precompile_collectives() == 0
    finally:
        env.config.precompile = False


def test_precompile_trainer_step0_has_no_compiles(env):
    """The models/train.py acceptance probe: with precompilation (session
    plans at Commit + trainer.precompile for the model-side programs), step 0
    contains no compilation at all — and precompile() leaves params
    untouched."""
    from mlsl_tpu.core import stats as stats_mod
    from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
    from mlsl_tpu.models.train import DataParallelTrainer

    env.config.precompile = True
    env.config.grad_bucket_mb = 4
    try:
        dist = env.create_distribution(8, 1)
        sess = env.create_session()
        sess.set_global_minibatch_size(32)
        t = DataParallelTrainer(env, dist, sess, init(jax.random.PRNGKey(0)),
                                loss_fn, LAYERS, get_layer, lr=0.1,
                                force_graph_path=True)
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 8)).astype(np.float32)
        y = rng.integers(0, 4, size=(32,)).astype(np.int32)
        batch = t.shard_batch(x, y)
        before = jax.device_get(t.params)
        t.precompile(batch)
        after = jax.device_get(t.params)
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        with stats_mod.count_backend_compiles() as n:
            loss = t.step(batch)
            jax.block_until_ready(loss)
        assert n[0] == 0, f"step 0 compiled {n[0]} program(s)"
        assert np.isfinite(float(np.asarray(loss).reshape(-1)[0]))
    finally:
        env.config.precompile = False
        env.config.grad_bucket_mb = 0


def test_precompile_warms_same_shape_sibling_buckets(env):
    """Bucket pack/unpack are per-instance jit closures: a second bucket with
    the same shape identity must be warmed too (a shape-keyed plan entry
    would skip it and leak its compiles into step 0)."""
    from mlsl_tpu.core.stats import count_backend_compiles

    env.config.precompile = True
    try:
        counts = [65536] * 6  # 1 MiB limit -> two same-shaped buckets
        dist, _, pss = _quant_session(env, counts, 1)
        assert len({id(ps.bucket) for ps in pss}) == 2
        vals = _vals(counts, seed=7)
        bufs = _bufs(dist, counts, vals)
        with count_backend_compiles() as n:
            _round(pss, bufs)
        assert n[0] == 0, f"sibling bucket leaked {n[0]} compiles into round 0"
    finally:
        env.config.precompile = False


def test_zero1_mixed_compression_shares_inc_bucket(env):
    """The increment all_gather is always uncompressed, so ZeRO-1 sets with
    DIFFERENT gradient compressions still coalesce their increments into ONE
    bucket; only the gradient phase partitions by compression."""
    env.config.grad_bucket_mb = 4
    try:
        dist = env.create_distribution(8, 1)
        s = env.create_session()
        s.set_global_minibatch_size(8)
        ops = []
        for comp in (CompressionType.QUANTIZATION, CompressionType.NONE,
                     CompressionType.QUANTIZATION, CompressionType.NONE):
            r = s.create_operation_reg_info(OpType.CC)
            r.add_input(8, 4)
            r.add_output(8, 4)
            r.add_parameter_set(1024, 1, distributed_update=True,
                                compression_type=comp)
            ops.append(s.get_operation(s.add_operation(r, dist)))
        s.commit()
        pss = [op.get_parameter_set(0) for op in ops]
        assert len({id(ps.inc_bucket) for ps in pss}) == 1
        assert len({(id(ps.bucket), ps.bucket.compression) for ps in pss}) == 2
    finally:
        env.config.grad_bucket_mb = 0


@pytest.mark.chaos
def test_precompile_warm_does_not_consume_chaos_budgets(env):
    """The Commit-time warm bypasses the chaos sites: an armed one-shot fault
    must survive precompilation and fire at the training step it targets —
    not be spent (or hung) inside Commit where no watchdog is armed."""
    from mlsl_tpu import chaos

    env.config.precompile = True
    try:
        with chaos.injected("collective.dispatch", "error", times=1) as p1, \
             chaos.injected("codec.roundtrip", "error", times=1) as p2:
            dist, _, pss = _quant_session(env, [512] * 2, 4)
            assert p1.fires == 0 and p2.fires == 0  # commit warmed cleanly
            vals = _vals([512] * 2, seed=6)
            bufs = _bufs(dist, [512] * 2, vals)
            with pytest.raises(chaos.ChaosError):
                for ps, b in zip(reversed(pss), reversed(bufs)):
                    ps.start_gradient_comm(b)
                for ps in pss:
                    ps.wait_gradient_comm()
            assert p1.fires + p2.fires >= 1  # the step consumed it
    finally:
        env.config.precompile = False


def test_clear_cache_clears_plan_cache(env):
    """Test-isolation contract: collectives.clear_cache() drops the AOT plan
    cache together with the program cache — a fresh program cache means cold
    jit dispatch caches, so stale plan entries must not suppress re-warming."""
    from mlsl_tpu.comm import collectives

    env.config.precompile = True
    try:
        _quant_session(env, [512] * 2, 4)
        assert collectives._plan_cache
        assert collectives._cache
        collectives.clear_cache()
        assert not collectives._plan_cache
        assert not collectives._cache
    finally:
        env.config.precompile = False
