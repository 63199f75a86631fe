"""Compiled overlap engine (comm/overlap.py): lockstep-twin parity against
the host per-layer path, plus the chaos / precompile / sentinel / tuner
integration contracts.

The host Start/Wait engine stays the parity ORACLE: every trainer test runs
the same model through ``force_graph_path=True`` (host) and
``overlap_compiled=True`` (in-graph) twins and pins losses and final params
against each other; the standalone grid pins the staged multi-tensor reduce
bit-exact on integer payloads against the host algorithm programs across
{lax, rhd, ring2d} x group shapes {8, (4,2), 6}.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mlsl_tpu import chaos
from mlsl_tpu.comm import algos, overlap, quant_ring
from mlsl_tpu.comm.mesh import ProcessGroup, Topology
from mlsl_tpu.core import stats
from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
from mlsl_tpu.models.train import DataParallelTrainer
from mlsl_tpu.types import CompressionType, ReductionType


def _make_trainer(env, overlap_on: bool, params, **kw):
    dist = env.create_distribution(8, 1)
    s = env.create_session()
    s.set_global_minibatch_size(32)
    return DataParallelTrainer(
        env, dist, s, params, loss_fn, LAYERS, get_layer, lr=0.1,
        overlap_compiled=overlap_on, force_graph_path=not overlap_on, **kw
    )


def _batch():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(32,)).astype(np.int32)
    return x, y


def _max_param_delta(a, b):
    return max(
        float(np.max(np.abs(np.asarray(la) - np.asarray(lb))))
        for la, lb in zip(
            jax.tree.leaves(jax.device_get(a)), jax.tree.leaves(jax.device_get(b))
        )
    )


def _run_twins(env, steps=4, **kw):
    params = init(jax.random.PRNGKey(0))
    th = _make_trainer(env, False, params, **kw)
    tc = _make_trainer(env, True, params, **kw)
    assert tc._overlap is not None, "compiled overlap did not engage"
    x, y = _batch()
    bh, bc = th.shard_batch(x, y), tc.shard_batch(x, y)
    lh = lc = None
    for _ in range(steps):
        lh, lc = th.step(bh), tc.step(bc)
    return th, tc, lh, lc


# ---------------------------------------------------------------------------
# trainer lockstep twins: {plain, quantized-EF, bucketed}
# ---------------------------------------------------------------------------


def test_twin_plain(env):
    th, tc, lh, lc = _run_twins(env)
    np.testing.assert_allclose(np.asarray(lh).reshape(-1),
                               np.asarray(lc).reshape(-1), rtol=1e-6)
    assert _max_param_delta(th.params, tc.params) <= 1e-6


def test_twin_quantized_ef(env):
    """The in-graph quantize -> ring -> dequantize with the error-feedback
    residual threaded through the step carry must track the host per-layer
    compressed requests exactly — same geometry, same body, multiple rounds
    so the residual state itself is pinned."""
    th, tc, lh, lc = _run_twins(
        env, steps=5, compression=CompressionType.QUANTIZATION
    )
    np.testing.assert_allclose(np.asarray(lh).reshape(-1),
                               np.asarray(lc).reshape(-1), rtol=1e-6)
    assert _max_param_delta(th.params, tc.params) <= 1e-6
    assert tc._overlap.plan.quant_units == len(LAYERS)
    assert tc._overlap.residuals  # EF state threaded as trainer state


def test_twin_bucketed(env):
    """grad_bucket_mb coalesces the compiled plan's small uncompressed
    layers with the SAME packing policy as the host buckets — fewer units
    than layers, parity intact."""
    env.config.grad_bucket_mb = 4
    try:
        th, tc, lh, lc = _run_twins(env)
    finally:
        env.config.grad_bucket_mb = 0
    assert len(tc._overlap.plan.units) < len(LAYERS)
    np.testing.assert_allclose(np.asarray(lh).reshape(-1),
                               np.asarray(lc).reshape(-1), rtol=1e-6)
    assert _max_param_delta(th.params, tc.params) <= 1e-6


def test_twin_forced_algos(env):
    """MLSL_ALGO reroutes the in-graph units through the same selection
    table as the host requests (explicit > tuned > lax)."""
    for name in ("rhd", "lax"):
        env.config.collective_algo = name
        env.config.validate()
        try:
            th, tc, _, _ = _run_twins(env, steps=3)
        finally:
            env.config.collective_algo = ""
            env.config.validate()
        assert all(u.algo == name for u in tc._overlap.plan.units)
        assert _max_param_delta(th.params, tc.params) <= 1e-6


def test_twin_clip_global_norm(env):
    th, tc, lh, lc = _run_twins(env, clip_global_norm=0.25)
    np.testing.assert_allclose(np.asarray(lh).reshape(-1),
                               np.asarray(lc).reshape(-1), rtol=1e-6)
    assert _max_param_delta(th.params, tc.params) <= 1e-6


def test_step_accum_rides_sync_program(env):
    """step_accum accumulates on the host then syncs through the engine's
    split comm/update program — parity with the host accum path."""
    params = init(jax.random.PRNGKey(0))
    th = _make_trainer(env, False, params)
    tc = _make_trainer(env, True, params)
    x, y = _batch()
    bh = [th.shard_batch(x, y), th.shard_batch(y_x := x * 0.5, y)]
    bc = [tc.shard_batch(x, y), tc.shard_batch(y_x, y)]
    for _ in range(3):
        lh, lc = th.step_accum(bh), tc.step_accum(bc)
    np.testing.assert_allclose(np.asarray(lh).reshape(-1),
                               np.asarray(lc).reshape(-1), rtol=1e-6)
    assert _max_param_delta(th.params, tc.params) <= 1e-6


# ---------------------------------------------------------------------------
# standalone grid: algos x group shapes, integer payloads bit-exact
# ---------------------------------------------------------------------------


def _grid_groups(env):
    return [
        (Topology(8, 1, devices=env.devices), ("data",), "8"),
        (Topology(4, 2, devices=env.devices), ("data", "model"), "(4,2)"),
        (Topology(6, 1, devices=env.devices[:6]), ("data",), "6"),
    ]


@pytest.mark.parametrize("algo", ["lax", "rhd", "ring2d"])
def test_standalone_int_parity(env, algo):
    """The staged multi-tensor reduce must be BIT-EXACT on integer payloads
    against the host algorithm programs (comm/algos.build — the exact
    executables CommRequest dispatches) on every group shape the algorithm
    serves. Integer sums are order-exact, so any placement/phase bug shows
    as a hard mismatch."""
    counts = [37, 256, 1000]
    for topo, axes, tag in _grid_groups(env):
        group = ProcessGroup(topo, axes)
        if not algos.eligible(algo, "allreduce", group, ReductionType.SUM):
            continue
        bufs = [
            topo.shard_buffer(
                np.random.default_rng(i).integers(
                    -40, 40, size=(*topo.grid_shape, c)
                ).astype(np.int32)
            )
            for i, c in enumerate(counts)
        ]
        for stages in (1, 3):
            fn, plan = overlap.build_multi_reduce(
                group, counts, algo=algo, stages=stages
            )
            outs = fn(bufs)
            for c, b, o in zip(counts, bufs, outs):
                host = algos.build(
                    "allreduce", group, np.int32, algo, op=ReductionType.SUM
                )(b)
                assert np.array_equal(np.asarray(o), np.asarray(host)), (
                    f"{algo} on {tag} stages={stages} count={c}"
                )


def test_standalone_float_parity(env):
    """f32/bf16 payloads: allclose against the host programs (identical op
    sequences — in practice bit-exact on the CPU backend, but only allclose
    is the contract for floats)."""
    import ml_dtypes

    topo = Topology(8, 1, devices=env.devices)
    group = ProcessGroup(topo, ("data",))
    counts = [129, 512]
    for dtype, tol in ((np.float32, 1e-6), (ml_dtypes.bfloat16, 1e-2)):
        bufs = [
            topo.shard_buffer(
                np.random.default_rng(i).normal(
                    size=(*topo.grid_shape, c)
                ).astype(dtype)
            )
            for i, c in enumerate(counts)
        ]
        for algo in ("lax", "rhd"):
            fn, _ = overlap.build_multi_reduce(group, counts, algo=algo)
            outs = fn(bufs)
            for b, o in zip(bufs, outs):
                host = algos.build(
                    "allreduce", group, dtype, algo, op=ReductionType.SUM
                )(b)
                np.testing.assert_allclose(
                    np.asarray(o, dtype=np.float32),
                    np.asarray(host, dtype=np.float32), rtol=tol, atol=tol,
                )


def test_standalone_quant_residual_parity(env):
    """Quantized standalone units: two rounds against the host compressed
    ring, pinning BOTH the delivered sums and the carried EF residuals
    (round 2 only matches if round 1's residual threading was exact)."""
    topo = Topology(8, 1, devices=env.devices)
    group = ProcessGroup(topo, ("data",))
    counts = [300, 1000]
    fn, plan = overlap.build_multi_reduce(
        group, counts, compression=CompressionType.QUANTIZATION, block=256
    )
    bufs = [
        topo.shard_buffer(
            np.random.default_rng(i).normal(
                size=(*topo.grid_shape, c)
            ).astype(np.float32)
        )
        for i, c in enumerate(counts)
    ]
    host_fns = [
        quant_ring.build_quantized_collective("allreduce", group, c, 256)
        for c in counts
    ]
    host_errs = [
        topo.shard_buffer(np.zeros((*topo.grid_shape, el), np.float32))
        for _, el in host_fns
    ]
    res = None
    for _ in range(2):
        outs, res = fn(bufs, res)
        host_outs = []
        for i, ((hfn, _), err) in enumerate(zip(host_fns, host_errs)):
            out, host_errs[i] = hfn(bufs[i], err)
            host_outs.append(out)
        for o, h in zip(outs, host_outs):
            np.testing.assert_allclose(np.asarray(o), np.asarray(h),
                                       rtol=1e-6, atol=1e-6)


def test_zero1_update_parity(env):
    """The staged ZeRO-1 two-phase update (reduce-scatter -> owned-shard
    SGD -> all-gather): bit-exact on integer payloads against the direct
    replicated update ``p - lr * (sum g) / denom`` across divisible, tiny,
    and ragged (padded) layer counts, every staging depth, every group
    shape. lr and denom are powers of two, so the float math is exact and
    any shard placement or phase-boundary bug is a hard mismatch."""
    counts = [8 * 96, 13, 8, 100]
    lr, denom = 0.5, 8.0
    for topo, axes, tag in _grid_groups(env):
        group = ProcessGroup(topo, axes)
        w = topo.world_size
        rngs = [np.random.default_rng(i) for i, _ in enumerate(counts)]
        params = [r.integers(-40, 40, size=c).astype(np.float32)
                  for r, c in zip(rngs, counts)]
        grads = [r.integers(-8, 8, size=(w, c)).astype(np.float32)
                 for r, c in zip(rngs, counts)]
        p_bufs = [topo.shard_buffer(np.tile(p, (w, 1)).reshape(
            *topo.grid_shape, c)) for p, c in zip(params, counts)]
        g_bufs = [topo.shard_buffer(g.reshape(*topo.grid_shape, c))
                  for g, c in zip(grads, counts)]
        for stages in (1, 3):
            fn, units = overlap.build_zero1_update(
                group, counts, lr=lr, denom=denom, config=env.config,
                stages=stages,
            )
            # off-chip no kernel is in-graph emittable: lax phases serve
            assert [u.algo for u in units] == ["lax"] * len(counts)
            outs = fn(p_bufs, g_bufs)
            for c, p, g, o in zip(counts, params, grads, outs):
                want = p - lr * (g.sum(axis=0) / denom)
                got = np.asarray(o).reshape(w, c)
                for i in range(w):  # replicated result, every member
                    assert np.array_equal(got[i], want), (
                        f"zero1 on {tag} stages={stages} count={c}")


def test_zero1_forced_kernel_falls_back_loudly(env):
    """A forced pallas algorithm that cannot emit in-graph off-chip must
    degrade the ZeRO-1 plan to the baseline phases (same loud-fallback
    contract as build_plan), not crash or silently mis-lower."""
    topo = Topology(8, 1, devices=env.devices)
    group = ProcessGroup(topo, ("data",))
    fn, units = overlap.build_zero1_update(
        group, [256], lr=0.5, denom=8.0, algo="pallas_ring",
        config=env.config,
    )
    assert [u.algo for u in units] == ["lax"]
    p = np.tile(np.arange(256, dtype=np.float32) % 9, (8, 1))
    g = np.ones((8, 256), np.float32)
    (out,) = fn([topo.shard_buffer(p.reshape(*topo.grid_shape, 256))],
                [topo.shard_buffer(g.reshape(*topo.grid_shape, 256))])
    want = p[0] - 0.5 * (8.0 / 8.0)
    np.testing.assert_array_equal(
        np.asarray(out).reshape(8, 256)[3], want)


# ---------------------------------------------------------------------------
# chaos / precompile / sentinel / config / stats integration
# ---------------------------------------------------------------------------


def test_color_group_rejected_loudly(env):
    """A color group's axes are () — no in-graph body can reduce it, and a
    silent identity 'reduction' must never ship: build_multi_reduce raises
    at plan build (trainer graphs with color groups never reach the engine
    — engine_for_trainer routes them to the host path)."""
    from mlsl_tpu.log import MLSLError

    topo = Topology(1, 1, devices=env.devices)  # flat mesh, as colors use
    group = ProcessGroup(topo, (), colors=(0, 0, 0, 0, 1, 1, 1, 1))
    assert not algos.inline_eligible("lax", "allreduce", group)
    with pytest.raises(MLSLError):
        overlap.build_multi_reduce(group, [64])


def test_chaos_budget_fires_at_step_boundary(env):
    """An armed collective.dispatch budget fires at the STEP it targets —
    the whole comm segment is one dispatch — and the engine recovers on the
    next step (no residual corruption: the program never launched)."""
    params = init(jax.random.PRNGKey(0))
    tc = _make_trainer(env, True, params)
    b = tc.shard_batch(*_batch())
    fired = []
    with chaos.injected("collective.dispatch", "error", after=2, times=1):
        for i in range(4):
            try:
                tc.step(b)
            except chaos.ChaosError:
                fired.append(i)
    assert fired == [2]


def test_chaos_budget_survives_precompile(env):
    """The precompile warm calls the jitted programs directly — an armed
    one-shot budget must survive to the training step it targets."""
    params = init(jax.random.PRNGKey(0))
    tc = _make_trainer(env, True, params)
    b = tc.shard_batch(*_batch())
    with chaos.injected("collective.dispatch", "error", times=1) as p:
        tc.precompile(b)
        assert p.fires == 0
        with pytest.raises(chaos.ChaosError):
            tc.step(b)
        assert p.fires == 1


def test_precompile_zero_compiles(env):
    params = init(jax.random.PRNGKey(0))
    tc = _make_trainer(env, True, params)
    b = tc.shard_batch(*_batch())
    tc.precompile(b)
    with stats.count_backend_compiles() as n:
        tc.step(b)
    assert n[0] == 0, f"{n[0]} backend compiles after precompile"


def test_sentinel_skip_step_lockstep(env):
    """With the quality gate armed the engine runs the two-program split; a
    NaN-poisoned step is skipped on BOTH twins — no comm starts, residuals
    never advance, final params stay bit-identical to the host path."""
    env.config.sentinel_gate = "skip_step"
    try:
        params = init(jax.random.PRNGKey(0))
        th = _make_trainer(env, False, params)
        tc = _make_trainer(env, True, params)
        assert tc.sentinel is not None and tc.sentinel.gate_armed
        x, y = _batch()
        bh, bc = th.shard_batch(x, y), tc.shard_batch(x, y)
        skipped_before = stats.SENTINEL_COUNTERS["gate_skip"]
        for i in range(5):
            if i == 2:
                with chaos.injected("train.grads", "silent", times=1,
                                    mag=float("nan")):
                    th.step(bh)
                with chaos.injected("train.grads", "silent", times=1,
                                    mag=float("nan")):
                    tc.step(bc)
            else:
                th.step(bh)
                tc.step(bc)
        assert stats.SENTINEL_COUNTERS["gate_skip"] - skipped_before == 2
        assert _max_param_delta(th.params, tc.params) == 0.0
    finally:
        env.config.sentinel_gate = ""


def test_degenerate_group_single_device(env):
    """force_graph_path + overlap_compiled on a single-device world (one
    chip): units have ZERO reduce phases — the compiled per-layer schedule
    still runs, bit-identical to the host no-comm per-layer path (this pins
    an IndexError regression)."""
    params = init(jax.random.PRNGKey(0))

    def mk(overlap_on):
        dist = env.create_distribution(1, 1, devices=env.devices[:1])
        s = env.create_session()
        s.set_global_minibatch_size(8)
        return DataParallelTrainer(
            env, dist, s, params, loss_fn, LAYERS, get_layer, lr=0.1,
            overlap_compiled=overlap_on, force_graph_path=True,
        )

    tc, th = mk(True), mk(False)
    assert tc._overlap is not None
    assert all(u.nphases == 0 for u in tc._overlap.plan.units)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(8,)).astype(np.int32)
    bc, bh = tc.shard_batch(x, y), th.shard_batch(x, y)
    for _ in range(3):
        tc.step(bc)
        th.step(bh)
    assert _max_param_delta(th.params, tc.params) == 0.0


def test_fallbacks_and_asserts(env):
    """TOPK rides the host path (engine is None, trainer still works);
    explicitly requesting overlap_compiled with a conflicting mode is a
    loud usage error."""
    import optax

    from mlsl_tpu.log import MLSLError

    params = init(jax.random.PRNGKey(0))
    t = _make_trainer(env, True, params, compression=CompressionType.TOPK)
    assert t._overlap is None
    t.step(t.shard_batch(*_batch()))  # host path serves the graph

    with pytest.raises(MLSLError):
        _make_trainer(env, True, params, optimizer=optax.sgd(0.1))


def test_env_knob_arms_engine(env, monkeypatch):
    """MLSL_OVERLAP_COMPILED=1 via config arms the engine with no ctor
    change; the env default silently skips graphs it cannot serve."""
    env.config.overlap_compiled = True
    try:
        params = init(jax.random.PRNGKey(0))
        dist = env.create_distribution(8, 1)
        s = env.create_session()
        s.set_global_minibatch_size(32)
        t = DataParallelTrainer(env, dist, s, params, loss_fn, LAYERS,
                                get_layer, lr=0.1)
        assert t._overlap is not None
        # a graph the engine cannot serve: env default skips, no raise
        import optax

        s2 = env.create_session()
        s2.set_global_minibatch_size(32)
        t2 = DataParallelTrainer(env, dist, s2, params,
                                 loss_fn, LAYERS, get_layer, lr=0.1,
                                 optimizer=optax.sgd(0.1))
        assert t2._overlap is None
    finally:
        env.config.overlap_compiled = False


def test_overlap_stages_knob(env):
    """MLSL_OVERLAP_STAGES validation + KNOB_RANGES registration + the
    sweep's measured cell; a profile knob applies through the standard
    explicit-env-wins path."""
    from mlsl_tpu.log import MLSLError
    from mlsl_tpu.tuner import KNOB_RANGES
    from mlsl_tpu.tuner.sweep import _sweep_overlap_stages

    assert KNOB_RANGES["overlap_stages"] == 1
    env.config.overlap_stages = 0
    with pytest.raises(MLSLError):
        env.config.validate()
    env.config.overlap_stages = 2
    env.config.validate()
    knobs = _sweep_overlap_stages(env.devices, iters=1)
    assert knobs["overlap_stages"] in (1, 2, 4)
    assert set(knobs["_overlap_measured"]) == {"1", "2", "4"}


def test_stats_and_trace_attribution(env):
    """Every engine step records OVERLAP counters, bulk-attributes its
    in-graph rounds to the shared ALGO table, and emits one step.overlap
    span; plan.describe() speaks the request descriptor grammar."""
    from mlsl_tpu.obs import tracer as obs

    stats.reset_overlap_counters()
    stats.reset_algo_counters()
    params = init(jax.random.PRNGKey(0))
    tc = _make_trainer(env, True, params)
    b = tc.shard_batch(*_batch())
    tr = obs.enable()
    try:
        tc.step(b)
    finally:
        obs.disable()
    oc = stats.OVERLAP_COUNTERS
    assert oc["steps"] == 1 and oc["units"] == len(LAYERS)
    assert stats.ALGO_COUNTERS.get(("allreduce", "lax"), 0) >= len(LAYERS)
    spans = [e for e in tr.snapshot() if e[1] == "step.overlap"]
    assert len(spans) == 1
    desc = tc._overlap.plan.describe()
    assert len(desc) == len(LAYERS) and all("in_graph=1" in d for d in desc)
    # the OVERLAP ENGINE line surfaces in the stats log
    sess = tc.session
    text = sess.get_stats().print_()
    assert "OVERLAP" in text and "ENGINE" in text


@pytest.mark.slow
def test_large_model_parity(env):
    """Slow: a ResNet-50-shaped 54-layer stream pinned host-vs-compiled
    over several steps."""
    from conftest import resnet50_counts

    counts = resnet50_counts(per_layer=True, scale=16)
    layers = [f"l{i}" for i in range(len(counts))]
    rng = np.random.default_rng(0)
    params = {
        n: {"w": jnp.asarray(rng.normal(size=(c,)).astype(np.float32) * 0.1)}
        for n, c in zip(layers, counts)
    }

    def big_loss(p, batch):
        x, _ = batch
        s = jnp.mean(x)
        tot = 0.0
        for n in layers:
            w = p[n]["w"]
            tot = tot + jnp.sum(w * s + 0.005 * w * w) / w.shape[0]
        return tot / len(layers)

    def gl(p, name):
        return p[name]

    def mk(overlap_on):
        dist = env.create_distribution(8, 1)
        s = env.create_session()
        s.set_global_minibatch_size(32)
        return DataParallelTrainer(
            env, dist, s, params, big_loss, layers, gl, lr=0.05,
            overlap_compiled=overlap_on, force_graph_path=not overlap_on,
        )

    th, tc = mk(False), mk(True)
    x, y = _batch()
    bh, bc = th.shard_batch(x, y), tc.shard_batch(x, y)
    for _ in range(3):
        th.step(bh)
        tc.step(bc)
    assert _max_param_delta(th.params, tc.params) <= 1e-6
