"""Collective algorithm engine tests (comm/algos): parity, selection, wiring.

The engine's contract is conservative: every algorithm must produce the SAME
answer as the single-shot ``lax`` baseline — bit-for-bit when the arithmetic
is exact (integer-valued payloads, MIN/MAX), allclose when float summation
order legitimately differs — and the untuned default must BE the baseline
program. The suite pins:

- parity for every registry algorithm across kinds, dtypes, power-of-two and
  non-2^k group sizes (the halving/doubling remainder step), 1D and 2D
  sub-torus shapes;
- fallback on groups an algorithm cannot serve (ragged color groups);
- the quantized and bucketed paths with a forced dense algorithm (the bucket
  collective rides the selection; the compressed wire is untouched);
- chaos faults at collective.dispatch firing through engine-built programs;
- trace spans / describe() / ALGO stats counters carrying the algorithm name.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax

from mlsl_tpu.comm import algos, collectives
from mlsl_tpu.comm.mesh import ProcessGroup, Topology
from mlsl_tpu.types import (
    CompressionType, DataType, GroupType, ReductionType,
)

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _run(fn, topo, vals):
    return np.asarray(jax.block_until_ready(fn(topo.shard_buffer(vals))))


def _int_vals(rng, topo, n, dtype=np.float32):
    """Integer-valued payloads: every summation order is exact, so parity is
    bit-for-bit regardless of the algorithm's combine tree."""
    return rng.integers(-8, 8, size=(*topo.grid_shape, n)).astype(dtype)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(7)


def _parity(kind, topo, group, n, algo, vals, *, op=ReductionType.SUM,
            recv_count=None, exact=True):
    kw = {"op": op}
    if recv_count is not None:
        kw["recv_count"] = recv_count
    base = algos.build(kind, group, vals.dtype, "lax", **kw)
    fn = algos.build(kind, group, vals.dtype, algo, **kw)
    want = _run(base, topo, vals)
    got = _run(fn, topo, vals)
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(
            got.astype(np.float64), want.astype(np.float64),
            rtol=1e-5, atol=1e-5,
        )


# -- parity: 1D ring ---------------------------------------------------------


@pytest.mark.parametrize("n", [64, 96, 1000])
@pytest.mark.parametrize("kind", ["allreduce", "reduce_scatter"])
def test_rhd_parity_1d_bitexact_sum(rng, kind, n):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    rc = None
    if kind == "reduce_scatter":
        n = -(-n // 8) * 8
        rc = n // 8
    _parity(kind, topo, g, n, "rhd", _int_vals(rng, topo, n),
            recv_count=rc, exact=True)


@pytest.mark.parametrize("op", [ReductionType.MIN, ReductionType.MAX])
def test_rhd_parity_minmax_bitexact(rng, op):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    vals = rng.normal(size=(*topo.grid_shape, 128)).astype(np.float32)
    # MIN/MAX are order-insensitive: bit-for-bit even on random floats
    _parity("allreduce", topo, g, 128, "rhd", vals, op=op, exact=True)


def test_rhd_parity_allclose_mean(rng):
    """Random float payloads: summation order differs between the pairwise
    tree and the baseline, so the averaged (mean) result is pinned allclose,
    not bit-for-bit."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 4096
    vals = rng.normal(size=(*topo.grid_shape, n)).astype(np.float32)
    base = algos.build("allreduce", g, np.float32, "lax", op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "rhd", op=ReductionType.SUM)
    want = _run(base, topo, vals) / 8.0
    got = _run(fn, topo, vals) / 8.0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("dtype", [np.float32, np.int32, "bfloat16"])
def test_rhd_parity_dtypes(rng, dtype):
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype == "bfloat16" else dtype
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 256
    vals = _int_vals(rng, topo, n, np.float32).astype(dtype)
    _parity("allreduce", topo, g, n, "rhd", vals, exact=True)


# -- parity: non-power-of-two (the remainder step) ---------------------------


@pytest.mark.parametrize("G", [3, 5, 6, 7])
def test_rhd_parity_non_power_of_two(rng, G):
    topo = Topology(G, 1, devices=jax.devices()[:G])
    g = ProcessGroup(topo, ("data",))
    n = 10 * G
    _parity("allreduce", topo, g, n, "rhd", _int_vals(rng, topo, n),
            exact=True)
    _parity("reduce_scatter", topo, g, n, "rhd", _int_vals(rng, topo, n),
            recv_count=10, exact=True)


def test_rhd_parity_non_power_of_two_floats(rng):
    topo = Topology(6, 1, devices=jax.devices()[:6])
    g = ProcessGroup(topo, ("data",))
    n = 999  # also exercises the pad path (999 % 4 != 0)
    vals = rng.normal(size=(*topo.grid_shape, n)).astype(np.float32)
    _parity("allreduce", topo, g, n, "rhd", vals, exact=False)


# -- parity: 2D sub-torus ----------------------------------------------------


@pytest.mark.parametrize("algo", ["rhd", "ring2d"])
@pytest.mark.parametrize("kind", ["allreduce", "reduce_scatter"])
def test_parity_2d(rng, algo, kind):
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("data", "model"))
    n = 320
    rc = n // 8 if kind == "reduce_scatter" else None
    _parity(kind, topo, g, n, algo, _int_vals(rng, topo, n),
            recv_count=rc, exact=True)


def test_ring2d_parity_global_group_with_degenerate_axes(rng):
    """A 4-axis global group over a (1, 4, 1, 2) grid has the same live
    (4, 2) shape — ring2d must handle the degenerate axes and share the
    selection cell."""
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("replica", "data", "seq", "model"))
    assert algos.group_shape(g) == (4, 2)
    n = 160
    _parity("allreduce", topo, g, n, "ring2d", _int_vals(rng, topo, n),
            exact=True)
    _parity("reduce_scatter", topo, g, n, "ring2d", _int_vals(rng, topo, n),
            recv_count=n // 8, exact=True)


def test_ring2d_padded_allreduce(rng):
    # n not divisible by the minor axis: the pad/strip path
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("data", "model"))
    n = 101
    _parity("allreduce", topo, g, n, "ring2d", _int_vals(rng, topo, n),
            exact=True)


# -- parity: color groups ----------------------------------------------------


def test_rhd_parity_uniform_color_group(rng):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, (), colors=(0, 1, 0, 1, 0, 1, 0, 1))
    n = 128
    _parity("allreduce", topo, g, n, "rhd", _int_vals(rng, topo, n),
            exact=True)


def test_ragged_color_group_falls_back(rng, env, monkeypatch):
    """rhd cannot serve a ragged partition (unequal member counts): the
    selection must fall back to the baseline and the answer must be the
    plain group sum."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, (), colors=(0, 0, 0, 0, 0, 1, 1, 1))
    assert not algos.eligible("rhd", "allreduce", g)
    assert algos.candidates("allreduce", g) == ("lax",)
    env.config.collective_algo = "rhd"
    env.config.validate()  # re-parse the forced spec
    assert algos.select("allreduce", g, 4096, CompressionType.NONE,
                        env.config) == "lax"


# -- selection ---------------------------------------------------------------


def test_selection_default_is_baseline(env):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    assert algos.select("allreduce", g, 1 << 20, CompressionType.NONE,
                        env.config) == "lax"
    # compression cells never choose a dense algorithm
    env.config.collective_algo = "rhd"
    env.config.validate()
    assert algos.select("allreduce", g, 1 << 20, CompressionType.QUANTIZATION,
                        env.config) == "lax"


def test_forced_spec_per_kind(env):
    env.config.collective_algo = "allreduce=rhd,reduce_scatter=ring2d"
    env.config.validate()
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("data", "model"))
    assert algos.select("allreduce", g, 4096, CompressionType.NONE,
                        env.config) == "rhd"
    assert algos.select("reduce_scatter", g, 4096, CompressionType.NONE,
                        env.config) == "ring2d"


def test_forced_unknown_algo_is_mlsl_error(monkeypatch):
    from mlsl_tpu.core.environment import Environment
    from mlsl_tpu.log import MLSLError

    monkeypatch.setenv("MLSL_ALGO", "warp_drive")
    e = Environment.get_env()
    with pytest.raises(MLSLError, match="not a registered collective"):
        e.init()
    assert not e._initialized


def test_contradictory_knob_is_mlsl_error(monkeypatch):
    from mlsl_tpu.core.environment import Environment
    from mlsl_tpu.log import MLSLError

    monkeypatch.setenv("MLSL_LARGE_MSG_CHUNKS", "0")
    e = Environment.get_env()
    with pytest.raises(MLSLError, match="LARGE_MSG_CHUNKS"):
        e.init()
    assert not e._initialized


# -- request / dispatch wiring ----------------------------------------------


def _allreduce_req(env, dist, n, name=""):
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    req = CommRequest(
        CommDesc("allreduce", dist._group(GroupType.DATA), n, DataType.FLOAT,
                 op=ReductionType.SUM),
        env.dispatcher, name=name,
    )
    req.setup()
    return req


def test_request_rides_forced_algo_end_to_end(env, monkeypatch):
    env.config.collective_algo = "rhd"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 512
    req = _allreduce_req(env, dist, n)
    assert req.algo == "rhd"
    assert "algo=rhd" in req.describe()
    buf = dist.make_buffer(lambda p: np.full(n, float(p + 1), np.float32), n)
    req.start(buf)
    out = req.wait()
    np.testing.assert_array_equal(np.asarray(dist.local_part(out, 0)),
                                  np.full(n, 36.0, np.float32))


def test_algo_dispatch_counters_and_stats_line(env):
    from mlsl_tpu.core import stats as stats_mod

    stats_mod.reset_algo_counters()
    env.config.collective_algo = "rhd"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    req = _allreduce_req(env, dist, 256)
    buf = dist.make_buffer(lambda p: np.ones(256, np.float32), 256)
    req.start(buf)
    req.wait()
    assert stats_mod.ALGO_COUNTERS.get(("allreduce", "rhd"), 0) >= 1
    s = env.create_session()
    text = s.get_stats().print_()
    assert "ALGO" in text and "allreduce:rhd=" in text


def test_trace_span_records_algo(env):
    from mlsl_tpu import obs
    from mlsl_tpu.obs.tracer import ARGS, NAME, PH

    env.config.collective_algo = "rhd"
    env.config.validate()
    tr = obs.enable()
    try:
        dist = env.create_distribution(8, 1)
        req = _allreduce_req(env, dist, 256, name="traced")
        buf = dist.make_buffer(lambda p: np.ones(256, np.float32), 256)
        req.start(buf)
        req.wait()
        for span_name in ("dispatch", "wait"):
            spans = [
                e for e in tr.snapshot()
                if e[PH] == "X" and e[NAME] == span_name
            ]
            assert spans, f"no {span_name} span captured"
            # both spans carry it: dispatch is the enqueue cost, wait holds
            # the wire time the per-algorithm trace summary attributes
            assert any(e[ARGS].get("algo") == "rhd" for e in spans)
    finally:
        obs.disable()


def test_chunked_request_uses_selected_algo(env):
    env.config.collective_algo = "rhd"
    env.config.validate()
    env.config.large_msg_size_mb = 1
    env.config.large_msg_chunks = 4
    dist = env.create_distribution(8, 1)
    n = 1 << 19  # 2 MiB > 1 MiB threshold
    req = _allreduce_req(env, dist, n)
    assert req.algo == "rhd" and len(req._chunk_slices) == 4
    rng = np.random.default_rng(3)
    vals = rng.integers(-4, 4, size=(*dist.topology.grid_shape, n)).astype(
        np.float32
    )
    buf = dist.topology.shard_buffer(vals)
    req.start(buf)
    got = np.asarray(dist.local_part(req.wait(), 0))
    want = vals.reshape(8, n).sum(axis=0)
    np.testing.assert_array_equal(got, want)


def test_plan_cache_key_carries_algo(env):
    """MLSL_PRECOMPILE plan entries must distinguish algorithms: warming a
    'lax' program must not suppress warming the 'rhd' program of the same
    (kind, group, count) after a profile switch."""
    from mlsl_tpu.types import OpType

    collectives.clear_cache()
    try:
        env.config.precompile = True

        def build_session():
            dist = env.create_distribution(8, 1)
            s = env.create_session()
            s.set_global_minibatch_size(8)
            r = s.create_operation_reg_info(OpType.CC)
            r.add_input(8, 4)
            r.add_output(8, 4)
            r.add_parameter_set(256, 1)
            s.get_operation(s.add_operation(r, dist))
            s.commit()
            return s

        build_session()
        keys_lax = {k for k in collectives._plan_cache if k[0] == "req"}
        assert all(k[-1] == "lax" for k in keys_lax)
        env.config.collective_algo = "rhd"
        env.config.validate()
        build_session()
        keys_all = {k for k in collectives._plan_cache if k[0] == "req"}
        assert any(k[-1] == "rhd" for k in keys_all - keys_lax)
    finally:
        env.config.precompile = False
        collectives.clear_cache()


def test_clear_cache_drops_algo_programs(env):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    algos.build("allreduce", g, np.float32, "rhd", op=ReductionType.SUM)
    assert any(k[0] == "algo" for k in collectives._cache)
    collectives.clear_cache()
    assert not any(k[0] == "algo" for k in collectives._cache)


# -- chaos at collective.dispatch through engine programs --------------------


def test_chaos_dispatch_fault_fires_on_algo_program(env):
    from mlsl_tpu import chaos

    env.config.collective_algo = "rhd"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 256
    req = _allreduce_req(env, dist, n)
    assert req.algo == "rhd"
    buf = dist.make_buffer(lambda p: np.ones(n, np.float32), n)
    with chaos.injected("collective.dispatch", "error"):
        # small message -> direct dispatch: the fault surfaces at start()
        with pytest.raises(chaos.ChaosError):
            req.start(buf)
    # recoverable: the next round is clean and exact
    req.start(buf)
    np.testing.assert_array_equal(
        np.asarray(dist.local_part(req.wait(), 0)), np.full(n, 8.0, np.float32)
    )


# -- quantized + bucketed paths under a forced dense algorithm ---------------


def _grad_session(env, dist, n_params, compression=CompressionType.NONE):
    from mlsl_tpu.types import OpType

    s = env.create_session()
    s.set_global_minibatch_size(8)
    r = s.create_operation_reg_info(OpType.CC)
    r.add_input(8, 4)
    r.add_output(8, 4)
    for n in n_params:
        r.add_parameter_set(n, 1, compression_type=compression)
    op = s.get_operation(s.add_operation(r, dist))
    s.commit()
    return s, op


def test_bucketed_grads_ride_selected_algo(env):
    """A plain gradient bucket's coalesced allreduce consults the same
    selection table; parity of every member's slice against the exact sum."""
    env.config.collective_algo = "rhd"
    env.config.validate()
    env.config.grad_bucket_mb = 1
    dist = env.create_distribution(8, 1)
    sizes = [300, 200, 100]
    s, op = _grad_session(env, dist, sizes)
    pss = [op.get_parameter_set(i) for i in range(len(sizes))]
    assert pss[0].bucket is not None
    assert pss[0].bucket.req.algo == "rhd"
    bufs = {}
    for i, (ps, n) in enumerate(zip(pss, sizes)):
        bufs[i] = dist.make_buffer(
            lambda p, i=i, n=n: np.full(n, float(p + i + 1), np.float32), n
        )
    for ps, i in zip(pss, range(len(sizes))):
        ps.start_gradient_comm(bufs[i])
    for i, (ps, n) in enumerate(zip(pss, sizes)):
        out = ps.wait_gradient_comm()
        want = sum(float(p + i + 1) for p in range(8))
        np.testing.assert_array_equal(
            np.asarray(dist.local_part(out, 0)), np.full(n, want, np.float32)
        )


def test_quantized_grads_unaffected_by_forced_algo(env):
    """CT_QUANTIZATION stays on the compressed ring (its own wire format):
    forcing a dense algorithm must neither break nor reroute it."""
    env.config.collective_algo = "rhd"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 512
    s, op = _grad_session(env, dist, [n],
                          compression=CompressionType.QUANTIZATION)
    ps = op.get_parameter_set(0)
    buf = dist.make_buffer(lambda p: np.full(n, p + 1.0, np.float32), n)
    ps.start_gradient_comm(buf)
    out = ps.wait_gradient_comm()
    assert ps.grad_req.algo == "quant_ring"
    np.testing.assert_allclose(
        np.asarray(dist.local_part(out, 0)), np.full(n, 36.0), rtol=0.01
    )


# -- benchmarks/algo_sweep_bench.py --------------------------------------------


@pytest.mark.slow
def test_algo_sweep_bench_full():
    """The full sweep (sizes to 8 MiB + the quant-block cell) standalone —
    slow-marked so tier-1 stays in budget; run via the capture suite or
    ``pytest -m slow``."""
    env_vars = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    for k in ("MLSL_ALGO", "MLSL_TUNE", "MLSL_TUNE_PROFILE", "MLSL_CHAOS"):
        env_vars.pop(k, None)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "algo_sweep_bench.py"),
         "--quant"],
        capture_output=True, text=True, timeout=1800, env=env_vars, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    sel = next(r for r in rows if r["metric"] == "algo_sweep_selection")
    assert sel["cells"] >= 8
    assert sel["knobs"].get("quant_block_elems") in (128, 256, 512)
    rt = next(r for r in rows if r["metric"] == "algo_profile_roundtrip")
    assert rt["ok"] and rt["parity_exact"], rt


def test_algo_sweep_bench_smoke():
    """benchmarks/algo_sweep_bench.py, the front end of ``tuner.run_sweep``
    (scripts/run_tune.sh calls it): the sweep runs on the 8-device CPU mesh,
    every cell names a lowering, the written profile reproduces every
    selection after a reload, and the chosen program of one cell is
    bit-identical to ``lax`` on integer payloads. Which lowering wins a cell
    is a time and is not asserted here."""
    env_vars = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    for k in ("MLSL_ALGO", "MLSL_TUNE", "MLSL_TUNE_PROFILE", "MLSL_CHAOS"):
        env_vars.pop(k, None)
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "algo_sweep_bench.py"), "--smoke"],
        capture_output=True, text=True, timeout=540, env=env_vars, cwd=REPO,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(l) for l in out.stdout.splitlines()
            if l.startswith("{")]
    cells = [r for r in rows if r["metric"] == "algo_sweep"]
    assert len(cells) >= 4
    assert all(c["chosen"] in c["us"] for c in cells)
    sel = next(r for r in rows if r["metric"] == "algo_sweep_selection")
    assert sel["cells"] == len(cells)
    rt = next(r for r in rows if r["metric"] == "algo_profile_roundtrip")
    assert rt["ok"] and rt["parity_exact"], rt
