"""Pallas fused-ring lowering tests (ops/ring_kernels.py, algos 'pallas_ring').

Tier-1 runs the kernels under the Pallas interpreter (MLSL_PALLAS_INTERPRET=1
— this jax's interpreter executes true cross-shard remote-DMA semantics over
a single-named-axis mesh, which is exactly how the host-dispatch programs
compile), pinning:

- dense parity bit-exact vs the ``lax`` baseline on integer sums (ring order
  vs psum tree: exact arithmetic ⇒ identical bits), allclose on floats;
- the quantized variant bit-exact vs the ``quant_ring`` oracle — output AND
  error-feedback residual across 2 rounds — on an *exact-scale* payload
  (sentinel ±127 per block keeps every entry/hop scale exactly 1.0, so both
  hop engines' arithmetic is exactly representable and FMA-contraction
  differences between the compiled oracle and the interpreted kernel cannot
  hide a real divergence), plus EF-residual lockstep on random floats;
- selection precedence (MLSL_ALGO > tuned profile > default), the off-TPU
  eligibility gate, breaker degradation to the baseline, chunked quantized
  requests, the overlap engine's loud off-chip fallback, plan-cache variant
  identity, and config/knob validation.

On-chip-only variants (compiled Mosaic kernels, in-graph overlap emission,
the capacity-handshake/bidir code paths that the interpreter statically
elides) carry the ``tpu`` marker and auto-skip off-chip (conftest).
"""

import numpy as np
import pytest
import jax

from mlsl_tpu import chaos, supervisor
from mlsl_tpu.comm import algos, collectives, quant_ring
from mlsl_tpu.comm.mesh import ProcessGroup, Topology
from mlsl_tpu.core import stats as stats_mod
from mlsl_tpu.ops import ring_kernels as rk
from mlsl_tpu.types import (
    CompressionType, DataType, GroupType, ReductionType,
)

BLOCK = 128  # quant block for the parity suites (any 128-multiple works)


@pytest.fixture(autouse=True)
def _interpret_gate(monkeypatch):
    """Arm interpret mode for every test in this file (the tier-1 CPU-mesh
    path); the tpu-marked tests run compiled because on_tpu() wins inside
    interpret_mode() only when the var forces it — on a real chip this
    fixture still runs the interpreter, which is fine: the compiled twins
    assert the Mosaic path explicitly via MLSL_PALLAS_INTERPRET=0."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "1")


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(11)


def _run(fn, topo, vals):
    return np.asarray(jax.block_until_ready(fn(topo.shard_buffer(vals))))


def _int_vals(rng, topo, n, dtype=np.float32):
    return rng.integers(-8, 8, size=(*topo.grid_shape, n)).astype(dtype)


def _exact_scale_vals(rng, n_dev, count, grid_shape):
    """Integer payload with a ±127 sentinel at position 0 of every quant
    block on rank 0 (zero there on the others): every entry and per-hop
    amax is exactly 127, every scale exactly 1.0, every product exactly
    representable — quantized parity is bit-for-bit regardless of FMA
    contraction differences between programs."""
    v = rng.integers(-3, 3, size=(n_dev, count)).astype(np.float32)
    v[:, ::BLOCK] = 0.0
    v[0, ::BLOCK] = 127.0
    return v.reshape(*grid_shape, count)


def _zerr(topo, el):
    return topo.shard_buffer(np.zeros((*topo.grid_shape, el), np.float32))


# -- eligibility gate ---------------------------------------------------------


def test_gate_off_by_default(monkeypatch, env):
    """Without the explicit interpret gate, off-TPU the lowering is never
    eligible: plain CPU runs must not select an interpreted kernel, and a
    forced MLSL_ALGO=pallas_ring falls back to the baseline loudly."""
    monkeypatch.delenv("MLSL_PALLAS_INTERPRET", raising=False)
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    assert not algos.eligible("pallas_ring", "allreduce", g)
    assert "pallas_ring" not in algos.candidates("allreduce", g)
    env.config.collective_algo = "pallas_ring"
    env.config.validate()
    assert algos.select("allreduce", g, 4096, CompressionType.NONE,
                        env.config) == "lax"
    assert algos.select("allreduce", g, 4096, CompressionType.QUANTIZATION,
                        env.config) == "lax"


def test_eligibility_shapes(env):
    """Single-live-axis groups only: a true 2D sub-torus and color groups
    keep the other lowerings; a (4, 2) mesh's single-axis subgroups ride."""
    t1 = Topology(8, 1)
    assert algos.eligible("pallas_ring", "allreduce",
                          ProcessGroup(t1, ("data",)))
    t2 = Topology(4, 2)
    assert algos.eligible("pallas_ring", "allreduce",
                          ProcessGroup(t2, ("data",)))
    assert algos.eligible("pallas_ring", "allreduce",
                          ProcessGroup(t2, ("model",)))
    assert not algos.eligible("pallas_ring", "allreduce",
                              ProcessGroup(t2, ("data", "model")))
    assert not algos.eligible(
        "pallas_ring", "allreduce",
        ProcessGroup(t1, (), colors=(0, 0, 0, 0, 1, 1, 1, 1)),
    )
    # SUM only
    assert not algos.eligible("pallas_ring", "allreduce",
                              ProcessGroup(t1, ("data",)),
                              op=ReductionType.MAX)


# -- dense parity -------------------------------------------------------------


@pytest.mark.parametrize("n", [512, 5000])
@pytest.mark.parametrize("kind", ["allreduce", "reduce_scatter"])
def test_dense_parity_bitexact_int(rng, env, kind, n):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    kw = {"op": ReductionType.SUM}
    if kind == "reduce_scatter":
        n = -(-n // 8) * 8
        kw["recv_count"] = n // 8
    vals = _int_vals(rng, topo, n)
    base = algos.build(kind, g, np.float32, "lax", **kw)
    fn = algos.build(kind, g, np.float32, "pallas_ring", **kw)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


@pytest.mark.parametrize("dtype", [np.int32, "bfloat16"])
def test_dense_parity_dtypes(rng, env, dtype):
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype == "bfloat16" else dtype
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 640
    vals = _int_vals(rng, topo, n, np.float32).astype(dtype)
    base = algos.build("allreduce", g, vals.dtype, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, vals.dtype, "pallas_ring",
                     op=ReductionType.SUM)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


def test_dense_parity_float_allclose(rng, env):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 4096
    vals = rng.normal(size=(*topo.grid_shape, n)).astype(np.float32)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "pallas_ring",
                     op=ReductionType.SUM)
    np.testing.assert_allclose(_run(fn, topo, vals) / 8.0,
                               _run(base, topo, vals) / 8.0,
                               rtol=1e-5, atol=1e-6)


def test_dense_bidir_parity(rng, env):
    """The bidirectional split reduces the two block-row halves on opposite
    rotations; integer sums are order-exact, so parity stays bit-for-bit."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 8 * rk.DENSE_UNIT  # rows split cleanly across directions
    vals = _int_vals(rng, topo, n)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    from mlsl_tpu.comm.algos import pallas_ring as pr

    fn = pr.build("allreduce", g, op=ReductionType.SUM, bidir=True)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


def test_dense_multi_instance_subgroup(rng, env):
    """A single-axis subgroup of a (4, 2) grid: two/four ring instances run
    in one program through the world-rank neighbor tables."""
    topo = Topology(4, 2)
    for axes in (("data",), ("model",)):
        g = ProcessGroup(topo, axes)
        n = 768
        vals = _int_vals(rng, topo, n)
        base = algos.build("allreduce", g, np.float32, "lax",
                           op=ReductionType.SUM)
        fn = algos.build("allreduce", g, np.float32, "pallas_ring",
                         op=ReductionType.SUM)
        np.testing.assert_array_equal(_run(fn, topo, vals),
                                      _run(base, topo, vals))


# -- 2D-torus snake ring (pallas_ring2d) --------------------------------------


def test_snake_eligibility_complement(env):
    """pallas_ring2d covers EXACTLY the groups the 1D ring refuses: two
    live axes — and refuses the single-axis groups the 1D ring owns, so
    the two lowerings never shadow each other in the candidate table."""
    t2 = Topology(4, 2)
    both = ProcessGroup(t2, ("data", "model"))
    one = ProcessGroup(t2, ("data",))
    assert algos.eligible("pallas_ring2d", "allreduce", both)
    assert not algos.eligible("pallas_ring", "allreduce", both)
    assert not algos.eligible("pallas_ring2d", "allreduce", one)
    assert algos.eligible("pallas_ring", "allreduce", one)
    assert "pallas_ring2d" in algos.candidates("allreduce", both)
    assert "pallas_ring" not in algos.candidates("allreduce", both)


@pytest.mark.parametrize("n", [8 * 640, 5000])
@pytest.mark.parametrize("kind", ["allreduce", "reduce_scatter"])
def test_snake_parity_bitexact_int(rng, env, kind, n):
    """The boustrophedon cycle over the full (4, 2) torus: same kernel,
    snake neighbor tables — integer sums stay bit-exact vs lax, padded
    and chunk-aligned counts alike."""
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("data", "model"))
    kw = {"op": ReductionType.SUM}
    if kind == "reduce_scatter":
        n = -(-n // 8) * 8
        kw["recv_count"] = n // 8
    vals = _int_vals(rng, topo, n)
    base = algos.build(kind, g, np.float32, "lax", **kw)
    fn = algos.build(kind, g, np.float32, "pallas_ring2d", **kw)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


def test_snake_request_e2e(env):
    """Forced through the request engine on a full-torus group: describe()
    names the algo and the result matches the baseline program."""
    from mlsl_tpu.comm.request import CommDesc, CommRequest
    from mlsl_tpu.types import DataType, GroupType

    env.config.collective_algo = "pallas_ring2d"
    env.config.validate()
    dist = env.create_distribution(4, 2)
    n = 1024
    req = CommRequest(
        CommDesc("allreduce", dist._group(GroupType.GLOBAL), n,
                 DataType.FLOAT, op=ReductionType.SUM),
        env.dispatcher, name="snake",
    )
    req.setup()
    assert req.algo == "pallas_ring2d"
    assert "hops=" in req._span_args["pallas.hop"]
    buf = dist.topology.shard_buffer(
        np.tile(np.arange(n, dtype=np.float32) % 7, (8, 1)).reshape(
            *dist.topology.grid_shape, n))
    out = np.asarray(req.start(buf).wait())
    np.testing.assert_array_equal(
        out.reshape(8, n)[0], (np.arange(n) % 7) * 8.0)


def test_all_gather_kernel_parity(rng, env):
    """The ZeRO-1 gather phase kind, standalone over the flat mesh — the
    1D ring AND the 2D snake: every member ends with every member's shard
    in group-position order (the snake path must undo its ring-order
    permutation)."""
    for topo, axes, snake in ((Topology(8, 1), ("data",), False),
                              (Topology(4, 2), ("data", "model"), True)):
        group = ProcessGroup(topo, axes)
        for shard in (640, 130):  # chunk-aligned and padded
            vals = _int_vals(rng, topo, shard)
            body = rk.dense_ring_body("all_gather", group, shard,
                                      np.float32, snake=snake)
            fn = rk.build_flat_program(body, group, "all_gather")
            out = _run(fn, topo, vals).reshape(8, 8 * shard)
            want = vals.reshape(8, shard).reshape(-1)
            for i in range(8):
                np.testing.assert_array_equal(out[i], want)


# -- quantized parity (the EF oracle) ----------------------------------------


def _quant_pair(g, count, kind="allreduce"):
    ofn, oel = quant_ring.build_quantized_collective(kind, g, count, BLOCK,
                                                     ring="lax")
    pfn, pel = quant_ring.build_quantized_collective(kind, g, count, BLOCK,
                                                     ring="pallas")
    assert oel == pel  # identical geometry => identical residual layout
    return ofn, pfn, oel


@pytest.mark.parametrize("kind", ["allreduce", "reduce_scatter"])
def test_quant_bitexact_vs_oracle(rng, env, kind):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    count = 8 * BLOCK * 32  # rc lands exactly on the shared chunk unit
    ofn, pfn, el = _quant_pair(g, count, kind)
    buf = topo.shard_buffer(
        _exact_scale_vals(rng, 8, count, topo.grid_shape))
    oo, oe = ofn(buf, _zerr(topo, el))
    po, pe = pfn(buf, _zerr(topo, el))
    np.testing.assert_array_equal(np.asarray(po), np.asarray(oo))
    np.testing.assert_array_equal(np.asarray(pe), np.asarray(oe))


def test_quant_two_round_ef_lockstep(rng, env):
    """Random floats: outputs allclose; the carried residual — entry math is
    the shared quant_ring code — stays BIT-exact across two rounds, the
    contract that makes the fused kernel a drop-in for the composed ring."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    count = 8 * BLOCK * 32
    ofn, pfn, el = _quant_pair(g, count)
    buf = topo.shard_buffer(
        (rng.standard_normal((*topo.grid_shape, count)) * 3).astype(
            np.float32))
    oo1, oe1 = ofn(buf, _zerr(topo, el))
    po1, pe1 = pfn(buf, _zerr(topo, el))
    np.testing.assert_array_equal(np.asarray(pe1), np.asarray(oe1))
    oo2, oe2 = ofn(buf, oe1)
    po2, pe2 = pfn(buf, pe1)
    np.testing.assert_array_equal(np.asarray(pe2), np.asarray(oe2))
    np.testing.assert_allclose(np.asarray(po2), np.asarray(oo2),
                               rtol=1e-5, atol=1e-4)


def test_quant_geometry_matches_ring_layout(env):
    """The degrade flush (quant_ring.logical_residual) assumes the
    slice-at-chunk-start layout; the pallas geometry must agree with the
    composed ring's pallas-path units so the SAME inversion applies."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    for count in (8 * BLOCK * 32, 5000, 8 * BLOCK * 32 * 3 + 8):
        gg, rc, chunk, el = rk.quant_geometry("allreduce", g, count, BLOCK)
        assert el == gg * chunk and chunk % (BLOCK * 32) == 0
        assert rc == -(-count // gg) and chunk >= rc


# -- request engine: selection, e2e, observability ---------------------------


def _allreduce_req(env, dist, n, name="", compression=CompressionType.NONE):
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    req = CommRequest(
        CommDesc("allreduce", dist._group(GroupType.DATA), n, DataType.FLOAT,
                 op=ReductionType.SUM, compression=compression),
        env.dispatcher, name=name,
    )
    req.setup()
    return req


def test_request_dense_e2e(env):
    env.config.collective_algo = "pallas_ring"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 512
    stats_mod.reset_algo_counters()
    req = _allreduce_req(env, dist, n, "pr")
    assert req.algo == "pallas_ring"
    assert "algo=pallas_ring" in req.describe()  # watchdog descriptor too
    assert "pallas.hop" in req._span_args
    assert "codec=float32" in req._span_args["pallas.hop"]
    buf = dist.make_buffer(lambda p: np.full(n, float(p + 1), np.float32), n)
    out = req.start(buf).wait()
    np.testing.assert_array_equal(np.asarray(dist.local_part(out, 0)),
                                  np.full(n, 36.0, np.float32))
    assert stats_mod.ALGO_COUNTERS.get(("allreduce", "pallas_ring"), 0) >= 1


def test_request_quant_e2e_vs_oracle(rng, env):
    """A QUANTIZATION request routed to the fused ring: output and residual
    bit-exact against the composed-ring request on the exact-scale payload,
    including the residual carried into round 2."""
    dist = env.create_distribution(8, 1)
    n = 8 * 256 * 32  # config block (256) x ROW_TILE: shared chunk unit
    oreq = _allreduce_req(env, dist, n, "oq",
                          compression=CompressionType.QUANTIZATION)
    assert oreq.algo == "quant_ring"
    env.config.collective_algo = "pallas_ring"
    env.config.validate()
    preq = _allreduce_req(env, dist, n, "pq",
                          compression=CompressionType.QUANTIZATION)
    assert preq.algo == "pallas_ring"
    assert "codec=int8" in preq._span_args["pallas.hop"]
    vals = _exact_scale_vals(rng, 8, n, dist.topology.grid_shape)
    buf = dist.topology.shard_buffer(vals)
    for _round in range(2):
        oo = np.asarray(oreq.start(buf).wait())
        po = np.asarray(preq.start(buf).wait())
        np.testing.assert_array_equal(po, oo)
        np.testing.assert_array_equal(np.asarray(preq._err),
                                      np.asarray(oreq._err))


def test_request_quant_chunked(rng, env, monkeypatch):
    """Large quantized allreduce: the request splits into independent
    per-chunk fused rings, each with its own residual — parity vs the
    composed ring under the same chunking."""
    env.config.large_msg_size_mb = 1
    env.config.large_msg_chunks = 2
    dist = env.create_distribution(8, 1)
    n = 8 * 256 * 32 * 10  # ~5 MB payload -> 2 chunks (config block 256)
    oreq = _allreduce_req(env, dist, n, "oc",
                          compression=CompressionType.QUANTIZATION)
    env.config.collective_algo = "pallas_ring"
    env.config.validate()
    preq = _allreduce_req(env, dist, n, "pc",
                          compression=CompressionType.QUANTIZATION)
    assert preq._quant_fns is not None and len(preq._quant_fns) == 2
    # the span names ONE chunk's ring geometry, tagged with the split
    assert "programs=2" in preq._span_args["pallas.hop"]
    vals = _exact_scale_vals(rng, 8, n, dist.topology.grid_shape)
    buf = dist.topology.shard_buffer(vals)
    np.testing.assert_array_equal(np.asarray(preq.start(buf).wait()),
                                  np.asarray(oreq.start(buf).wait()))


def test_selection_tuned_profile_cell(env):
    """A tuned profile can route dense AND quantized cells to the fused
    ring per (kind x size x shape) band; explicit MLSL_ALGO still wins."""
    from mlsl_tpu.tuner.profile import TunedProfile

    prof = TunedProfile(fingerprint={}, cells=[
        {"kind": "allreduce", "shape": [8], "compression": "none",
         "max_bytes": None, "algo": "pallas_ring"},
        {"kind": "allreduce", "shape": [8], "compression": "quantization",
         "max_bytes": None, "algo": "pallas_ring"},
    ])
    env.config.tuned_profile = prof
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    assert algos.select("allreduce", g, 1 << 20, CompressionType.NONE,
                        env.config) == "pallas_ring"
    assert algos.select("allreduce", g, 1 << 20,
                        CompressionType.QUANTIZATION,
                        env.config) == "pallas_ring"
    # explicit env wins over the tuned cell
    env.config.collective_algo = "rhd"
    env.config.validate()
    assert algos.select("allreduce", g, 1 << 20, CompressionType.NONE,
                        env.config) == "rhd"
    # a tuned quant cell on an ineligible group falls back to the wire family
    g2 = ProcessGroup(Topology(4, 2), ("data", "model"))
    env.config.collective_algo = ""
    env.config.validate()
    assert algos.select("allreduce", g2, 1 << 20,
                        CompressionType.QUANTIZATION,
                        env.config) == "lax"


# -- supervisor: breaker degradation -----------------------------------------


def test_dense_breaker_degrades_to_lax(env):
    """A failing pallas dispatch rides the algo breaker's rung 3: the
    tripping round is served by the 'lax' baseline, bit-exact."""
    env.config.breaker_cooldown_s = 60.0
    supervisor.configure(env.config)
    env.config.collective_algo = "pallas_ring"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 256
    req = _allreduce_req(env, dist, n, "brk")
    assert req.algo == "pallas_ring"
    buf = dist.make_buffer(
        lambda p: (np.arange(n) % 13 * (p + 1)).astype(np.float32), n)
    base = np.asarray(req.start(buf).wait())
    thr = supervisor.breaker("algo").threshold
    for _ in range(thr - 1):
        chaos.plan("collective.dispatch", "error")
        with pytest.raises(chaos.ChaosError):
            req.start(buf).wait()
        chaos.clear()
    chaos.plan("collective.dispatch", "error")
    out_trip = np.asarray(req.start(buf).wait())  # tripping round: lax serves
    chaos.clear()
    np.testing.assert_array_equal(out_trip, base)
    assert supervisor.breaker("algo").state == supervisor.OPEN
    # new requests pin to the baseline while OPEN
    req2 = _allreduce_req(env, dist, n, "brk2")
    assert req2.algo == algos.DEFAULT


def test_quant_breaker_degrades_to_plain(rng, env):
    """The fused quantized ring rides the quant breaker: when it opens, the
    dispatch degrades to the plain f32 SUM with the residual flushed — the
    SAME contract (and, geometry shared, the same logical_residual math) as
    the composed ring, pinned by lockstep against a quant_ring twin that
    degrades on the open breaker without a fault of its own."""
    env.config.breaker_cooldown_s = 60.0
    supervisor.configure(env.config)
    dist = env.create_distribution(8, 1)
    n = 8 * 256 * 32
    oreq = _allreduce_req(env, dist, n, "qbrk-o",
                          compression=CompressionType.QUANTIZATION)
    env.config.collective_algo = "pallas_ring"
    env.config.validate()
    preq = _allreduce_req(env, dist, n, "qbrk-p",
                          compression=CompressionType.QUANTIZATION)
    assert oreq.algo == "quant_ring" and preq.algo == "pallas_ring"
    buf = dist.topology.shard_buffer(
        (rng.standard_normal((*dist.topology.grid_shape, n)) * 3).astype(
            np.float32))
    # healthy round: residuals advance in lockstep (shared entry math)
    np.testing.assert_array_equal(np.asarray(preq.start(buf).wait()),
                                  np.asarray(oreq.start(buf).wait()))
    np.testing.assert_array_equal(np.asarray(preq._err),
                                  np.asarray(oreq._err))
    thr = supervisor.breaker("quant").threshold
    for _ in range(thr - 1):
        chaos.plan("codec.roundtrip", "error")
        with pytest.raises(chaos.ChaosError):
            preq.start(buf).wait()
        chaos.clear()
    chaos.plan("codec.roundtrip", "error")
    out_trip = np.asarray(preq.start(buf).wait())  # tripping round: degraded
    chaos.clear()
    assert supervisor.breaker("quant").state == supervisor.OPEN
    # the twin degrades on the OPEN breaker (no fault of its own): both
    # flush their identical residuals through the identical plain program
    out_twin = np.asarray(oreq.start(buf).wait())
    np.testing.assert_array_equal(out_trip, out_twin)


# -- overlap engine -----------------------------------------------------------


def test_overlap_inline_gate_off_chip(env):
    """In-graph emission is TPU-only (the interpreter cannot resolve remote
    DMA inside the 4-axis grid shard_map): off-chip the plan falls back to
    the baseline loudly, and inline_plan refuses the algorithm outright."""
    from mlsl_tpu.comm import overlap

    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    assert not algos.inline_eligible("pallas_ring", "allreduce", g)
    plan = overlap.build_plan(
        g, [("l0", 4096, CompressionType.NONE)], env.config,
        algo="pallas_ring",
    )
    assert [u.algo for u in plan.units] == ["lax"]
    from mlsl_tpu.log import MLSLError

    with pytest.raises(MLSLError, match="in-graph"):
        algos.inline_plan("allreduce", g, "pallas_ring", 4096)


def test_steps_builder_shape(env):
    """The phase form exists and follows the rhd/ring2d convention: one
    kernel-launch phase between prep and finish (built here, executed by
    the tpu-marked twin — building must not require a chip)."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    prep, phases, finish = rk.steps("allreduce", g, 4096,
                                    op=ReductionType.SUM)
    assert len(phases) == 1 and callable(prep) and callable(finish)


# -- config / tuner plumbing --------------------------------------------------


def test_config_knob_validation(monkeypatch):
    from mlsl_tpu.core.environment import Environment
    from mlsl_tpu.log import MLSLError

    monkeypatch.setenv("MLSL_PALLAS_RING_SLOTS", "1")
    e = Environment.get_env()
    with pytest.raises(MLSLError, match="PALLAS_RING_SLOTS"):
        e.init()
    monkeypatch.setenv("MLSL_PALLAS_RING_SLOTS", "3")
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "yes")
    with pytest.raises(MLSLError, match="PALLAS_INTERPRET"):
        e.init()


def test_profile_knob_range(tmp_path):
    from mlsl_tpu import tuner
    from mlsl_tpu.log import MLSLError
    from mlsl_tpu.tuner.profile import KNOB_RANGES, TunedProfile

    assert "pallas_ring_slots" in KNOB_RANGES
    bad = TunedProfile(fingerprint={}, cells=[],
                       knobs={"pallas_ring_slots": 0})
    p = tmp_path / "prof.json"
    bad.save(str(p))
    with pytest.raises(MLSLError, match="pallas_ring_slots"):
        tuner.load_profile(str(p))
    ok = TunedProfile(fingerprint={}, cells=[],
                      knobs={"pallas_ring_slots": 4})
    ok.save(str(p))
    assert tuner.load_profile(str(p)).knobs["pallas_ring_slots"] == 4


def test_plan_key_carries_slot_geometry(env):
    """MLSL_PRECOMPILE plan entries must distinguish the kernel's slot
    geometry: a warmed slots=2 program must not suppress re-warming after
    the knob changes (the compiled kernel is different)."""
    from mlsl_tpu.types import OpType

    collectives.clear_cache()
    try:
        env.config.precompile = True
        env.config.collective_algo = "pallas_ring"
        env.config.validate()

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
        keys2 = {k for k in collectives._plan_cache
                 if k[0] == "req" and k[-1] == "pallas_ring"}
        assert keys2 and all(k[-2] == (2, False) for k in keys2)
        env.config.pallas_ring_slots = 3
        build_session()
        keys3 = {k for k in collectives._plan_cache
                 if k[0] == "req" and k[-1] == "pallas_ring"} - keys2
        assert keys3 and all(k[-2] == (3, False) for k in keys3)
    finally:
        env.config.precompile = False
        collectives.clear_cache()


# -- on-chip-only variants (auto-skip off TPU) --------------------------------


@pytest.mark.tpu
def test_tpu_compiled_dense_parity(rng, env, monkeypatch):
    """The compiled Mosaic kernel (capacity handshake included) bit-exact vs
    lax on integer sums — the on-chip twin of the interpret parity pin."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    topo = Topology(jax.device_count(), 1)
    g = ProcessGroup(topo, ("data",))
    n = 1 << 16
    vals = _int_vals(rng, topo, n)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "pallas_ring",
                     op=ReductionType.SUM)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


@pytest.mark.tpu
def test_tpu_quant_variant_is_parked(env, monkeypatch):
    """The int8-fused variant does not compile for the chip (rk.QUANT_PARKED,
    KNOWN_FAILURES.md): on the compiled backend it is ineligible, so a
    forced pallas_ring on a quantized request keeps the composed ring at
    selection — nothing raises or degrades at dispatch."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    topo = Topology(jax.device_count(), 1)
    g = ProcessGroup(topo, ("data",))
    assert not rk.eligible_quant(g, BLOCK)
    env.config.collective_algo = "pallas_ring"
    env.config.quant_block_elems = BLOCK
    env.config.validate()
    assert algos.select("allreduce", g, 1 << 20,
                        CompressionType.QUANTIZATION, env.config) == "lax"
    assert algos.select("allreduce", g, 1 << 20, CompressionType.NONE,
                        env.config, op=ReductionType.SUM) == "pallas_ring"


@pytest.mark.tpu
def test_tpu_overlap_in_graph_emission(rng, env, monkeypatch):
    """In-graph emission through the compiled overlap engine: the staged
    multi-tensor reduce with pallas_ring units, bit-exact vs the lax build
    on integer payloads (the standalone-grid pattern of
    test_overlap_compiled)."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    from mlsl_tpu.comm import overlap

    n_dev = jax.device_count()
    topo = Topology(n_dev, 1)
    g = ProcessGroup(topo, ("data",))
    assert algos.inline_eligible("pallas_ring", "allreduce", g)
    counts = [4096, 8192, 4096]
    bufs = [topo.shard_buffer(_int_vals(rng, topo, c)) for c in counts]
    fn_p, _ = overlap.build_multi_reduce(g, counts, algo="pallas_ring")
    fn_l, _ = overlap.build_multi_reduce(g, counts, algo="lax")
    for got, want in zip(fn_p(bufs), fn_l(bufs)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.tpu
def test_tpu_compiled_snake_parity(rng, env, monkeypatch):
    """pallas_ring2d compiled: the snake cycle over the 2x2 torus of a
    four-chip host, bit-exact vs lax on integer sums."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    if jax.device_count() < 4:
        pytest.skip("needs four chips")
    topo = Topology(2, 2, devices=jax.devices()[:4])
    g = ProcessGroup(topo, ("data", "model"))
    vals = _int_vals(rng, topo, 1 << 16)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "pallas_ring2d",
                     op=ReductionType.SUM)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


@pytest.mark.tpu
def test_tpu_compiled_all_gather_phase(rng, env, monkeypatch):
    """The ZeRO-1 all-gather phase kernel compiled: every member ends with
    every member's shard in group-position order."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    n_dev = jax.device_count()
    topo = Topology(n_dev, 1)
    group = ProcessGroup(topo, ("data",))
    for shard in (640, 130):  # chunk-aligned and padded
        vals = _int_vals(rng, topo, shard)
        body = rk.dense_ring_body("all_gather", group, shard, np.float32)
        fn = rk.build_flat_program(body, group, "all_gather")
        out = _run(fn, topo, vals).reshape(n_dev, n_dev * shard)
        want = vals.reshape(-1)
        for i in range(n_dev):
            np.testing.assert_array_equal(out[i], want)
