"""Fused all-to-all kernel tests (ops/a2a_kernels.py, algos 'pallas_a2a') —
the first member of the NEW ``'alltoall'`` engine kind.

Tier-1 runs the kernel under the Pallas interpreter (MLSL_PALLAS_INTERPRET=1,
real remote-DMA semantics over the flat world mesh), pinning:

- dense-variant parity BIT-exact vs the lax exchange on random floats (an
  all-to-all is a pure permutation — no arithmetic on the wire);
- quantized parity bit-exact vs the same lax exchange on the exact-scale
  payload (integer entries with a ±127 sentinel at every block start keep
  every blockwise scale exactly 1.0, so the int8 round trip is the
  identity), and 2-round entry-error-feedback lockstep against a host
  oracle built from quant_ring's own codec helpers — bit-exact on random
  floats, because the exchange after the codec is a pure chunk transpose;
- the selection contract for the new kind: forced MLSL_ALGO and tuned
  cells route 'alltoall' to pallas_a2a, the central kind guard keeps every
  reduction algorithm (a global MLSL_ALGO=rhd) off the exchange, and
  models/moe.py's inline route falls back to lax LOUDLY off-TPU while
  staying bit-identical to the hardcoded-axis path;
- the PR 10 integration contract: request e2e with ``pallas.hop`` span +
  ALGO counters, breaker degradation to the lax exchange, program-cache
  codec identity, the wire-bytes <= 1/3 analytic, the knob toggles, and
  the A130-A132 static-accounting mirror across group sizes the 8-device
  proof mesh cannot instantiate live."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mlsl_tpu import chaos, supervisor
from mlsl_tpu.comm import algos, collectives, quant_ring
from mlsl_tpu.comm.mesh import ProcessGroup, Topology
from mlsl_tpu.core import stats as stats_mod
from mlsl_tpu.ops import a2a_kernels as a2a
from mlsl_tpu.types import (
    CompressionType, DataType, GroupType, ReductionType,
)

BLOCK = 128              # codec block for the parity suites
UNIT = BLOCK * 32        # quantized chunk unit (block x ROW_TILE)


@pytest.fixture(autouse=True)
def _interpret_gate(monkeypatch):
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "1")


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(29)


def _run(fn, topo, vals):
    return np.asarray(jax.block_until_ready(fn(topo.shard_buffer(vals))))


def _exact_scale_vals(rng, n_dev, count, grid_shape):
    """Integer payload with a ±127 sentinel at every BLOCK start on every
    member: every blockwise amax is exactly 127, every scale exactly 1.0,
    the int8 round trip is the identity — the fused quantized wire must
    match the RAW f32 exchange bit-for-bit."""
    v = rng.integers(-10, 10, size=(n_dev, count)).astype(np.float32)
    v[:, ::BLOCK] = 127.0
    return v.reshape(*grid_shape, count)


# -- eligibility & the new engine kind ----------------------------------------


def test_gate_off_by_default(monkeypatch, env):
    """Off-TPU without the interpret gate the kernel is never eligible and
    the alltoall kind offers only the baseline."""
    monkeypatch.delenv("MLSL_PALLAS_INTERPRET", raising=False)
    g = ProcessGroup(Topology(8, 1), ("data",))
    assert not algos.eligible("pallas_a2a", "alltoall", g)
    assert algos.candidates("alltoall", g) == ("lax",)
    env.config.collective_algo = "alltoall=pallas_a2a"
    env.config.validate()
    assert algos.select("alltoall", g, 4096, CompressionType.NONE,
                        env.config) == "lax"


def test_alltoall_kind_guard(env):
    """The central guard: no reduction algorithm may claim the exchange —
    a global MLSL_ALGO=rhd must not break MoE dispatch."""
    t1 = Topology(8, 1)
    g = ProcessGroup(t1, ("data",))
    for algo in ("rhd", "ring2d", "pallas_ring", "pallas_rhd",
                 "pallas_ring2d", "hier"):
        assert not algos.eligible(algo, "alltoall", g), algo
    assert algos.candidates("alltoall", g) == ("lax", "pallas_a2a")
    env.config.collective_algo = "rhd"
    env.config.validate()
    assert algos.select("alltoall", g, 4096, CompressionType.NONE,
                        env.config) == "lax"
    # the per-kind spelling routes the exchange without touching reductions
    env.config.collective_algo = "alltoall=pallas_a2a"
    env.config.validate()
    assert algos.select("alltoall", g, 4096, CompressionType.NONE,
                        env.config) == "pallas_a2a"
    assert algos.select("allreduce", g, 4096, CompressionType.NONE,
                        env.config) == "lax"


def test_eligibility_shapes(env):
    """Axis-aligned uniform groups of any axis count; colors, ops and
    ragged counts are rejected."""
    t2 = Topology(4, 2)
    assert algos.eligible("pallas_a2a", "alltoall",
                          ProcessGroup(t2, ("data",)))
    assert algos.eligible("pallas_a2a", "alltoall",
                          ProcessGroup(t2, ("data", "model")))
    assert not algos.eligible(
        "pallas_a2a", "alltoall",
        ProcessGroup(Topology(8, 1), (), colors=(0, 0, 0, 0, 1, 1, 1, 1)))
    assert not algos.eligible("pallas_a2a", "allreduce",
                              ProcessGroup(t2, ("data",)))
    g = ProcessGroup(Topology(8, 1), ("data",))
    assert not a2a.eligible("alltoall", g, op=ReductionType.SUM)
    assert not a2a.eligible("alltoall", g, count=8 * 100 + 3)
    assert a2a.eligible("alltoall", g, count=8 * 100)


def test_geometry_and_wire_bytes():
    """The analytic wire contract: int8 payload + one f32 scale per block
    row is <= 1/3 of the dense f32 wire at every block-grid payload."""
    for g, count in ((8, 8 * UNIT), (8, 8 * UNIT * 3), (4, 4 * UNIT * 2),
                     (64, 64 * UNIT)):
        rc, chunk, rows = a2a.geometry(g, count, BLOCK, True)
        assert rc == count // g and chunk % UNIT == 0 and rows == chunk // BLOCK
        wq = a2a.wire_bytes(g, count, BLOCK, True)
        wf = a2a.wire_bytes(g, count, BLOCK, False)
        assert wq * 3 <= wf, (g, count, wq, wf)
    d = a2a.describe_plan(8, 8 * UNIT, BLOCK, True, 2)
    assert "hops=7" in d and f"codec=int8/b{BLOCK}" in d
    assert "codec=float32" in a2a.describe_plan(8, 8 * UNIT, BLOCK, False, 2)


# -- parity -------------------------------------------------------------------


def test_dense_parity_bitexact(rng, env):
    """The dense variant is a pure permutation: bit-exact on random floats."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    count = 8 * 640
    vals = rng.normal(size=(*topo.grid_shape, count)).astype(np.float32)
    base = algos.build("alltoall", g, np.float32, "lax",
                       send_count=count // 8)
    fn = algos.build("alltoall", g, np.float32, "pallas_a2a",
                     block=BLOCK, quantized=False)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


def test_quant_parity_exact_scale(rng, env):
    """The quantized wire on the exact-scale payload: the codec round trip
    is the identity, so the fused exchange == the raw f32 exchange."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    count = 8 * UNIT
    vals = _exact_scale_vals(rng, 8, count, topo.grid_shape)
    base = algos.build("alltoall", g, np.float32, "lax",
                       send_count=count // 8)
    fn = algos.build("alltoall", g, np.float32, "pallas_a2a",
                     block=BLOCK, quantized=True)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


def test_parity_subgroup_instances(rng, env):
    """Single-axis subgroups of a (4, 2) grid: multiple exchange instances
    run in one program through the world-rank tables (dense variant —
    bit-exact regardless of payload)."""
    topo = Topology(4, 2)
    for axes, gsz in ((("data",), 4), (("model",), 2)):
        g = ProcessGroup(topo, axes)
        count = gsz * 512
        vals = rng.normal(size=(*topo.grid_shape, count)).astype(np.float32)
        base = algos.build("alltoall", g, np.float32, "lax",
                           send_count=count // gsz)
        fn = algos.build("alltoall", g, np.float32, "pallas_a2a",
                         block=BLOCK, quantized=False)
        np.testing.assert_array_equal(_run(fn, topo, vals),
                                      _run(base, topo, vals))


def _composed_ef_oracle(group, count, block):
    """The composed form of the fused kernel, the ring lockstep precedent:
    quant_ring's entry codec (the SHARED error-feedback math), the kernel's
    second codec round trip at the wire boundary (self chunk included —
    the fused int8 wire), then a plain lax.all_to_all for the exchange.
    Compiled over the same flat mesh as the kernel program."""
    from jax import lax

    from mlsl_tpu.ops import ring_kernels as rk

    g = int(group.size)
    rc, chunk, _rows = a2a.geometry(g, count, block, True)

    def body(x, err):
        xc = quant_ring._to_chunks(
            x.astype(jnp.float32), g, rc, chunk).reshape(-1)
        xq = xc + err
        q, s = quant_ring._quant(xq.reshape(-1, block), False)
        xhat = quant_ring._dequant(q.reshape(-1, block), s, False).reshape(-1)
        new_err = xq - xhat
        q2, s2 = quant_ring._quant(xhat.reshape(-1, block), False)
        wire = quant_ring._dequant(
            q2.reshape(-1, block), s2, False).reshape(g, chunk)
        ex = lax.all_to_all(wire, "world", split_axis=0, concat_axis=0,
                            tiled=True)
        return ex[:, :rc].reshape(-1), new_err

    return rk.build_flat_program(body, group, "alltoall", stateful=True)


def test_quant_two_round_ef_lockstep(rng, env):
    """Random floats through the stateful (x, err) -> (out, new_err) form:
    output AND residual bit-exact against the composed oracle across two
    rounds — the entry codec is quant_ring's shared math, the second codec
    is the fused wire's only transform, and the exchange itself is a pure
    permutation, so the fused kernel is a drop-in for the composed form."""
    topo = Topology(8, 1)
    group = ProcessGroup(topo, ("data",))
    count = 8 * UNIT
    fn = algos.build("alltoall", group, np.float32, "pallas_a2a",
                     block=BLOCK, quantized=True, ef=True)
    ofn = _composed_ef_oracle(group, count, BLOCK)
    _rc, chunk, _rows = a2a.geometry(8, count, BLOCK, True)
    el = 8 * chunk
    buf = topo.shard_buffer(
        (rng.standard_normal((*topo.grid_shape, count)) * 3).astype(
            np.float32))
    ze = topo.shard_buffer(np.zeros((*topo.grid_shape, el), np.float32))
    po1, pe1 = fn(buf, ze)
    oo1, oe1 = ofn(buf, ze)
    np.testing.assert_array_equal(np.asarray(pe1), np.asarray(oe1))
    np.testing.assert_array_equal(np.asarray(po1), np.asarray(oo1))
    po2, pe2 = fn(buf, pe1)       # carry each side's own residual
    oo2, oe2 = ofn(buf, oe1)
    np.testing.assert_array_equal(np.asarray(pe2), np.asarray(oe2))
    np.testing.assert_array_equal(np.asarray(po2), np.asarray(oo2))


# -- selection & the inline MoE route -----------------------------------------


def test_selection_tuned_profile_cell(env):
    from mlsl_tpu.tuner.profile import TunedProfile

    prof = TunedProfile(fingerprint={}, cells=[
        {"kind": "alltoall", "shape": [8], "compression": "none",
         "max_bytes": None, "algo": "pallas_a2a"},
    ])
    env.config.tuned_profile = prof
    g = ProcessGroup(Topology(8, 1), ("data",))
    assert algos.select("alltoall", g, 1 << 16, CompressionType.NONE,
                        env.config) == "pallas_a2a"
    # explicit env wins over the tuned cell
    env.config.collective_algo = "alltoall=lax"
    env.config.validate()
    assert algos.select("alltoall", g, 1 << 16, CompressionType.NONE,
                        env.config) == "lax"


def test_inline_loud_fallback_off_tpu(env, capfd):
    """models/moe.py's route: the table selects pallas_a2a (forced), but the
    interpreter cannot emit the kernel inside the grid shard_map — the
    inline exchange falls back to lax WITH a debug log, bit-identical to
    the hardcoded-axis path."""
    from jax.sharding import PartitionSpec as P

    from mlsl_tpu import log

    from mlsl_tpu.models.train import smap

    env.config.collective_algo = "alltoall=pallas_a2a"
    env.config.validate()
    dist = env.create_distribution(1, 4)
    group = dist._group(GroupType.MODEL)
    assert not algos.inline_eligible("pallas_a2a", "alltoall", group)
    rng = np.random.default_rng(3)
    # local leading dim == group size (the MoE chunks-by-member layout):
    # global (4*4, n) over 4 shards -> (4, n) per member
    x = rng.normal(size=(16, 256)).astype(np.float32)

    def body_routed(x):
        return algos.inline_alltoall(x, "model", group=group,
                                     config=env.config)

    def body_bare(x):
        return algos.inline_alltoall(x, "model")

    mesh = dist.topology.mesh
    prev = log.get_log_level()
    log.set_log_level(log.LogLevel.DEBUG)
    try:
        got = jax.jit(smap(body_routed, mesh, in_specs=P("model"),
                           out_specs=P("model"), check=False))(x)
    finally:
        log.set_log_level(prev)
    assert "falling back to the lax exchange" in capfd.readouterr().err
    want = jax.jit(smap(body_bare, mesh, in_specs=P("model"),
                        out_specs=P("model"), check=False))(x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_moe_e2e_table_routed_matches_hardcoded(env):
    """moe_ffn with the group/config threaded (the table-routed exchange)
    vs group=None (the pre-engine hardcoded axis): identical off-TPU, with
    an untuned config AND with the kernel forced (loud lax fallback)."""
    from jax.sharding import PartitionSpec as P

    from mlsl_tpu.models import moe
    from mlsl_tpu.models.train import smap

    ep = 4
    params = moe.init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    dist = env.create_distribution(1, ep)
    group = dist._group(GroupType.MODEL)
    spec_p = {"wg": P(), "w1": P("model", None, None),
              "w2": P("model", None, None)}

    def run(g, cfg):
        def body(params, x):
            out, _aux = moe.moe_ffn(x, params, "model", ep, group=g,
                                    config=cfg)
            return out

        return np.asarray(jax.jit(smap(
            body, dist.topology.mesh, in_specs=(spec_p, P()),
            out_specs=P(), check=False))(params, x))

    want = run(None, None)
    np.testing.assert_array_equal(run(group, env.config), want)
    env.config.collective_algo = "alltoall=pallas_a2a"
    env.config.validate()
    np.testing.assert_array_equal(run(group, env.config), want)


# -- request engine: e2e, observability, degradation --------------------------


def _a2a_req(env, dist, rc, name=""):
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    req = CommRequest(
        CommDesc("alltoall", dist._group(GroupType.DATA), rc, DataType.FLOAT),
        env.dispatcher, name=name,
    )
    req.setup()
    return req


def test_request_e2e(rng, env):
    env.config.collective_algo = "alltoall=pallas_a2a"
    env.config.quant_block_elems = BLOCK
    env.config.validate()
    dist = env.create_distribution(8, 1)
    rc = UNIT            # per-destination slice (an alltoall desc's count)
    count = 8 * rc
    stats_mod.reset_algo_counters()
    req = _a2a_req(env, dist, rc, "a2a")
    assert req.algo == "pallas_a2a"
    assert "algo=pallas_a2a" in req.describe()
    assert "hops=7" in req._span_args["pallas.hop"]
    assert f"codec=int8/b{BLOCK}" in req._span_args["pallas.hop"]
    vals = _exact_scale_vals(rng, 8, count, dist.topology.grid_shape)
    buf = dist.topology.shard_buffer(vals)
    env.config.collective_algo = ""
    env.config.validate()
    lax_req = _a2a_req(env, dist, rc, "lax")
    assert lax_req.algo == "lax"
    np.testing.assert_array_equal(np.asarray(req.start(buf).wait()),
                                  np.asarray(lax_req.start(buf).wait()))
    assert stats_mod.ALGO_COUNTERS.get(("alltoall", "pallas_a2a"), 0) >= 1


def test_breaker_degrades_to_lax(rng, env):
    """A failing a2a dispatch rides the algo breaker: the tripping round is
    served by the lax exchange — bit-exact on the exact-scale payload —
    and new requests pin to the baseline while OPEN."""
    env.config.breaker_cooldown_s = 60.0
    supervisor.configure(env.config)
    env.config.collective_algo = "alltoall=pallas_a2a"
    env.config.quant_block_elems = BLOCK
    env.config.validate()
    dist = env.create_distribution(8, 1)
    rc = UNIT
    req = _a2a_req(env, dist, rc, "brk")
    assert req.algo == "pallas_a2a"
    vals = _exact_scale_vals(rng, 8, 8 * rc, dist.topology.grid_shape)
    buf = dist.topology.shard_buffer(vals)
    base = np.asarray(req.start(buf).wait())
    thr = supervisor.breaker("algo").threshold
    for _ in range(thr - 1):
        chaos.plan("collective.dispatch", "error")
        with pytest.raises(chaos.ChaosError):
            req.start(buf).wait()
        chaos.clear()
    chaos.plan("collective.dispatch", "error")
    out_trip = np.asarray(req.start(buf).wait())
    chaos.clear()
    np.testing.assert_array_equal(out_trip, base)
    assert supervisor.breaker("algo").state == supervisor.OPEN
    req2 = _a2a_req(env, dist, rc, "brk2")
    assert req2.algo == algos.DEFAULT


def test_program_cache_codec_identity(env):
    """Toggling the codec (or its block grid) is a DIFFERENT program: the
    build cache must not alias the dense and quantized variants."""
    collectives.clear_cache()
    g = ProcessGroup(Topology(8, 1), ("data",))
    algos.build("alltoall", g, np.float32, "pallas_a2a",
                block=BLOCK, quantized=True)
    algos.build("alltoall", g, np.float32, "pallas_a2a",
                block=BLOCK, quantized=False)
    algos.build("alltoall", g, np.float32, "pallas_a2a",
                block=2 * BLOCK, quantized=True)
    keys = [k for k in collectives._cache if k[0] == "algo"
            and k[1] == "pallas_a2a"]
    assert len(keys) == 3
    collectives.clear_cache()


# -- knobs --------------------------------------------------------------------


def test_quant_toggle(env, monkeypatch):
    assert a2a.quant_enabled(env.config)          # default ON
    env.config.pallas_a2a_quant = False
    assert not a2a.quant_enabled(env.config)
    monkeypatch.setenv("MLSL_PALLAS_A2A_QUANT", "0")
    assert not a2a.quant_enabled(None)
    monkeypatch.setenv("MLSL_PALLAS_A2A_QUANT", "1")
    assert a2a.quant_enabled(None)


def test_profile_knob_carries_codec(tmp_path):
    """pallas_a2a_quant rides tuned profiles as a 0/1 int (the KNOB_RANGES
    table rejects bools) and lands on the boolean config field truthily."""
    from mlsl_tpu.config import Config
    from mlsl_tpu.tuner import apply_knobs
    from mlsl_tpu.tuner.profile import TunedProfile, load_profile

    p = tmp_path / "prof.json"
    TunedProfile(fingerprint={}, cells=[],
                 knobs={"pallas_a2a_quant": 0}).save(str(p))
    prof = load_profile(str(p))
    cfg = Config()
    apply_knobs(cfg, prof)
    assert not a2a.quant_enabled(cfg)


# -- A130-A132 static accounting ----------------------------------------------


def test_accounting_balanced_across_groups():
    from mlsl_tpu.analysis import plan as plan_mod

    for g in (2, 3, 4, 5, 8, 16, 64):
        for slots in (2, 3, 8):
            ev, th, nd = a2a.static_accounting(g, slots)
            assert th == g - 1
            rep = plan_mod.verify_hop_trace(ev, slots=slots, ndirs=nd,
                                            total_hops=th)
            assert not rep.diagnostics, (g, slots)


def test_accounting_tamper_detected():
    from mlsl_tpu.analysis import plan as plan_mod

    ev, th, nd = a2a.static_accounting(8, 2)
    bad = list(ev)
    bad.remove([e for e in ev if e[0] == "free"][-1])
    rep = plan_mod.verify_hop_trace(bad, slots=2, ndirs=nd, total_hops=th)
    assert any(d.code == "MLSL-A130" for d in rep.diagnostics)


# -- on-chip-only variants (auto-skip off TPU) --------------------------------


@pytest.mark.tpu
def test_tpu_compiled_dense_parity(rng, env, monkeypatch):
    """The compiled kernel with the dense f32 wire, bit-exact vs lax; the
    int8 wire is parked on the compiled backend (rk.QUANT_PARKED,
    KNOWN_FAILURES.md) and leaves the algorithm ineligible while armed."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    n = jax.device_count()
    topo = Topology(n, 1)
    g = ProcessGroup(topo, ("data",))
    monkeypatch.setenv("MLSL_PALLAS_A2A_QUANT", "1")
    assert not algos.eligible("pallas_a2a", "alltoall", g)
    monkeypatch.setenv("MLSL_PALLAS_A2A_QUANT", "0")
    assert algos.eligible("pallas_a2a", "alltoall", g)
    count = n * UNIT
    vals = _exact_scale_vals(rng, n, count, topo.grid_shape)
    base = algos.build("alltoall", g, np.float32, "lax",
                       send_count=count // n)
    fn = algos.build("alltoall", g, np.float32, "pallas_a2a",
                     block=BLOCK, quantized=False)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


@pytest.mark.tpu
def test_tpu_moe_kernel_routed(env, monkeypatch):
    """On-chip the forced kernel actually rides the MoE exchange in-graph
    (inline_eligible true) and the e2e output stays allclose to the lax
    route (dense wire: the int8 one is parked on the compiled backend)."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("MLSL_PALLAS_A2A_QUANT", "0")
    env.config.pallas_a2a_quant = False
    from jax.sharding import PartitionSpec as P

    from mlsl_tpu.models import moe
    from mlsl_tpu.models.train import smap

    ep = min(4, jax.device_count())
    params = moe.init_moe_params(jax.random.PRNGKey(0), 16, 32, ep)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(64, 16)).astype(np.float32))
    dist = env.create_distribution(1, ep)
    group = dist._group(GroupType.MODEL)
    assert algos.inline_eligible("pallas_a2a", "alltoall", group)
    env.config.collective_algo = "alltoall=pallas_a2a"
    env.config.validate()
    spec_p = {"wg": P(), "w1": P("model", None, None),
              "w2": P("model", None, None)}

    def run(g, cfg):
        def body(params, x):
            out, _aux = moe.moe_ffn(x, params, "model", ep, group=g,
                                    config=cfg)
            return out

        return np.asarray(jax.jit(smap(
            body, dist.topology.mesh, in_specs=(spec_p, P()),
            out_specs=P(), check=False))(params, x))

    np.testing.assert_allclose(run(group, env.config), run(None, None),
                               rtol=0.05, atol=0.05)
