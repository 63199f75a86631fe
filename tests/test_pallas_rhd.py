"""Latency-class rhd allreduce kernel tests (ops/rhd_kernels.py, algos
'pallas_rhd').

Tier-1 runs the kernel under the Pallas interpreter (MLSL_PALLAS_INTERPRET=1
— real remote-DMA semantics over the flat world mesh), pinning:

- bit-exact parity vs the ``lax`` baseline on integer sums (the pairwise
  halving/doubling schedule and the psum tree are both exact arithmetic),
  allclose on floats;
- the selection contract: the explicit/tuned rungs like every algorithm,
  PLUS the opt-in heuristic rung — ``MLSL_PALLAS_RHD=1`` routes dense SUM
  allreduces at or below the ``MLSL_PALLAS_RHD_MAX_BYTES`` band (default:
  the ``msg_priority_threshold`` small-message class) while untuned default
  behavior stays bit-for-bit the baseline;
- the full PR 10 integration contract: request e2e with ``pallas.hop``
  span + ALGO counter attribution, breaker degradation to the baseline,
  MLSL_PRECOMPILE plan-key variant identity, tuner knob validation, and the
  A130-A132 static-accounting mirror (including the pre/post fold rounds
  for non-2^k groups the 8-device mesh cannot instantiate live).

The compiled Mosaic variant carries the ``tpu`` marker (auto-skip
off-chip, conftest)."""

import numpy as np
import pytest
import jax

from mlsl_tpu import chaos, supervisor
from mlsl_tpu.comm import algos, collectives
from mlsl_tpu.comm.mesh import ProcessGroup, Topology
from mlsl_tpu.core import stats as stats_mod
from mlsl_tpu.ops import rhd_kernels as rhd
from mlsl_tpu.types import (
    CompressionType, DataType, GroupType, ReductionType,
)


@pytest.fixture(autouse=True)
def _interpret_gate(monkeypatch):
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "1")


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(23)


def _run(fn, topo, vals):
    return np.asarray(jax.block_until_ready(fn(topo.shard_buffer(vals))))


def _int_vals(rng, topo, n):
    return rng.integers(-8, 8, size=(*topo.grid_shape, n)).astype(np.float32)


# -- eligibility & schedule math ----------------------------------------------


def test_gate_off_by_default(monkeypatch, env):
    """Off-TPU without the interpret gate the kernel is never eligible, and
    a forced MLSL_ALGO=pallas_rhd falls back to the baseline loudly."""
    monkeypatch.delenv("MLSL_PALLAS_INTERPRET", raising=False)
    g = ProcessGroup(Topology(8, 1), ("data",))
    assert not algos.eligible("pallas_rhd", "allreduce", g)
    assert "pallas_rhd" not in algos.candidates("allreduce", g)
    env.config.collective_algo = "pallas_rhd"
    env.config.validate()
    assert algos.select("allreduce", g, 4096, CompressionType.NONE,
                        env.config) == "lax"


def test_eligibility_shapes(env):
    """World-rank pairwise addressing frees rhd from the single-live-axis
    ring restriction: ANY uniform axis-aligned sub-grid rides, including
    the full 2-axis torus where the 1D ring is ineligible."""
    t1 = Topology(8, 1)
    t2 = Topology(4, 2)
    assert algos.eligible("pallas_rhd", "allreduce", ProcessGroup(t1, ("data",)))
    assert algos.eligible("pallas_rhd", "allreduce", ProcessGroup(t2, ("data",)))
    assert algos.eligible("pallas_rhd", "allreduce",
                          ProcessGroup(t2, ("data", "model")))
    assert not algos.eligible("pallas_rhd", "allreduce",
                              ProcessGroup(t1, (),
                                           colors=(0, 0, 0, 0, 1, 1, 1, 1)))
    # allreduce SUM only: the halving phase is a reduce-scatter in disguise
    assert not algos.eligible("pallas_rhd", "reduce_scatter",
                              ProcessGroup(t1, ("data",)))
    assert not algos.eligible("pallas_rhd", "allreduce",
                              ProcessGroup(t1, ("data",)),
                              op=ReductionType.MAX)


def test_schedule_math():
    """rounds/_split: the exact pre-fold + 2·log2(c) + post-fold schedule."""
    assert rhd._split(8) == (8, 3, 0)
    assert rhd._split(6) == (4, 2, 2)
    assert rhd._split(2) == (2, 1, 0)
    assert rhd.rounds(8) == 6          # 2*log2(8), no fold
    assert rhd.rounds(6) == 6          # fold + 2*log2(4) + unfold
    assert rhd.rounds(64) == 12
    m, m_rows = rhd.geometry(8, 5000)
    assert m % (8 * rhd.UNIT) == 0 and m >= 5000
    assert m_rows == m // 128


# -- parity -------------------------------------------------------------------


@pytest.mark.parametrize("n", [512, 5000])
def test_parity_bitexact_int(rng, env, n):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    vals = _int_vals(rng, topo, n)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "pallas_rhd",
                     op=ReductionType.SUM)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


def test_parity_float_allclose(rng, env):
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 4096
    vals = rng.normal(size=(*topo.grid_shape, n)).astype(np.float32)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "pallas_rhd",
                     op=ReductionType.SUM)
    np.testing.assert_allclose(_run(fn, topo, vals), _run(base, topo, vals),
                               rtol=1e-5, atol=1e-5)


def test_parity_two_axis_group(rng, env):
    """The full (4, 2) torus — a group the 1D ring cannot serve — reduces
    bit-exact through the world-rank pairwise schedule."""
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("data", "model"))
    n = 768
    vals = _int_vals(rng, topo, n)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "pallas_rhd",
                     op=ReductionType.SUM)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


def test_parity_subgroup_instances(rng, env):
    """Single-axis subgroups of the (4, 2) grid: multiple pairwise-schedule
    instances run in one program through the world-rank tables."""
    topo = Topology(4, 2)
    for axes in (("data",), ("model",)):
        g = ProcessGroup(topo, axes)
        vals = _int_vals(rng, topo, 640)
        base = algos.build("allreduce", g, np.float32, "lax",
                           op=ReductionType.SUM)
        fn = algos.build("allreduce", g, np.float32, "pallas_rhd",
                         op=ReductionType.SUM)
        np.testing.assert_array_equal(_run(fn, topo, vals),
                                      _run(base, topo, vals))


# -- selection: the opt-in heuristic rung -------------------------------------


def test_heuristic_rung_opt_in(env):
    """Untuned default stays the baseline; MLSL_PALLAS_RHD=1 routes the
    small-message band; payloads above the band keep the baseline; an
    explicit 'lax' pins the baseline even when armed."""
    g = ProcessGroup(Topology(8, 1), ("data",))
    cfg = env.config
    # untuned, unarmed: bit-for-bit baseline
    assert algos.select("allreduce", g, 4096, CompressionType.NONE,
                        cfg) == "lax"
    cfg.pallas_rhd = True
    assert algos.select("allreduce", g, 4096, CompressionType.NONE,
                        cfg) == "pallas_rhd"
    # above the band (default: 4 x msg_priority_threshold bytes) -> baseline
    over = rhd.env_max_bytes(cfg) + 1
    assert algos.select("allreduce", g, over, CompressionType.NONE,
                        cfg) == "lax"
    # the explicit knob narrows the band
    cfg.pallas_rhd_max_bytes = 2048
    assert rhd.env_max_bytes(cfg) == 2048
    assert algos.select("allreduce", g, 4096, CompressionType.NONE,
                        cfg) == "lax"
    assert algos.select("allreduce", g, 2048, CompressionType.NONE,
                        cfg) == "pallas_rhd"
    # an explicit 'lax' pins the baseline ahead of the heuristic rung
    cfg.pallas_rhd_max_bytes = 0
    cfg.collective_algo = "lax"
    cfg.validate()
    assert algos.select("allreduce", g, 2048, CompressionType.NONE,
                        cfg) == "lax"
    # compressed payloads never ride the dense latency kernel
    cfg.collective_algo = ""
    cfg.validate()
    assert algos.select("allreduce", g, 2048, CompressionType.QUANTIZATION,
                        cfg) != "pallas_rhd"


def test_selection_tuned_profile_cell(env):
    from mlsl_tpu.tuner.profile import TunedProfile

    prof = TunedProfile(fingerprint={}, cells=[
        {"kind": "allreduce", "shape": [8], "compression": "none",
         "max_bytes": None, "algo": "pallas_rhd"},
    ])
    env.config.tuned_profile = prof
    g = ProcessGroup(Topology(8, 1), ("data",))
    assert algos.select("allreduce", g, 1 << 16, CompressionType.NONE,
                        env.config) == "pallas_rhd"
    # explicit env wins over the tuned cell
    env.config.collective_algo = "rhd"
    env.config.validate()
    assert algos.select("allreduce", g, 1 << 16, CompressionType.NONE,
                        env.config) == "rhd"


# -- request engine: e2e, observability, degradation --------------------------


def _allreduce_req(env, dist, n, name=""):
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    req = CommRequest(
        CommDesc("allreduce", dist._group(GroupType.DATA), n, DataType.FLOAT,
                 op=ReductionType.SUM),
        env.dispatcher, name=name,
    )
    req.setup()
    return req


def test_request_e2e(env):
    env.config.collective_algo = "pallas_rhd"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 512
    stats_mod.reset_algo_counters()
    req = _allreduce_req(env, dist, n, "rhd")
    assert req.algo == "pallas_rhd"
    assert "algo=pallas_rhd" in req.describe()
    assert "codec=rhd/f32" in req._span_args["pallas.hop"]
    assert f"hops={rhd.rounds(8)}" in req._span_args["pallas.hop"]
    buf = dist.make_buffer(lambda p: np.full(n, float(p + 1), np.float32), n)
    out = req.start(buf).wait()
    np.testing.assert_array_equal(np.asarray(dist.local_part(out, 0)),
                                  np.full(n, 36.0, np.float32))
    assert stats_mod.ALGO_COUNTERS.get(("allreduce", "pallas_rhd"), 0) >= 1


def test_breaker_degrades_to_lax(env):
    """A failing rhd dispatch rides the algo breaker: the tripping round is
    served by the 'lax' baseline bit-exact, and new requests pin to the
    baseline while the breaker is OPEN."""
    env.config.breaker_cooldown_s = 60.0
    supervisor.configure(env.config)
    env.config.collective_algo = "pallas_rhd"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 256
    req = _allreduce_req(env, dist, n, "brk")
    assert req.algo == "pallas_rhd"
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
    out_trip = np.asarray(req.start(buf).wait())
    chaos.clear()
    np.testing.assert_array_equal(out_trip, base)
    assert supervisor.breaker("algo").state == supervisor.OPEN
    req2 = _allreduce_req(env, dist, n, "brk2")
    assert req2.algo == algos.DEFAULT


def test_plan_key_carries_slot_geometry(env):
    """MLSL_PRECOMPILE plan entries distinguish the rhd slot depth: a warmed
    slots=2 program must not suppress re-warming after the knob changes."""
    from mlsl_tpu.types import OpType

    collectives.clear_cache()
    try:
        env.config.precompile = True
        env.config.collective_algo = "pallas_rhd"
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
                 if k[0] == "req" and k[-1] == "pallas_rhd"}
        assert keys2 and all(k[-2] == (2,) for k in keys2)
        env.config.pallas_ring_slots = 3
        build_session()
        keys3 = {k for k in collectives._plan_cache
                 if k[0] == "req" and k[-1] == "pallas_rhd"} - keys2
        assert keys3 and all(k[-2] == (3,) for k in keys3)
    finally:
        env.config.precompile = False
        collectives.clear_cache()


# -- knobs --------------------------------------------------------------------


def test_config_knob_validation(monkeypatch):
    from mlsl_tpu.config import Config
    from mlsl_tpu.log import MLSLError

    c = Config()
    c.pallas_rhd_max_bytes = -1
    with pytest.raises(MLSLError):
        c.validate()
    monkeypatch.setenv("MLSL_PALLAS_RHD", "1")
    monkeypatch.setenv("MLSL_PALLAS_RHD_MAX_BYTES", "65536")
    monkeypatch.setenv("MLSL_PALLAS_A2A_QUANT", "0")
    c2 = Config.from_env()
    assert c2.pallas_rhd and c2.pallas_rhd_max_bytes == 65536
    assert not c2.pallas_a2a_quant


def test_profile_knob_range(tmp_path):
    """pallas_rhd_max_bytes is a legal profile knob; a bool-typed value is
    rejected at load (the KNOB_RANGES contract)."""
    from mlsl_tpu.log import MLSLError
    from mlsl_tpu.tuner.profile import TunedProfile, load_profile

    p = tmp_path / "prof.json"
    prof = TunedProfile(fingerprint={}, cells=[],
                        knobs={"pallas_rhd_max_bytes": 32768,
                               "pallas_a2a_quant": 0})
    prof.save(str(p))
    got = load_profile(str(p))
    assert got.knobs["pallas_rhd_max_bytes"] == 32768
    prof.knobs["pallas_rhd_max_bytes"] = True
    prof.save(str(p))
    with pytest.raises(MLSLError):
        load_profile(str(p))


# -- A130-A132 static accounting ----------------------------------------------


def test_accounting_balanced_across_groups():
    """The rhd capacity-semaphore trace balances for every group size the
    engine can select — including the fold rounds of non-2^k groups the
    8-device proof mesh cannot instantiate live."""
    from mlsl_tpu.analysis import plan as plan_mod

    for g in (2, 3, 4, 5, 6, 8, 12, 64):
        for slots in (2, 3, 8):
            ev, th, nd = rhd.static_accounting(g, slots)
            assert th == rhd.rounds(g)
            rep = plan_mod.verify_hop_trace(ev, slots=slots, ndirs=nd,
                                            total_hops=th)
            assert not rep.diagnostics, (g, slots)


def test_accounting_tamper_detected():
    """Dropping the last free signal breaks the drain invariant (A130)."""
    from mlsl_tpu.analysis import plan as plan_mod

    ev, th, nd = rhd.static_accounting(8, 2)
    bad = list(ev)
    bad.remove(("free", 0, [e for e in ev if e[0] == "free"][-1][2]))
    rep = plan_mod.verify_hop_trace(bad, slots=2, ndirs=nd, total_hops=th)
    assert any(d.code == "MLSL-A130" for d in rep.diagnostics)


# -- on-chip-only variant (auto-skip off TPU) ---------------------------------


@pytest.mark.tpu
def test_tpu_compiled_parity(rng, env, monkeypatch):
    """The compiled Mosaic kernel (capacity handshake active when
    slots < rounds) bit-exact vs lax on integer sums."""
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    topo = Topology(jax.device_count(), 1)
    g = ProcessGroup(topo, ("data",))
    vals = _int_vals(rng, topo, 2048)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "pallas_rhd",
                     op=ReductionType.SUM)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))
