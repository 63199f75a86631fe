"""Two-tier hierarchical collectives (comm/algos/hier.py): tier structure,
dense/compressed parity across tier splits, EF-residual machinery, selection,
breaker degrade, the overlap-engine staged emission, the plan-verifier tier
rules (A114, per-tier in-flight budget), and the 3D pipeline x ZeRO-1 x MoE
composition — the ROADMAP #2 acceptance suite.

Parity contract (the test_algos convention): integer-valued payloads make
every summation order exact, so dense hier is pinned BIT-FOR-BIT against the
lax baseline; the compressed wire is pinned bit-exact on the shared-sentinel
construction (identical member buffers with a per-block +-127 sentinel keep
every scale an exact integer, so the int8 hop and the flat quant ring both
deliver the exact integer sum) and allclose + EF-lockstep elsewhere."""

import os

import numpy as np
import pytest
import jax

from mlsl_tpu.comm import algos, collectives, quant_ring
from mlsl_tpu.comm.algos import hier
from mlsl_tpu.comm.mesh import (
    ProcessGroup, Topology, parse_mesh_tiers, world_tiers,
)
from mlsl_tpu.types import CompressionType, DataType, ReductionType

SPLITS = ["2x4", "4x2", "1x8", "8x1"]


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(11)


@pytest.fixture()
def tiers24(monkeypatch):
    monkeypatch.setenv("MLSL_MESH_TIERS", "2x4")


def _run(fn, topo, vals):
    return np.asarray(jax.block_until_ready(fn(topo.shard_buffer(vals))))


def _int_vals(rng, topo, n, dtype=np.float32):
    return rng.integers(-8, 8, size=(*topo.grid_shape, n)).astype(dtype)


# -- tier structure ----------------------------------------------------------


def test_parse_mesh_tiers_grammar():
    from mlsl_tpu.log import MLSLError

    assert parse_mesh_tiers("") is None
    assert parse_mesh_tiers("2x4") == (2, 4)
    assert parse_mesh_tiers(" 8X1 ") == (8, 1)
    for bad in ("2x", "x4", "2x4x2", "axb", "0x8", "-1x8"):
        with pytest.raises(MLSLError):
            parse_mesh_tiers(bad)


def test_config_validates_tier_knobs(monkeypatch):
    from mlsl_tpu.config import Config
    from mlsl_tpu.log import MLSLError

    c = Config()
    c.mesh_tiers = "2x4"
    c.hier_dcn_codec = "topk"
    c.validate()
    c.hier_dcn_codec = "fp4"
    with pytest.raises(MLSLError):
        c.validate()
    c.hier_dcn_codec = "int8"
    c.mesh_tiers = "banana"
    with pytest.raises(MLSLError):
        c.validate()


@pytest.mark.parametrize("spec", SPLITS)
def test_tier_structure_on_world_ring(monkeypatch, spec):
    monkeypatch.setenv("MLSL_MESH_TIERS", spec)
    t, l = (int(p) for p in spec.split("x"))
    assert world_tiers() == (t, l)
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    assert hier.tier_structure(g) == (t, l)
    assert algos.eligible("hier", "allreduce", g, ReductionType.SUM)


def test_tier_structure_none_without_tiers(monkeypatch):
    monkeypatch.delenv("MLSL_MESH_TIERS", raising=False)
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    assert world_tiers() is None
    assert hier.tier_structure(g) is None
    assert not algos.eligible("hier", "allreduce", g, ReductionType.SUM)


def test_tier_structure_of_subgroup(tiers24):
    """A ("data",) group of a (4, 2) grid: each instance's 4 members stride
    the world by 2, landing 2 per world tier -> a (2, 2) split."""
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("data",))
    assert hier.tier_structure(g) == (2, 2)
    # the model group's 2 members sit inside one tier -> degenerate (1, 2)
    gm = ProcessGroup(topo, ("model",))
    assert hier.tier_structure(gm) == (1, 2)


def test_tier_structure_rejects_interleaved(monkeypatch):
    """A split whose tiers interleave in group-rank order has no uniform
    two-tier shape: a ("model",) group of a (2, 4) grid strides the world
    by 1 within an instance, so 4-member instances span 2x4 world tiers as
    contiguous runs — but a (4, 2)-grid data group under 4x2 world tiers
    alternates tiers member-to-member and must be rejected."""
    monkeypatch.setenv("MLSL_MESH_TIERS", "4x2")
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("data",))  # members stride 2: tiers 0,1,2,3 -> runs of 1
    assert hier.tier_structure(g) == (4, 1)
    monkeypatch.setenv("MLSL_MESH_TIERS", "2x4")
    gm = ProcessGroup(topo, ("model",))  # ranks 0,1 inside tier -> (1,2)
    assert hier.tier_structure(gm) == (1, 2)


def test_tier_structure_on_subworld_topology(tiers24):
    """A Topology over a SUBSET of the world's devices (the test_moe /
    test_pipeline pattern) must not crash on a world-sized tier spec: each
    device maps to its world tier by world position — mirroring how
    device.slice_index survives sub-world Topologies on real multislice —
    so eligibility degrades gracefully instead of raising."""
    devs = jax.devices()
    # first 4 devices: all inside world tier 0 -> degenerate (1, 4)
    t_lo = Topology(4, 1, devices=tuple(devs[:4]))
    g_lo = ProcessGroup(t_lo, ("data",))
    assert hier.tier_structure(g_lo) == (1, 4)
    # middle 4 devices straddle the 2x4 boundary -> a true (2, 2) split
    t_mid = Topology(4, 1, devices=tuple(devs[2:6]))
    g_mid = ProcessGroup(t_mid, ("data",))
    assert hier.tier_structure(g_mid) == (2, 2)
    # last 4: inside world tier 1, normalized ids -> degenerate (1, 4)
    t_hi = Topology(4, 1, devices=tuple(devs[4:]))
    g_hi = ProcessGroup(t_hi, ("data",))
    assert hier.tier_structure(g_hi) == (1, 4)
    # a PERMUTED full-size tuple maps by world identity, not position: the
    # interleaved order has no contiguous split and must stay flat
    perm = tuple(devs[i] for i in (0, 4, 1, 5, 2, 6, 3, 7))
    t_perm = Topology(8, 1, devices=perm)
    g_perm = ProcessGroup(t_perm, ("data",))
    assert hier.tier_structure(g_perm) is None
    # dense parity still holds on the straddling sub-world
    n = 64
    vals = np.stack([np.full(n, p + 1.0, np.float32) for p in range(4)])
    vals = vals.reshape(*t_mid.grid_shape, n)
    fn = algos.build("allreduce", g_mid, np.float32, "hier",
                     op=ReductionType.SUM)
    out = _run(fn, t_mid, vals)
    np.testing.assert_array_equal(out[t_mid.coords(0)],
                                  np.full(n, 10.0, np.float32))


def test_fingerprint_carries_tiers(tiers24):
    from mlsl_tpu import sysinfo

    fp = sysinfo.topology_fingerprint()
    assert fp["tiers"] == [2, 4]


# -- dense parity ------------------------------------------------------------


@pytest.mark.parametrize("spec", SPLITS)
@pytest.mark.parametrize("kind", ["allreduce", "reduce_scatter"])
def test_dense_parity_bitexact_across_splits(monkeypatch, rng, spec, kind):
    monkeypatch.setenv("MLSL_MESH_TIERS", spec)
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 1000
    kw = {"op": ReductionType.SUM}
    if kind == "reduce_scatter":
        n = -(-n // 8) * 8
        kw["recv_count"] = n // 8
    vals = _int_vals(rng, topo, n)
    base = algos.build(kind, g, np.float32, "lax", **kw)
    fn = algos.build(kind, g, np.float32, "hier", **kw)
    np.testing.assert_array_equal(_run(fn, topo, vals), _run(base, topo, vals))


@pytest.mark.parametrize("dtype", [np.float32, np.int32, "bfloat16"])
def test_dense_parity_dtypes(tiers24, rng, dtype):
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if dtype == "bfloat16" else dtype
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    vals = _int_vals(rng, topo, 256, np.float32).astype(dtype)
    base = algos.build("allreduce", g, vals.dtype, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, vals.dtype, "hier",
                     op=ReductionType.SUM)
    np.testing.assert_array_equal(_run(fn, topo, vals),
                                  _run(base, topo, vals))


def test_dense_parity_subgroup_grid(tiers24, rng):
    """The (4, 2) grid's data groups — 2 instances, (2, 2) tier split each —
    reduce bit-exactly per instance."""
    topo = Topology(4, 2)
    g = ProcessGroup(topo, ("data",))
    vals = _int_vals(rng, topo, 300)
    base = algos.build("allreduce", g, np.float32, "lax",
                       op=ReductionType.SUM)
    fn = algos.build("allreduce", g, np.float32, "hier",
                     op=ReductionType.SUM)
    np.testing.assert_array_equal(_run(fn, topo, vals),
                                  _run(base, topo, vals))


# -- compressed wire ---------------------------------------------------------


def _sentinel_vals(rng, topo, n, block):
    """Identical integer buffers on every member with a +-127 sentinel at
    each block start: every flat-ring hop scale and the hier shared scale
    come out exact integers, so BOTH compressed wires deliver the exact
    integer sum bit-for-bit (see module docstring)."""
    x = rng.integers(-8, 8, size=n).astype(np.float32)
    x[::block] = 127.0
    return np.broadcast_to(x, (*topo.grid_shape, n)).copy()


def _quant_fns(g, n, block, ring):
    return quant_ring.build_quantized_collective("allreduce", g, n, block,
                                                 ring=ring)


@pytest.mark.parametrize("spec", ["2x4", "4x2", "1x8"])
def test_quant_integer_sum_bitexact_vs_flat_ring(monkeypatch, rng, spec):
    """The acceptance pin: bit-exact integer sums across tier splits, hier
    int8 vs the flat quant ring vs the true sum — all three equal."""
    monkeypatch.setenv("MLSL_MESH_TIERS", spec)
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n, block = 1024, 64
    vals = _sentinel_vals(rng, topo, n, block)
    buf = topo.shard_buffer(vals)
    want = vals.sum(axis=(0, 1, 2, 3))

    fh, elh = _quant_fns(g, n, block, "hier")
    ff, elf = _quant_fns(g, n, block, "lax")
    zero = lambda el: topo.shard_buffer(
        np.zeros((*topo.grid_shape, el), np.float32))
    out_h, err_h = jax.block_until_ready(fh(buf, zero(elh)))
    out_f, _ = jax.block_until_ready(ff(buf, zero(elf)))
    got_h = np.asarray(out_h)
    got_f = np.asarray(out_f)
    for p in range(8):
        np.testing.assert_array_equal(got_h[topo.coords(p)], want)
    np.testing.assert_array_equal(got_h, got_f)
    # an exact round leaves zero residual
    assert float(np.abs(np.asarray(err_h)).max()) == 0.0


def test_quant_two_round_ef_lockstep(tiers24, rng):
    """2-round EF-residual lockstep: an independently built twin program
    replays the same inputs to bit-identical outputs AND residuals both
    rounds — the deterministic-state contract snapshot/rewind relies on."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n, block = 700, 64
    fn, el = _quant_fns(g, n, block, "hier")
    body, el2 = hier.quant_body("allreduce", g, n, block)
    twin = collectives.build_stateful_collective(body, topo.mesh)
    assert el == el2
    vals = rng.normal(size=(*topo.grid_shape, n)).astype(np.float32)
    buf = topo.shard_buffer(vals)
    err = topo.shard_buffer(np.zeros((*topo.grid_shape, el), np.float32))
    a_out, a_err = fn(buf, err)
    b_out, b_err = twin(buf, err)
    np.testing.assert_array_equal(np.asarray(a_out), np.asarray(b_out))
    np.testing.assert_array_equal(np.asarray(a_err), np.asarray(b_err))
    a2, a2e = fn(buf, a_err)
    b2, b2e = twin(buf, b_err)
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(b2))
    np.testing.assert_array_equal(np.asarray(a2e), np.asarray(b2e))
    # and the residual is genuinely live: round 2 differs from round 1
    assert not np.array_equal(np.asarray(a_out), np.asarray(a2))


def test_quant_f32_codec_matches_dense(tiers24, rng):
    """MLSL_HIER_DCN_CODEC=f32: no compression anywhere -> the compressed
    wire equals the dense hier program bit-for-bit on integer payloads and
    carries a zero residual."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 512
    vals = _int_vals(rng, topo, n)
    buf = topo.shard_buffer(vals)
    fn, el = quant_ring.build_quantized_collective(
        "allreduce", g, n, 64, ring="hier", dcn_codec="f32")
    dense = algos.build("allreduce", g, np.float32, "hier",
                        op=ReductionType.SUM)
    err = topo.shard_buffer(np.zeros((*topo.grid_shape, el), np.float32))
    out, new_err = fn(buf, err)
    np.testing.assert_array_equal(np.asarray(out), _run(dense, topo, vals))
    assert float(np.abs(np.asarray(new_err)).max()) == 0.0


def test_quant_topk_codec_ef_accumulates(tiers24, rng):
    """top-k DCN codec: the kept coordinates sum exactly; dropped mass rides
    the residual and the time-averaged delivery converges (the EF
    contract)."""
    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 512
    vals = rng.normal(size=(*topo.grid_shape, n)).astype(np.float32)
    buf = topo.shard_buffer(vals)
    want = vals.sum(axis=(0, 1, 2, 3))
    fn, el = quant_ring.build_quantized_collective(
        "allreduce", g, n, 64, ring="hier", dcn_codec="topk",
        topk_ratio=0.25)
    err = topo.shard_buffer(np.zeros((*topo.grid_shape, el), np.float32))
    acc = np.zeros_like(want)
    rounds = 8
    for _ in range(rounds):
        out, err = fn(buf, err)
        acc += np.asarray(out)[topo.coords(0)]
    rel = np.linalg.norm(acc / rounds - want) / (np.linalg.norm(want) + 1e-9)
    assert rel < 0.35, rel  # averaged delivery approaches the true sum


def test_quant_geometry_block_alignment():
    """A114's healthy side: the shard never straddles the block grid and
    always covers the payload."""
    topo = Topology(8, 1)
    os.environ["MLSL_MESH_TIERS"] = "2x4"
    try:
        g = ProcessGroup(topo, ("data",))
        for n in (64, 100, 1000, 4096, 4097):
            for block in (64, 256):
                _, slen, el, (t, l) = hier.quant_geometry(
                    "allreduce", g, n, block)
                assert slen % block == 0
                assert slen * l >= n
                assert el == slen
    finally:
        os.environ.pop("MLSL_MESH_TIERS", None)


# -- selection / request path ------------------------------------------------


def test_request_rides_forced_hier_dense_and_quant(tiers24, env):
    env.config.collective_algo = "hier"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 1000
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    req = CommRequest(
        CommDesc("allreduce", dist.data_group, n, DataType.FLOAT,
                 op=ReductionType.SUM), env.dispatcher)
    req.setup()
    assert req.algo == "hier"
    assert "algo=hier" in req.describe()
    buf = dist.make_buffer(lambda p: np.full(n, float(p + 1), np.float32), n)
    out = req.start(buf).wait()
    np.testing.assert_array_equal(np.asarray(dist.local_part(out, 0)),
                                  np.full(n, 36.0, np.float32))

    rq = CommRequest(
        CommDesc("allreduce", dist.data_group, n, DataType.FLOAT,
                 op=ReductionType.SUM,
                 compression=CompressionType.QUANTIZATION), env.dispatcher)
    rq.setup()
    assert rq.algo == "hier" and rq._err_layout == "hier"
    out = rq.start(buf).wait()
    got = np.asarray(dist.local_part(out, 0))
    want = np.full(n, 36.0, np.float32)
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 0.02, rel


def test_forced_hier_without_tiers_falls_back(monkeypatch, env):
    monkeypatch.delenv("MLSL_MESH_TIERS", raising=False)
    env.config.collective_algo = "hier"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    req = CommRequest(
        CommDesc("allreduce", dist.data_group, 256, DataType.FLOAT,
                 op=ReductionType.SUM), env.dispatcher)
    req.setup()
    assert req.algo == "lax"  # ineligible -> baseline, not an error
    rq = CommRequest(
        CommDesc("allreduce", dist.data_group, 256, DataType.FLOAT,
                 op=ReductionType.SUM,
                 compression=CompressionType.QUANTIZATION), env.dispatcher)
    rq.setup()
    assert rq.algo == "quant_ring"


def test_quant_reduce_scatter_keeps_flat_ring(tiers24, env):
    """The compressed hier wire is allreduce-only: a quantized ZeRO-1
    reduce_scatter keeps the flat ring even under a forced 'hier'."""
    env.config.collective_algo = "hier"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    rq = CommRequest(
        CommDesc("reduce_scatter", dist.data_group, 1024, DataType.FLOAT,
                 op=ReductionType.SUM, recv_count=128,
                 compression=CompressionType.QUANTIZATION), env.dispatcher)
    rq.setup()
    assert rq.algo == "quant_ring"


def test_tuned_profile_cell_selects_hier(tiers24, env):
    from mlsl_tpu.tuner import TunedProfile

    env.config.tuned_profile = TunedProfile(
        fingerprint={}, cells=[
            {"kind": "allreduce", "shape": [8], "compression": "none",
             "max_bytes": None, "algo": "hier"},
            {"kind": "allreduce", "shape": [8],
             "compression": "quantization", "max_bytes": None,
             "algo": "hier"},
        ])
    dist = env.create_distribution(8, 1)
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    for comp in (CompressionType.NONE, CompressionType.QUANTIZATION):
        req = CommRequest(
            CommDesc("allreduce", dist.data_group, 2048, DataType.FLOAT,
                     op=ReductionType.SUM, compression=comp),
            env.dispatcher)
        req.setup()
        assert req.algo == "hier", comp


def test_profile_knob_choices_validated(tmp_path, tiers24):
    from mlsl_tpu.log import MLSLError
    from mlsl_tpu.tuner import TunedProfile, load_profile

    p = TunedProfile(fingerprint={"x": 1}, cells=[],
                     knobs={"hier_dcn_codec": "topk"})
    path = str(tmp_path / "prof.json")
    p.save(path)
    assert load_profile(path).knobs["hier_dcn_codec"] == "topk"
    p.knobs["hier_dcn_codec"] = "fp8"
    p.save(path)
    with pytest.raises(MLSLError, match="hier_dcn_codec"):
        load_profile(path)


def test_chunked_quant_hier_request(tiers24, env):
    """Large-message splitting: independent per-chunk hier programs, each
    with its own shard-layout residual; result allclose to the exact sum."""
    env.config.collective_algo = "hier"
    env.config.large_msg_size_mb = 1
    env.config.large_msg_chunks = 3
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 1 << 19  # 2 MiB > 1 MiB threshold
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    rq = CommRequest(
        CommDesc("allreduce", dist.data_group, n, DataType.FLOAT,
                 op=ReductionType.SUM,
                 compression=CompressionType.QUANTIZATION), env.dispatcher)
    rq.setup()
    assert rq.algo == "hier" and len(rq._chunk_slices) == 3
    rng = np.random.default_rng(5)
    vals = {p: rng.normal(size=n).astype(np.float32) for p in range(8)}
    buf = dist.make_buffer(lambda p: vals[p], n)
    out = rq.start(buf).wait()
    want = sum(vals.values())
    got = np.asarray(dist.local_part(out, 0))
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 0.02, rel


def test_breaker_degrade_flushes_shard_residual_once(tiers24, env):
    """Rung 3 on the hier wire: trip the quant breaker after one compressed
    round; the degraded dispatch must deliver plain-f32 PLUS every member's
    shard residual at its own logical slice — exactly once."""
    from mlsl_tpu import supervisor
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    env.config.collective_algo = "hier"
    env.config.validate()
    dist = env.create_distribution(8, 1)
    n = 1000
    rng = np.random.default_rng(7)
    vals = {p: rng.normal(size=n).astype(np.float32) for p in range(8)}
    buf = dist.make_buffer(lambda p: vals[p], n)
    exact = sum(vals.values())
    rq = CommRequest(
        CommDesc("allreduce", dist.data_group, n, DataType.FLOAT,
                 op=ReductionType.SUM,
                 compression=CompressionType.QUANTIZATION), env.dispatcher)
    rq.setup()
    rq.start(buf).wait()
    err = np.asarray(rq._err)  # round-1 residual, global layout
    supervisor.configure(threshold=1, cooldown_s=3600)
    supervisor.breaker("quant").record_failure(RuntimeError("boom"))
    out = rq.start(buf).wait()
    got = np.asarray(dist.local_part(out, 0))
    # oracle: plain sum + each member's residual at its intra-tier slice
    L, slen = 4, rq._err_len
    topo = dist.topology
    flush = np.zeros(n, np.float64)
    for p in range(8):
        l = dist.data_group.group_idx_of(p) % L
        logical = np.zeros(L * slen, np.float64)
        logical[l * slen:(l + 1) * slen] = err[topo.coords(p)]
        flush += logical[:n]
    want = exact.astype(np.float64) + flush
    np.testing.assert_allclose(got, want, atol=1e-4)
    # the residual was consumed: reset for the next healthy round
    assert rq._err is None


# -- overlap engine ----------------------------------------------------------


def test_overlap_dense_hier_staged_parity(tiers24, rng):
    from mlsl_tpu.comm import overlap
    from mlsl_tpu.config import Config

    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    cfg = Config()
    cfg.validate()
    counts = [300, 512, 128]
    bufs = [topo.shard_buffer(_int_vals(rng, topo, c)) for c in counts]
    exact = [np.asarray(b).sum(axis=(0, 1, 2, 3)) for b in bufs]
    for stages in (1, 3):
        fn, plan = overlap.build_multi_reduce(g, counts, algo="hier",
                                              config=cfg, stages=stages)
        assert all(u.algo == "hier" and u.nphases == 3 for u in plan.units)
        outs = fn(bufs)
        for o, e in zip(outs, exact):
            np.testing.assert_array_equal(np.asarray(o)[0, 0, 0, 0], e)


def test_overlap_quant_hier_staged_bitexact_vs_host(tiers24, rng):
    """Quantized units emitted as staged hier phases are op-for-op the host
    ring='hier' program: outputs AND residuals bit-exact over 2 rounds."""
    from mlsl_tpu.comm import overlap
    from mlsl_tpu.config import Config

    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    cfg = Config()
    cfg.validate()
    block = 64
    counts = [300, 512]
    bufs = [
        topo.shard_buffer(
            rng.normal(size=(*topo.grid_shape, c)).astype(np.float32))
        for c in counts
    ]
    fn, plan = overlap.build_multi_reduce(
        g, counts, compression=CompressionType.QUANTIZATION, algo="hier",
        config=cfg, block=block)
    assert all(u.algo == "hier" and u.nphases == 3 for u in plan.units)
    res = overlap.zero_residuals(plan, topo)
    outs, res = fn(bufs, res)
    outs2, res2 = fn(bufs, res)
    for i, c in enumerate(counts):
        fh, el = _quant_fns(g, c, block, "hier")
        err = topo.shard_buffer(
            np.zeros((*topo.grid_shape, el), np.float32))
        o1, err = fh(bufs[i], err)
        o2, err = fh(bufs[i], err)
        np.testing.assert_array_equal(np.asarray(outs[i]), np.asarray(o1))
        np.testing.assert_array_equal(np.asarray(outs2[i]), np.asarray(o2))


def test_overlap_plan_verifies_hier_units(tiers24, rng):
    """verify_overlap_plan knows the hier residual geometry (A112) and the
    staged retirement of the 3-phase units (A120/A122): green when healthy,
    pinned when tampered."""
    from mlsl_tpu.analysis import plan as plan_mod
    from mlsl_tpu.comm import overlap
    from mlsl_tpu.config import Config

    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    cfg = Config()
    cfg.validate()
    _, plan = overlap.build_multi_reduce(
        g, [512, 256], compression=CompressionType.QUANTIZATION,
        algo="hier", config=cfg, block=64)
    rep = plan_mod.verify_overlap_plan(plan, block=64)
    assert not rep.diagnostics, rep.format()
    plan.units[0].err_len += 64  # tamper
    rep = plan_mod.verify_overlap_plan(plan, block=64)
    assert "MLSL-A112" in rep.codes() and "MLSL-A120" in rep.codes()


# -- plan verifier: A114 + per-tier budget -----------------------------------


def _quant_session(env, count=2048):
    from mlsl_tpu.types import OpType

    dist = env.create_distribution(8, 1)
    s = env.create_session()
    s.set_global_minibatch_size(8)
    r = s.create_operation_reg_info(OpType.CC)
    r.set_name("op0")
    r.add_output(8, 4)
    r.add_parameter_set(count, 1,
                        compression_type=CompressionType.QUANTIZATION)
    s.get_operation(s.add_operation(r, dist))
    s.commit()
    return s


def test_verify_green_on_hier_session(tiers24, env):
    from mlsl_tpu.analysis import plan as plan_mod

    env.config.collective_algo = "hier"
    env.config.validate()
    s = _quant_session(env)
    rep = plan_mod.verify_session(s)
    assert not rep.errors, rep.format()


def test_verify_a114_on_tampered_shard_length(tiers24, env):
    from mlsl_tpu.analysis import plan as plan_mod

    env.config.collective_algo = "hier"
    env.config.validate()
    s = _quant_session(env)
    req = next(
        ps.grad_req for op in s.operations for ps in op.parameter_sets
        if ps.grad_req is not None
    )
    assert req.algo == "hier"
    req._err_len += 7  # off the block grid
    rep = plan_mod.verify_session(s)
    assert "MLSL-A114" in rep.codes(), rep.format()
    assert "MLSL-A112" in rep.codes()


def test_verify_a121_on_missing_hier_meta(tiers24, env):
    from mlsl_tpu.analysis import plan as plan_mod

    env.config.collective_algo = "hier"
    env.config.validate()
    s = _quant_session(env)
    req = next(
        ps.grad_req for op in s.operations for ps in op.parameter_sets
        if ps.grad_req is not None
    )
    req._hier_meta = None
    rep = plan_mod.verify_session(s)
    assert "MLSL-A121" in rep.codes(), rep.format()


def test_spans_tiers_predicate(tiers24):
    from mlsl_tpu.analysis.plan import _spans_tiers
    from mlsl_tpu.comm.mesh import world_tier_ids

    topo = Topology(4, 2)
    tids = world_tier_ids(tuple(topo.mesh.devices.flat))
    assert _spans_tiers(ProcessGroup(topo, ("data",)), tids)
    assert not _spans_tiers(ProcessGroup(topo, ("model",)), tids)
    assert not _spans_tiers(ProcessGroup(topo, ()), tids)


def test_verify_dcn_budget_overcommit(tiers24, env, monkeypatch):
    """The per-tier A102: a graph within the global budget but past the
    DCN-crossing budget is flagged with the two-tier wording."""
    from mlsl_tpu.analysis import plan as plan_mod

    s = _quant_session(env)
    monkeypatch.setattr(plan_mod, "INFLIGHT_BUDGET", {"cpu": 9})
    monkeypatch.setattr(plan_mod, "_dcn_budget", lambda b: 0)
    rep = plan_mod.verify_session(s)
    dcn = [d for d in rep.diagnostics if d.code == "MLSL-A102"
           and "DCN-crossing" in d.message]
    assert dcn, rep.format()


# -- 3D composition: pipeline x ZeRO-1 x MoE through the engine --------------


def test_composition_pipeline_zero1_moe_through_engine(tiers24, rng):
    """The ROADMAP #2 composition: a 2-stage pipeline over 'model' whose
    stages embed an engine-routed MoE layer over 'seq', differentiated with
    jax.grad, the stage grads reduced data-parallel THROUGH the overlap
    engine (pipeline.reduce_microbatch_grads) with the hier lowering, and a
    ZeRO-1-style engine reduce_scatter/all_gather pair — every collective
    in the step rides the selection table, none is a raw lax call."""
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from mlsl_tpu.comm.collectives import smap, _BUF_SPEC
    from mlsl_tpu.config import Config
    from mlsl_tpu.models import moe
    from mlsl_tpu.parallel import pipeline

    topo = Topology(2, 2, seq_parts=2)  # (R=1, D=2, S=2, M=2) on 8 devices
    mesh = topo.mesh
    cfg = Config()
    cfg.validate()

    S, EP, M_CNT, MB, D = 2, 2, 4, 4, 8
    w_stage = rng.normal(size=(S, D, D)).astype(np.float32) * 0.3
    moe_params = moe.init_moe_params(jax.random.PRNGKey(0), D, 16, 2)
    # per-data-rank microbatches (the DP dimension the reduction closes)
    x_all = rng.normal(size=(2, M_CNT, MB, D)).astype(np.float32)
    y_all = rng.normal(size=(2, M_CNT, MB, D)).astype(np.float32)

    def stage_fn(sp, x):
        h = jnp.tanh(x @ sp)
        # this rank's expert shard: El = E/ep experts per seq rank
        si = lax.axis_index("seq")
        local = {
            "wg": moe_params["wg"],
            "w1": lax.dynamic_slice_in_dim(moe_params["w1"], si, 1, axis=0),
            "w2": lax.dynamic_slice_in_dim(moe_params["w2"], si, 1, axis=0),
        }
        m, _aux = moe.moe_ffn(h.reshape(-1, D), local, "seq", EP)
        return h + m.reshape(h.shape)

    def loss_head(y, t):
        return jnp.mean((y - t) ** 2)

    def body():
        def f(w):
            di = lax.axis_index("data")
            x = lax.dynamic_index_in_dim(jnp.asarray(x_all), di, 0,
                                         keepdims=False)
            y = lax.dynamic_index_in_dim(jnp.asarray(y_all), di, 0,
                                         keepdims=False)
            me = lax.axis_index("model")
            sp = lax.dynamic_index_in_dim(w, me, 0, keepdims=False)
            return pipeline.pipeline_loss(
                stage_fn, loss_head, sp, x, y, "model", S)

        loss, gw = jax.value_and_grad(f)(jnp.asarray(w_stage))
        me = lax.axis_index("model")
        g_mine = lax.dynamic_index_in_dim(gw, me, 0, keepdims=False)
        return (loss[None, None, None, None, None],
                g_mine.reshape(-1)[None, None, None, None])

    fn = jax.jit(smap(body, mesh, in_specs=(),
                      out_specs=(_BUF_SPEC, _BUF_SPEC)))
    loss_buf, grads_buf = fn()
    assert np.isfinite(np.asarray(loss_buf)).all()

    # DP reduction of the per-stage grads through the overlap engine, hier
    dp = ProcessGroup(topo, ("data",))
    assert hier.tier_structure(dp) is not None
    n = D * D
    red_fn, plan = pipeline.reduce_microbatch_grads(
        dp, [n], config=cfg, algo="hier")
    assert plan.units[0].algo == "hier"
    reduced = red_fn([grads_buf])[0]
    base = algos.build("allreduce", dp, np.float32, "lax",
                       op=ReductionType.SUM)
    want = np.asarray(jax.block_until_ready(base(grads_buf)))
    np.testing.assert_allclose(np.asarray(reduced), want, rtol=1e-5,
                               atol=1e-6)

    # ZeRO-1 phases through the engine table: reduce_scatter the grads over
    # data, update the owned shard, all_gather the increments back
    rs = algos.build("reduce_scatter", dp, np.float32,
                     algos.select("reduce_scatter", dp, n * 4,
                                  CompressionType.NONE, cfg,
                                  op=ReductionType.SUM),
                     op=ReductionType.SUM, recv_count=n // 2)
    shard = rs(grads_buf)
    inc = jax.jit(lambda v: -0.1 * v)(shard)
    ag = algos.build("allgather", dp, np.float32, "lax")
    full_inc = np.asarray(jax.block_until_ready(ag(inc)))
    np.testing.assert_allclose(
        full_inc[topo.coords(0)], -0.1 * want[topo.coords(0)],
        rtol=1e-5, atol=1e-6)
