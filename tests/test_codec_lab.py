"""Codec lab (mlsl_tpu.codecs + tuner/calibrate.py): registry contract,
codec x transport parity matrix, EF lockstep against the pre-registry
oracles, the calibration round trip, and the sentinel-fed guardrail
demotion.

Parity convention (test_algos/test_hier): integer payloads pin lossless
codecs (f32, prune/topk at keep-ratio 1.0) BIT-FOR-BIT against the dense
sum; the VQ wire is pinned bit-exact on a dyadic-codebook construction
(identical member buffers whose vectors are codebook rows with dyadic
entries and per-chunk max-abs 1, so every ring partial re-encodes exactly);
genuinely lossy settings (int8, default-codebook VQ) get the quantized
tolerance contract. The EF lockstep tests pin the registry routes
bit-identical to the pre-registry front doors they subsume: the topk route
against sparse.build_sparse_collective, the compressed-ring route against a
user-plugged QuantParams codec carrying the same encode/decode."""

import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mlsl_tpu import codecs, supervisor
from mlsl_tpu.core import stats
from mlsl_tpu.log import MLSLError
from mlsl_tpu.types import (
    CompressionType, DataType, GroupType, OpType, QuantParams, ReductionType,
)


# -- harness -----------------------------------------------------------------


def _req(env, dist, n, *, name="", kind="allreduce", recv_count=None):
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    req = CommRequest(
        CommDesc(
            kind, dist._group(GroupType.DATA), n, DataType.FLOAT,
            op=ReductionType.SUM, recv_count=recv_count,
            compression=CompressionType.QUANTIZATION,
        ),
        env.dispatcher,
        name=name,
    )
    req.setup()
    return req


def _round(dist, req, vals, n):
    buf = dist.make_buffer(lambda p: vals[p], n)
    req.start(buf)
    return np.asarray(dist.local_part(req.wait(), 0))


def _int_vals(n, seed=0):
    rng = np.random.default_rng(seed)
    return {p: rng.integers(-8, 8, size=n).astype(np.float32) for p in range(8)}


def _normal_vals(n, seed=0):
    rng = np.random.default_rng(seed)
    return {p: rng.normal(size=n).astype(np.float32) for p in range(8)}


# dyadic codebook for the bit-exact VQ construction: every entry is a small
# dyadic rational (exact under f32 add/scale by integers <= 8), each nonzero
# row carries a +-1 so any chunk tiled from them has max-abs exactly 1, and
# row 0 is the zero row per the codec's sparse contract
DYADIC_CB = [
    [0.0, 0.0, 0.0, 0.0],
    [1.0, 0.5, 0.25, -0.5],
    [0.5, -1.0, 0.25, -0.25],
    [-0.5, 0.25, -1.0, 1.0],
]


def _dyadic_vq_vals(n):
    """Identical member buffers tiled from the nonzero dyadic codebook rows:
    every ring partial is an exact small-integer multiple of the buffer, so
    encode normalizes back onto codebook rows exactly."""
    assert n % 4 == 0
    rows = np.asarray(DYADIC_CB, np.float32)[1:]
    x = np.tile(rows, (n // 4 // 3 + 1, 1)).reshape(-1)[:n].astype(np.float32)
    return {p: x for p in range(8)}, x


# -- registry contract -------------------------------------------------------


def test_registry_names_and_instance_caching():
    assert {"int8", "f32", "topk", "vq", "prune"} <= set(codecs.names())
    a = codecs.get("prune", ratio=0.25)
    assert codecs.get("prune", ratio=0.25) is a       # knob-keyed cache
    assert codecs.get("prune", ratio=0.5) is not a
    with pytest.raises(MLSLError, match="unknown codec"):
        codecs.get("fp4")


def test_configure_precedence_cell_config_default():
    from mlsl_tpu.config import Config

    cfg = Config()
    cfg.prune_ratio = 0.5
    cell = {"codec": "prune", "params": {"ratio": 0.25}}
    assert codecs.configure("prune", cfg, cell).ratio == 0.25   # cell wins
    assert codecs.configure("prune", cfg).ratio == 0.5          # then config
    assert codecs.configure("prune").ratio == 0.05              # then default
    assert codecs.configure("int8", cfg, {"codec": "int8", "block": 512}
                            ).block == 512


@pytest.mark.parametrize("name", ["int8", "f32", "topk", "vq", "prune"])
def test_wire_len_matches_encode_and_geometry(name):
    codec = codecs.get(name)
    n = 1000  # off the block/vector grid: padding paths engage
    x = jnp.asarray(np.random.default_rng(3).normal(size=n).astype(np.float32))
    wire = codec.encode(x)
    assert wire.dtype == jnp.uint8
    assert int(wire.shape[0]) == codec.wire_len(n)
    g = codec.geometry(n)
    assert g["codec"] == name and g["chunk"] == n
    assert g["wire_len"] == codec.wire_len(n)
    xhat = codec.decode(wire, n)
    assert xhat.shape == (n,) and bool(jnp.all(jnp.isfinite(xhat)))


@pytest.mark.parametrize("name", ["int8", "f32", "topk", "vq", "prune"])
def test_wire_row_on_the_calibration_sample(name):
    """What calibration weighs for one codec: its wire image against float32
    and the noise-to-signal it measures on the standard sample (made from
    the name alone, so a row can be had again). f32 is the identity row; the
    uniform int8 wire sits far inside the default budget; the sparse codecs
    are cheaper than int8 and pay for it in noise."""
    from mlsl_tpu.config import Config
    from mlsl_tpu.tuner import calibrate

    n = 65536
    x = calibrate.gradient_sample(f"row/{n}", n)
    np.testing.assert_array_equal(x, calibrate.gradient_sample(f"row/{n}", n))
    codec = codecs.get(name)
    wire, nsr = codec.wire_len(n), calibrate.measure_nsr(codec, x)
    budget = Config().codec_nsr_budget
    if name == "f32":
        assert wire == 4 * n and nsr == 0.0
    elif name == "int8":
        assert wire < 0.26 * 4 * n
        assert 0.0 < nsr < budget / 10
    else:
        assert 0 < wire < codecs.get("int8").wire_len(n)
        assert np.isfinite(nsr) and nsr > budget


def test_lossless_codecs_roundtrip_exactly():
    n = 768
    x = jnp.asarray(
        np.random.default_rng(4).integers(-8, 8, size=n).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(codecs.get("f32").decode(codecs.get("f32").encode(x), n)),
        np.asarray(x))
    keep_all = codecs.get("prune", ratio=1.0)
    np.testing.assert_array_equal(
        np.asarray(keep_all.decode(keep_all.encode(x), n)), np.asarray(x))


def test_assigned_precedence_env_calibrated_config_default():
    from mlsl_tpu.config import Config

    cfg = Config()
    assert codecs.assigned(cfg, "g")[::2] == ("int8", "default")
    cfg.codec = "vq"
    assert codecs.assigned(cfg, "g")[::2] == ("vq", "config")
    cell = {"codec": "prune", "params": {"ratio": 0.1}}
    cfg.codec_assignment = {"g": cell}
    name, got_cell, src = codecs.assigned(cfg, "g")
    assert (name, src) == ("prune", "calibrated") and got_cell is cell
    assert codecs.assigned(cfg, "other")[::2] == ("vq", "config")
    cfg._explicit = ("codec",)  # exported MLSL_CODEC pins every set
    assert codecs.assigned(cfg, "g")[::2] == ("vq", "env")


# -- parity matrix: codec x {plain ring, ZeRO-1, chunked, hier, bucketed} ----


@pytest.mark.parametrize("name,algo", [
    ("f32", "codec:f32"), ("prune", "codec:prune"), ("topk", "topk"),
])
def test_plain_ring_exact_sum_lossless(env, name, algo):
    """Lossless settings (keep-ratio 1.0 / f32) through the registry-routed
    compressed ring: bit-exact integer sums."""
    n = 1024
    env.config.codec = name
    env.config.prune_ratio = 1.0
    env.config.topk_ratio = 1.0
    dist = env.create_distribution(8, 1)
    vals = _int_vals(n)
    req = _req(env, dist, n)
    assert req.algo == algo and req.codec_name == name
    assert req.codec_source == "config"
    out = _round(dist, req, vals, n)
    np.testing.assert_array_equal(out, sum(vals[p] for p in range(8)))
    # and the lossless wire leaves a virgin residual
    assert float(np.abs(np.asarray(req._err)).max()) == 0.0


def test_plain_ring_tolerance_int8(env):
    """The seed int8 wire selected BY NAME through the registry still meets
    the quantized tolerance contract (and still rides quant_ring — the
    registry adds no indirection to the proven path)."""
    n = 2048
    env.config.codec = "int8"
    dist = env.create_distribution(8, 1)
    vals = _normal_vals(n, seed=1)
    req = _req(env, dist, n)
    assert req.algo == "quant_ring" and req.codec_name == "int8"
    out = _round(dist, req, vals, n)
    exact = sum(vals[p] for p in range(8))
    rel = np.linalg.norm(out - exact) / np.linalg.norm(exact)
    assert rel < 0.02, rel
    # error feedback is live: the residual carries the dropped mass
    assert float(np.abs(np.asarray(req._err)).max()) > 0.0


def test_vq_learned_codebook_reduces_nsr():
    """The calibration-time Lloyd fit (codecs/vq.py learn_codebook):
    deterministic, and a bigger codebook strictly sharpens the round trip
    on the data it was fit to — the knob the solver spends bytes on."""
    from mlsl_tpu.codecs import vq as vq_mod

    n = 2048
    x = np.random.default_rng(1).normal(size=n).astype(np.float32)
    xj = jnp.asarray(x)
    sig = float(np.sum(x ** 2))

    def nsr(k):
        cb = vq_mod.learn_codebook(x, k=k, dim=4)
        np.testing.assert_array_equal(cb, vq_mod.learn_codebook(x, k=k, dim=4))
        codec = codecs.get("vq", dim=4, k=k, codebook=cb)
        xhat = np.asarray(codec.decode(codec.encode(xj), n))
        return float(np.sum((xhat - x) ** 2)) / sig

    n16, n64, n256 = nsr(16), nsr(64), nsr(256)
    assert n256 < n64 < n16 < 1.0, (n16, n64, n256)


def test_vq_dyadic_construction_is_bit_exact(env):
    """The VQ pinning construction (codecs/vq.py docstring): identical member
    buffers of dyadic codebook rows -> every partial sum is an exact integer
    multiple, encode re-normalizes onto the codebook exactly, and the ring
    delivers the bit-exact sum with a zero residual."""
    n = 512
    env.config.codec_assignment = {
        "vqx": {"codec": "vq",
                "params": {"vq_dim": 4, "vq_codebook": 4,
                           "codebook": DYADIC_CB}},
    }
    dist = env.create_distribution(8, 1)
    vals, x = _dyadic_vq_vals(n)
    req = _req(env, dist, n, name="vqx")
    assert req.algo == "codec:vq" and req.codec_source == "calibrated"
    out = _round(dist, req, vals, n)
    np.testing.assert_array_equal(out, 8.0 * x)
    assert float(np.abs(np.asarray(req._err)).max()) == 0.0


@pytest.mark.parametrize("name", ["f32", "prune"])
def test_zero1_reduce_scatter_exact_shards(env, name):
    """The ZeRO-1 gradient phase (reduce_scatter) through the registry route:
    every rank's shard is the bit-exact integer sum slice (MPI placement)."""
    n_owned = 256
    n = n_owned * 8
    env.config.codec = name
    env.config.prune_ratio = 1.0
    dist = env.create_distribution(8, 1)
    vals = _int_vals(n, seed=5)
    req = _req(env, dist, n, kind="reduce_scatter", recv_count=n_owned)
    assert req.algo == f"codec:{name}"
    buf = dist.make_buffer(lambda p: vals[p], n)
    req.start(buf)
    out = req.wait()
    exact = sum(vals[p] for p in range(8))
    for p in range(8):
        np.testing.assert_array_equal(
            np.asarray(dist.local_part(out, p)),
            exact[p * n_owned:(p + 1) * n_owned])


def test_chunked_allreduce_exact_through_registry(env):
    """Large-message chunking composed with a registry codec: independent
    per-chunk compressed rings with per-chunk residuals, still bit-exact on
    the lossless construction."""
    env.config.large_msg_size_mb = 1
    env.config.large_msg_chunks = 4
    env.config.codec = "prune"
    env.config.prune_ratio = 1.0
    n = 1024 * 1024  # 4 MiB fp32 > 1 MiB threshold
    dist = env.create_distribution(8, 1)
    vals = _int_vals(n, seed=6)
    req = _req(env, dist, n)
    assert req.algo == "codec:prune" and len(req._chunk_slices) == 4
    assert len(req._codec_geoms) == 4  # per-chunk geometry pinned (A116)
    out = _round(dist, req, vals, n)
    np.testing.assert_array_equal(out, sum(vals[p] for p in range(8)))


@pytest.mark.parametrize("name", ["vq", "prune"])
def test_hier_dcn_hop_through_registry(name, monkeypatch):
    """The generalized DCN hop (comm/algos/hier.py): a registry codec on the
    inter-tier wire. Keep-ratio 1.0 prune is lossless; VQ carries its error
    into the residual — both must stay within the EF contract on a 2x4
    split, with knobs reaching the hop through Config.from_env."""
    monkeypatch.setenv("MLSL_MESH_TIERS", "2x4")
    monkeypatch.setenv("MLSL_PRUNE_RATIO", "1.0")
    # VQ knobs must reach the hop through Config.from_env: a dim-2 k=256
    # codebook is fine enough for the averaged-delivery bound below
    monkeypatch.setenv("MLSL_VQ_DIM", "2")
    monkeypatch.setenv("MLSL_VQ_CODEBOOK", "256")
    from mlsl_tpu.comm import quant_ring
    from mlsl_tpu.comm.mesh import ProcessGroup, Topology

    topo = Topology(8, 1)
    g = ProcessGroup(topo, ("data",))
    n = 512
    rng = np.random.default_rng(11)
    # shared-sentinel construction (test_hier): identical member buffers with
    # a per-block +-127 sentinel keep the intra-tier int8 hop exact, so the
    # DCN codec is the only lossy stage under test
    base = rng.integers(-8, 8, size=n).astype(np.float32)
    base[::64] = 127.0
    vals = np.broadcast_to(base, (*topo.grid_shape, n)).copy()
    buf = topo.shard_buffer(vals)
    fn, el = quant_ring.build_quantized_collective(
        "allreduce", g, n, 64, ring="hier", dcn_codec=name)
    err = topo.shard_buffer(np.zeros((*topo.grid_shape, el), np.float32))
    want = vals.sum(axis=(0, 1, 2, 3))
    acc = np.zeros_like(want)
    rounds = 1 if name == "prune" else 8
    for _ in range(rounds):
        out, err = fn(buf, err)
        acc += np.asarray(out)[topo.coords(0)]
    if name == "prune":  # keep-all: bit-exact, zero residual
        np.testing.assert_array_equal(np.asarray(out)[topo.coords(0)], want)
        assert float(np.abs(np.asarray(err)).max()) == 0.0
    else:  # VQ: time-averaged delivery converges (the EF contract)
        rel = np.linalg.norm(acc / rounds - want) / (np.linalg.norm(want) + 1e-9)
        assert rel < 0.15, rel


# -- bucketing: per-set codec partitions -------------------------------------


def _codec_session(env, counts, bucket_mb=4, names=None):
    env.config.grad_bucket_mb = bucket_mb
    dist = env.create_distribution(8, 1)
    s = env.create_session()
    s.set_global_minibatch_size(8)
    ops = []
    for i, c in enumerate(counts):
        r = s.create_operation_reg_info(OpType.CC)
        if names:
            r.set_name(names[i])
        r.add_input(8, 4)
        r.add_output(8, 4)
        r.add_parameter_set(c, 1,
                            compression_type=CompressionType.QUANTIZATION)
        ops.append(s.get_operation(s.add_operation(r, dist)))
    s.commit()
    env.config.grad_bucket_mb = 0
    return dist, s, [op.get_parameter_set(0) for op in ops]


def test_bucketed_codec_exact_sum(env):
    """Two sets sharing one registry codec coalesce into ONE compressed
    bucket whose ring runs the codec route, and the members' results are the
    bit-exact integer sums."""
    env.config.codec = "prune"
    env.config.prune_ratio = 1.0
    counts = [512, 768]
    dist, s, pss = _codec_session(env, counts)
    assert pss[0].bucket is not None and pss[0].bucket is pss[1].bucket
    breq = pss[0].bucket.req
    assert breq.algo == "codec:prune" and breq.codec_name == "prune"
    vals = [_int_vals(c, seed=7 + i) for i, c in enumerate(counts)]
    for ps, c, v in zip(pss, counts, vals):
        ps.start_gradient_comm(dist.make_buffer(lambda p, v=v: v[p], c))
    for ps, c, v in zip(pss, counts, vals):
        out = ps.wait_gradient_comm()
        np.testing.assert_array_equal(
            np.asarray(dist.local_part(out, 0)),
            sum(v[p] for p in range(8)))


def test_mixed_codec_buckets_stay_split(env):
    """Per-set calibrated assignments with DIFFERENT codecs must not share a
    bucket (the 4-tuple partition key): one compressed ring has ONE wire
    format."""
    env.config.codec_assignment = {
        "a/grad0": {"codec": "prune", "params": {"ratio": 1.0}},
        "b/grad0": {"codec": "f32", "params": {}},
    }
    dist, s, pss = _codec_session(env, [512, 512], names=["a", "b"])
    assert pss[0].grad_req.codec_name == "prune"
    assert pss[1].grad_req.codec_name == "f32"
    b0, b1 = pss[0].bucket, pss[1].bucket
    assert b0 is None or b1 is None or b0 is not b1
    # and a solo member still runs its own codec route
    for ps, want in zip(pss, ["codec:prune", "codec:f32"]):
        req = ps.bucket.req if ps.bucket is not None else ps.grad_req
        assert req.algo == want


# -- EF lockstep vs the pre-registry oracles ---------------------------------


def test_topk_registry_matches_sparse_oracle(env):
    """MLSL_CODEC=topk routes into the seed sparsifier: two rounds in
    lockstep with a hand-built sparse collective must be bit-identical in
    BOTH the delivered sums and the carried residuals."""
    from mlsl_tpu.comm import sparse

    n = 1024
    env.config.codec = "topk"
    env.config.topk_ratio = 0.1
    dist = env.create_distribution(8, 1)
    req = _req(env, dist, n)
    assert req.algo == "topk" and req.codec_name == "topk"

    fn, el = sparse.build_sparse_collective(
        "allreduce", dist._group(GroupType.DATA), n, 0.1)
    topo = dist._group(GroupType.DATA).topology
    err = topo.shard_buffer(np.zeros((*topo.grid_shape, el), np.float32))
    for r in range(2):
        vals = _normal_vals(n, seed=20 + r)
        out = _round(dist, req, vals, n)
        buf = dist.make_buffer(lambda p: vals[p], n)
        want, err = fn(buf, err)
        np.testing.assert_array_equal(
            out, np.asarray(dist.local_part(want, 0)))
        np.testing.assert_array_equal(
            np.asarray(req._err), np.asarray(err))


def test_registry_ring_matches_custom_codec_oracle(env):
    """The registry's compressed-ring transport IS the dlopen-era custom
    path: a request routed through codec:vq must run bit-identically to the
    same encode/decode plugged through set_quantization_params — outputs AND
    error-feedback residuals, two rounds in lockstep."""
    n = 768
    vq = codecs.get("vq")  # default deterministic codebook
    env.config.codec = "vq"
    dist = env.create_distribution(8, 1)
    reg_req = _req(env, dist, n, name="reg")
    assert reg_req.algo == "codec:vq"

    env.set_quantization_params(QuantParams(
        compress_fn=vq.encode,
        decompress_fn=lambda p, m: vq.decode(p, m),
    ))
    oracle_req = _req(env, dist, n, name="oracle")
    assert oracle_req.algo == "custom_codec"
    for r in range(2):
        vals = _normal_vals(n, seed=30 + r)
        got = _round(dist, reg_req, vals, n)
        want = _round(dist, oracle_req, vals, n)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(
            np.asarray(reg_req._err), np.asarray(oracle_req._err))


# -- calibration round trip --------------------------------------------------


def _calib_session(e, names=("small", "wide"), counts=(2048, 32768)):
    dist = e.create_distribution(8, 1)
    s = e.create_session()
    s.set_global_minibatch_size(8)
    pss = []
    for name, c in zip(names, counts):
        r = s.create_operation_reg_info(OpType.CC)
        r.set_name(name)
        r.add_output(8, 4)
        r.add_parameter_set(c, 1,
                            compression_type=CompressionType.QUANTIZATION)
        pss.append(s.get_operation(s.add_operation(r, dist))
                   .get_parameter_set(0))
    s.commit()
    return s, pss


def test_calibration_assigns_persists_and_fresh_env_honors(tmp_path,
                                                           monkeypatch):
    """The acceptance round trip (docs/TUNING.md §22): MLSL_TUNE_CODEC=1
    calibrates at commit, re-routes the live requests, and persists the
    per-set table into the topology-keyed profile; a FRESH environment
    loading that profile reproduces the assignment on a new session without
    re-calibrating."""
    from mlsl_tpu.core.environment import Environment

    path = str(tmp_path / "tuned.json")
    monkeypatch.setenv("MLSL_TUNE_CODEC", "1")
    monkeypatch.setenv("MLSL_TUNE_PROFILE", path)
    e = Environment.get_env().init()
    _, pss = _calib_session(e)
    live = {ps.grad_req.name: ps.grad_req for ps in pss}
    assert all(r.codec_source == "calibrated" for r in live.values())
    recorded = {k: v["codec"] for k, v in e.config.codec_assignment.items()}
    assert set(recorded) == set(live)
    for name, req in live.items():
        assert req.codec_name == recorded[name]
    # the wide sparse set must calibrate CHEAPER than the uniform seed wire
    wide = live["wide/grad0"]
    assert wide._wire_rec[1] < codecs.get("int8").wire_len(32768)
    with open(path) as f:
        doc = json.load(f)
    assert set(doc["codecs"]) == set(recorded)
    assert stats.CODEC_COUNTERS["assignments"] >= 2
    e.finalize()

    monkeypatch.delenv("MLSL_TUNE_CODEC")
    e = Environment.get_env().init()
    try:
        assert not getattr(e.config, "tune_codec", False)
        assert {k: v["codec"] for k, v in e.config.codec_assignment.items()
                } == recorded
        _, pss = _calib_session(e)
        for ps in pss:
            req = ps.grad_req
            assert req.codec_source == "calibrated"
            assert req.codec_name == recorded[req.name]
    finally:
        e.finalize()


def test_calibrated_stream_is_cheaper_than_int8_and_inside_the_budget(env):
    """Calibration's two promises on a ragged stream (tensor sizes of a
    ResNet-50, divided by 16): the per-set assignment carries
    fewer wire bytes a round than the uniform int8 wire it starts from, and
    no cell's measured noise-to-signal exceeds the budget it was given."""
    from conftest import resnet50_counts

    # every fourth distinct size: 256, 2,304, 16,384 and 128,000 elements
    counts = sorted(set(resnet50_counts(scale=16, floor=256)))[::4]

    def wire_bytes(tune):
        env.config.tune_codec = tune
        env.config.codec_assignment = {}
        _, pss = _calib_session(env, [f"t{c}" for c in counts], counts)
        env.config.tune_codec = False
        # each request pins its per-round compressed image at setup
        return sum(int(ps.grad_req._wire_rec[1]) for ps in pss)

    uniform = wire_bytes(False)
    calibrated = wire_bytes(True)
    cells = env.config.codec_assignment
    assert len(cells) == len(counts)
    assert calibrated < uniform
    assert max(c["nsr"] for c in cells.values()) <= env.config.codec_nsr_budget


def test_stale_codec_profile_rejected(tmp_path, monkeypatch, capfd):
    """A codec table measured on different hardware must NOT reach a live
    session: the fingerprint gate rejects the whole profile with a
    warning."""
    from mlsl_tpu.core.environment import Environment
    from mlsl_tpu.tuner.profile import PROFILE_VERSION

    path = str(tmp_path / "stale.json")
    with open(path, "w") as f:
        json.dump({
            "version": PROFILE_VERSION,
            "fingerprint": {"platform": "tpu", "device_kind": "TPU v9",
                            "num_devices": 4096, "num_hosts": 512},
            "cells": [],
            "codecs": {"wide/grad0": {"codec": "prune",
                                      "params": {"ratio": 0.05}}},
        }, f)
    monkeypatch.setenv("MLSL_TUNE_PROFILE", path)
    e = Environment.get_env().init()
    try:
        assert e.config.tuned_profile is None
        assert not getattr(e.config, "codec_assignment", {})
        assert "different topology" in capfd.readouterr().err
        _, pss = _calib_session(e)
        assert all(ps.grad_req.codec_source == "default" for ps in pss)
    finally:
        e.finalize()


def test_profile_with_unknown_codec_rejected(tmp_path):
    from mlsl_tpu import sysinfo
    from mlsl_tpu.tuner.profile import PROFILE_VERSION, load_profile

    path = str(tmp_path / "bad.json")
    with open(path, "w") as f:
        json.dump({
            "version": PROFILE_VERSION,
            "fingerprint": sysinfo.topology_fingerprint(),
            "cells": [],
            "codecs": {"g": {"codec": "fp4"}},
        }, f)
    with pytest.raises(MLSLError, match="codec"):
        load_profile(path)


def test_explicit_codec_blocks_calibrated_assignment(env):
    """Exported MLSL_CODEC wins over a calibrated table on every set (the
    operator's override contract)."""
    env.config.codec = "int8"
    env.config._explicit = ("codec",)
    env.config.codec_assignment = {
        "g": {"codec": "prune", "params": {"ratio": 0.05}}}
    dist = env.create_distribution(8, 1)
    req = _req(env, dist, 512, name="g")
    assert req.codec_name == "int8" and req.codec_source == "env"


# -- guardrail: sentinel loss screen -> int8 demotion ------------------------


def _calibrated_prune_req(env, dist, n, ratio=0.25, name="g"):
    env.config.codec_assignment = {
        name: {"codec": "prune", "params": {"ratio": ratio}}}
    return _req(env, dist, n, name=name)


def test_guard_demotes_after_window_with_exactly_once_flush(env):
    """The online guardrail: ``window`` consecutive loss z-score breaches
    demote every calibrated set to int8 in one rung. The demoted codec's EF
    residual is folded into the next round exactly once, and from then on
    the request is bit-for-bit a fresh int8 request in lockstep."""
    n = 1024
    dist = env.create_distribution(8, 1)
    req = _calibrated_prune_req(env, dist, n)
    assert req.codec_source == "calibrated" and codecs.guard_active()

    vals1 = _normal_vals(n, seed=40)
    _round(dist, req, vals1, n)  # round 1: prune wire, residual accrues

    # two breaches + a healthy step: the streak resets, nothing demotes
    assert not codecs.guard_note(True, window=3)
    assert not codecs.guard_note(True, window=3)
    codecs.guard_note(False, window=3)
    assert not req._codec_demoted
    # three consecutive breaches: the demotion fires
    assert not codecs.guard_note(True, window=3, step=7)
    assert not codecs.guard_note(True, window=3, step=8)
    assert codecs.guard_note(True, window=3, step=9)
    assert req._codec_demoted and req.codec_name == "int8"
    assert req.codec_source == "demoted" and req.algo == "quant_ring"
    assert not codecs.guard_active()
    assert stats.CODEC_COUNTERS["demotions"] == 1
    assert any("codec:prune -> int8" in d for d in stats.CODEC_DEMOTIONS)

    # the captured residual: entry EF of round 1 = x - prune(x) per chunk
    prune = codecs.get("prune", ratio=0.25)
    chunk = n // 8

    def residual(x):
        parts = [x[j * chunk:(j + 1) * chunk] for j in range(8)]
        return np.concatenate([
            p - np.asarray(prune.decode(prune.encode(jnp.asarray(p)), chunk))
            for p in parts])

    # round 2 (flush round) and round 3 must run in bit-exact lockstep with
    # a fresh int8 request fed the flushed payload explicitly
    oracle = _req(env, dist, n, name="oracle_int8")
    assert oracle.codec_name == "int8" and oracle.algo == "quant_ring"
    vals2 = _normal_vals(n, seed=41)
    flushed = {p: vals2[p] + residual(vals1[p]) for p in range(8)}
    np.testing.assert_array_equal(
        _round(dist, req, vals2, n), _round(dist, oracle, flushed, n))
    assert req._pending_flush is None  # consumed exactly once
    vals3 = _normal_vals(n, seed=42)
    np.testing.assert_array_equal(
        _round(dist, req, vals3, n), _round(dist, oracle, vals3, n))
    np.testing.assert_array_equal(
        np.asarray(req._err), np.asarray(oracle._err))


def test_demotion_before_first_round_is_plain_int8(env):
    """Demoting a virgin request (no residual yet) must leave zero trace:
    the first round after demotion is bit-identical to a fresh int8 ring."""
    n = 512
    dist = env.create_distribution(8, 1)
    req = _calibrated_prune_req(env, dist, n)
    req.demote_codec("test")
    oracle = _req(env, dist, n, name="oracle")
    vals = _normal_vals(n, seed=50)
    np.testing.assert_array_equal(
        _round(dist, req, vals, n), _round(dist, oracle, vals, n))


def test_sentinel_gate_feeds_guardrail(monkeypatch):
    """End to end through the sentinel: a pinned loss-EMA makes every
    screened step a z-score outlier; after ``codec_guard_breaches``
    consecutive screens the calibrated request demotes — within one screen
    window, no training-loop plumbing required."""
    from mlsl_tpu.core.environment import Environment

    monkeypatch.setenv("MLSL_SENTINEL_GATE", "warn")
    monkeypatch.setenv("MLSL_SENTINEL_WARMUP", "1")
    monkeypatch.setenv("MLSL_SENTINEL_ZMAX", "3")
    monkeypatch.setenv("MLSL_CODEC_GUARD_BREACHES", "2")
    e = Environment.get_env().init()
    from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
    from mlsl_tpu.models.train import DataParallelTrainer

    dist = e.create_distribution(8, 1)
    sess = e.create_session()
    sess.set_global_minibatch_size(16)
    tr = DataParallelTrainer(
        e, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
        get_layer, lr=0.1)

    n = 512
    req = _calibrated_prune_req(e, dist, n, name="guarded")
    assert codecs.guard_active()

    def batch(step):
        rng = np.random.default_rng(step)
        return (rng.normal(size=(16, 8)).astype(np.float32),
                rng.integers(0, 4, size=(16,)).astype(np.int32))

    tr.step(tr.shard_batch(*batch(0)))  # warmup: EMA seeds
    tr.sentinel._loss_mean = 1e6        # every later loss is an outlier
    tr.sentinel._loss_var = 1.0
    tr.step(tr.shard_batch(*batch(1)))
    assert not req._codec_demoted       # one breach < window of 2
    tr.sentinel._loss_mean = 1e6
    tr.sentinel._loss_var = 1.0
    tr.step(tr.shard_batch(*batch(2)))
    assert req._codec_demoted and req.codec_name == "int8"


def test_supervisor_status_codecs_section(env):
    """supervisor.status()['codecs'] is the JSON-serializable codec-lab
    health block: registry names, guarded sets, counters, wire bytes."""
    dist = env.create_distribution(8, 1)
    req = _calibrated_prune_req(env, dist, 512)
    _round(dist, req, _normal_vals(512, seed=60), 512)
    st = supervisor.status()["codecs"]
    json.dumps(st)  # serializable end to end
    assert set(st["registered"]) >= {"int8", "f32", "topk", "vq", "prune"}
    assert "g" in st["guarded"]
    assert st["wire_bytes"].get("prune", 0) > 0
