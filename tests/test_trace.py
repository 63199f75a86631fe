"""Comm timeline tracer (mlsl_tpu.obs): span lifecycle through the real
request paths, the disabled-path zero-allocation contract, ring wraparound,
Perfetto export validity, and the watchdog flight recorder."""

import json
import os
import time
import tracemalloc

import numpy as np
import pytest

from mlsl_tpu import chaos, obs
from mlsl_tpu.log import MLSLTimeoutError
from mlsl_tpu.obs import tracer as tracer_mod
from mlsl_tpu.obs.tracer import ARGS, CAT, DUR, NAME, PH, TRACK
from mlsl_tpu.types import CompressionType, DataType, OpType, ReductionType


@pytest.fixture()
def tracing():
    """A fresh enabled tracer; always disarmed afterwards (process-global)."""
    obs.disable()
    tr = obs.enable(capacity=8192)
    yield tr
    obs.disable()


@pytest.fixture(autouse=True)
def _disarm():
    yield
    obs.disable()
    chaos.clear()


def _spans(tr, name=None, cat=None):
    return [
        e for e in tr.snapshot()
        if (name is None or e[NAME] == name) and (cat is None or e[CAT] == cat)
    ]


def _request(env, count=64, name="t", compression=CompressionType.NONE):
    from mlsl_tpu.comm.request import CommDesc, CommRequest

    dist = env.create_distribution(8, 1)
    req = CommRequest(
        CommDesc("allreduce", dist.data_group, count, DataType.FLOAT,
                 op=ReductionType.SUM, compression=compression),
        env.dispatcher, name=name,
    )
    req.setup()
    buf = dist.make_buffer(lambda p: np.full(count, float(p + 1)), count)
    return req, buf


# -- span lifecycle through the real paths ------------------------------------


def test_plain_request_lifecycle(env, tracing):
    req, buf = _request(env, name="plainreq")
    req.start(buf)
    req.wait()
    track = f"mlsl:allreduce:plainreq"
    subs = [e for e in _spans(tracing, "submit") if e[TRACK] == track]
    disp = [e for e in _spans(tracing, "dispatch") if e[TRACK] == track]
    waits = [e for e in _spans(tracing, "wait") if e[TRACK] == track]
    assert len(subs) == 1 and subs[0][PH] == "i"
    assert subs[0][ARGS]["bytes"] == 64 * 4
    assert len(disp) == 1 and disp[0][PH] == "X" and disp[0][DUR] > 0
    assert len(waits) == 1 and waits[0][PH] == "X"
    # lifecycle ordering: submit <= dispatch start <= wait end
    assert subs[0][tracer_mod.TS] <= disp[0][tracer_mod.TS] + disp[0][DUR]
    assert waits[0][tracer_mod.TS] + waits[0][DUR] >= disp[0][tracer_mod.TS]


def test_chunked_request_lifecycle(env, tracing):
    """A >threshold allreduce dispatches as independent chunks under ONE
    dispatch span (one host enqueue covering all chunk programs)."""
    env.config.large_msg_size_mb = 1
    env.config.large_msg_chunks = 4
    try:
        count = 1 << 19  # 2 MiB payload -> 4 chunks
        req, buf = _request(env, count=count, name="bigreq")
        assert len(req._chunk_slices) == 4  # chunking engaged
        req.start(buf)
        req.wait()
    finally:
        env.config.large_msg_size_mb = 128
        env.config.large_msg_chunks = 4
    track = "mlsl:allreduce:bigreq"
    assert [e for e in _spans(tracing, "submit") if e[TRACK] == track]
    assert [e for e in _spans(tracing, "dispatch") if e[TRACK] == track]
    assert [e for e in _spans(tracing, "wait") if e[TRACK] == track]


def test_quant_request_lifecycle(env, tracing):
    """The int8 ring path records its encode/ring/decode enqueue as a
    quant.roundtrip span on top of the request lifecycle."""
    req, buf = _request(env, count=1024, name="quantreq",
                        compression=CompressionType.QUANTIZATION)
    req.start(buf)
    req.wait()
    track = "mlsl:allreduce:quantreq"
    assert [e for e in _spans(tracing, "wait") if e[TRACK] == track]
    rts = _spans(tracing, "quant.roundtrip", cat="quant")
    assert rts and rts[0][PH] == "X"


def test_deferred_request_records_defer(env, tracing):
    """msg_priority deferral shows up as a defer instant before dispatch."""
    env.config.msg_priority = True
    env.config.msg_priority_threshold = 0  # defer everything
    try:
        req, buf = _request(env, name="defreq")
        req.start(buf)
        req.wait()
    finally:
        env.config.msg_priority = False
    track = "mlsl:allreduce:defreq"
    defers = [e for e in _spans(tracing, "defer") if e[TRACK] == track]
    assert defers and defers[0][PH] == "i"


def test_bucketed_request_lifecycle(env, tracing):
    """A full bucket round: bucket.pack span + bucket.dispatched instant on
    the shared bucket request's track, then one wait span per member wait."""
    env.config.grad_bucket_mb = 4
    try:
        dist = env.create_distribution(8, 1)
        s = env.create_session()
        s.set_global_minibatch_size(8)
        ops = []
        for i, c in enumerate([512, 512]):
            r = s.create_operation_reg_info(OpType.CC)
            r.set_name(f"blayer{i}")
            r.add_input(8, 4)
            r.add_output(8, 4)
            r.add_parameter_set(c, 1)
            ops.append(s.get_operation(s.add_operation(r, dist)))
        s.commit()
        pss = [op.get_parameter_set(0) for op in ops]
        assert all(ps.bucket is not None for ps in pss)
        bufs = [
            dist.make_buffer(lambda p: np.full(512, float(p + 1)), 512)
            for _ in pss
        ]
        for ps, b in zip(reversed(pss), reversed(bufs)):
            ps.start_gradient_comm(b)
        for ps in pss:
            assert ps.wait_gradient_comm() is not None
    finally:
        env.config.grad_bucket_mb = 0
    packs = _spans(tracing, "bucket.pack", cat="bucket")
    assert len(packs) == 1 and packs[0][ARGS]["members"] == 2
    assert packs[0][TRACK].startswith("mlsl:allreduce:bucket-")
    assert _spans(tracing, "bucket.dispatched", cat="bucket")
    waits = [e for e in _spans(tracing, "wait")
             if str(e[ARGS].get("req", "")).startswith("bucket-")]
    assert waits  # the coalesced request's wait stall is on its track


# -- disabled path ------------------------------------------------------------


def _import_probe(value):
    """What a fresh process with MLSL_TRACE=<value> (None: unset) finds at
    import: the module's arming code run again against that environment."""
    import importlib

    before = os.environ.pop(tracer_mod.ENV_TRACE, None)
    # a fresh process has no ring size either: whatever another test left in
    # this worker's environment (--dist loadfile may hand this file to a
    # worker after any other) is not this probe's
    capacity = os.environ.pop(tracer_mod.ENV_CAPACITY, None)
    if value is not None:
        os.environ[tracer_mod.ENV_TRACE] = value
    try:
        importlib.reload(tracer_mod)
        return tracer_mod.get_tracer()
    finally:
        os.environ.pop(tracer_mod.ENV_TRACE, None)
        if before is not None:
            os.environ[tracer_mod.ENV_TRACE] = before
        if capacity is not None:
            os.environ[tracer_mod.ENV_CAPACITY] = capacity
        importlib.reload(tracer_mod)


def test_disabled_path_records_nothing_and_allocates_nothing(env, monkeypatch):
    """MLSL_TRACE=0: the ring does not exist after import and the hot paths
    run with the tracer global None — no events anywhere, and ZERO
    allocations attributed to mlsl_tpu/obs/* (the acceptance contract;
    tracemalloc attributes every allocation to the frame that made it, so any
    tracer-side tuple/dict would show up)."""
    assert _import_probe("0") is None
    monkeypatch.setenv(tracer_mod.ENV_TRACE, "0")
    assert not tracer_mod.armed_by_env()
    from mlsl_tpu.config import Config

    assert Config.from_env().trace is False
    obs.disable()
    assert obs.get_tracer() is None
    req, buf = _request(env, name="offreq")
    req.start(buf)
    req.wait()  # warm every code path first (jit caches, lazy imports)
    obs_dir = os.path.dirname(os.path.abspath(obs.__file__))
    tracemalloc.start()
    try:
        req.start(buf)
        req.wait()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    stats = snap.filter_traces(
        [tracemalloc.Filter(True, os.path.join(obs_dir, "*"))]
    ).statistics("filename")
    assert not stats, f"tracer allocated while disabled: {stats}"
    assert obs.get_tracer() is None


@pytest.mark.parametrize("value", [None, "", "1", "true"])
def test_ring_is_armed_by_default(monkeypatch, value):
    """MLSL_TRACE unset (or truthy): the ring is the flight recorder and
    exists after import at its full capacity; Config.trace follows."""
    tr = _import_probe(value)
    assert tr is not None and tr.capacity == tracer_mod.DEFAULT_CAPACITY
    if value is None:
        monkeypatch.delenv(tracer_mod.ENV_TRACE, raising=False)
    else:
        monkeypatch.setenv(tracer_mod.ENV_TRACE, value)
    assert tracer_mod.armed_by_env()
    from mlsl_tpu.config import Config

    assert Config.from_env().trace is True


# -- ring buffer --------------------------------------------------------------


def test_ring_buffer_wraparound():
    obs.disable()
    tr = obs.enable(capacity=32)
    try:
        for i in range(100):
            tr.instant(f"ev{i}", "t")
        evs = tr.snapshot()
        assert len(evs) == 32
        assert evs[0][NAME] == "ev68"   # oldest surviving
        assert evs[-1][NAME] == "ev99"  # newest
        assert tr.capacity == 32
    finally:
        obs.disable()


def test_enable_is_idempotent_and_env_capacity(monkeypatch):
    obs.disable()
    monkeypatch.setenv(tracer_mod.ENV_CAPACITY, "64")
    tr = obs.enable()
    assert tr.capacity == 64
    assert obs.enable() is tr  # idempotent: same ring
    obs.disable()


# -- exporter -----------------------------------------------------------------


def test_exporter_emits_valid_perfetto_json(env, tracing, tmp_path):
    req, buf = _request(env, name="expreq")
    req.start(buf)
    req.wait()
    path = obs.write_trace(path=str(tmp_path / "t.json"))
    assert path and os.path.exists(path)
    doc = json.loads(open(path).read())  # must be loadable JSON
    evs = doc["traceEvents"]
    assert evs
    for e in evs:
        assert "ph" in e and "pid" in e and "tid" in e
        if e["ph"] != "M":
            assert "ts" in e
    # complete spans carry dur; instants carry scope
    assert any(e["ph"] == "X" and "dur" in e for e in evs)
    assert any(e["ph"] == "i" and e.get("s") == "t" for e in evs)
    # track metadata: the request has its own named track
    names = [
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert "mlsl:allreduce:expreq" in names
    # and the summarizer renders it without choking
    text = obs.summarize(doc)
    assert "wait" in text


# -- flight recorder ----------------------------------------------------------


def test_flight_recorder_on_watchdog_trip(env, tracing, tmp_path, monkeypatch):
    """The acceptance scenario: a chaos-hung dispatch (armed via the
    MLSL_CHAOS grammar) under MLSL_TRACE with MLSL_WATCHDOG_TIMEOUT produces
    a trace-crash-*.json that parses as a Perfetto trace and contains the
    stuck request's span and trip record."""
    monkeypatch.setenv("MLSL_TRACE_DIR", str(tmp_path))
    chaos.refresh_from_env("collective.dispatch:hang=8")
    env.config.msg_priority = True
    env.config.msg_priority_threshold = 0   # defer everything
    env.config.msg_priority_flush_ms = 1.0
    env.config.watchdog_timeout_s = 0.5
    try:
        req, buf = _request(env, name="flightcheck")
        req.start(buf)
        time.sleep(0.3)  # progress thread grabs the deferred entry, hangs
        with pytest.raises(MLSLTimeoutError, match="watchdog"):
            req.wait()
    finally:
        chaos.clear()  # wake the hang
        env.config.msg_priority = False
        env.config.watchdog_timeout_s = 0.0
    crashes = sorted(tmp_path.glob("trace-crash-*.json"))
    assert crashes, "watchdog trip did not write a flight record"
    doc = json.loads(crashes[-1].read_text())
    assert doc["otherData"]["kind"] == "flight_record"
    assert "flightcheck" in doc["otherData"]["reason"]
    evs = doc["traceEvents"]
    for e in evs:
        assert "ph" in e and "pid" in e
    # the stuck request's own track and its trip instant are in the dump
    names = [
        e["args"]["name"] for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    ]
    assert "mlsl:allreduce:flightcheck" in names
    trips = [e for e in evs if e["name"] == "watchdog.trip"]
    assert trips and "flightcheck" in trips[-1]["args"]["descriptor"]
    # the watchdog event record points back at the dump
    from mlsl_tpu.core import stats

    assert stats.WATCHDOG_EVENTS[-1].get("flight_record") == str(crashes[-1])


# -- span-derived stats fields ------------------------------------------------


def test_overlap_report_gains_wait_stall_fields(env, tracing):
    env.config.enable_stats = True
    try:
        dist = env.create_distribution(8, 1)
        s = env.create_session()
        s.set_global_minibatch_size(8)
        r = s.create_operation_reg_info(OpType.CC)
        r.set_name("l1")
        r.add_input(8, 4)
        r.add_output(8, 4)
        r.add_parameter_set(256, 1)
        op = s.get_operation(s.add_operation(r, dist))
        s.commit()  # isolation replay runs here (stats enabled)
        ps = op.get_parameter_set(0)
        buf = dist.make_buffer(lambda p: np.ones(256, np.float32), 256)
        for _ in range(3):
            ps.start_gradient_comm(buf)
            ps.wait_gradient_comm()
        rep = s.get_stats().overlap_report()
        ent = rep["ops"]["l1"]
        assert ent["wait_spans"] >= 3
        assert ent["wait_stall_p95_ms"] >= ent["wait_stall_p50_ms"] >= 0
        assert rep["total"]["wait_spans"] >= ent["wait_spans"]
        # tracing off: the report keeps its classic shape (no span fields)
        obs.disable()
        rep2 = s.get_stats().overlap_report()
        assert "wait_stall_p50_ms" not in rep2["ops"]["l1"]
    finally:
        env.config.enable_stats = False


def test_bucket_line_gains_wait_stall_fields(env, tracing):
    from mlsl_tpu.core import stats as stats_mod

    env.config.grad_bucket_mb = 4
    stats_mod.reset_bucket_counters()
    try:
        dist = env.create_distribution(8, 1)
        s = env.create_session()
        s.set_global_minibatch_size(8)
        ops = []
        for i in range(2):
            r = s.create_operation_reg_info(OpType.CC)
            r.set_name(f"wl{i}")
            r.add_input(8, 4)
            r.add_output(8, 4)
            r.add_parameter_set(512, 1)
            ops.append(s.get_operation(s.add_operation(r, dist)))
        s.commit()
        pss = [op.get_parameter_set(0) for op in ops]
        bufs = [
            dist.make_buffer(lambda p: np.ones(512, np.float32), 512)
            for _ in pss
        ]
        for ps, b in zip(reversed(pss), reversed(bufs)):
            ps.start_gradient_comm(b)
        for ps in pss:
            ps.wait_gradient_comm()
        text = s.get_stats().print_(path=os.devnull)
        assert "BUCKET" in text and "wait_p50" in text and "wait_p95" in text
    finally:
        env.config.grad_bucket_mb = 0
        stats_mod.reset_bucket_counters()


# -- stats log routing (MLSL_STATS_DIR) ---------------------------------------


def test_stats_log_routed_through_stats_dir(tmp_path, monkeypatch):
    from mlsl_tpu.core import stats

    d = tmp_path / "statsdir"
    d.mkdir()
    # hermetic CWD: the nothing-in-CWD assertion below must not fail on a
    # stray mlsl_stats.log left in the repo root by an ad-hoc (non-pytest)
    # run from before this suite started
    monkeypatch.chdir(tmp_path)
    monkeypatch.setenv("MLSL_STATS_DIR", str(d))
    stats.record_watchdog_event("routecheck allreduce", "wait", 1.0)
    log = d / stats.STATS_OUTPUT_FILE
    assert log.exists() and "routecheck" in log.read_text()
    assert not os.path.exists(stats.STATS_OUTPUT_FILE)  # nothing in CWD


# -- count_backend_compiles cleanup -------------------------------------------


def test_count_backend_compiles_unregisters_on_exception():
    """A failing body must not leak the jax monitoring listener into later
    tests: after the context exits via an exception, firing the compile event
    must not bump the counter."""
    from jax._src import monitoring

    from mlsl_tpu.core.stats import BACKEND_COMPILE_EVENT, count_backend_compiles

    captured = []
    with pytest.raises(RuntimeError, match="boom"):
        with count_backend_compiles() as n:
            captured.append(n)
            raise RuntimeError("boom")
    before = captured[0][0]
    monitoring.record_event_duration_secs(BACKEND_COMPILE_EVENT, 0.01)
    assert captured[0][0] == before, "listener leaked past the context"
