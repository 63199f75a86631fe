"""Telemetry plane (ISSUE 15): typed time-series registry, Prometheus +
JSONL export, the /metrics + /healthz + /statusz scrape surface, the
straggler sentinel's skew verdicts, and the zero-alloc disabled contract.

Acceptance pins (the ISSUE checklist):
- metrics disabled path is zero-allocation (tracemalloc, tracer precedent);
- /metrics parses as valid Prometheus text exposition and /healthz returns
  supervisor.status() verbatim as JSON, both over the in-process server;
- supervisor.status() is JSON round-trip serializable (it backs /healthz);
- the straggler sentinel flags a sustained-slow replica within one audit
  interval and fires nothing on a skew-free world;
- lint A207 pins the registry's single-mutation discipline (known-bad
  fixture in tests/test_analysis.py's pattern, pinned here).
"""

import json
import os
import re
import subprocess
import sys
import tracemalloc
import urllib.error
import urllib.request

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from mlsl_tpu import chaos, supervisor
from mlsl_tpu.core import stats
from mlsl_tpu.obs import metrics as metrics_mod
from mlsl_tpu.obs import serve as serve_mod
from mlsl_tpu.obs import straggler as straggler_mod
from mlsl_tpu.types import CompressionType, DataType, ReductionType


@pytest.fixture(autouse=True)
def _disarm():
    yield
    serve_mod.stop_server()
    metrics_mod.disable()
    straggler_mod.reset()
    chaos.clear()


@pytest.fixture()
def registry():
    metrics_mod.disable()
    yield metrics_mod.enable(every=2, retention=16)
    metrics_mod.disable()


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


def _make_trainer(env, batch=16, **kw):
    from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
    from mlsl_tpu.models.train import DataParallelTrainer

    d = env.get_process_count()
    dist = env.create_distribution(d, 1)
    sess = env.create_session()
    sess.set_global_minibatch_size(batch)
    return DataParallelTrainer(
        env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
        get_layer, lr=0.1, **kw,
    )


def _mlp_batch(trainer, seed=0, batch=16):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(batch, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(batch,)).astype(np.int32)
    return trainer.shard_batch(x, y)


# -- registry types -----------------------------------------------------------


def test_counter_gauge_histogram_basics(registry):
    r = registry
    r.inc("c", 2)
    r.inc("c")
    assert r.find("c").value == 3
    r.set("g", 1.5)
    r.set("g", 2.5)
    assert r.find("g").value == 2.5
    h = r.histogram("h")
    for v in (1.0, 2.0, 4.0):
        h.observe(v)
    assert h.count == 3
    assert h.sum == 7.0
    assert 0 < h.percentile(50) <= 2.5


def test_labels_make_distinct_series(registry):
    r = registry
    r.inc("dispatches", 1, algo="lax")
    r.inc("dispatches", 5, algo="rhd")
    assert r.find("dispatches", algo="lax").value == 1
    assert r.find("dispatches", algo="rhd").value == 5
    assert r.find("dispatches") is None
    # label order never makes a new series
    r.inc("d2", 1, a="1", b="2")
    r.inc("d2", 1, b="2", a="1")
    assert r.find("d2", a="1", b="2").value == 2


def test_histogram_percentiles_monotone_and_bounded(registry):
    h = registry.histogram("lat", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 3.5, 7.0):
        h.observe(v)
    p50, p95, p99 = h.percentile(50), h.percentile(95), h.percentile(99)
    assert 0 < p50 <= p95 <= p99 <= 8.0
    # overflow values report the top finite bound, not infinity
    h.observe(1e9)
    assert h.percentile(99.9) == 8.0
    # empty histogram is 0, not NaN
    assert registry.histogram("empty").percentile(99) == 0.0


def test_sample_ring_retention(registry):
    r = registry
    g = r.gauge("g")
    for i in range(40):
        g.set(float(i))
        r.sample()
    assert len(g._msamples) == 16  # MLSL_METRICS_RETENTION ring
    assert g._msamples[-1]["value"] == 39.0
    assert r.samples_taken == 40


def test_enable_idempotent_and_env_knobs(monkeypatch):
    metrics_mod.disable()
    monkeypatch.setenv("MLSL_METRICS_EVERY", "7")
    monkeypatch.setenv("MLSL_METRICS_RETENTION", "32")
    r = metrics_mod.enable()
    assert (r.every, r.retention) == (7, 32)
    assert metrics_mod.enable() is r  # idempotent: knobs stick
    # an EXPLICIT knob binds even on a live registry: MLSL_METRICS=1 arms
    # at import with env defaults, and Environment.init's re-enable with
    # the validated/tuned Config values must not be silently dropped
    assert metrics_mod.enable(every=13) is r
    assert r.every == 13
    metrics_mod.disable()
    assert metrics_mod.get_registry() is None


# -- the zero-alloc disabled contract (tracer precedent) ----------------------


def test_disabled_path_zero_alloc_request_round(env):
    """With the registry disarmed, a full request start/wait round must
    attribute ZERO allocations to obs/metrics.py — the instrumented sites
    are one module-attr load and a None test."""
    metrics_mod.disable()
    req, buf = _request(env, name="offreq")
    req.start(buf)
    req.wait()  # warm every code path first
    metrics_file = os.path.abspath(metrics_mod.__file__)
    tracemalloc.start()
    try:
        req.start(buf)
        req.wait()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    hits = snap.filter_traces(
        [tracemalloc.Filter(True, metrics_file)]
    ).statistics("filename")
    assert not hits, f"metrics allocated while disabled: {hits}"
    assert metrics_mod.get_registry() is None


# -- instrumented feeds -------------------------------------------------------


def test_request_feeds_dispatch_wait_and_algbw(env, registry):
    req, buf = _request(env, name="mreq")
    req.start(buf)
    req.wait()
    h = registry.find("mlsl_dispatch_wait_ms", kind="allreduce")
    assert h is not None and h.count == 1
    algbw = [s for s in registry.series() if s.name == "mlsl_algbw_gbps"]
    assert len(algbw) == 1
    (s,) = algbw
    labels = dict(s.labels)
    assert labels["algo"] == "lax" and labels["tier"] == "flat"
    assert s.count == 1 and s.sum > 0
    # test() completion feeds the same histograms
    req.start(buf)
    while not req.test()[0]:
        pass
    assert h.count == 2


def test_trainer_step_feeds_and_cadence(env, registry, tmp_path):
    trainer = _make_trainer(env, force_graph_path=True)
    b = _mlp_batch(trainer)
    for _ in range(4):
        trainer.step(b)
    jax.block_until_ready(trainer.params)
    h = registry.find("mlsl_step_ms")
    assert h is not None and h.count == 4
    # cadence tick (every=2): loss + grad-norm gauges, family snapshot,
    # JSONL appended under MLSL_STATS_DIR (conftest routes it to tmp)
    assert registry.find("mlsl_loss") is not None
    assert registry.find("mlsl_loss").value > 0
    assert registry.find("mlsl_grad_norm").value > 0
    assert registry.find("mlsl_input_stall_ms") is not None
    assert registry.find("mlsl_sentinel_screened") is not None
    assert registry.find("mlsl_elastic_shrinks") is not None
    path = metrics_mod.jsonl_path()
    assert os.path.exists(path)
    recs = [json.loads(l) for l in open(path) if l.strip()]
    assert {r["series"] for r in recs} >= {"mlsl_step_ms", "mlsl_loss"}
    # summarizer round-trip over the real file
    acc = metrics_mod.summarize_jsonl(open(path))
    assert any(name == "mlsl_step_ms" for name, _ in acc)


# -- exports ------------------------------------------------------------------

#: Prometheus text exposition grammar (the subset the exporter emits): a
#: comment/TYPE line, or  name{labels} value  with a float value
_PROM_LINE = re.compile(
    r"^(# (TYPE|HELP) [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})? "
    r"[-+]?([0-9]*\.?[0-9]+([eE][-+]?[0-9]+)?|Inf|NaN))$"
)


def _assert_valid_prometheus(text):
    assert text.strip(), "empty exposition"
    for line in text.splitlines():
        assert _PROM_LINE.match(line), f"invalid exposition line: {line!r}"


def test_prometheus_exposition_valid(registry):
    r = registry
    r.inc("mlsl_total", 3)
    r.set("mlsl_gauge", -1.25, shard="0")
    h = r.histogram("mlsl_lat_ms", labels_x="a b")
    for v in (0.05, 3.0, 77.0, 1e6):
        h.observe(v)
    text = r.to_prometheus()
    _assert_valid_prometheus(text)
    assert "# TYPE mlsl_total counter" in text
    assert "# TYPE mlsl_lat_ms histogram" in text
    # histogram triple: cumulative buckets, +Inf == count, sum present
    lines = text.splitlines()
    bucket_vals = [int(l.rsplit(" ", 1)[1]) for l in lines
                   if l.startswith("mlsl_lat_ms_bucket")]
    assert bucket_vals == sorted(bucket_vals)
    assert bucket_vals[-1] == 4  # le="+Inf" carries the full count
    assert any(l.startswith("mlsl_lat_ms_count") and l.endswith(" 4")
               for l in lines)


# -- the scrape surface -------------------------------------------------------


def test_http_round_trip(env, registry):
    """The in-process server acceptance: /metrics parses as Prometheus
    text, /healthz IS supervisor.status() as JSON, /statusz renders."""
    trainer = _make_trainer(env, force_graph_path=True)
    b = _mlp_batch(trainer)
    for _ in range(3):
        trainer.step(b)
    jax.block_until_ready(trainer.params)
    srv = serve_mod.start_server(port=0)
    assert srv is not None and srv.port > 0
    base = f"http://127.0.0.1:{srv.port}"
    prom = urllib.request.urlopen(base + "/metrics", timeout=10
                                  ).read().decode()
    _assert_valid_prometheus(prom)
    assert "mlsl_step_ms_bucket" in prom
    assert "mlsl_dispatch_wait_ms" in prom
    body = urllib.request.urlopen(base + "/healthz", timeout=10
                                  ).read().decode()
    assert json.loads(body) == supervisor.status()
    sz = urllib.request.urlopen(base + "/statusz", timeout=10
                                ).read().decode()
    assert "mlsl_tpu statusz" in sz and "metrics: armed" in sz
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(base + "/nope", timeout=10)
    assert ei.value.code == 404
    serve_mod.stop_server()
    assert serve_mod.get_server() is None


def test_start_server_idempotent_and_env_gate(monkeypatch):
    monkeypatch.delenv("MLSL_METRICS_PORT", raising=False)
    assert serve_mod.start_server() is None  # unset env = no server
    monkeypatch.setenv("MLSL_METRICS_PORT", "0")
    assert serve_mod.start_server() is None  # env 0 = off (explicit 0 = test)
    srv = serve_mod.start_server(port=0)
    assert srv is not None
    assert serve_mod.start_server(port=0) is srv  # idempotent


def test_healthz_json_round_trip_under_armed_subsystems(env):
    """The /healthz satellite: supervisor.status() must survive a JSON
    round trip VERBATIM — including with a tripped breaker, an armed
    straggler sentinel, and the registry live. A non-serializable field
    must fail here, in tier-1, not in a production scrape."""
    doc = supervisor.status()
    assert json.loads(json.dumps(doc)) == doc
    # now with state in every new subsystem
    metrics_mod.enable(every=2, retention=8)
    s = straggler_mod.StragglerSentinel(skew=1.2, every=3, sustain=1,
                                        shed=True)
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 100.0, wait_ms=5.0)
    s.maybe_audit(step=3)
    br = supervisor.breaker("quant")
    br.record_failure(RuntimeError("boom"))
    doc = supervisor.status()
    assert doc["straggler"]["state"] == "flagged"
    assert doc["straggler"]["shed_candidate"] == 1
    assert doc["metrics"]["armed"] is True
    assert json.loads(json.dumps(doc)) == doc


# -- straggler sentinel -------------------------------------------------------


def test_straggler_flags_against_peer_baseline():
    s = straggler_mod.StragglerSentinel(skew=1.5, every=3, sustain=1)
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 11.0)
        s.observe(2, 35.0, wait_ms=2.0)
    v = s.maybe_audit(step=3)
    assert v is not None
    # replica 2 is 35/10.5 ~ 3.3x its PEERS' median (self excluded)
    assert v["confirmed"] == [2]
    assert stats.STRAGGLER_COUNTERS["audits"] == 1
    assert stats.STRAGGLER_COUNTERS["flags"] == 1
    assert s.status()["flagged"]["2"]["skew"] > 3.0
    # observe-only: no shed candidate without MLSL_STRAGGLER_SHED
    assert s.shed_candidate() is None


def test_straggler_zero_false_positives_on_skew_free_world():
    s = straggler_mod.StragglerSentinel(skew=1.5, every=4, sustain=1)
    for i in range(4):
        s.observe(0, 10.0 + 0.1 * i)
        s.observe(1, 10.0 - 0.1 * i)
    v = s.maybe_audit(step=4)
    assert v["suspects"] == [] and v["confirmed"] == []
    assert stats.STRAGGLER_COUNTERS["flags"] == 0
    assert s.status()["state"] == "watching"


def test_straggler_single_replica_never_fires():
    """One replica reporting = no baseline = no verdicts (the degenerate
    single-controller world must be silent, not noisy)."""
    s = straggler_mod.StragglerSentinel(skew=1.2, every=4, sustain=1)
    for _ in range(8):
        s.observe(0, 100.0)
    s.maybe_audit(step=8)
    assert stats.STRAGGLER_COUNTERS["flags"] == 0


def test_straggler_sustain_filters_one_slow_window():
    s = straggler_mod.StragglerSentinel(skew=1.5, every=6, sustain=2)
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 40.0)
    v1 = s.audit_now(step=6)
    assert v1["suspects"] == [1] and v1["confirmed"] == []  # streak 1 < 2
    # a healthy window resets the streak
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 10.0)
    v2 = s.audit_now(step=12)
    assert v2["suspects"] == [] and v2["confirmed"] == []
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 40.0)
    s.audit_now(step=18)
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 40.0)
    v4 = s.audit_now(step=24)
    assert v4["confirmed"] == [1]  # two consecutive suspect audits


def test_straggler_candidate_lifecycle():
    s = straggler_mod.StragglerSentinel(skew=1.2, every=3, sustain=1,
                                        shed=True)
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 50.0)
    s.maybe_audit(step=3)
    assert s.shed_candidate() == 1
    s.clear_candidate()
    assert s.shed_candidate() is None
    # re-confirmation required after a clear
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 50.0)
    s.audit_now(step=12)
    assert s.shed_candidate() == 1


def test_straggler_feeds_registry_histograms(registry):
    s = straggler_mod.StragglerSentinel(skew=2.0, every=100, sustain=1)
    s.observe(3, 12.5, wait_ms=1.5)
    h = registry.find("mlsl_replica_step_ms", replica=3)
    assert h is not None and h.count == 1
    assert registry.find("mlsl_replica_wait_ms", replica=3).count == 1


def test_trainer_arms_straggler_from_config(env, monkeypatch):
    monkeypatch.setenv("MLSL_STRAGGLER_SKEW", "1.5")
    monkeypatch.setenv("MLSL_STRAGGLER_EVERY", "5")
    monkeypatch.setenv("MLSL_STRAGGLER_SUSTAIN", "3")
    env.finalize()
    from mlsl_tpu.core.environment import Environment

    env2 = Environment.get_env().init()
    trainer = _make_trainer(env2, force_graph_path=True)
    assert trainer.straggler is not None
    assert trainer.straggler.skew == 1.5
    assert trainer.straggler.every == 5
    assert trainer.straggler.sustain == 3
    # the armed instance is the process-wide one /healthz reports
    assert straggler_mod.get_active() is trainer.straggler
    b = _mlp_batch(trainer)
    for _ in range(6):
        trainer.step(b)
    # single replica: observations flow, audits run, nothing fires
    assert trainer.straggler._audits >= 1
    assert stats.STRAGGLER_COUNTERS["flags"] == 0


# -- shed handoff into the elastic coordinator --------------------------------


def test_shed_maps_replica_to_device_and_shrinks(monkeypatch, tmp_path):
    """ElasticCoordinator.shed: a confirmed straggler replica becomes a
    synthetic DEVICE_LOSS through the full shrink machinery (world 8 -> 7,
    capacity budget spent, STRAGGLER sheds counted)."""
    from mlsl_tpu import elastic
    from mlsl_tpu.core.environment import Environment

    monkeypatch.setenv("MLSL_ELASTIC", "1")
    batch = 56  # divides 8 and 7 ranks (the elastic-soak contract)

    def make_trainer():
        env = Environment.get_env().init()
        return _make_trainer(env, batch=batch)

    trainer = make_trainer()
    coord = elastic.ElasticCoordinator()
    new_trainer = coord.shed(trainer, make_trainer, replica=1, step=3)
    assert new_trainer.dist.topology.world_size == 7
    assert stats.ELASTIC_COUNTERS["shrinks"] == 1
    assert stats.STRAGGLER_COUNTERS["sheds"] == 1
    assert elastic.status()["state"] == "shrunk"
    Environment.get_env().finalize()


def test_shed_refused_out_of_range_counts_fallback(monkeypatch):
    from mlsl_tpu import elastic
    from mlsl_tpu.core.environment import Environment
    from mlsl_tpu.log import MLSLError

    monkeypatch.setenv("MLSL_ELASTIC", "1")
    env = Environment.get_env().init()
    trainer = _make_trainer(env)
    coord = elastic.ElasticCoordinator()
    with pytest.raises(MLSLError):
        coord.shed(trainer, lambda: trainer, replica=99, step=0)
    assert stats.STRAGGLER_COUNTERS["shed_fallbacks"] == 1
    assert stats.ELASTIC_COUNTERS["shrinks"] == 0


# -- stats lines --------------------------------------------------------------


def test_straggler_stats_line_and_degrade_vocabulary(env):
    s = straggler_mod.StragglerSentinel(skew=1.2, every=3, sustain=1)
    # un-flagged: the DEGRADE ladder line must NOT list straggler (the
    # elastic 'full'-state lesson: healthy vocabulary never reads degraded)
    stats.record_degrade("quant", "fallback")
    sess = env.create_session()
    text = sess.get_stats().print_()
    assert "straggler:" not in text
    for _ in range(3):
        s.observe(0, 10.0)
        s.observe(1, 50.0)
    s.maybe_audit(step=3)
    text = sess.get_stats().print_()
    assert "STRAGGLER" in text and "flags 1" in text
    assert "straggler:flagged" in text


# -- config / knobs -----------------------------------------------------------


def test_config_validation(monkeypatch):
    from mlsl_tpu.config import Config
    from mlsl_tpu.log import MLSLError

    Config(metrics_every=1, straggler_skew=1.5).validate()
    with pytest.raises(MLSLError):
        Config(metrics_every=0).validate()
    with pytest.raises(MLSLError):
        Config(metrics_port=70000).validate()
    with pytest.raises(MLSLError):
        Config(metrics_retention=1).validate()
    with pytest.raises(MLSLError):
        Config(straggler_skew=0.9).validate()  # (0, 1] flags healthy worlds
    with pytest.raises(MLSLError):
        Config(straggler_skew=1.0).validate()
    with pytest.raises(MLSLError):
        Config(straggler_every=0).validate()
    with pytest.raises(MLSLError):
        # below the judgeable minimum: the window would close before any
        # replica has MIN_WINDOW_SAMPLES and detection silently turns off
        Config(straggler_every=2).validate()
    with pytest.raises(MLSLError):
        Config(straggler_sustain=0).validate()
    monkeypatch.setenv("MLSL_STRAGGLER_SKEW", "1.4")
    monkeypatch.setenv("MLSL_METRICS", "1")
    monkeypatch.setenv("MLSL_PROFILE_ON_TRIP", "1")
    c = Config.from_env()
    assert c.straggler_skew == 1.4 and c.metrics and c.profile_on_trip
    c.validate()


def test_knobs_in_tuner_ranges_and_env_fields():
    from mlsl_tpu.config import _ENV_FIELDS
    from mlsl_tpu.tuner import KNOB_RANGES

    assert "metrics_every" in KNOB_RANGES
    assert "straggler_every" in KNOB_RANGES
    assert _ENV_FIELDS["MLSL_METRICS_EVERY"] == "metrics_every"
    assert _ENV_FIELDS["MLSL_STRAGGLER_EVERY"] == "straggler_every"


def test_env_init_arms_registry(monkeypatch):
    from mlsl_tpu.core.environment import Environment

    metrics_mod.disable()
    monkeypatch.setenv("MLSL_METRICS", "1")
    monkeypatch.setenv("MLSL_METRICS_EVERY", "9")
    env = Environment.get_env().init()
    try:
        r = metrics_mod.get_registry()
        assert r is not None and r.every == 9
    finally:
        env.finalize()


# -- trace_view --metrics -----------------------------------------------------


def test_trace_view_metrics_mode(tmp_path):
    r = metrics_mod.enable(every=1, retention=8)
    h = r.histogram("mlsl_step_ms")
    for v in (5.0, 6.0, 50.0):
        h.observe(v)
    r.set("mlsl_loss", 0.25)
    path = str(tmp_path / "m.jsonl")
    r.write_jsonl(path=path, records=r.sample())
    r.write_jsonl(path=path, records=r.sample())
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "scripts", "trace_view.py"),
         "--metrics", path],
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-1000:]
    assert "health summary" in out.stdout
    assert "mlsl_step_ms" in out.stdout
    assert "loss" in out.stdout


# -- watchdog device profile (MLSL_PROFILE_ON_TRIP) ---------------------------


def _wedged_wait(env, monkeypatch, name):
    """Drive the flight-recorder scenario (test_trace precedent): a deferred
    dispatch hangs on the progress thread; the watchdog trips the wait."""
    import time as _time

    from mlsl_tpu.log import MLSLTimeoutError

    chaos.refresh_from_env("collective.dispatch:hang=8")
    env.config.msg_priority = True
    env.config.msg_priority_threshold = 0  # defer everything
    env.config.msg_priority_flush_ms = 1.0
    env.config.watchdog_timeout_s = 0.5
    try:
        req, buf = _request(env, name=name)
        req.start(buf)
        _time.sleep(0.3)  # progress thread grabs the deferred entry, hangs
        with pytest.raises(MLSLTimeoutError, match="watchdog"):
            req.wait()
    finally:
        chaos.clear()  # wake the hang
        env.config.msg_priority = False
        env.config.watchdog_timeout_s = 0.0


def test_profile_on_trip_writes_device_trace(env, monkeypatch):
    """A watchdog trip with MLSL_PROFILE_ON_TRIP=1 captures a jax.profiler
    trace directory next to the flight record and records it on the
    watchdog event; the MLSLTimeoutError stays primary."""
    monkeypatch.setenv("MLSL_PROFILE_ON_TRIP", "1")
    _wedged_wait(env, monkeypatch, "wedge")
    evt = stats.WATCHDOG_EVENTS[-1]
    assert "device_profile" in evt, evt
    assert os.path.isdir(evt["device_profile"])
    # the capture landed under MLSL_TRACE_DIR (conftest routes it to tmp)
    assert os.path.basename(evt["device_profile"]).startswith("profile-trip-")


def test_profile_on_trip_off_by_default(env, monkeypatch):
    monkeypatch.delenv("MLSL_PROFILE_ON_TRIP", raising=False)
    _wedged_wait(env, monkeypatch, "wedge2")
    assert "device_profile" not in stats.WATCHDOG_EVENTS[-1]
