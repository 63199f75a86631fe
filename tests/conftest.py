"""Test harness: an 8-device virtual CPU mesh simulating a multi-chip TPU slice.

The reference tests multi-node behavior with 4 MPI ranks on one host
(tests/examples/mlsl_test/Makefile:56-105); the JAX analog is
--xla_force_host_platform_device_count, giving real SPMD execution of the sharded
programs without TPU hardware.
"""

import os

# The CPU mesh unless the caller already chose a platform: the tier-1 command
# sets JAX_PLATFORMS=cpu itself, and `JAX_PLATFORMS=tpu pytest -m tpu tests/`
# on a chip host is how the tpu-marked tests get to run at all.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Auto-skip ``tpu``-marked tests off-chip: the compiled Pallas kernel
    variants need real hardware (``JAX_PLATFORMS=tpu python -m pytest -m tpu
    tests/`` on a chip host); tier-1 covers their interpret-mode twins and
    their cross-lowering for the TPU (tests/test_chip_smoke.py)."""
    from mlsl_tpu.sysinfo import on_tpu

    if on_tpu():
        return
    skip = pytest.mark.skip(reason="tpu marker: requires a real TPU")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)


@pytest.fixture()
def env():
    """A fresh initialized Environment; finalized after the test."""
    from mlsl_tpu.core.environment import Environment

    e = Environment.get_env().init()
    yield e
    e.finalize()


@pytest.fixture(autouse=True)
def _clean_singleton():
    yield
    from mlsl_tpu.core.environment import Environment

    if Environment._instance is not None:
        Environment._instance.finalize()


@pytest.fixture(autouse=True)
def _reset_supervisor():
    """Close every circuit breaker and clear the degrade counters between
    tests: breakers are process-wide BY DESIGN (subsystem health survives
    Environment rebuilds), so without this a test that trips one would
    silently degrade every later test's fast path."""
    yield
    from mlsl_tpu import supervisor
    from mlsl_tpu.core import stats

    supervisor.reset()
    # restore the knob defaults too: tests shorten/zero the cooldown to
    # admit half-open probes deterministically, and configure() is
    # process-wide by design (defaults come from Config so they cannot
    # drift from the real ones)
    from mlsl_tpu.config import Config

    c = Config()
    supervisor.configure(threshold=c.breaker_threshold,
                         window_s=c.breaker_window_s,
                         cooldown_s=c.breaker_cooldown_s)
    stats.reset_degrade_counters()
    # the sentinel/checker counters are process-wide for the same reason
    # (trainer/request layers hold no Session handle) and need the same
    # between-test isolation
    stats.reset_sentinel_counters()
    stats.reset_chkp_counters()
    from mlsl_tpu import checker, sentinel

    checker._pending.clear()
    sentinel._last_audit = None
    # the elastic active-world registry is process-wide by design (a shrunk
    # world must survive Environment rebuilds); tests that shrink must not
    # leave later tests running on a survivor subset
    from mlsl_tpu import elastic

    elastic.reset()
    stats.reset_elastic_counters()
    # the telemetry plane is process-wide by design (registry/server/
    # straggler survive Environment rebuilds); tests that arm it must not
    # leave later tests sampling into a stale registry or a bound port
    from mlsl_tpu.obs import metrics as obs_metrics
    from mlsl_tpu.obs import serve as obs_serve
    from mlsl_tpu.obs import straggler as obs_straggler

    obs_serve.stop_server()
    obs_metrics.disable()
    obs_straggler.reset()
    stats.reset_straggler_counters()
    # the pod control plane is process-wide by design (membership outlives
    # Environment rebuilds); tests that arm one must not leave later tests
    # heartbeating into dead sockets
    from mlsl_tpu import control

    control.reset()
    stats.reset_control_counters()
    # the serving engine's SLA governor registry is process-wide by design
    # (supervisor.status() reports it); tests that run an engine must not
    # leave later tests reading a stale ladder state
    from mlsl_tpu import serve

    serve.reset()
    stats.reset_serve_counters()
    # the codec guardrail registry is process-wide by design (the sentinel
    # gate feeds it without a Session handle); a test that arms it must not
    # leave later tests' requests demotable by a stale breach streak
    from mlsl_tpu import codecs

    codecs.guard_reset()
    stats.reset_codec_counters()
    # the lock witness's edge/cycle record is process-wide by design (a
    # soak accumulates across Environment rebuilds); a test that arms it
    # must not leave later agreement tests reading its synthetic cycles
    from mlsl_tpu.analysis import witness

    witness.reset()
    stats.reset_lock_witness_counters()


@pytest.fixture(autouse=True)
def _route_artifacts(tmp_path, monkeypatch):
    """Route mlsl_stats.log and trace-*.json into the test's tmp dir: a test
    run must never litter the CWD (core/stats.stats_path and obs.trace_dir
    both resolve their env var per call)."""
    monkeypatch.setenv("MLSL_STATS_DIR", str(tmp_path))
    monkeypatch.setenv("MLSL_TRACE_DIR", str(tmp_path))


@pytest.fixture(autouse=True)
def _default_tracer():
    """Every test starts as a fresh process would: the span ring armed unless
    MLSL_TRACE=0 says otherwise, and empty. The ring is process-wide, so
    without this a test that disarms it (or fills it) decides what the next
    one in its worker sees."""
    from mlsl_tpu.obs import tracer

    tracer.disable()
    if tracer.armed_by_env():
        tracer.enable()


def resnet50_counts(per_layer: bool = False, scale: int = 1, floor: int = 64):
    """Parameter counts of a ResNet-50's gradient stream, for tests that need
    a realistically ragged list of tensors: 53 convolutions each with a batch
    norm's (gamma, beta), and the fc head. Per tensor that is 161 counts; per
    layer (convolution with its batch norm, fc weight with bias) 54.
    ``scale`` divides every count without changing how many there are."""
    convs = [(3, 64, 7)]
    cin = 64
    for blocks, mid in [(3, 64), (4, 128), (6, 256), (3, 512)]:
        for b in range(blocks):
            convs += [(cin, mid, 1), (mid, mid, 3), (mid, mid * 4, 1)]
            if b == 0:  # downsample projection
                convs.append((cin, mid * 4, 1))
            cin = mid * 4
    tensors = [[ci * co * k * k, co, co] for ci, co, k in convs]
    tensors.append([2048 * 1000, 1000])
    counts = ([sum(t) for t in tensors] if per_layer
              else [c for t in tensors for c in t])
    return [max(c // scale, floor) for c in counts]


def ref_coords(p, data_parts, model_parts):
    """The reference's rank->color math (src/mlsl_impl.hpp:224-240), used as the
    oracle for grid tests."""
    l_size = data_parts * model_parts
    l_id = p % l_size
    i_r = p // l_size
    i_m = l_id // model_parts   # index within the data group
    i_f = l_id % model_parts    # index within the model group
    model_color = i_r * l_size + i_m
    data_color = i_r * l_size + i_f
    return i_r, i_m, i_f, data_color, model_color
