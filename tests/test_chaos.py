"""Chaos layer + watchdog: injected faults at every registered site must be
recovered by FaultTolerantLoop with bit-for-bit identical final params; corrupt
checkpoints fall back to the newest verified step; synthetic hangs trip the
watchdog instead of blocking."""

import os
import signal
import time

import numpy as np
import pytest
import jax

from mlsl_tpu import chaos
from mlsl_tpu.core.environment import Environment
from mlsl_tpu.log import MLSLTimeoutError

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clear_chaos():
    chaos.clear()
    yield
    chaos.clear()


# -- shared harness -----------------------------------------------------------


def _make_factory(cfg: str = "plain"):
    from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
    from mlsl_tpu.models.train import DataParallelTrainer
    from mlsl_tpu.types import CompressionType

    def make_trainer():
        env = Environment.get_env().init()
        dist = env.create_distribution(8, 1)
        sess = env.create_session()
        sess.set_global_minibatch_size(16)
        kw = {}
        if cfg == "quant":
            kw["compression"] = CompressionType.QUANTIZATION
        elif cfg == "overlap":
            kw["overlap_updates"] = True
        elif cfg == "adam":
            import optax

            kw["optimizer"] = optax.adam(1e-3)
        return DataParallelTrainer(
            env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
            get_layer, lr=0.1, **kw,
        )

    return make_trainer


def _host_batch(step):
    rng = np.random.default_rng(step)
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int32)
    return x, y


def _batch_fn(trainer, step):
    return trainer.shard_batch(*_host_batch(step))


def _loader_batch_fn():
    """Step-deterministic batches THROUGH AsyncLoader, so a fault injected at
    the data.prefetch site surfaces in batch_fn and takes the recovery path;
    the loader is rebuilt after the fault, resuming at the first uncached
    step with an identical stream."""
    from mlsl_tpu.data import AsyncLoader

    cache = {}
    box = [None]

    def source_from(start):
        def gen():
            i = start
            while True:
                yield _host_batch(i)
                i += 1

        return gen()

    def batch_fn(trainer, step):
        while step not in cache:
            if box[0] is None:
                box[0] = AsyncLoader(
                    source_from(len(cache)), place=lambda x, y: (x, y), depth=2
                )
            try:
                cache[len(cache)] = next(box[0])
            except (RuntimeError, StopIteration):
                box[0] = None
                raise
        return trainer.shard_batch(*cache[step])

    return batch_fn


def _assert_params_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


_BASELINES = {}


def _baseline(cfg, tmp_path_factory):
    """Fault-free reference params per trainer config (computed once)."""
    if cfg not in _BASELINES:
        from mlsl_tpu.resilience import FaultTolerantLoop

        d = tmp_path_factory.mktemp(f"chaos_base_{cfg}")
        trainer = FaultTolerantLoop(
            _make_factory(cfg), str(d), save_every=2
        ).run(_batch_fn, steps=8)
        _BASELINES[cfg] = jax.device_get(trainer.params)
        Environment.get_env().finalize()
    return _BASELINES[cfg]


# -- the fault matrix ---------------------------------------------------------

# site -> (trainer config, step at which the fault is armed). The quantized
# codec carries error-feedback state that is NOT checkpointed, so its fault is
# armed at step 0 (recovery replays from scratch with identical virgin state);
# every other path is stateless across recovery, so mid-run faults replay
# bit-for-bit.
SITE_CONFIGS = {
    "request.start": ("plain", 3),
    "request.wait": ("plain", 3),
    "request.test": ("overlap", 3),
    "collective.dispatch": ("plain", 3),
    "codec.roundtrip": ("quant", 0),
    "checkpoint.save": ("plain", 3),
    "checkpoint.restore": ("plain", 3),
    "data.prefetch": ("plain", 3),
    # the ISSUE 9 trainer-state sites: an ERROR plan raises at step entry /
    # the gradient boundary like any other site (recovered here); their
    # 'silent' kind — corruption without raising — is exercised by
    # tests/test_sentinel.py and the silent soak in tests/test_soak.py
    "train.params": ("plain", 3),
    # the opt_state site is only consulted when the trainer CARRIES state
    # (a stateless SGD trainer must not burn a plan's budget corrupting
    # nothing), so its matrix row needs the optax config
    "train.opt_state": ("adam", 3),
    "train.grads": ("plain", 3),
    # the elastic-mesh fault (ISSUE 14): with NO coordinator armed (this
    # harness), an MLSLDeviceLossError at dispatch takes the restart rung
    # like any recoverable fault and replays bit-exact; the reshard rung it
    # takes when MLSL_ELASTIC=1 is pinned by tests/test_elastic.py and the
    # elastic soak in tests/test_soak.py
    "device.lost": ("plain", 3),
}

# The pod-control sites fire on the control plane's heartbeat thread, not
# inside a training step, so the loop-recovery matrix above cannot exercise
# them: their error/delay/hang behaviors (dropped frames within the miss
# budget, a stalled sender detected as death, a lost notice degrading to
# retry) are pinned by the chaos tests in tests/test_control.py.
CONTROL_SITES = {"control.heartbeat", "control.notice"}

# The serving sites fire inside InferenceEngine's admit/decode paths, not a
# training step, so the loop-recovery matrix cannot exercise them either:
# their behaviors (admit fault fails ONE request closed, transient decode
# errors retried in place, device loss shedding the ladder with the engine
# surviving, a hang breaching the TPOT window) are pinned by the chaos tests
# in tests/test_serve.py.
SERVE_SITES = {"serve.admit", "serve.decode"}


def test_matrix_covers_every_registered_site():
    assert set(SITE_CONFIGS) | CONTROL_SITES | SERVE_SITES == set(chaos.SITES)
    assert not (set(SITE_CONFIGS) & (CONTROL_SITES | SERVE_SITES))
    assert not (CONTROL_SITES & SERVE_SITES)


@pytest.mark.slow
@pytest.mark.parametrize("site", sorted(SITE_CONFIGS))
def test_fault_matrix(site, tmp_path, tmp_path_factory):
    """A fault injected at every registered chaos site is recovered by
    FaultTolerantLoop and the final params match the fault-free run
    bit-for-bit."""
    from mlsl_tpu.resilience import FaultTolerantLoop

    cfg, arm_step = SITE_CONFIGS[site]
    baseline = _baseline(cfg, tmp_path_factory)

    armed = [False]

    def arm(step, attempt):
        # Arming from the fault_hook (inside the loop's try) pins the fault to
        # a step attempt, independent of how many site hits setup performs.
        if step == arm_step and attempt == 0 and not armed[0]:
            armed[0] = True
            chaos.plan(site, "error")
            if site == "checkpoint.restore":
                # restore only runs during recovery: trigger one, so the
                # injected restore fault exercises the verified-fallback path
                raise RuntimeError("trigger recovery to reach restore")

    loop = FaultTolerantLoop(
        _make_factory(cfg), str(tmp_path / "ck"), save_every=2,
        max_retries=3, fault_hook=arm,
    )
    bf = _loader_batch_fn() if site == "data.prefetch" else _batch_fn
    trainer = loop.run(bf, steps=8)
    assert loop.recoveries >= 1, f"fault at {site} never took the recovery path"
    _assert_params_equal(baseline, jax.device_get(trainer.params))


# -- watchdog -----------------------------------------------------------------


def test_watchdog_trips_on_synthetic_hang(env):
    """A hang injected at the dispatch layer (running on the progress thread)
    must trip the watchdog within the configured timeout, log the stuck
    descriptor, and raise the recoverable MLSLTimeoutError."""
    from mlsl_tpu.comm.request import CommDesc, CommRequest
    from mlsl_tpu.core import stats
    from mlsl_tpu.types import DataType, ReductionType

    env.config.msg_priority = True
    env.config.msg_priority_threshold = 0   # defer everything
    env.config.msg_priority_flush_ms = 1.0  # progress thread picks it up fast
    env.config.watchdog_timeout_s = 0.5
    try:
        dist = env.create_distribution(8, 1)
        req = CommRequest(
            CommDesc("allreduce", dist.data_group, 4, DataType.FLOAT,
                     op=ReductionType.SUM),
            env.dispatcher,
            name="hangcheck",
        )
        req.setup()
        buf = dist.make_buffer(lambda p: np.full(4, 1.0), 4)
        events_before = len(stats.WATCHDOG_EVENTS)
        with chaos.injected("collective.dispatch", "hang", seconds=8):
            req.start(buf)
            time.sleep(0.3)  # progress thread grabs the deferred entry, hangs
            t0 = time.monotonic()
            with pytest.raises(MLSLTimeoutError, match="watchdog"):
                req.wait()
            assert time.monotonic() - t0 < 4  # tripped, not sat out the hang
        evts = list(stats.WATCHDOG_EVENTS)[events_before:]
        assert evts and "allreduce" in evts[-1]["descriptor"]
        assert "hangcheck" in evts[-1]["descriptor"]
    finally:
        env.config.msg_priority = False
        env.config.watchdog_timeout_s = 0.0


def test_timeout_error_is_recoverable():
    from mlsl_tpu.resilience import RECOVERABLE

    assert issubclass(MLSLTimeoutError, RECOVERABLE)


# -- checkpoint hardening -----------------------------------------------------


@pytest.mark.slow
def test_corrupt_latest_checkpoint_falls_back(tmp_path):
    """Manually rotted bytes in the latest step: restore skips it via the
    checksum manifest and resumes from the previous verified step."""
    from mlsl_tpu.resilience import FaultTolerantLoop

    d = str(tmp_path / "ck")
    FaultTolerantLoop(_make_factory(), d, save_every=1).run(_batch_fn, steps=4)
    Environment.get_env().finalize()
    # corrupt the biggest file of the newest committed step (step 3)
    loop2 = FaultTolerantLoop(_make_factory(), d, save_every=1)
    step_dir = loop2.ckpt._step_dir(3)
    assert step_dir is not None and loop2.ckpt.verify(3) is True
    loop2.ckpt._apply_bitrot(3, step_dir)  # rot bytes AFTER the manifest
    assert loop2.ckpt.verify(3) is False
    seen = []
    loop2.run(_batch_fn, steps=6, on_step=lambda s, l: seen.append(s))
    # fell back to verified step 2 -> resumed at 3 (not 4)
    assert seen == [3, 4, 5]


@pytest.mark.slow
def test_chaos_bitrot_detected_by_manifest(tmp_path):
    """The chaos 'bitrot' kind corrupts a committed checkpoint AFTER its
    manifest is written; the next restore detects it and falls back."""
    from mlsl_tpu.resilience import FaultTolerantLoop

    d = str(tmp_path / "ck")
    with chaos.injected("checkpoint.save", "bitrot", after=3, times=1):
        loop = FaultTolerantLoop(_make_factory(), d, save_every=1)
        loop.run(_batch_fn, steps=4)  # hits: steps 0..3; fires on step 3
    assert loop.ckpt.verify(3) is False
    assert loop.ckpt.verify(2) is True
    Environment.get_env().finalize()
    seen = []
    FaultTolerantLoop(_make_factory(), d, save_every=1).run(
        _batch_fn, steps=6, on_step=lambda s, l: seen.append(s)
    )
    assert seen == [3, 4, 5]


@pytest.mark.slow
def test_save_retries_transient_io_error(tmp_path):
    """Two injected OSErrors at the save site are absorbed by the retry/backoff
    path: no loop recovery, checkpoints land."""
    from mlsl_tpu.resilience import FaultTolerantLoop

    loop = FaultTolerantLoop(_make_factory(), str(tmp_path / "ck"), save_every=1)
    with chaos.injected("checkpoint.save", "error", exc=OSError, times=2):
        loop.run(_batch_fn, steps=3)
    assert loop.recoveries == 0
    assert loop.ckpt.latest_step() == 2


@pytest.mark.slow
def test_save_retry_exhaustion_raises(tmp_path):
    """A persistent IO failure exhausts the retries and surfaces as OSError
    (not silently swallowed, not treated as recoverable device loss)."""
    from mlsl_tpu.resilience import FaultTolerantLoop

    loop = FaultTolerantLoop(_make_factory(), str(tmp_path / "ck"), save_every=1)
    with chaos.injected("checkpoint.save", "error", exc=OSError, times=None):
        with pytest.raises(OSError):
            loop.run(_batch_fn, steps=3)
    assert loop.recoveries == 0


def test_async_save_errors_surface(tmp_path, monkeypatch):
    """A failed background save must not be mistaken for a committed resume
    point: the next save()/wait() re-raises it (orbax check_for_errors)."""
    import jax.numpy as jnp

    from mlsl_tpu.checkpoint import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(0, {"a": jnp.zeros(4)}, wait=True)

    def boom():
        raise RuntimeError("async save failed")

    monkeypatch.setattr(mgr._mgr, "check_for_errors", boom, raising=False)
    with pytest.raises(RuntimeError, match="async save failed"):
        mgr.save(1, {"a": jnp.zeros(4)})


# -- preemption ---------------------------------------------------------------


@pytest.mark.slow
def test_sigterm_drains_and_writes_final_checkpoint(tmp_path):
    from mlsl_tpu.resilience import FaultTolerantLoop

    d = str(tmp_path / "ck")

    def on_step(s, l):
        if s == 2:
            os.kill(os.getpid(), signal.SIGTERM)

    loop = FaultTolerantLoop(_make_factory(), d, save_every=10)
    loop.run(_batch_fn, steps=8, on_step=on_step)
    assert loop.preempted
    # cadence would only have saved step 0; preemption wrote a final step-2
    # checkpoint and drained it (manifest present => committed and verified)
    assert loop.ckpt.latest_step() == 2
    assert loop.ckpt.verify(2) is True
    Environment.get_env().finalize()
    seen = []
    loop2 = FaultTolerantLoop(_make_factory(), d, save_every=10)
    loop2.run(_batch_fn, steps=5, on_step=lambda s, l: seen.append(s))
    assert not loop2.preempted
    assert seen == [3, 4]  # resumed exactly after the preemption checkpoint


# -- spec / registry ----------------------------------------------------------


def test_env_spec_round_trip(monkeypatch):
    plans = chaos.refresh_from_env(
        "request.start:error=oserror@2x3,checkpoint.save:bitrot,"
        "request.wait:delay=0.25x*,collective.dispatch:hang=8"
    )
    got = {(p.site, p.kind, p.exc.__name__, p.seconds, p.after, p.times)
           for p in plans}
    assert got == {
        ("request.start", "error", "OSError", 0.1, 2, 3),
        ("checkpoint.save", "bitrot", "ChaosError", 0.1, 0, 1),
        ("request.wait", "delay", "ChaosError", 0.25, 0, None),
        ("collective.dispatch", "hang", "ChaosError", 8.0, 0, 1),
    }
    chaos.clear()
    assert not chaos.active()


def test_unknown_site_and_kind_rejected():
    with pytest.raises(ValueError, match="unknown chaos site"):
        chaos.plan("request.strat")
    with pytest.raises(ValueError, match="unknown chaos kind"):
        chaos.plan("request.start", kind="explode")
    with pytest.raises(ValueError, match="unknown exception"):
        chaos.refresh_from_env("request.start:error=kaboom")
    chaos.clear()
