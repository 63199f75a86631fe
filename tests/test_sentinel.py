"""Integrity sentinel: step quality gates, cross-replica consistency audits,
and verified-good rollback (mlsl_tpu.sentinel, ISSUE 9).

The gate tests pin the response ladder (warn / skip_step / rollback) against
seeded ``silent`` chaos faults at the new ``train.*`` sites; skip_step is
pinned by a lockstep twin (a skipped step must be bit-for-bit a step that
never ran — params, optimizer state, AND quantization error-feedback
residuals). The audit tests prove the on-device pmin/pmax fingerprint
comparison catches a single corrupted replica copy, that the fingerprint is
stable across comm paths whose parity is already pinned bit-exact (plain vs
bucketed), and that the verified-checkpoint contract holds end to end:
manifests record passing digests, restore prefers the newest verified step,
and FaultTolerantLoop answers MLSLIntegrityError with rollback + re-audit
inside the restart budget.
"""

import math

import numpy as np
import pytest
import jax

from mlsl_tpu import chaos, sentinel, supervisor
from mlsl_tpu.core import stats
from mlsl_tpu.core.environment import Environment
from mlsl_tpu.log import (
    MLSLCorruptionError,
    MLSLError,
    MLSLIntegrityError,
)


@pytest.fixture(autouse=True)
def _clear_chaos():
    chaos.clear()
    yield
    chaos.clear()


def _env(monkeypatch, **vars_):
    for k, v in vars_.items():
        monkeypatch.setenv(k, str(v))
    return Environment.get_env().init()


def _trainer(env, **kw):
    from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
    from mlsl_tpu.models.train import DataParallelTrainer

    dist = env.create_distribution(8, 1)
    sess = env.create_session()
    sess.set_global_minibatch_size(16)
    kw.setdefault("lr", 0.1)
    return DataParallelTrainer(
        env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
        get_layer, **kw,
    )


def _batch(step):
    rng = np.random.default_rng(step)
    x = rng.normal(size=(16, 8)).astype(np.float32)
    y = rng.integers(0, 4, size=(16,)).astype(np.int32)
    return x, y


def _params_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# -- layer 1: the step quality gate ------------------------------------------


def test_gate_nonfinite_warn_continues(monkeypatch):
    e = _env(monkeypatch, MLSL_SENTINEL_GATE="warn")
    tr = _trainer(e)
    p = chaos.plan("train.grads", "silent", mag=float("nan"))
    tr.step(tr.shard_batch(*_batch(0)))
    assert p.fires == 1
    assert stats.SENTINEL_COUNTERS["gate_warn"] == 1
    # warn CONTINUES: the poisoned update was applied, so the params now
    # carry the NaN and the next step's param screen fires again
    tr.step(tr.shard_batch(*_batch(1)))
    assert stats.SENTINEL_COUNTERS["gate_warn"] == 2


def test_gate_skip_lockstep_twin_parity(monkeypatch):
    """A skipped step must equal a step that never happened: the faulted
    trainer (skip at step 2) and a twin that was never fed batch 2 land on
    bit-identical params."""
    e = _env(monkeypatch, MLSL_SENTINEL_GATE="skip_step")
    tr_a = _trainer(e)
    tr_b = _trainer(e)
    for s in range(2):
        tr_a.step(tr_a.shard_batch(*_batch(s)))
        tr_b.step(tr_b.shard_batch(*_batch(s)))
    chaos.plan("train.grads", "silent", mag=float("inf"))
    tr_a.step(tr_a.shard_batch(*_batch(2)))  # fires -> skipped
    assert stats.SENTINEL_COUNTERS["gate_skip"] == 1
    for s in range(3, 5):
        tr_a.step(tr_a.shard_batch(*_batch(s)))
        tr_b.step(tr_b.shard_batch(*_batch(s)))
    _params_equal(jax.device_get(tr_a.params), jax.device_get(tr_b.params))


def test_gate_skip_preserves_ef_residual(monkeypatch):
    """skip_step on the QUANTIZED path: no comm starts, so the per-layer
    error-feedback residuals never advance — pinned against both the
    pre-step snapshot and a lockstep twin that skipped the batch."""
    from mlsl_tpu.types import CompressionType

    e = _env(monkeypatch, MLSL_SENTINEL_GATE="skip_step")
    tr_a = _trainer(e, compression=CompressionType.QUANTIZATION)
    tr_b = _trainer(e, compression=CompressionType.QUANTIZATION)
    for s in range(2):
        tr_a.step(tr_a.shard_batch(*_batch(s)))
        tr_b.step(tr_b.shard_batch(*_batch(s)))
    res_before = {
        n: np.asarray(tr_a.ops[n].get_parameter_set(0).grad_req._err)
        for n in tr_a.layers
    }
    chaos.plan("train.grads", "silent", mag=float("nan"))
    tr_a.step(tr_a.shard_batch(*_batch(2)))  # skipped
    assert stats.SENTINEL_COUNTERS["gate_skip"] == 1
    for n in tr_a.layers:
        np.testing.assert_array_equal(
            np.asarray(tr_a.ops[n].get_parameter_set(0).grad_req._err),
            res_before[n],
        )
    for s in range(3, 5):
        tr_a.step(tr_a.shard_batch(*_batch(s)))
        tr_b.step(tr_b.shard_batch(*_batch(s)))
    _params_equal(jax.device_get(tr_a.params), jax.device_get(tr_b.params))


def test_gate_rollback_raises_and_preserves_state(monkeypatch):
    e = _env(monkeypatch, MLSL_SENTINEL_GATE="rollback")
    tr = _trainer(e)
    tr.step(tr.shard_batch(*_batch(0)))
    before = jax.device_get(tr.params)
    chaos.plan("train.grads", "silent", mag=float("nan"))
    with pytest.raises(MLSLIntegrityError) as ei:
        tr.step(tr.shard_batch(*_batch(1)))
    # the new error is CORRUPTION in the supervisor taxonomy (it subclasses
    # MLSLCorruptionError), so breakers/restart policy treat it as integrity
    assert isinstance(ei.value, MLSLCorruptionError)
    assert supervisor.classify(ei.value) is supervisor.ErrorClass.CORRUPTION
    assert stats.SENTINEL_COUNTERS["gate_rollback"] == 1
    # the raise happened BEFORE any comm/update: params are untouched
    _params_equal(before, jax.device_get(tr.params))


def test_gate_grad_norm_spike(monkeypatch):
    e = _env(monkeypatch, MLSL_SENTINEL_GATE="skip_step",
             MLSL_SENTINEL_WARMUP="2", MLSL_SENTINEL_SPIKE="5")
    tr = _trainer(e)
    for s in range(3):  # healthy EMA history
        tr.step(tr.shard_batch(*_batch(s)))
    before = jax.device_get(tr.params)
    # large FINITE perturbation: the nonfinite screen stays silent, the
    # spike screen must catch it
    chaos.plan("train.grads", "silent", mag=1e8)
    tr.step(tr.shard_batch(*_batch(3)))
    assert stats.SENTINEL_COUNTERS["gate_skip"] == 1
    _params_equal(before, jax.device_get(tr.params))


def test_gate_loss_outlier(monkeypatch):
    # the three ordinary steps are the warm-up: after one or two observations
    # the EMA's variance is still near zero, and under it an ordinary loss on
    # a fresh batch IS a 3-sigma outlier (the gate would rightly fire)
    e = _env(monkeypatch, MLSL_SENTINEL_GATE="skip_step",
             MLSL_SENTINEL_WARMUP="3", MLSL_SENTINEL_ZMAX="3")
    tr = _trainer(e)
    for s in range(3):
        tr.step(tr.shard_batch(*_batch(s)))
    assert stats.SENTINEL_COUNTERS["gate_skip"] == 0
    s_obj = tr.sentinel
    # pin the EMA so the next (ordinary) loss is a guaranteed z-outlier;
    # grad norm stays ordinary so only the z-score screen can fire
    s_obj._loss_mean = 1e6
    s_obj._loss_var = 1.0
    tr.step(tr.shard_batch(*_batch(3)))
    assert stats.SENTINEL_COUNTERS["gate_skip"] == 1


def test_gate_spans_on_timeline(monkeypatch):
    from mlsl_tpu import obs

    e = _env(monkeypatch, MLSL_SENTINEL_GATE="skip_step",
             MLSL_SENTINEL_EVERY="1")
    tr = _trainer(e)
    obs.enable()
    try:
        tr.step(tr.shard_batch(*_batch(0)))
        chaos.plan("train.grads", "silent", mag=float("nan"))
        tr.step(tr.shard_batch(*_batch(1)))
        res = tr.sentinel.audit_now(tr, step=2)
        assert res.equal
        names = {ev[1] for ev in obs.get_tracer().snapshot()}
        assert "sentinel.gate" in names
        assert "sentinel.audit" in names
        assert "integrity.gate" in names
    finally:
        obs.disable()


# -- layer 2: the cross-replica consistency audit ----------------------------


def test_audit_passes_on_healthy_state(monkeypatch):
    e = _env(monkeypatch, MLSL_SENTINEL_EVERY="1")
    tr = _trainer(e)
    tr.step(tr.shard_batch(*_batch(0)))
    res1 = tr.sentinel.audit_now(tr, step=1)
    res2 = tr.sentinel.audit_now(tr, step=1)
    assert res1.equal and res2.equal
    assert res1.digest == res2.digest  # deterministic fingerprint
    assert res1.blocks > 0


def test_audit_detects_param_replica_divergence(monkeypatch):
    e = _env(monkeypatch, MLSL_SENTINEL_EVERY="1")
    tr = _trainer(e)
    tr.step(tr.shard_batch(*_batch(0)))
    assert tr.sentinel.audit_now(tr, step=1).equal
    # the train.params silent site fires at the next step's entry and
    # perturbs one element of ONE replica's copy. A perturbation (not a bit
    # flip) because a full update runs before the audit: a low-mantissa
    # flip's delta can legitimately round away under p - lr*g (delta below
    # the result's ulp) — bitflip detection on the un-updated state is
    # pinned by test_corrupt_silent_single_replica below.
    p = chaos.plan("train.params", "silent", mag=0.01)
    tr.step(tr.shard_batch(*_batch(1)))
    assert p.fires == 1
    with pytest.raises(MLSLIntegrityError):
        tr.sentinel.maybe_audit(tr, step=2)
    assert stats.SENTINEL_COUNTERS["audit_mismatch"] >= 1
    st = supervisor.status()
    assert st["sentinel"]["state"] == "tripped"
    assert st["sentinel"]["last_audit"]["equal"] is False


def test_audit_detects_opt_state_divergence(monkeypatch):
    optax = pytest.importorskip("optax")
    e = _env(monkeypatch, MLSL_SENTINEL_EVERY="1")
    tr = _trainer(e, optimizer=optax.adam(1e-3))
    tr.step(tr.shard_batch(*_batch(0)))
    assert tr.sentinel.audit_now(tr, step=1).equal
    p = chaos.plan("train.opt_state", "silent", mag=0.01)
    tr.step(tr.shard_batch(*_batch(1)))
    assert p.fires == 1
    res = tr.sentinel.audit_now(tr, step=2)
    assert not res.equal


def test_audit_fingerprint_stable_across_bucket_path(monkeypatch, tmp_path):
    """The plain and bucketed gradient paths are pinned bit-exact (PR 2);
    the state fingerprint must therefore be identical too — integer math
    end to end, no reduction-order sensitivity."""
    e = _env(monkeypatch, MLSL_SENTINEL_EVERY="1")
    tr = _trainer(e)
    for s in range(3):
        tr.step(tr.shard_batch(*_batch(s)))
    d_plain = tr.sentinel.audit_now(tr, step=3).digest
    e.finalize()

    e2 = _env(monkeypatch, MLSL_SENTINEL_EVERY="1", MLSL_GRAD_BUCKET_MB="1")
    tr2 = _trainer(e2)
    for s in range(3):
        tr2.step(tr2.shard_batch(*_batch(s)))
    d_bucket = tr2.sentinel.audit_now(tr2, step=3).digest
    assert d_plain == d_bucket


def test_audit_fingerprint_stable_quant_rerun(monkeypatch):
    """Two identical quantized runs fingerprint identically (EF residuals
    and the int8 ring are deterministic)."""
    from mlsl_tpu.types import CompressionType

    digests = []
    for _ in range(2):
        e = _env(monkeypatch, MLSL_SENTINEL_EVERY="1")
        tr = _trainer(e, compression=CompressionType.QUANTIZATION)
        for s in range(2):
            tr.step(tr.shard_batch(*_batch(s)))
        digests.append(tr.sentinel.audit_now(tr, step=2).digest)
        e.finalize()
    assert digests[0] == digests[1]


def test_integrity_error_breaker_interaction():
    err = MLSLIntegrityError("divergence")
    assert isinstance(err, MLSLCorruptionError)
    assert isinstance(err, MLSLError)
    assert supervisor.classify(err) is supervisor.ErrorClass.CORRUPTION
    # CORRUPTION counts against a subsystem breaker like any other
    # classified failure (rung 3 composes with the sentinel's rung)
    supervisor.configure(threshold=2, window_s=60.0, cooldown_s=60.0)
    br = supervisor.breaker("quant")
    assert not br.record_failure(err)
    assert br.record_failure(err)
    assert br.state == supervisor.OPEN


# -- layer 3: verified checkpoints + rollback --------------------------------


def test_verified_restore_preference(monkeypatch, tmp_path):
    from mlsl_tpu.checkpoint import (
        CheckpointManager,
        restore_trainer,
        save_trainer,
    )

    e = _env(monkeypatch)
    tr = _trainer(e)
    mgr = CheckpointManager(str(tmp_path / "ck"))
    tr.step(tr.shard_batch(*_batch(0)))
    snap1 = jax.device_get(tr.params)
    fp = "f" * 64
    save_trainer(mgr, tr, step=1, wait=True, fingerprint=fp)
    assert mgr.recorded_fingerprint(1) == fp
    tr.step(tr.shard_batch(*_batch(1)))
    save_trainer(mgr, tr, step=2, wait=True)  # newer but UNVERIFIED
    assert mgr.recorded_fingerprint(2) is None

    # restore prefers the older VERIFIED step over the newer unverified one
    assert restore_trainer(mgr, tr) == 1
    _params_equal(snap1, jax.device_get(tr.params))

    # a newer verified step wins once it exists
    tr.step(tr.shard_batch(*_batch(2)))
    snap3 = jax.device_get(tr.params)
    save_trainer(mgr, tr, step=3, wait=True, fingerprint="e" * 64)
    assert restore_trainer(mgr, tr) == 3
    _params_equal(snap3, jax.device_get(tr.params))
    mgr.close()


def _loop_batch_fn(trainer, step):
    return trainer.shard_batch(*_batch(step))


def _make_loop_trainer():
    from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
    from mlsl_tpu.models.train import DataParallelTrainer

    env = Environment.get_env().init()
    dist = env.create_distribution(8, 1)
    sess = env.create_session()
    sess.set_global_minibatch_size(16)
    return DataParallelTrainer(
        env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
        get_layer, lr=0.1,
    )


def test_loop_rollback_to_verified_and_reaudit(monkeypatch, tmp_path):
    """End to end: a silent param corruption is caught by the cadence audit,
    FaultTolerantLoop rolls back to the newest VERIFIED checkpoint, the
    post-restore re-audit passes against the recorded fingerprint, and the
    replayed run lands bit-exact on the fault-free trajectory."""
    from mlsl_tpu.resilience import FaultTolerantLoop

    monkeypatch.setenv("MLSL_SENTINEL_EVERY", "1")
    # fault-free reference
    base_losses = {}
    loop0 = FaultTolerantLoop(_make_loop_trainer, str(tmp_path / "base"),
                              save_every=2, max_retries=3,
                              max_total_recoveries=5)
    tr0 = loop0.run(_loop_batch_fn, steps=8,
                    on_step=lambda s, l: base_losses.__setitem__(
                        s, float(np.asarray(l).reshape(-1)[0])))
    base_params = jax.device_get(tr0.params)
    Environment.get_env().finalize()
    assert stats.SENTINEL_COUNTERS["audit_mismatch"] == 0
    assert stats.SENTINEL_COUNTERS["verified_saves"] >= 4
    stats.reset_sentinel_counters()

    # corrupted run: one replica bit-flip at step 4's entry
    chaos.plan("train.params", "silent", after=4)
    losses = {}
    loop = FaultTolerantLoop(_make_loop_trainer, str(tmp_path / "soak"),
                             save_every=2, max_retries=3,
                             max_total_recoveries=5)
    tr = loop.run(_loop_batch_fn, steps=8,
                  on_step=lambda s, l: losses.__setitem__(
                      s, float(np.asarray(l).reshape(-1)[0])))
    assert loop.recoveries == 1
    assert stats.SENTINEL_COUNTERS["audit_mismatch"] >= 1
    assert stats.SENTINEL_COUNTERS["reaudits"] >= 1
    assert losses == base_losses
    _params_equal(base_params, jax.device_get(tr.params))
    Environment.get_env().finalize()


def test_rollback_budget_exhaustion_aborts(monkeypatch, tmp_path):
    """A corruption that re-fires on every step (and every replay) exhausts
    MLSL_RESTART_BUDGET and aborts with the ORIGINAL MLSLIntegrityError."""
    from mlsl_tpu.resilience import FaultTolerantLoop

    monkeypatch.setenv("MLSL_SENTINEL_EVERY", "1")
    chaos.plan("train.params", "silent", times=None)
    loop = FaultTolerantLoop(_make_loop_trainer, str(tmp_path / "ck"),
                             save_every=2, max_retries=10,
                             max_total_recoveries=2)
    with pytest.raises(MLSLIntegrityError):
        loop.run(_loop_batch_fn, steps=6)
    assert loop.recoveries == 2


# -- chaos silent grammar + applier ------------------------------------------


def test_silent_grammar_parses():
    plans = chaos.refresh_from_env(
        "train.grads:silent=nanx*%0.25,train.params:silent=0.5,"
        "train.opt_state:silent"
    )
    chaos.clear()
    assert [p.site for p in plans] == [
        "train.grads", "train.params", "train.opt_state"
    ]
    assert plans[0].kind == "silent" and math.isnan(plans[0].mag)
    assert plans[0].times is None and plans[0].prob == 0.25
    assert plans[1].mag == 0.5
    assert plans[2].mag is None  # default: bit flip


def test_corrupt_silent_single_replica(monkeypatch):
    """corrupt_silent on a replicated array touches exactly ONE device's
    copy — the divergence the audit hunts — and is seeded/replayable."""
    e = _env(monkeypatch)
    tr = _trainer(e)
    leaf = jax.tree.leaves(tr.params)[0]
    p = chaos.Plan(site="train.params", kind="silent")
    chaos.seed(7)
    corrupted = sentinel.corrupt_silent(tr.params, p)
    diffs = 0
    for la, lb in zip(jax.tree.leaves(tr.params), jax.tree.leaves(corrupted)):
        for sa, sb in zip(la.addressable_shards, lb.addressable_shards):
            if not np.array_equal(np.asarray(sa.data), np.asarray(sb.data),
                                  equal_nan=True):
                diffs += 1
    assert diffs == 1, "exactly one replica copy must differ"
    assert leaf.shape == jax.tree.leaves(corrupted)[0].shape
    clean = tr.params
    # the audit catches a single-BIT flip on the un-updated state: the
    # fingerprint compares raw bits, so even a delta far below any float
    # tolerance diverges pmin/pmax
    s = sentinel.Sentinel(tr.mesh, every=1)
    assert s.audit_now(tr, step=0).equal
    tr.params = corrupted
    assert not s.audit_now(tr, step=0).equal
    tr.params = clean
    # replay: same seed, same corruption
    chaos.seed(7)
    p2 = chaos.Plan(site="train.params", kind="silent")
    corrupted2 = sentinel.corrupt_silent(clean, p2)
    for la, lb in zip(jax.tree.leaves(corrupted), jax.tree.leaves(corrupted2)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb)
        )


def test_corrupt_silent_bf16_leaf():
    """ml_dtypes bfloat16 is NOT np.floating — the applier must still treat
    bf16 leaves as corruptible (a bf16 model's silent fault has to actually
    land, not burn the plan budget as a no-op)."""
    import jax.numpy as jnp

    tree = {"w": jnp.ones((16,), jnp.bfloat16)}
    p = chaos.Plan(site="train.params", kind="silent", mag=float("nan"))
    out = sentinel.corrupt_silent(tree, p)
    vals = np.asarray(out["w"]).astype(np.float32)
    assert not np.isfinite(vals).all(), "bf16 leaf was never corrupted"


# -- config validation + stats surface ---------------------------------------


def test_sentinel_config_validation(monkeypatch):
    monkeypatch.setenv("MLSL_SENTINEL_GATE", "explode")
    with pytest.raises(MLSLError, match="MLSL_SENTINEL_GATE"):
        Environment.get_env().init()
    monkeypatch.setenv("MLSL_SENTINEL_GATE", "warn")
    monkeypatch.setenv("MLSL_SENTINEL_SPIKE", "0.5")
    with pytest.raises(MLSLError, match="MLSL_SENTINEL_SPIKE"):
        Environment.get_env().init()
    monkeypatch.setenv("MLSL_SENTINEL_SPIKE", "10")
    monkeypatch.setenv("MLSL_SENTINEL_EVERY", "-1")
    with pytest.raises(MLSLError, match="MLSL_SENTINEL_EVERY"):
        Environment.get_env().init()


def test_sentinel_stats_line(monkeypatch):
    e = _env(monkeypatch, MLSL_SENTINEL_GATE="skip_step",
             MLSL_SENTINEL_EVERY="1")
    tr = _trainer(e)
    tr.step(tr.shard_batch(*_batch(0)))
    tr.sentinel.audit_now(tr, step=1)
    text = tr.session.get_stats().print_()
    assert "SENTINEL" in text
    assert "audits 1" in text


def test_sentinel_every_in_tuner_knob_ranges():
    from mlsl_tpu.tuner import KNOB_RANGES

    assert "sentinel_every" in KNOB_RANGES
