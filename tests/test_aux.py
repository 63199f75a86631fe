"""Auxiliary subsystems: buffer checker, checkpoint/resume, async data loader."""

import os

import numpy as np
import pytest
import jax

from mlsl_tpu.types import DataType, GroupType, ReductionType


class TestChecker:
    def test_checker_catches_wrong_shape(self, env, monkeypatch):
        from mlsl_tpu.log import MLSLError

        monkeypatch.setenv("MLSL_CHKP", "1")
        dist = env.create_distribution(8, 1)
        other = env.create_distribution(4, 2)
        buf = other.make_buffer(lambda p: np.zeros(8), 8)  # wrong topology layout
        with pytest.raises(MLSLError):
            dist.all_reduce(buf, 8, DataType.FLOAT, ReductionType.SUM, GroupType.DATA)

    def test_checker_catches_short_buffer(self, env, monkeypatch):
        from mlsl_tpu.log import MLSLError

        monkeypatch.setenv("MLSL_CHKP", "1")
        dist = env.create_distribution(8, 1)
        buf = dist.make_buffer(lambda p: np.zeros(4), 4)
        with pytest.raises(MLSLError):
            dist.all_reduce(buf, 8, DataType.FLOAT, ReductionType.SUM, GroupType.DATA)

    def test_checker_catches_nonfinite(self, env, monkeypatch):
        """CHKP_VALUES batches its finiteness verdicts per round: the verdict
        is QUEUED at Start (no device sync) and raised at the round's first
        wait, naming the offending buffer."""
        from mlsl_tpu.log import MLSLError

        monkeypatch.setenv("MLSL_CHKP", "2")
        dist = env.create_distribution(8, 1)
        buf = dist.make_buffer(lambda p: np.full(8, np.nan), 8)
        req = dist.all_reduce(
            buf, 8, DataType.FLOAT, ReductionType.SUM, GroupType.DATA
        )
        with pytest.raises(MLSLError, match="non-finite"):
            env.wait(req)

    def test_checker_passes_valid(self, env, monkeypatch):
        monkeypatch.setenv("MLSL_CHKP", "2")
        dist = env.create_distribution(8, 1)
        buf = dist.make_buffer(lambda p: np.full(8, float(p)), 8)
        out = env.wait(
            dist.all_reduce(buf, 8, DataType.FLOAT, ReductionType.SUM, GroupType.DATA)
        )
        np.testing.assert_allclose(dist.local_part(out, 0), np.full(8, 28.0))

    def test_checker_counters_and_batched_sync(self, env, monkeypatch):
        """CHKP accounting (CHKP line in mlsl_stats.log): two Starts queue
        two finiteness verdicts but the round pays exactly ONE device sync —
        the point of batching — and counters record hits vs violations."""
        from mlsl_tpu.core import stats

        monkeypatch.setenv("MLSL_CHKP", "2")
        stats.reset_chkp_counters()
        dist = env.create_distribution(8, 1)
        b1 = dist.make_buffer(lambda p: np.full(8, 1.0), 8)
        b2 = dist.make_buffer(lambda p: np.full(8, 2.0), 8)
        r1 = dist.all_reduce(b1, 8, DataType.FLOAT, ReductionType.SUM,
                             GroupType.DATA)
        r2 = dist.all_reduce(b2, 8, DataType.FLOAT, ReductionType.SUM,
                             GroupType.DATA)
        env.wait(r1)
        env.wait(r2)
        c = stats.CHKP_COUNTERS
        assert c["checks"] == 2
        assert c["value_checks"] == 2
        assert c["value_syncs"] == 1, (
            "two queued verdicts must resolve in one batched sync"
        )
        assert c["violations"] == 0
        stats.reset_chkp_counters()

    def test_checker_failed_round_does_not_leak_verdicts(self, env, monkeypatch):
        """A round that FAILS before its flush must drain its queued
        CHKP_VALUES verdicts (logged, the real error stays primary) — a
        later healthy request's wait must never inherit a stale nonfinite
        verdict from a dead round."""
        from mlsl_tpu import chaos
        from mlsl_tpu.core import stats

        monkeypatch.setenv("MLSL_CHKP", "2")
        stats.reset_chkp_counters()
        dist = env.create_distribution(8, 1)
        bad = dist.make_buffer(lambda p: np.full(8, np.nan), 8)
        # PERSISTENT (no rung-2 retry): the wait raises the chaos error
        chaos.plan("request.wait", "error", exc=RuntimeError)
        req = dist.all_reduce(bad, 8, DataType.FLOAT, ReductionType.SUM,
                              GroupType.DATA)
        with pytest.raises(RuntimeError, match="chaos injected"):
            env.wait(req)
        chaos.clear()
        # the dead round's verdict was drained AND counted, not inherited
        assert stats.CHKP_COUNTERS["violations"] == 1
        good = dist.make_buffer(lambda p: np.full(8, 1.0), 8)
        out = env.wait(dist.all_reduce(good, 8, DataType.FLOAT,
                                       ReductionType.SUM, GroupType.DATA))
        np.testing.assert_allclose(dist.local_part(out, 0), np.full(8, 8.0))
        assert stats.CHKP_COUNTERS["violations"] == 1  # no stale re-raise

    def test_checker_validates_bucket_members(self, monkeypatch):
        """CHKP through the bucket pack: a member buffer that violates its
        own descriptor is rejected AT REGISTRATION (named per member), not
        blended into the coalesced concatenation."""
        from mlsl_tpu.core.environment import Environment
        from mlsl_tpu.log import MLSLError
        from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
        from mlsl_tpu.models.train import DataParallelTrainer

        monkeypatch.setenv("MLSL_GRAD_BUCKET_MB", "1")
        import jax as _jax

        env = Environment.get_env().init()  # bucketing knob read at init
        dist = env.create_distribution(8, 1)
        sess = env.create_session()
        sess.set_global_minibatch_size(16)
        trainer = DataParallelTrainer(
            env, dist, sess, init(_jax.random.PRNGKey(0)), loss_fn, LAYERS,
            get_layer, lr=0.1,
        )
        ps = trainer.ops[LAYERS[0]].get_parameter_set(0)
        assert ps.bucket is not None, "bucketing must be armed for this test"
        monkeypatch.setenv("MLSL_CHKP", "1")
        bad = dist.make_buffer(lambda p: np.zeros(4, np.float32), 4)  # short
        with pytest.raises(MLSLError, match="OUT_OF_RANGE"):
            ps.start_gradient_comm(bad)


class TestCheckpoint:
    def test_roundtrip_trainer_state(self, env, tmp_path):
        from mlsl_tpu.checkpoint import CheckpointManager, restore_trainer, save_trainer
        from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
        from mlsl_tpu.models.train import DataParallelTrainer

        dist = env.create_distribution(8, 1)
        sess = env.create_session()
        sess.set_global_minibatch_size(16)
        trainer = DataParallelTrainer(
            env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS, get_layer,
            lr=0.1,
        )
        rng = np.random.default_rng(0)
        x = rng.normal(size=(16, 8)).astype(np.float32)
        y = rng.integers(0, 4, size=(16,)).astype(np.int32)
        for _ in range(2):
            trainer.step(trainer.shard_batch(x, y))
        before = jax.device_get(trainer.params)

        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        save_trainer(mgr, trainer, step=2, wait=True)

        # keep training, then restore and confirm exact rollback
        trainer.step(trainer.shard_batch(x, y))
        step = restore_trainer(mgr, trainer)
        assert step == 2
        after = jax.device_get(trainer.params)
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        mgr.close()


class TestAsyncLoader:
    def test_prefetch_delivers_in_order(self, env):
        from mlsl_tpu.data import AsyncLoader, synthetic_source
        from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
        from mlsl_tpu.models.train import DataParallelTrainer

        dist = env.create_distribution(8, 1)
        sess = env.create_session()
        sess.set_global_minibatch_size(16)
        trainer = DataParallelTrainer(
            env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS, get_layer,
        )
        loader = AsyncLoader(
            synthetic_source(16, (8,), 4, steps=5), trainer.shard_batch, depth=2
        )
        losses = [float(np.asarray(trainer.step(b)).reshape(-1)[0]) for b in loader]
        assert len(losses) == 5 and np.isfinite(losses).all()
        loader.close()

    def test_file_source_trains_from_disk(self, env, tmp_path):
        """file_source streams .npz batches through the background loader (the
        reference's endpoint-server file-IO offload, eplib/eplib.h:51-58) and
        lands on the same trajectory as feeding the arrays directly."""
        from mlsl_tpu.data import AsyncLoader, file_source
        from mlsl_tpu.models.mlp import LAYERS, get_layer, init, loss_fn
        from mlsl_tpu.models.train import DataParallelTrainer

        rng = np.random.default_rng(0)
        paths, arrays = [], []
        for i in range(3):
            x = rng.normal(size=(16, 8)).astype(np.float32)
            y = rng.integers(0, 4, size=(16,)).astype(np.int32)
            p = tmp_path / f"batch{i}.npz"
            np.savez(p, x=x, y=y)
            paths.append(str(p))
            arrays.append((x, y))

        def run_files():
            dist = env.create_distribution(8, 1)
            sess = env.create_session()
            sess.set_global_minibatch_size(16)
            tr = DataParallelTrainer(
                env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
                get_layer,
            )
            loader = AsyncLoader(file_source(paths, epochs=2), tr.shard_batch,
                                 depth=2)
            n = sum(1 for b in loader if np.isfinite(float(
                np.asarray(tr.step(b)).reshape(-1)[0])))
            loader.close()
            assert n == 6  # 3 files x 2 epochs
            return jax.device_get(tr.params)

        def run_arrays():
            dist = env.create_distribution(8, 1)
            sess = env.create_session()
            sess.set_global_minibatch_size(16)
            tr = DataParallelTrainer(
                env, dist, sess, init(jax.random.PRNGKey(0)), loss_fn, LAYERS,
                get_layer,
            )
            for _ in range(2):
                for x, y in arrays:
                    tr.step(tr.shard_batch(x, y))
            return jax.device_get(tr.params)

        for a, b in zip(jax.tree.leaves(run_files()),
                        jax.tree.leaves(run_arrays())):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    def test_worker_exception_surfaces(self, env):
        from mlsl_tpu.data import AsyncLoader

        def bad_source():
            yield from ()
            raise RuntimeError("boom")  # pragma: no cover

        def explode():
            raise RuntimeError("boom")

        loader = AsyncLoader(explode, lambda *a: a, depth=1)
        with pytest.raises(RuntimeError, match="boom"):
            next(iter(loader))
        loader.close()


class TestAutoConfig:
    """auto_config keys dispatch knobs on the probed device class + HBM
    (reference AutoConfig src/mlsl.cpp:649-682); explicit MLSL_* env always
    wins (VERDICT r4 item 7)."""

    V5E = None  # built in _si to avoid import at collection time

    def _si(self, platform, kind, mem):
        from mlsl_tpu import sysinfo

        return sysinfo.SysInfo(platform=platform, device_kind=kind,
                               num_devices=8, num_hosts=1,
                               memory_per_device=mem)

    def _tuned(self, monkeypatch, si, env_vars=()):
        from mlsl_tpu import sysinfo
        from mlsl_tpu.config import Config

        for k, v in env_vars:
            monkeypatch.setenv(k, v)
        c = Config.from_env()
        c.auto_config_type = 1
        monkeypatch.setattr(sysinfo, "probe", lambda: si)
        sysinfo.auto_config(c)
        return c

    def test_classes_differ(self, monkeypatch):
        from mlsl_tpu import sysinfo

        v5e = self._si("tpu", "TPU v5 lite", 16 * 2**30)
        v5p = self._si("tpu", "TPU v5p", 95 * 2**30)
        cpu = self._si("cpu", "cpu", 0)
        assert sysinfo.device_class(v5e) == "tpu-efficiency"
        assert sysinfo.device_class(v5p) == "tpu-performance"
        assert sysinfo.device_class(cpu) == "host-sim"
        ce = self._tuned(monkeypatch, v5e)
        cp = self._tuned(monkeypatch, v5p)
        cc = self._tuned(monkeypatch, cpu)
        # v5e defers earlier than v5p; both differ from the CPU sim defaults
        assert ce.msg_priority_threshold < cp.msg_priority_threshold
        assert ce.msg_priority_threshold != cc.msg_priority_threshold
        assert cc.large_msg_chunks == 1 and ce.large_msg_chunks == 4
        # HBM-keyed: gather cap is a quarter of the chip, chunk size bounded
        assert ce.gather_device_limit_mb == 4096       # 16 GiB / 4
        assert cp.gather_device_limit_mb == 95 * 1024 // 4
        assert ce.large_msg_size_mb <= 64

    def test_explicit_env_wins(self, monkeypatch):
        v5e = self._si("tpu", "TPU v5 lite", 16 * 2**30)
        c = self._tuned(monkeypatch, v5e,
                        env_vars=[("MLSL_MSG_PRIORITY_THRESHOLD", "777")])
        assert c.msg_priority_threshold == 777         # user export untouched
        assert c.msg_priority_flush_ms == 2.0          # others still tuned
        assert c.gather_device_limit_mb == 4096

    def test_gate_off_by_default(self, monkeypatch):
        from mlsl_tpu import sysinfo
        from mlsl_tpu.config import Config

        c = Config.from_env()
        monkeypatch.setattr(
            sysinfo, "probe",
            lambda: self._si("tpu", "TPU v5 lite", 16 * 2**30),
        )
        before = dataclasses_asdict_safe(c)
        sysinfo.auto_config(c)  # auto_config_type defaults to 0: no-op
        assert dataclasses_asdict_safe(c) == before


def dataclasses_asdict_safe(c):
    import dataclasses as _d

    return {f.name: getattr(c, f.name) for f in _d.fields(c)}


class TestPackaging:
    """Install-story parity (reference scripts/install.sh + Makefile staging
    targets): the package must build a valid wheel OFFLINE from a clean
    checkout, with the library packaged and tests/benchmarks excluded."""

    @pytest.mark.slow
    def test_wheel_builds_offline(self, tmp_path):
        import glob
        import subprocess
        import sys
        import zipfile

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        run = subprocess.run(
            [sys.executable, "-m", "pip", "wheel", ".", "--no-deps",
             "--no-build-isolation", "-w", str(tmp_path)],
            cwd=repo, capture_output=True, text=True, timeout=300,
        )
        assert run.returncode == 0, run.stderr[-2000:]
        wheels = glob.glob(str(tmp_path / "*.whl"))
        assert len(wheels) == 1
        names = zipfile.ZipFile(wheels[0]).namelist()
        assert "mlsl_tpu/__init__.py" in names
        assert any(n.startswith("mlsl_tpu/comm/") for n in names)
        assert any(n.startswith("mlsl_tpu/models/") for n in names)
        assert not any(n.startswith(("tests/", "benchmarks/")) for n in names)

    def test_install_script_present(self):
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(repo, "scripts", "install.sh")
        assert os.path.exists(path) and os.access(path, os.X_OK)
