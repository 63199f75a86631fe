"""Statistics engine tests: online accounting, isolation replay, queries, printer."""

import numpy as np
import pytest

from mlsl_tpu.types import OpType


@pytest.fixture()
def stats_env(env, monkeypatch):
    env.config.enable_stats = True
    yield env
    env.config.enable_stats = False


def _grad_session(env, dist, count=256):
    s = env.create_session()
    s.set_global_minibatch_size(8)
    r = s.create_operation_reg_info(OpType.CC)
    r.add_input(8, 4)
    r.add_output(8, 4)
    r.add_parameter_set(count, 1)
    op = s.get_operation(s.add_operation(r, dist))
    s.commit()
    return s, op


def test_online_accounting_and_queries(stats_env):
    env = stats_env
    dist = env.create_distribution(8, 1)
    s, op = _grad_session(env, dist)
    ps = op.get_parameter_set(0)
    buf = dist.make_buffer(lambda p: np.ones(256, np.float32), 256)
    for _ in range(3):
        ps.start_gradient_comm(buf)
        ps.wait_gradient_comm()
    # bytes: 3 starts x 256 elems x 4 B
    assert s.get_stats().get_comm_size(op.op_idx) == 3 * 256 * 4
    assert s.get_stats().get_comm_cycles(op.op_idx) > 0
    assert s.get_stats().get_total_comm_size() == 3 * 256 * 4
    assert s.get_stats().get_total_compute_cycles() >= 0


def test_isolation_replay_runs_at_commit(stats_env):
    env = stats_env
    dist = env.create_distribution(8, 1)
    s, op = _grad_session(env, dist)
    assert s.get_stats().get_isolation_comm_cycles(op.op_idx) > 0
    assert s.get_stats().get_total_isolation_comm_cycles() > 0


def test_printer_and_reset(stats_env, tmp_path):
    env = stats_env
    dist = env.create_distribution(8, 1)
    s, op = _grad_session(env, dist)
    ps = op.get_parameter_set(0)
    buf = dist.make_buffer(lambda p: np.ones(256, np.float32), 256)
    ps.start_gradient_comm(buf)
    ps.wait_gradient_comm()
    text = s.get_stats().print_(str(tmp_path / "stats.log"))
    assert "GRAD0" in text and "ISOLATE" in text
    assert (tmp_path / "stats.log").exists()
    s.get_stats().reset()
    assert s.get_stats().get_total_comm_size() == 0


def test_start_stop_gating(stats_env):
    env = stats_env
    dist = env.create_distribution(8, 1)
    s, op = _grad_session(env, dist)
    ps = op.get_parameter_set(0)
    buf = dist.make_buffer(lambda p: np.ones(256, np.float32), 256)
    s.get_stats().reset()
    s.get_stats().stop()
    ps.start_gradient_comm(buf)
    ps.wait_gradient_comm()
    assert s.get_stats().get_total_comm_size() == 0  # gated off
    s.get_stats().start()
    ps.start_gradient_comm(buf)
    ps.wait_gradient_comm()
    assert s.get_stats().get_total_comm_size() == 256 * 4


def _retry_overlap_comparison(measure_blocked, measure_overlapped,
                              exposed_ratio, context, attempts=5):
    """Comparative-only overlap assertion with load-spike retries: a sustained
    spike (e.g. a concurrent JAX import pinning the shared core) can straddle
    every rep of one phase and invert the blocking-vs-overlapped comparison,
    so the comparison itself retries with backoff before failing (5 attempts:
    3 was still observed losing 1-in-N under a sustained spike on the shared
    box — only failing runs pay the extra backoff)."""
    import time

    for attempt in range(attempts):
        blocked, blocked_exposed = measure_blocked()
        overlapped, overlapped_exposed = measure_overlapped()
        assert blocked is not None and overlapped is not None
        if (overlapped > blocked
                and overlapped_exposed < exposed_ratio * blocked_exposed):
            return
        if attempt < attempts - 1:  # no dead sleep after the final attempt
            time.sleep(5 * (attempt + 1))
    raise AssertionError(
        f"overlapped pattern never beat blocking across {attempts} attempts: "
        f"fractions {overlapped} vs {blocked}, exposed {overlapped_exposed} "
        f"vs {blocked_exposed}, {context}"
    )


def test_overlap_blocking_vs_overlapped(stats_env):
    """overlap_report: Start->Wait back-to-back exposes the whole collective;
    Start->host-compute->Wait hides it (the async engine's entire purpose)."""
    import time

    env = stats_env
    dist = env.create_distribution(8, 1)
    n = 1 << 20
    s, op = _grad_session(env, dist, count=n)
    ps = op.get_parameter_set(0)
    st = s.get_stats()
    iso = st.get_isolation_comm_cycles(op.op_idx)
    assert iso > 0
    buf = dist.make_buffer(lambda p: np.ones(n, np.float32), n)

    def measure(sleep_s):
        # Best-of-3 single reps: machine-load spikes only ever INFLATE exposed
        # time, so the minimum is the pattern's capability estimate.
        best = None
        for _ in range(3):
            st.reset()
            ps.start_gradient_comm(buf)
            if sleep_s:
                time.sleep(sleep_s)  # 'compute' outlasting the collective
            ps.wait_gradient_comm()
            frac = st.get_overlap_fraction()
            exposed = st.overlap_report()["total"]["exposed_ns"]
            if best is None or exposed < best[1]:
                best = (frac, exposed)
        return best

    _retry_overlap_comparison(
        lambda: measure(0), lambda: measure(iso / 1e9 * 4 + 0.02),
        exposed_ratio=0.6, context=f"iso {iso}",
    )


def test_overlap_test_driven_path(stats_env):
    """The reference's canonical TestGradientComm polling loop (per-layer update
    the moment a collective lands, mlsl_test.cpp:660-698) must hide comm that
    the blocking Start->Wait pattern exposes. Both patterns are measured live on
    the SAME session so machine-load noise cancels in the comparison."""
    import time

    env = stats_env
    dist = env.create_distribution(8, 1)
    n = 1 << 20
    s = env.create_session()
    s.set_global_minibatch_size(8)
    ops = []
    for _ in range(3):
        r = s.create_operation_reg_info(OpType.CC)
        r.add_input(8, 4)
        r.add_output(8, 4)
        r.add_parameter_set(n, 1)
        ops.append(s.get_operation(s.add_operation(r, dist)))
    s.commit()
    iso_total = s.get_stats().get_total_isolation_comm_cycles()
    assert iso_total > 0
    buf = dist.make_buffer(lambda p: np.ones(n, np.float32), n)
    st = s.get_stats()

    def measure_blocking():
        # blocking pattern: every collective's full latency is exposed
        st.reset()
        for _ in range(2):
            for op in ops:
                op.get_parameter_set(0).start_gradient_comm(buf)
                op.get_parameter_set(0).wait_gradient_comm()
        return st.get_overlap_fraction(), st.overlap_report()["total"]["exposed_ns"]

    def measure_test_driven():
        # Test-driven pattern: start all (newest first), poll while 'computing'
        st.reset()
        for _ in range(2):
            for op in reversed(ops):
                op.get_parameter_set(0).start_gradient_comm(buf)
            pending = list(ops)
            deadline = time.monotonic() + 30.0
            while pending:
                time.sleep(2 * iso_total / 1e9)  # simulated per-layer compute
                still = []
                for op in pending:
                    done, _ = op.get_parameter_set(0).test_gradient_comm()
                    if not done:
                        still.append(op)
                pending = still
                assert time.monotonic() < deadline, "collectives never completed"
        return st.get_overlap_fraction(), st.overlap_report()["total"]["exposed_ns"]

    # the polling path must expose well under what blocking exposes. 0.7, not
    # 0.5: under residual load right after the full suite the poll loop's
    # sleep quantum stretches and exposed time creeps toward the blocking
    # number on EVERY retry attempt (observed 1-in-a-suite on the shared
    # box; passes 5/5 in isolation) — the comparison stays meaningful at 0.7
    # while no longer sitting on the loaded-box noise floor
    _retry_overlap_comparison(
        measure_blocking, measure_test_driven,
        exposed_ratio=0.7, context=f"iso {iso_total}",
    )


def test_peer_op_redirection(stats_env):
    """WaitComm on op2's input must charge comm time to op1 (the FPROP owner)."""
    env = stats_env
    dist = env.create_distribution(2, 4)
    s = env.create_session()
    s.set_global_minibatch_size(8)

    def mk(fm_in, fm_out):
        r = s.create_operation_reg_info(OpType.CC)
        r.add_input(fm_in, 4)
        r.add_output(fm_out, 4)
        return s.get_operation(s.add_operation(r, dist))

    op1, op2 = mk(16, 32), mk(32, 8)
    op1.set_next(op2, 0, 0)
    s.commit()
    out_act, in_act = op1.get_output(0), op2.get_input(0)
    n = out_act.comm_req.desc.count
    buf = dist.make_buffer(lambda p: np.ones(n, np.float32), n)
    s.get_stats().reset()
    out_act.start_comm(buf)
    before_wait_op1 = s.get_stats().get_comm_cycles(op1.op_idx)
    in_act.wait_comm()  # waits op1's FPROP request
    # the wait's comm time lands on op1's OA slot, not op2's IA slot
    assert s.get_stats().get_comm_cycles(op1.op_idx) > before_wait_op1
    assert s.get_stats().get_comm_cycles(op2.op_idx) == 0
