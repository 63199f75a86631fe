"""The measurement tools kept beside the benchmark (benchmarks/): what their
shared helpers promise apart from any time they report, and that the on-chip
kernel table refuses to run off the chip."""

import os
import subprocess
import sys
import time

import jax.numpy as jnp

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks._common import device_sync, timed


def test_device_sync_returns_a_float_for_a_tree_and_for_nothing():
    tree = {"a": jnp.arange(6.0).reshape(2, 3) + 7.0, "b": jnp.zeros(3)}
    assert isinstance(device_sync(tree), float)
    # nothing to read back (a wait on a one-member group returns None):
    # still a round trip to the host, and a float
    assert device_sync(None) == 0.0


def test_timed_warms_up_before_the_clock_and_keeps_its_floor(monkeypatch):
    """What ``timed``'s callers rely on: the first call (the compile) is
    outside the timed window even with ``warmup=0``, and the result is never
    under the floor they divide by."""
    events = []
    clock = time.perf_counter

    def fn(x):
        events.append("call")
        return x + 1.0

    def perf_counter():
        events.append("clock")
        return clock()

    monkeypatch.setattr(time, "perf_counter", perf_counter)
    ms = timed(fn, jnp.zeros(4), iters=1, warmup=0, blocks=2)
    assert events[0] == "call" and "clock" in events
    assert isinstance(ms, float) and ms >= 1e-3


def test_kernel_table_refuses_the_cpu():
    """benchmarks/kernels_on_chip.py times compiled Pallas kernels: off the
    chip it exits non-zero and prints no row (an interpreter's time under a
    device's name is the one thing it must never write)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "kernels_on_chip.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert out.returncode != 0
    assert "needs a TPU backend" in out.stderr
    assert not [l for l in out.stdout.splitlines() if l.startswith("{")]
