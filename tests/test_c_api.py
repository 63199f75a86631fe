"""Builds and runs the C-consumer test program against the flat C API
(the analog of the reference's cmlsl_test run, tests/examples/mlsl_test/Makefile)."""

import os
import shutil
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_c_api_end_to_end():
    build = subprocess.run(
        ["make", "-s", "test_c_api"], cwd=NATIVE, capture_output=True, text=True,
        timeout=180,
    )
    assert build.returncode == 0, build.stderr

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["MLSL_STATS"] = "1"  # exercise the statistics queries section
    run = subprocess.run(
        [os.path.join(NATIVE, "test_c_api")], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert run.returncode == 0, f"stdout:\n{run.stdout}\nstderr:\n{run.stderr}"
    assert "C API TEST PASSED" in run.stdout
    assert "world = 8" in run.stdout
    assert "allreduce OK (36)" in run.stdout
    assert "allgatherv/alltoallv OK" in run.stdout
    assert "alltoallv_full per-rank OK" in run.stdout
    assert "activation fwd ReduceScatter OK" in run.stdout
    assert "activation bwd AllGather OK" in run.stdout
    assert "distributed-update increment AllGather OK" in run.stdout
    assert "statistics queries OK" in run.stdout


@pytest.mark.skipif(shutil.which("g++") is None, reason="no C++ toolchain")
def test_cpp_api_end_to_end():
    build = subprocess.run(
        ["make", "-s", "test_cpp_api"], cwd=NATIVE, capture_output=True, text=True,
        timeout=180,
    )
    assert build.returncode == 0, build.stderr
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    run = subprocess.run(
        [os.path.join(NATIVE, "test_cpp_api")], capture_output=True, text=True,
        timeout=300, env=env,
    )
    assert run.returncode == 0, f"stdout:\n{run.stdout}\nstderr:\n{run.stderr}"
    assert "CPP API TEST PASSED" in run.stdout
