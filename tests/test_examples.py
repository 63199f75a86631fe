"""The example scripts are user-facing surfaces: run them end-to-end on the mesh."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh_env():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_example(name, timeout=420):
    env = _mesh_env()
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", name)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )


def test_mlsl_example_runs():
    r = _run_example("mlsl_example.py")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "example OK" in r.stdout
    assert "global allreduce: [36. 36. 36. 36.]" in r.stdout


@pytest.mark.slow
def test_train_transformer_example_runs():
    # the single heaviest tier-1 test (~7 min of subprocess transformer
    # training on the CPU mesh): slow-marked for the driver time budget;
    # the other five example tests keep the example surface in tier-1
    r = _run_example("train_transformer.py")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "transformer example OK" in r.stdout
    assert "checkpoint restored from step 10" in r.stdout


def test_custom_codec_example_runs():
    r = _run_example("custom_codec.py")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "custom codec example OK" in r.stdout
    assert "inconsistent geometry rejected" in r.stdout


def test_train_zero1_adam_example_runs():
    r = _run_example("train_zero1_adam.py")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
    assert "resumed from step 2" in r.stdout


def test_compat_cpp_example_builds_and_runs():
    """The drop-in C++ example (examples/compat_example.cpp) must compile
    against include/mlsl.hpp and run on the 8-device mesh."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain")
    native = os.path.join(REPO, "native")
    build = subprocess.run(
        ["make", "-s", "compat_example"], cwd=native, capture_output=True,
        text=True, timeout=300,
    )
    assert build.returncode == 0, build.stderr
    exe = os.path.join(native, "compat_example")
    r = subprocess.run([exe], capture_output=True, text=True, timeout=420,
                       env=_mesh_env(), cwd=REPO)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    assert "compat example OK" in r.stdout


def test_long_context_example_runs():
    r = _run_example("long_context.py")
    assert r.returncode == 0, r.stderr[-2000:]
    assert "long-context example OK" in r.stdout
    assert "zigzag == ring trajectory (to rounding): OK" in r.stdout
