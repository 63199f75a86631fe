"""chip_smoke.py and the start-up code it rests on, as far as the CPU can tell.

- the smoke's dry run passes on the CPU, and the smoke refuses the CPU without
  ``--tiny`` (on the chip the driver runs it for real);
- the one compile-cache resolver leaves the directory to JAX when
  ``JAX_COMPILATION_CACHE_DIR`` is set and otherwise yields a fixed path;
- nothing slides off the device quietly: the interpreter gate, the CPU-fallback
  check, the unknown-device and unknown-knob errors, the stale native library;
- every Pallas kernel that is eligible on a TPU cross-lowers for the TPU
  (``jax.export``, ``platforms=["tpu"]``: the Pallas -> Mosaic lowering runs
  without a chip), so the next JAX-side break shows here and not on the chip.
  What this cannot see — the VMEM limit, tiling of slices, the semaphores at
  run time — only the chip shows (``JAX_PLATFORMS=tpu pytest -m tpu tests/``).
"""

import json
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import export

from mlsl_tpu import native, sysinfo
from mlsl_tpu.log import MLSLError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args, devices=1, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


# -- the smoke itself ---------------------------------------------------------


def test_tiny_dry_run_passes_on_cpu():
    """The script end to end — header, phases, exit checks, result line — on
    the three cheap phases; ResNet-50 compiles for twenty seconds even at toy
    size and tier-1 is cut by its timeout, so P1 and M1-M3 run in the slow
    tier below (and by hand before chip time is spent)."""
    out = _run_smoke("--tiny", "--phases", "P2,P3,P4")
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines[0] == "DRY RUN (cpu): not a chip result"
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    for phase in ("P2", "P3", "P4"):
        assert f"--- {phase} passed" in out.stdout
    assert "multi-chip phases: skipped, 1 device" in out.stdout
    assert "compile cache: off (cpu)" in out.stdout
    assert "breakers closed" in out.stdout


@pytest.mark.slow
def test_tiny_dry_run_every_phase():
    out = _run_smoke("--tiny", devices=4, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    for phase in ("P1", "P2", "P3", "P4", "M1", "M2", "M3"):
        assert f"--- {phase} passed" in out.stdout


def test_without_a_chip_and_without_tiny_it_fails():
    out = _run_smoke()
    assert out.returncode != 0
    assert "need 'tpu'" in out.stderr
    assert out.stdout == ""


# -- one compile cache, placeable from outside --------------------------------


@pytest.fixture()
def cache_config():
    """Restore the three JAX cache knobs the resolver may touch."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = [getattr(jax.config, n) for n in names]
    yield
    for n, v in zip(names, before):
        jax.config.update(n, v)


def test_resolver_leaves_the_directory_to_jax(monkeypatch, tmp_path,
                                              cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert sysinfo.resolve_compile_cache() == str(tmp_path)
    # JAX read the variable itself at import; the resolver set no directory
    assert jax.config.jax_compilation_cache_dir == before


def test_resolver_is_off_on_the_cpu(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert sysinfo.resolve_compile_cache() == ""
    assert jax.config.jax_compilation_cache_dir == before


def test_resolver_path_on_tpu_is_fixed(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(sysinfo, "on_tpu", lambda: True)
    path = sysinfo.resolve_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    # a cache that moves never hits: no pid, no clock, no temporary name
    assert str(os.getpid()) not in path
    assert not path.startswith(tempfile.gettempdir())
    assert path == sysinfo.resolve_compile_cache()


def test_environment_records_the_cache_dir(env):
    assert env.compile_cache_dir == ""   # CPU mesh: JAX's default, off


# -- nothing that hides the device --------------------------------------------


def test_interpreter_only_on_request(monkeypatch):
    monkeypatch.delenv("MLSL_PALLAS_INTERPRET", raising=False)
    assert sysinfo.pallas_interpret()          # the CPU was chosen
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    assert not sysinfo.pallas_interpret()
    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "1")
    assert sysinfo.pallas_interpret()
    # a CPU nobody chose (JAX fell back to it) is an error, not a quiet
    # interpreted run
    monkeypatch.delenv("MLSL_PALLAS_INTERPRET")
    monkeypatch.setattr(sysinfo, "chosen_platform", lambda: "")
    with pytest.raises(MLSLError, match="did not select"):
        sysinfo.pallas_interpret()


def test_cpu_fallback_is_refused(monkeypatch):
    sysinfo.require_chosen_backend()           # JAX_PLATFORMS=cpu: chosen
    monkeypatch.setattr(sysinfo, "chosen_platform", lambda: "")
    with pytest.raises(MLSLError, match="TPU backend failed to start"):
        sysinfo.require_chosen_backend()


def test_unknown_tpu_kind_has_no_class():
    si = sysinfo.SysInfo("tpu", "TPU v9 mega", 4, 1, 0)
    with pytest.raises(MLSLError, match="unknown TPU device kind"):
        sysinfo.device_class(si)


def test_resnet_s2d_rejects_unknown_values(monkeypatch):
    from mlsl_tpu.models import resnet

    monkeypatch.setenv("MLSL_RESNET_S2D", "1")
    assert resnet._use_s2d_stem()
    monkeypatch.setenv("MLSL_RESNET_S2D", "yes please")
    with pytest.raises(MLSLError, match="MLSL_RESNET_S2D"):
        resnet._use_s2d_stem()


def test_failed_build_beside_a_library_is_an_error(monkeypatch):
    assert native.load() is not None
    assert native.status()["loaded"]
    monkeypatch.setattr(native, "_lib", None)

    def failing_make(*a, **kw):
        raise subprocess.CalledProcessError(2, "make", stderr=b"no compiler")

    monkeypatch.setattr(native.subprocess, "run", failing_make)
    with pytest.raises(MLSLError, match="possibly stale"):
        native.load()


# -- cross-lowering for the TPU on the CPU ------------------------------------


def _lowers_for_tpu(fn, *shapes, calls=1):
    text = export.export(fn, platforms=["tpu"])(*shapes).mlir_module()
    assert text.count("tpu_custom_call") >= calls, text.count(
        "tpu_custom_call")
    return text


S = jax.ShapeDtypeStruct
_OFF = S((1,), jnp.int32)


@pytest.mark.parametrize("sq,d", [(512, 64), (512, 128)])
def test_flash_forward_and_backward_lower(sq, d):
    from mlsl_tpu.ops import attention_kernels as ak

    q = S((4, sq, d), jnp.bfloat16)
    fwd = jax.jit(lambda q, k, v, a, b: ak.flash_attention(
        q, k, v, a, b, True, False))
    assert 'kernel_name = "_flash_kernel"' in _lowers_for_tpu(
        fwd, q, q, q, _OFF, _OFF)

    def loss(q, k, v, a, b):
        out = ak.flash_attention(q, k, v, a, b, True, False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = _lowers_for_tpu(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                           q, q, q, _OFF, _OFF, calls=3)
    assert "_bwd_dq_kernel" in text and "_bwd_dkv_kernel" in text


def test_flash_block_update_lowers():
    from mlsl_tpu.ops import attention_kernels as ak

    q = S((4, 1024, 64), jnp.bfloat16)
    acc = S((4, 1024, 64), jnp.float32)
    ml = S((4, 1024, 128), jnp.float32)
    fn = jax.jit(lambda q, k, v, acc, m, l, a, b: ak.flash_block_update(
        q, k, v, acc, m, l, a, b, True, False))
    _lowers_for_tpu(fn, q, q, q, acc, ml, ml, _OFF, _OFF)


@pytest.mark.parametrize("rows", [2048, 96])   # packed scales, ragged (r, 1)
def test_quant_kernels_lower(rows):
    from mlsl_tpu.ops import quant_kernels as qk

    _lowers_for_tpu(jax.jit(lambda x: qk._quantize_pallas(x)),
                    S((rows, 256), jnp.float32))
    _lowers_for_tpu(jax.jit(lambda q, s: qk._dequantize_pallas(q, s)),
                    S((rows, 256), jnp.int8), S((rows,), jnp.float32))


@pytest.fixture()
def as_if_on_tpu(monkeypatch):
    """Eligibility as a TPU would answer it, kernels compiled (no
    interpreter): what the cross-lowering needs from a CPU process."""
    from mlsl_tpu.comm import quant_ring
    from mlsl_tpu.ops import ring_kernels as rk

    monkeypatch.setenv("MLSL_PALLAS_INTERPRET", "0")
    monkeypatch.setattr(rk, "_on_tpu", lambda: True)
    monkeypatch.setattr(quant_ring, "use_pallas_for",
                        lambda group, block: block % 128 == 0)
    # programs are cached per group; a lowering built here must not be found
    # by a later interpret-mode test
    from mlsl_tpu.comm import collectives

    yield
    collectives.clear_cache()
    quant_ring._cache.clear()
    rk._ring_call.cache_clear()


def _groups():
    from mlsl_tpu.comm.mesh import ProcessGroup, Topology

    devs = jax.devices()[:4]
    ring = Topology(4, 1, devices=devs)
    torus = Topology(2, 2, devices=devs)
    return (ring, ProcessGroup(ring, ("data",)),
            torus, ProcessGroup(torus, ("data", "model")))


def test_pallas_collectives_lower(as_if_on_tpu, monkeypatch):
    """pallas_ring (allreduce, reduce-scatter, bidirectional), pallas_ring2d,
    pallas_rhd, pallas_a2a (dense wire) and the ZeRO-1 all-gather phase
    kernel: each reaches a tpu_custom_call that opens with the entry
    barrier's collective_id (jax refuses one without the other). The
    int8-fused variants are parked on the compiled backend and are not
    eligible there (rk.QUANT_PARKED)."""
    from mlsl_tpu.comm import algos
    from mlsl_tpu.ops import ring_kernels as rk
    from mlsl_tpu.types import ReductionType

    ring, g, torus, g2 = _groups()
    assert not rk.eligible_quant(g, 256)
    assert not algos.eligible("pallas_a2a", "alltoall", g)
    monkeypatch.setenv("MLSL_PALLAS_A2A_QUANT", "0")
    n = 1 << 14
    buf = S((*ring.grid_shape, n), jnp.float32)
    buf2 = S((*torus.grid_shape, n), jnp.float32)
    sum_ = {"op": ReductionType.SUM}
    for algo, kind, group, shape, kw in (
        ("pallas_ring", "allreduce", g, buf, sum_),
        ("pallas_ring", "allreduce", g, buf, {**sum_, "bidir": True}),
        ("pallas_ring", "reduce_scatter", g, buf,
         {**sum_, "recv_count": n // 4}),
        ("pallas_ring2d", "allreduce", g2, buf2, sum_),
        ("pallas_rhd", "allreduce", g, buf, sum_),
        ("pallas_a2a", "alltoall", g, buf, {"quantized": False}),
    ):
        assert algos.eligible(algo, kind, group, kw.get("op")), (algo, kind)
        fn = algos.build(kind, group, np.float32, algo, **kw)
        text = _lowers_for_tpu(getattr(fn, "_mlsl_inner", fn), shape)
        assert "collective_id" in text, algo
    body = rk.dense_ring_body("all_gather", g, 640, np.float32)
    fn = rk.build_flat_program(body, g, "all_gather")
    _lowers_for_tpu(fn, S((*ring.grid_shape, 640), jnp.float32))


def test_composed_quantised_ring_lowers(as_if_on_tpu):
    """The composed int8 ring with the Pallas quantise kernel inside
    shard_map (chip_smoke M3)."""
    from mlsl_tpu.comm import quant_ring

    topo, g, _, _ = _groups()
    count = 4 * 256 * 32
    fn, el = quant_ring.build_quantized_collective(
        "allreduce", g, count, 256, ring="lax")
    _lowers_for_tpu(fn._mlsl_inner,
                    S((*topo.grid_shape, count), jnp.float32),
                    S((*topo.grid_shape, el), jnp.float32), calls=2)
