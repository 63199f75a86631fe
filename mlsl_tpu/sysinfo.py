"""Platform probing and auto-configuration.

TPU counterpart of the reference's sysinfo (src/sysinfo.hpp:27-48: Xeon-vs-Phi CPU and
ETH/MLX/HFI NIC probing feeding AutoConfig, src/mlsl.cpp:649-682). Here the probed
"hardware" is the JAX device set: platform kind, chip generation, per-chip memory, and
the host topology — used to pick dispatch defaults (chunk sizes, lanes).
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax

from mlsl_tpu.log import MLSLError


@dataclasses.dataclass(frozen=True)
class SysInfo:
    platform: str            # 'tpu' | 'cpu' | 'gpu'
    device_kind: str         # e.g. 'TPU v5 lite'
    num_devices: int
    num_hosts: int
    memory_per_device: int   # bytes, 0 if unknown


def on_tpu() -> bool:
    """True when the active JAX backend is a TPU — the one platform probe
    model/kernel code should key fast-path defaults on. A backend that fails
    to start raises here: a chip held by another process must not read as
    "not a TPU" and send every kernel to its reference."""
    return jax.default_backend() == "tpu"


def chosen_platform() -> str:
    """The first platform JAX was TOLD to use (``JAX_PLATFORMS`` or the
    ``jax_platforms`` config), '' when the choice was left to JAX."""
    return (jax.config.jax_platforms or "").split(",")[0].strip().lower()


def require_chosen_backend() -> None:
    """Refuse JAX's silent CPU fallback. With libtpu installed and the
    platform left to JAX, a CPU default backend means the TPU runtime failed
    to start (no chip, or a chip another process holds) and JAX carried on
    without it — every kernel would then quietly take its reference path.
    Running on the CPU is something the caller asks for: JAX_PLATFORMS=cpu."""
    if chosen_platform() or jax.default_backend() != "cpu":
        return
    import importlib.util

    if importlib.util.find_spec("libtpu") is not None:
        raise MLSLError(
            "libtpu is installed and JAX_PLATFORMS is unset, but JAX came up "
            "on the CPU: the TPU backend failed to start (no chip attached, "
            "or another process holds it). Set JAX_PLATFORMS=cpu to run on "
            "the CPU deliberately."
        )


def pallas_interpret() -> bool:
    """Whether Pallas kernels run under the interpreter — the ONE place that
    decides. Only a request does it: ``MLSL_PALLAS_INTERPRET=1`` (``0``
    forces compiled Mosaic), or a platform chosen to be the CPU (the test
    mesh, ``chip_smoke.py --tiny``). A kernel reached on a non-TPU backend
    nobody chose is an error, never a quiet interpreted run."""
    v = os.environ.get("MLSL_PALLAS_INTERPRET", "").strip()
    if v in ("0", "1"):
        return v == "1"
    if on_tpu():
        return False
    if chosen_platform() == "cpu":
        return True
    raise MLSLError(
        f"a Pallas kernel was requested on the {jax.default_backend()!r} "
        "backend, which JAX_PLATFORMS did not select; refusing to run it "
        "under the interpreter (set JAX_PLATFORMS=cpu or "
        "MLSL_PALLAS_INTERPRET=1 to ask for that)"
    )


#: the compile cache of a checkout that runs on the chip, when the
#: environment names none: derived from the package path so it never moves
#: (the path is part of what a cache hit depends on across processes)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def resolve_compile_cache() -> str:
    """Arm JAX's persistent compilation cache — the one place that does —
    and return the directory in use ('' = off). ``JAX_COMPILATION_CACHE_DIR``
    wins: JAX already holds it and no directory is set here. Otherwise a TPU
    backend caches under the checkout's ``.jax_cache``; the CPU backend
    keeps JAX's default (off — tier-1 must not start writing a cache).
    Thresholds are zeroed while the cache is on: it exists to remove
    recompiles, and the per-layer programs are many and small."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not cache_dir:
        if not on_tpu():
            return ""
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def _num_hosts(devices) -> int:
    """DISTINCT hosts, not max(process_index)+1: a survivor subset may
    exclude every device of a low-indexed host, and a profile measured on
    a genuine N-host spread must not transfer to it."""
    return len({d.process_index for d in devices})


@functools.lru_cache(maxsize=1)
def probe() -> SysInfo:
    devices = jax.devices()
    d0 = devices[0]
    mem = 0
    try:
        stats = d0.memory_stats()
        if stats:
            mem = int(stats.get("bytes_limit", 0))
    except Exception:
        mem = 0
    return SysInfo(
        platform=d0.platform,
        device_kind=getattr(d0, "device_kind", d0.platform),
        num_devices=len(devices),
        num_hosts=_num_hosts(devices),
        memory_per_device=mem,
    )


def topology_fingerprint(devices=None) -> dict:
    """The identity a tuner profile (mlsl_tpu.tuner) is keyed by: measured
    algorithm selections transfer exactly to the hardware they were measured
    on — same platform, same chip generation, same world size and host
    spread. A profile whose fingerprint disagrees with the probe is stale
    (different machine / different slice shape) and must be re-measured, the
    same contract as the reference's AutoConfig re-probing per launch.

    ``devices``: the ACTIVE world (default the full jax world). An elastic
    reshard (mlsl_tpu.elastic) re-initializes the Environment over a
    survivor subset, and a profile measured at the full world size must go
    stale there — world size and tier shape are computed from the active
    set, not the physical machine."""
    si = probe()
    from mlsl_tpu.comm.mesh import world_tiers

    devices = tuple(jax.devices() if devices is None else devices)
    num_hosts = _num_hosts(devices)
    tiers = world_tiers(devices)
    return {
        "platform": si.platform,
        "device_kind": si.device_kind,
        "num_devices": len(devices),
        "num_hosts": num_hosts,
        # two-tier shape (T slices x L devices/slice) or None for a flat
        # world: a profile tuned on a two-tier mesh — where 'hier' cells
        # and the DCN codec knob were measured — must not transfer to a
        # flat one, and vice versa (comm/algos/hier.py)
        "tiers": list(tiers) if tiers is not None else None,
    }


def device_class(si: SysInfo) -> str:
    """Coarse tuning class from the probed device kind (the analog of the
    reference's Xeon-vs-Phi x ETH-vs-MLX-vs-HFI matrix, src/sysinfo.hpp:27-48):

    - 'tpu-performance': v4/v5p-class (3D-torus ICI, wide links) — dispatch
      overhead dominates; defer only genuinely large messages, few chunks.
    - 'tpu-efficiency': v5e/v6e-class ('lite' kinds; 2D-torus, narrower links)
      — collectives are slower relative to compute; defer earlier and chunk
      more so Waits can complete (and overlap) incrementally.
    - 'host-sim': CPU/GPU simulation meshes — keep chunking off so tests stay
      cheap and deterministic.
    """
    if si.platform != "tpu":
        return "host-sim"
    k = si.device_kind.lower()
    if "lite" in k or "v5e" in k or "v6e" in k:
        return "tpu-efficiency"
    if any(g in k for g in ("v2", "v3", "v4", "v5p")):
        return "tpu-performance"
    raise MLSLError(
        f"unknown TPU device kind {si.device_kind!r}: no tuning class in "
        "sysinfo.device_class (add it rather than guessing one)"
    )


# Per-class knob defaults applied by auto_config (each may be further keyed on
# probed HBM below). Values are design-rule settings pending on-chip tuning —
# the table exists so the tuning has one place to land, and so v5e-class and
# host-sim probes demonstrably pick different dispatch policies.
_CLASS_DEFAULTS = {
    "tpu-performance": dict(
        msg_priority_threshold=1 << 20,   # defer only >1 MiB
        msg_priority_flush_ms=1.0,        # fast dispatch: short coalescing
        large_msg_size_mb=128,
        large_msg_chunks=4,
        grad_bucket_mb=4,                 # coalesce launch-bound small grads
    ),
    "tpu-efficiency": dict(
        msg_priority_threshold=1 << 18,   # defer >256 KiB: narrower ICI
        msg_priority_flush_ms=2.0,
        large_msg_size_mb=64,             # chunk earlier
        large_msg_chunks=4,
        grad_bucket_mb=4,
    ),
    "host-sim": dict(
        msg_priority_threshold=10000,
        msg_priority_flush_ms=2.0,
        large_msg_size_mb=128,
        large_msg_chunks=1,               # chunking only costs on a sim mesh
        grad_bucket_mb=0,                 # keep sim tests launch-for-launch
    ),
}


def auto_config(config) -> None:
    """Adjust config defaults from probed hardware (reference AutoConfig,
    src/mlsl.cpp:649-682): pick the device-class row from _CLASS_DEFAULTS,
    then key memory-sensitive knobs on probed per-device HBM. Knobs the user
    exported explicitly (Config._explicit, tracked by from_env) are NEVER
    overridden — same contract as the reference, where AutoConfig fills only
    unset variables. Gated on MLSL_AUTO_CONFIG_TYPE != 0."""
    si = probe()
    if config.auto_config_type == 0:
        return
    tuned = dict(_CLASS_DEFAULTS[device_class(si)])
    if si.memory_per_device:
        # one deferred chunk should stay under ~1.5% of per-device HBM so a
        # chunked large allreduce never spikes transient memory
        cap_mb = max(8, si.memory_per_device // (64 * 1024 * 1024))
        tuned["large_msg_size_mb"] = min(tuned["large_msg_size_mb"], cap_mb)
        # the device-gather cap scales with the actual HBM: a quarter of the
        # chip, rather than a fixed 1 GiB, keeps the contract meaningful on
        # both 16 GiB v5e and 95 GiB v5p
        tuned["gather_device_limit_mb"] = max(
            256, si.memory_per_device // (4 * 1024 * 1024)
        )
    explicit = getattr(config, "_explicit", set())
    for k, v in tuned.items():
        if k not in explicit:
            setattr(config, k, v)
