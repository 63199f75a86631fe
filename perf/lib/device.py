"""What the run ran on, as JAX reports it."""


def require_chip(jax, chips):
    """Exit with a message unless the TPU holds the chips the cell asks for."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"perf/run.py: JAX found platform {devs[0].platform!r}, the "
            f"benchmark measures on a TPU only")
    if len(devs) < chips:
        raise SystemExit(
            f"perf/run.py: the cell asks for {chips} chips, JAX found "
            f"{len(devs)}")


def describe(jax, chips):
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}
