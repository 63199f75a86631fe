"""memory_stats()['peak_bytes_in_use'] of the fullest device, read when the
window closed and before the reference ran."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return peak / 2**30 if peak else None
