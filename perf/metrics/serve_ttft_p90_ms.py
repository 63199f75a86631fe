"""90th percentile of due time to first token over every request due in the
window: the tail that ttft_p50_ms leaves out (too few requests in a window
to bound it end to end)."""

from perf.lib import serve_cell


def read(run):
    if not run.window.get("ttft_ms"):
        return None
    return serve_cell.percentile(run.window["ttft_ms"], 90)
