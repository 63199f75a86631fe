"""Median duration of a ``serve.prefill.chunk`` span over the window: one
chunk of a prompt through the paged cache, dispatched and awaited. Measured
inside the program."""

import statistics

from perf.lib import keye_spans


def read(run):
    spans = keye_spans.chunks(run, traced=False)
    if not spans:
        return None
    return statistics.median(e[4] for e in spans) / 1e6
