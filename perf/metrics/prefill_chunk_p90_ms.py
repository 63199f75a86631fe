"""90th percentile (nearest rank) of the window's ``serve.prefill.chunk``
spans: a chunk late in a long prompt, whose selection runs over the widest
context, where the median (``prefill_chunk_ms``) is a chunk early in one.
Measured inside the program."""

from perf.lib import keye_spans, serve_cell


def read(run):
    spans = keye_spans.chunks(run, traced=False)
    if not spans:
        return None
    return serve_cell.percentile([e[4] for e in spans], 90) / 1e6
