"""Largest share of the KV pool's pages held at the end of a decode step of
the window (``serve.decode``'s ``pages_held`` over ``pool_pages``)."""

from perf.lib import program_spans


def read(run):
    counts = program_spans.decode_counts(run)
    if not counts:
        return None
    return 100.0 * max(c["pages_held"] / c["pool_pages"] for c in counts)
