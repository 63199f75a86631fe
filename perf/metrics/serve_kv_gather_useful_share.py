"""Mean over the window's decode steps of pages held by a sequence over
pages the decode program gathers (``max_batch`` x pages a sequence), both
counted on ``serve.decode`` where the step runs."""

import statistics

from perf.lib import program_spans


def read(run):
    counts = program_spans.decode_counts(run)
    if not counts:
        return None
    return 100.0 * statistics.fmean(
        c["pages_held"] / c["pages_gathered"] for c in counts)
