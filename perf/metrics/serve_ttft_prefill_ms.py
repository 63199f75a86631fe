"""Median, over the window's requests, of the time from the start of a
request's first ``serve.prefill.chunk`` to the end of its last: the part of a
first token's time that a prompt spends being prefilled over several steps,
which ``ttft_parts`` (queue + first admit + that step's tail) leaves out on
the chunked path. Measured inside the program."""

import statistics

from perf.lib import keye_spans


def read(run):
    by = {}
    for e in keye_spans.chunks(run, traced=False):
        first, last = by.get(e[6]["req"], (e[3], None))
        by[e[6]["req"]] = (min(first, e[3]),
                           e[3] + e[4] if e[6]["last"] else last)
    spans = [(end - start) / 1e6 for start, end in by.values()
             if end is not None]
    return statistics.median(spans) if spans else None
