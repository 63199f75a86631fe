"""Median, over the finished requests due in the window, of the time from
``submit()`` to the scheduler taking the request off its queue
(``serve.admit``'s ``queue_wait_ns``): measured inside the program."""

from perf.lib import program_spans


def read(run):
    return program_spans.ttft_part_median(run, "queue_wait_ms")
