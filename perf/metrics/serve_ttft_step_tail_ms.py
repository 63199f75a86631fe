"""Median of (end of the ``serve.step`` that admitted a request - end of its
``serve.admit``): how long a first token that exists waits for the rest of
the step before a caller can see it. Measured inside the program."""

from perf.lib import program_spans


def read(run):
    return program_spans.ttft_part_median(run, "step_tail_ms")
