"""Median duration of a request's first ``serve.admit`` span: the prefill,
the KV write and the first token's read-back, measured inside the program."""

from perf.lib import program_spans


def read(run):
    return program_spans.ttft_part_median(run, "admit_ms")
