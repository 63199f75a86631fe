"""Device time of the walks over the experts' tiles over the decode
program's device time, traced window."""

from perf.lib import keye_spans


def read(run):
    return keye_spans.decode_share(run, lambda layer: layer["experts"])
