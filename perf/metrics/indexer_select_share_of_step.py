"""Device time from a layer's start to the end of its counting passes
(projections and pool writes, the gather of the index keys, the scores, the
exact top-2048) over the decode program's device time, traced window."""

from perf.lib import keye_spans


def read(run):
    return keye_spans.decode_share(
        run, lambda layer: layer["before_select"] + layer["select"])
