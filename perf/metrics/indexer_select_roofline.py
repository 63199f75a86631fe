"""Scores plus exact top-2048 of the decode step: the least time to read
the live index keys of every layer once (or to score them, whichever is
longer), over the device time from a layer's start to the end of its
counting passes (projections and pool writes, the gather of the index keys,
the scores, the passes), traced window. Contexts are the program's
counters."""

from perf.lib import counts_keye as counts, keye_spans


def read(run):
    def least(d, layers):
        return layers * counts.roofline(*counts.index_select(
            run.config, d["ctx_tokens"]), run.peaks())

    return keye_spans.decode_share(
        run, lambda layer: layer["before_select"] + layer["select"], least)
