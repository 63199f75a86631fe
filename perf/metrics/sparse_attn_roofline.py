"""Attention over the selected rows in the decode step: the least time to
read the selected rows of K and V of every layer once (or to multiply them,
whichever is longer), over the device time between the end of a layer's
counting passes and the start of its experts' walk (the selection compacted,
the rows gathered, the attention, and with them the output projection, the
norm, the router and the sort of the pairs), traced window. Selected rows
are the program's counters."""

from perf.lib import counts_keye as counts, keye_spans


def read(run):
    def least(d, layers):
        return layers * counts.roofline(*counts.selected_rows(
            run.config, d["selected_tokens"]), run.peaks())

    return keye_spans.decode_share(
        run, lambda layer: layer["before_experts"], least)
