"""The grouped products of the decode step's experts: the least time to
read the weights of the experts that got a token once (or to multiply the
token-expert pairs, whichever is longer), over the device time of the walks
over the experts' tiles in the decode program, traced window. Experts hit
and pairs are the program's counters."""

from perf.lib import counts_keye as counts, keye_spans


def read(run):
    def least(d, layers):       # the counters are sums over the layers
        return counts.roofline(*counts.expert_walk(
            run.config, d["experts_hit"], d["expert_tokens"]), run.peaks())

    return keye_spans.decode_share(run, lambda layer: layer["experts"], least)
