"""A prefill chunk's expanded attention: the least time its causal
operations take at the bf16 peak (or reading the context's latent rows,
whichever is longer), over the device time of the prefill kernel
(``latent_prefill_attention``, one a layer) in the chunk program's runs,
traced window. Offsets and lengths are the program's counters on
``serve.prefill.chunk``."""

from perf.lib import axk1_spans, counts_axk1 as counts


def read(run):
    pairs = axk1_spans.paired(run, axk1_spans.CHUNK_PROGRAM,
                              axk1_spans.chunks(run))
    timed = [axk1_spans.prefill_kernel_ns(run, lo, hi) for (lo, hi), _ in pairs]
    if not timed or None in timed or not sum(timed):
        return None
    least = sum(counts.roofline(*counts.latent_prefill(
        run.config, c["offset"], c["offset"] + c["tokens"]), run.peaks())
        for _, c in pairs)
    return 100.0 * least / (sum(timed) / 1e9)
