"""Operations of the active parameters (dense matrices, a token's eight
experts, the head where logits are made), the index scores and the selected
attention of the tokens prefilled and decoded in the traced window, over
window x the bf16 peak. The tokens and their contexts come from the
program's counters (``serve.decode``, ``serve.prefill.chunk``)."""

from perf.lib import counts_keye as counts, keye_spans


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window["traced"]
    ops = sum(counts.decode_ops(run.config, d["inflight"], d["ctx_tokens"],
                                d["selected_tokens"])
              for d in keye_spans.decodes(run))
    ops += sum(counts.prefill_ops(
        run.config, e[6]["offset"], e[6]["offset"] + e[6]["tokens"],
        e[6]["last"]) for e in keye_spans.chunks(run))
    if not ops:
        return None
    return 100.0 * ops / (hi - lo) / run.peaks()["bf16_flops"]
