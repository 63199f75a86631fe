"""Model operations of the tokens prefilled and decoded in the traced
window over window x the bf16 peak."""

from perf.lib import counts


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window["traced"]
    ops = 0
    for n, times in zip(run.window["prompts"], run.window["arrivals"]):
        for k, t in enumerate(times):
            if not lo <= t <= hi:
                continue
            ops += (counts.gpt2_sequence_ops(run.config, 0, n) if k == 0
                    else counts.gpt2_token_ops_at(run.config, n + k - 1))
    if not ops:
        return None
    return 100.0 * ops / (hi - lo) / run.peaks()["bf16_flops"]
