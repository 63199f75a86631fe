"""Positions the decoding sequences attend to over the positions they hold
(``serve.decode``'s ``selected_tokens`` over ``ctx_tokens``, summed over the
window's steps): how hard the selection is at work. 100 = every context is
no longer than ``topk``."""

from perf.lib import keye_spans


def read(run):
    steps = keye_spans.decodes(run, traced=False)
    held = sum(d["ctx_tokens"] for d in steps)
    if not held:
        return None
    return 100.0 * sum(d["selected_tokens"] for d in steps) / held
