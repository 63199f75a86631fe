"""Distinct experts that got a token, a layer and decode step, mean over the
window's steps (``serve.decode``'s ``experts_hit`` over the layers): what a
decode step reads of the experts' weights."""

from perf.lib import keye_spans


def read(run):
    steps = keye_spans.decodes(run, traced=False)
    if not steps:
        return None
    layers = run.config["num_hidden_layers"]
    return sum(d["experts_hit"] for d in steps) / len(steps) / layers
