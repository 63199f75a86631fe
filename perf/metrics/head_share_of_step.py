"""Busy time of the vocabulary head (scope ``mlsl_head``: the head's
product, log-softmax and loss), forward and backward, over the step's busy
time, traced window (``perf/lib/scopes.py``). None where the trace carries
no scope path or no operation under the scope."""

from perf.lib import scopes


def read(run):
    return scopes.share(scopes.by_scope(run.trace),
                        lambda phase, scope: scope == "head")
