"""Busy time of the norms (scope ``mlsl_norm``; in ResNet-50 batch norm
with the activations and residual adds round it), forward and backward,
over the step's busy time, traced window; a fusion rooted at a convolution
is ``mlsl_conv``'s (``perf/lib/scopes.py``). None where the trace carries
no scope path or no operation under the scope."""

from perf.lib import scopes


def read(run):
    return scopes.share(scopes.by_scope(run.trace),
                        lambda phase, scope: scope == "norm")
