"""Busy time under any ``mlsl_`` scope over all busy time, traced window,
of a serving cell (``perf/lib/scopes.py``): 0 for an executable built
without scopes, None where the trace carries no scope path."""

from perf.lib import scopes


def read(run):
    return scopes.scoped_share(scopes.by_scope(run.trace))
