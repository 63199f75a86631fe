"""Busy time of the optimizer's update (scope ``mlsl_update``) over the
step's busy time, traced window (``perf/lib/scopes.py``). None where the
trace carries no scope path or no operation under the scope."""

from perf.lib import scopes


def read(run):
    return scopes.share(scopes.by_scope(run.trace),
                        lambda phase, scope: scope == "update")
