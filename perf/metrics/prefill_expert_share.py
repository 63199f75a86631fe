"""Busy time of the experts (scope ``mlsl_experts``: router and routed walk)
inside the chunk programs' runs over those runs' busy time, traced window
(``perf/lib/scopes.py``; the runs are the ``chunk_body`` modules, as
``keye_spans`` finds them). None where the trace carries no scope path, no
chunk program ran or no operation of it is under the scope."""

from perf.lib import keye_spans, scopes


def read(run):
    if run.trace is None:
        return None
    runs = keye_spans.program_runs(run, keye_spans.CHUNK_PROGRAM)
    if not runs:
        return None
    return scopes.share(scopes.by_scope(run.trace, within=runs),
                        lambda phase, scope: scope == "experts")
