"""Share of the traced window's idle device time that falls inside a span of
the program once its clock is joined to the trace's (the table by span goes
to the step record and to standard error). ``None`` without a device trace,
and where the join's residual is over its limit."""

from perf.lib import program_spans


def read(run):
    table = program_spans.idle_table(run)
    if table is None or table["attributed_share"] is None:
        return None
    return 100.0 * table["attributed_share"]
