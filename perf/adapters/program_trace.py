"""The program's span ring (``mlsl_tpu.obs``) as plain lists, for the readers
of the metrics that are measured inside the program. Like its siblings this
file imports the program, and nothing else of the benchmark does.

A program without the ring, or with the ring disarmed (``MLSL_TRACE=0``; the
default before the ring became the flight recorder), gives ``(False, [])``:
the readers then find nothing to read and leave their metrics out."""


def snapshot():
    """-> (armed, events). An event is ``[ph, name, cat, ts_ns, dur_ns,
    track, args]``: ``ph`` 'X' for a span and 'i' for an instant, the times
    on ``time.perf_counter_ns()``, ``args`` a dict (empty where the span
    carries none)."""
    try:
        from mlsl_tpu.obs import tracer
    except ImportError:
        return False, []
    ring = tracer.get_tracer()
    if ring is None:
        return False, []
    return True, [
        [e[tracer.PH], e[tracer.NAME], e[tracer.CAT], e[tracer.TS],
         e[tracer.DUR], e[tracer.TRACK], dict(e[tracer.ARGS] or {})]
        for e in ring.snapshot()]
