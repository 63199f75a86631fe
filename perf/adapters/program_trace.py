"""The program's span ring (``mlsl_tpu.obs``) as plain lists, for the readers
of the metrics that are measured inside the program. Like its siblings this
file imports the program, and nothing else of the benchmark does.

A program without the ring, or with the ring disarmed (``MLSL_TRACE=0``; the
default before the ring became the flight recorder), gives ``(False, [])``:
the readers then find nothing to read and leave their metrics out.

The ring holds ``MLSL_TRACE_CAPACITY`` events (65,536 unless set) and a
serving window at the knee leaves more: the serving runner asks the
adapter's ``environment`` for a larger one, made anew once the program is
imported (a training cell does not), and ``capacity`` says what the
ring got, so that a reader can tell a whole window from one whose beginning
was overwritten."""


def _ring():
    try:
        from mlsl_tpu.obs import tracer
    except ImportError:
        return None, None
    return tracer, tracer.get_tracer()


def snapshot():
    """-> (armed, events). An event is ``[ph, name, cat, ts_ns, dur_ns,
    track, args]``: ``ph`` 'X' for a span and 'i' for an instant, the times
    on ``time.perf_counter_ns()``, ``args`` a dict (empty where the span
    carries none)."""
    tracer, ring = _ring()
    if ring is None:
        return False, []
    return True, [
        [e[tracer.PH], e[tracer.NAME], e[tracer.CAT], e[tracer.TS],
         e[tracer.DUR], e[tracer.TRACK], dict(e[tracer.ARGS] or {})]
        for e in ring.snapshot()]


def capacity():
    """-> the ring's size in events, ``None`` without a ring."""
    ring = _ring()[1]
    return None if ring is None else ring.capacity
