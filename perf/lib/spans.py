"""Host spans round the calls into each layer. With the profiler on they are
``jax.profiler.TraceAnnotation``s, so that idle gaps of the device are
attributed on the trace's own clock; the host stamps for the step record are
taken by the loops themselves, traced or not."""

import contextlib


class Spans:
    def __init__(self, traced):
        self.traced = traced
        if traced:
            import jax

            self._annot = jax.profiler.TraceAnnotation

    def __call__(self, name):
        if self.traced:
            return self._annot(name)
        return contextlib.nullcontext()


class Tracer:
    """Start and stop of the profiler round the traced window."""

    def __init__(self, out_dir):
        self.dir = str(out_dir)
        self.on = False
        self._window = None

    def start(self, python_tracer=True):
        """``python_tracer=False`` leaves the profiler's Python tracer (on by
        default) off: it stamps every call of the host's loop, which slows a
        loop of short steps and lengthens the stop. The readers take device
        operations and the ``perf.*`` annotations and do not need it."""
        import jax

        if python_tracer:
            jax.profiler.start_trace(self.dir)
        else:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
        self.on = True
        self._window = jax.profiler.TraceAnnotation("perf.window")
        self._window.__enter__()

    def stop(self):
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False
