"""mlsl_tpu.obs — structured comm-timeline tracing (docs/DESIGN.md
"Observability & tracing").

The span ring is the flight recorder and is on in every run: the most recent
65,536 events sit in memory at one tuple and one deque append an event, and
cost nothing else until somebody asks for them::

    python serve.py                     # the ring is armed
    from mlsl_tpu import obs
    ... run ...
    path = obs.write_trace()            # load in ui.perfetto.dev

``MLSL_TRACE=0`` (or ``obs.disable()``) disarms it: every instrumented site
is then one attribute load and a ``None`` test, and allocates nothing.
Other knobs: ``MLSL_TRACE_DIR`` (output directory, default CWD),
``MLSL_TRACE_CAPACITY`` (ring size in events, default 65536).

The serving engine records one span tree a step (``serve.step`` and its
children, each carrying ``step=<n>``, the request-scoped ones ``req=<id>``
too) and one ``serve.request`` a request on its own track; PERF.md section 3
lists every span with the metric that reads it.

On a watchdog trip (``MLSLTimeoutError``) the flight recorder dumps the
trailing window of spans to ``trace-crash-<ts>.json`` automatically (and,
with ``MLSL_PROFILE_ON_TRIP=1``, a jax.profiler device trace next to it);
since the ring is armed by default the dump holds the timeline that led to
the trip without anybody having armed anything beforehand.

The telemetry plane (docs/DESIGN.md "Telemetry plane") rides in the same
package: ``obs.metrics`` (typed time-series registry, ``MLSL_METRICS=1``),
``obs.serve`` (``/metrics`` + ``/healthz`` + ``/statusz`` on
``MLSL_METRICS_PORT``), ``obs.straggler`` (cross-replica skew sentinel,
``MLSL_STRAGGLER_SKEW``)::

    MLSL_METRICS=1 MLSL_METRICS_PORT=9090 python train.py
    curl localhost:9090/metrics   # Prometheus text
    curl localhost:9090/healthz   # supervisor.status() as JSON
"""

from mlsl_tpu.obs.tracer import (  # noqa: F401
    DEFAULT_CAPACITY,
    Tracer,
    disable,
    enable,
    enabled,
    get_tracer,
    trace_dir,
)
from mlsl_tpu.obs.export import (  # noqa: F401
    flight_record,
    render,
    summarize,
    to_trace_events,
    write_trace,
)
from mlsl_tpu.obs import metrics  # noqa: F401
from mlsl_tpu.obs import serve  # noqa: F401
from mlsl_tpu.obs import straggler  # noqa: F401
from mlsl_tpu.obs.metrics import (  # noqa: F401
    MetricsRegistry,
    enable as enable_metrics,
    disable as disable_metrics,
    get_registry,
)
from mlsl_tpu.obs.serve import start_server, stop_server  # noqa: F401
from mlsl_tpu.obs.straggler import StragglerSentinel  # noqa: F401

__all__ = [
    "DEFAULT_CAPACITY",
    "Tracer",
    "disable",
    "enable",
    "enabled",
    "get_tracer",
    "trace_dir",
    "flight_record",
    "render",
    "summarize",
    "to_trace_events",
    "write_trace",
    "metrics",
    "serve",
    "straggler",
    "MetricsRegistry",
    "StragglerSentinel",
    "enable_metrics",
    "disable_metrics",
    "get_registry",
    "start_server",
    "stop_server",
]
