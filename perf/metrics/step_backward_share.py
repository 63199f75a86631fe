"""Busy time of the backward pass over the step's busy time, traced window:
the operations whose scope path holds ``transpose(`` (the forward that
``jax.checkpoint`` recomputes there among them; ``perf/lib/scopes.py``).
None where the trace carries no scope path, no backward operation, or no
operation under an ``mlsl_`` scope: the phase is JAX's own, but an
executable without scopes is one a compile cache served from another build,
and its readings are not this program's."""

from perf.lib import scopes


def read(run):
    parts = scopes.by_scope(run.trace)
    if not scopes.scoped_share(parts):
        return None
    return scopes.share(parts, lambda phase, scope: phase == "backward")
