"""Serving engine: continuous batching, paged KV cache, SLA-protected decode.

The serving stack reuses the training stack rather than forking it:

- **engine.py** — :class:`InferenceEngine`: admission queue with
  AsyncLoader-style backpressure accounting, iteration-level (continuous)
  batching where sequences join and retire at decode-step granularity, and
  prefill/decode compiled as donation-enabled smap programs so TP decode
  allreduces route through the comm/algos selection table (pallas_rhd
  eligible in the µs class; circuit-breaker degradation to lax intact).
- **kv_cache.py** — :class:`PagedKVCache`: the feed cache's
  AdmissionBudget generalized to fixed-size HBM pages with a free-list,
  per-sequence page tables, and eviction; optional int8-blockwise pages.
- **sla.py** — :class:`SLAGovernor`: the supervisor degradation ladder
  repurposed for load. Under sustained queue growth or a p99 TPOT breach
  the engine sheds batch size, then precision, then admission (429-style
  :class:`ServeOverloadError` with a retry-after hint) — never dying.

This module stays import-light (no jax at import time): supervisor.status()
and the test teardown call :func:`reset`/:func:`status` in every test, and
the engine/kv symbols are resolved lazily on first touch.
"""

from __future__ import annotations

from mlsl_tpu.serve.sla import (  # noqa: F401  (re-exports)
    RUNGS,
    ServeOverloadError,
    SLAGovernor,
    get_active,
    reset,
    status,
)

__all__ = [
    "RUNGS",
    "ServeOverloadError",
    "SLAGovernor",
    "get_active",
    "reset",
    "status",
    "InferenceEngine",
    "Request",
    "PagedKVCache",
    "oracle_generate",
    "oracle_logit_gap",
    "oracle_logits",
]

_LAZY = {
    "InferenceEngine": "mlsl_tpu.serve.engine",
    "Request": "mlsl_tpu.serve.engine",
    "oracle_generate": "mlsl_tpu.serve.engine",
    "oracle_logit_gap": "mlsl_tpu.serve.engine",
    "oracle_logits": "mlsl_tpu.serve.engine",
    "PagedKVCache": "mlsl_tpu.serve.kv_cache",
}


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)
