"""Unified telemetry layer (DESIGN.md §16): tracing + metrics + health.

One switch governs everything::

    from repro import obs
    obs.enable()                  # or REPRO_OBS=1 in the environment

    with obs.span("serve.batch", rows=64):
        ...
    obs.metrics.counter("serve.requests").inc()

    obs.trace.export_chrome("trace.json")     # chrome://tracing / Perfetto
    print(obs.metrics.dump())                 # Prometheus-style text

Disabled (the default), every instrumentation site costs one function call
plus one module-global load — no locks, no allocation, no host syncs — so
the hot paths keep their benchmarked numbers (gated ~0% by
benchmarks/obs_overhead.py; enabled mode is gated <= 2%).  While enabled,
spans also annotate a ``jax.profiler`` trace being taken.  The flag is
process-wide and can be toggled at runtime; jitted code is never touched
(all instrumentation lives on the host driver side), so toggling never
retraces anything.

Naming conventions (§16): spans are ``subsystem.verb_noun``
(``ingest.select_chunk``), metrics are ``subsystem.noun``
(``serve.queue_depth``) with low-cardinality labels (pow2 ``bucket``,
eigenvalue index ``k``).
"""
from __future__ import annotations

import os

from repro.obs import metrics, trace
from repro.obs.spectral import SpectralHealth
from repro.obs.trace import span

__all__ = ["enable", "disable", "enabled", "span", "metrics", "trace",
           "SpectralHealth"]

_ENABLED = False


def enabled() -> bool:
    """The single flag every instrumentation site consults (via its local
    module's mirror — one global load on the disabled hot path)."""
    return _ENABLED


def enable() -> None:
    global _ENABLED
    _ENABLED = True
    trace._ENABLED = True
    metrics._ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False
    trace._ENABLED = False
    metrics._ENABLED = False


def _enable_from_env() -> None:
    """``REPRO_OBS=1`` turns observability on at import (how the demo and
    the overhead bench's enabled mode run without code changes)."""
    if os.environ.get("REPRO_OBS", "0") not in ("", "0"):
        enable()


_enable_from_env()
