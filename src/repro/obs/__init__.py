"""Unified observability for the PUL serving stack.

Two pieces, both dependency-free (no jax — importable from tools and CI
jobs without a device runtime):

  - :mod:`repro.obs.tracer`  — structured tracing: synchronous spans,
    cross-scope async spans, instants, counters, on two clocks (wall µs
    for the serving engine, model time for the DMA twin), exported as
    Chrome/Perfetto trace-event JSON. While a ``jax.profiler`` session
    records, each synchronous span also shows in the profile (jax is
    imported on the first span). ``DEFAULT_TRACER``, which the serving
    stack gets when none is passed, records only synchronous spans and only
    while such a session records; ``NULL_TRACER`` records nothing.
  - :mod:`repro.obs.metrics` — flat metrics registry (JSON + Prometheus
    text exporters) and the cache-economics accounting: bytes moved per
    token emitted per tier, and prefetch accuracy / timeliness / coverage
    for planned d* restores.
"""
from repro.obs.metrics import (
    MetricsRegistry,
    Sample,
    cache_economics,
    economics_into_registry,
    serving_roofline,
)
from repro.obs.tracer import (
    DEFAULT_TRACER,
    NULL_TRACER,
    NullTracer,
    Span,
    TraceEvent,
    Tracer,
    load_chrome_trace,
    page_events_from_chrome,
    profiler_recording,
    validate_chrome_trace,
)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "TraceEvent",
    "DEFAULT_TRACER", "Span", "profiler_recording",
    "load_chrome_trace", "validate_chrome_trace", "page_events_from_chrome",
    "MetricsRegistry", "Sample", "cache_economics",
    "economics_into_registry", "serving_roofline",
]
