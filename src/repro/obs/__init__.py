"""Unified observability: typed metrics registry, deterministic span
tracing, and the counter-reconciliation checker.

Import surface is deliberately dependency-free (numpy + stdlib only) so
every layer of the serving stack can import it without cycles.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Reservoir,
    publish_all,
)
from repro.obs.reconcile import (  # noqa: F401
    check_all,
    check_trace_vs_metrics,
    reconcile,
)
from repro.obs.tracing import (  # noqa: F401
    NullTracer,
    SpanTracer,
    Steps,
    get_tracer,
    install_tracer,
    validate_chrome_trace,
)
