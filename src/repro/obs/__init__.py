"""Observability for the serving engine: spans, metrics, exporters.

See ``src/repro/serving/README.md`` ("Observability") for the
instrumentation-point diagram and how the pieces compose.
"""
from repro.obs.attribution import attribution_report, decompose
from repro.obs.export import chrome_trace, validate_chrome_trace, write_chrome_trace
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsCollector,
    MetricsRegistry,
)
from repro.obs.stream import HOOKS, InstrumentationStream, build_stream
from repro.obs.trace import SPAN_KINDS, NullTracer, SimClock, Span, SpanTracer

__all__ = [
    "attribution_report",
    "decompose",
    "chrome_trace",
    "validate_chrome_trace",
    "write_chrome_trace",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsCollector",
    "MetricsRegistry",
    "HOOKS",
    "InstrumentationStream",
    "build_stream",
    "SPAN_KINDS",
    "NullTracer",
    "SimClock",
    "Span",
    "SpanTracer",
]
