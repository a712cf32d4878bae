"""Observability subsystem: tracing, metrics, exporters.

See ``docs/observability.md`` for the span taxonomy, metric names and
exporter usage. Quickstart::

    from repro import obs
    from repro.obs import export

    with obs.tracing() as tr:
        run = runtime.run_stream(stream, catalog, cfg)
    export.to_chrome_trace(tr, "stream.trace.json")   # chrome://tracing
    export.to_jsonl(tr, "stream.trace.jsonl")
    print(export.summary_table(tr))
"""
from repro.obs.trace import (
    DecisionChannel, NULL_TRACER, PROFILER_PREFIX, Span, Tracer,
    filter_decision_channel, get_tracer, record_filter_decision,
    set_tracer, tracing,
)
from repro.obs.metrics import (
    Counter, Gauge, Histogram, Metrics, get_metrics, set_metrics,
)
from repro.obs import export

__all__ = [
    "Span", "Tracer", "DecisionChannel", "NULL_TRACER", "PROFILER_PREFIX",
    "get_tracer", "set_tracer", "tracing",
    "record_filter_decision", "filter_decision_channel",
    "Counter", "Gauge", "Histogram", "Metrics",
    "get_metrics", "set_metrics",
    "export",
]
