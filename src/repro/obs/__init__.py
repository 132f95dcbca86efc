"""repro.obs: tracing, metrics, drift detection, and telemetry.

One observability layer for the whole data path — see ``trace`` (span
facility + Chrome trace-event export), ``metrics`` (counters, gauges,
mergeable latency histograms), ``drift`` (observed-vs-profiled speed
ratios), and ``telemetry`` (crash-safe on-disk metric time-series, SLO
classes/burn rates, deduplicated alerts — see README.md).  The package
``__init__``'s import cost is stdlib-only; the rest of the tree imports
it freely, including from inside codec hot paths.  ``telemetry`` needs
msgpack (the on-disk frame codec, same as the cluster wire), so it stays
a submodule import: ``from repro.obs import telemetry``.
"""

from .drift import DriftDetector, merge_reports, retrieval_expectations
from .metrics import DEFAULT_BOUNDS, Histogram, MetricsRegistry
from .trace import (COMPILES, TRACER, Span, Tracer, chrome_trace_events,
                    count, enable, export_trace, span)

__all__ = [
    "COMPILES", "TRACER", "Span", "Tracer", "chrome_trace_events", "count",
    "enable", "export_trace", "span",
    "DEFAULT_BOUNDS", "Histogram", "MetricsRegistry",
    "DriftDetector", "merge_reports", "retrieval_expectations",
]
