"""deppy_tpu.telemetry — pipeline-wide observability (ISSUE 1 + 4).

A dependency-free span/counter/histogram registry plus the structured
per-batch :class:`SolveReport`, threaded through encode → pad/pack →
device transfer → solve → decode.  The service's ``/metrics`` endpoint,
the ``deppy stats`` CLI, the JSONL event sink, and the benchmark BENCH
rows all read from here.  ISSUE 4 adds the request dimension: per-request
trace contexts (W3C ``traceparent`` interop), span trees with links
across coalesced dispatches, and the :class:`trace.FlightRecorder`
behind ``GET /debug/traces`` and ``deppy trace``.  See
docs/observability.md for the metric/span name table and the JSONL
event schema.
"""

from . import trace  # noqa: F401 — re-exported subsystem (ISSUE 4)
from .registry import (
    LANE_BUCKETS,
    RATIO_BUCKETS,
    SECONDS_BUCKETS,
    STAGE_BUCKETS,
    TIMELINE,
    Counter,
    Gauge,
    Histogram,
    Registry,
    Span,
    configure_sink,
    default_registry,
    iter_merged_sink_events,
    iter_sink_events,
    percentile,
    set_default_registry,
)
from .report import (
    SolveReport,
    begin_report,
    current_report,
    detach_report,
    end_report,
    last_report,
)

__all__ = [
    "trace",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "Span",
    "SolveReport",
    "LANE_BUCKETS",
    "RATIO_BUCKETS",
    "SECONDS_BUCKETS",
    "STAGE_BUCKETS",
    "TIMELINE",
    "begin_report",
    "configure_sink",
    "current_report",
    "default_registry",
    "detach_report",
    "end_report",
    "iter_merged_sink_events",
    "iter_sink_events",
    "last_report",
    "percentile",
    "set_default_registry",
]
