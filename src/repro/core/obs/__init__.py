"""Plan-aware observability: compile tracing, runtime telemetry, exports.

Coordinated layers over the symbolic-shape pipeline:

* :mod:`.trace` — hierarchical compile-phase spans + a structured
  decision log, recorded by ``optimize`` and every bucket specialization
  (background compiles included, on their own track);
* :mod:`.telemetry` — a fixed-capacity per-call ring buffer behind a
  single disabled-path attribute check (the ≤2% overhead contract);
* :mod:`.timeline` — exact per-instruction memory timelines, off the hot
  path: the plan's symbolic events replayed at one env and diffed
  against the plan's predicted occupancy;
* :mod:`.export` / :mod:`.explain` — Chrome-trace/Perfetto JSON,
  Prometheus text metrics, and the human-readable
  ``DynamicShapeFunction.explain()`` report;
* :mod:`.profile` — under a ``jax.profiler`` session, spans inside each
  call (solve, resolve, one per op group, Python GC) written into the
  profiler's own trace, and one record per call in the process-wide
  :data:`.telemetry.PROFILE_SINK`.
"""
from .explain import build_explain
from .export import chrome_trace, chrome_trace_json, prometheus_text
from .telemetry import (AdmissionEvent, CallRecord, Telemetry,
                        TelemetryRing)
from .timeline import (Timeline, TimelineDiff, TimelinePoint,
                       actual_timeline, diff_timeline, planned_timeline)
from .trace import (NULL_TRACER, Decision, DecisionLog, NullTracer, Span,
                    Tracer)

__all__ = [
    "Tracer", "NullTracer", "NULL_TRACER", "Span",
    "Decision", "DecisionLog",
    "Telemetry", "TelemetryRing", "CallRecord", "AdmissionEvent",
    "Timeline", "TimelinePoint", "TimelineDiff",
    "actual_timeline", "planned_timeline", "diff_timeline",
    "chrome_trace", "chrome_trace_json", "prometheus_text",
    "build_explain",
]
