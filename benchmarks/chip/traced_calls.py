"""The program's own records of the traced cycle's calls.

Under a profiler session every call of the symbolic step pushes one
record (``CallRecord``: phase times, binds, GC pauses, HBM held above the
plan) to a process-wide sink, ``repro.core.obs.telemetry.PROFILE_SINK``.
The traced cycle's calls are the sink's newest ``run["trace"]["steps"]``
records.  A program without the sink, or records whose ``(b, s)`` are not
the cycle's shapes, give ``None``: the metric is then left out."""
from __future__ import annotations

from typing import Any, Dict, List, Optional


def traced_records(run: Dict[str, Any]) -> Optional[List[Any]]:
    tr = run.get("trace")
    if tr is None:
        return None
    try:
        from repro.core.obs.telemetry import PROFILE_SINK
    except ImportError:
        return None
    n = tr["steps"]
    recs = PROFILE_SINK.ring.records()[-n:]
    shapes = [tuple(s["shape"]) for s in run["window"]["steps"][:n]]
    got = [(dict(r.env).get("b"), dict(r.env).get("s")) for r in recs]
    if n == 0 or got != shapes:
        return None
    return recs
