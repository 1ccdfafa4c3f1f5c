"""From a profiler trace to the device's busy time, its idle gaps, and
what the host was doing in each.

``capture`` runs a callable under ``jax.profiler`` and returns the trace's
events in a plain form: device operations from the ``XLA Ops`` line of
each TPU plane, and host events from the host plane's threads (the
benchmark's own ``bench_step`` spans among them).  ``reduce`` turns those
into the numbers the per-layer metrics read and the ``breakdown`` the
result line carries.  A recorded event list (JSON) reduces the same way,
which is how the reduction is tested without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Any, Callable, Dict, List, Tuple

STEP_SPAN = "bench_step"
DEVICE_LINE = "XLA Ops"


def capture(fn: Callable[[], Any]) -> Dict[str, list]:
    """Run ``fn`` under the profiler; returns {"device": [[name, start_ns,
    dur_ns, plane], ...], "host": [[thread, name, start_ns, dur_ns], ...]}.
    The trace is written to a temporary directory and removed."""
    import jax
    from jax.profiler import ProfileData
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        data = ProfileData.from_file(paths[0])
        device, host = [], []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == DEVICE_LINE:
                        device += [[e.name, e.start_ns, e.duration_ns,
                                    plane.name] for e in line.events]
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    host += [[line.name, e.name, e.start_ns, e.duration_ns]
                             for e in line.events]
        return {"device": device, "host": host}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


_SUFFIX = re.compile(r"[.:]\d+$")


def op_name(name: str) -> str:
    """An op's kind and result shape, without XLA's instance number.

    A TPU trace names an op by its HLO text (``%fusion.12 = bf16[14,768]
    {1,0:T(8,128)} fusion(...)``); that becomes ``fusion bf16[14,768]``."""
    lhs, eq, rhs = name.partition(" = ")
    kind = _SUFFIX.sub("", lhs.strip().lstrip("%"))
    if not eq:
        return kind
    return f"{kind} {rhs.split('{')[0].split(' ')[0]}"


def reduce(events: Dict[str, list], top: int = 10) -> Dict[str, Any]:
    """Busy and idle time of the device over the traced steps.

    The window runs from the start of the first ``bench_step`` span to
    the end of the last.  Busy is the union of device-op intervals in it,
    averaged over the chips traced; each idle gap between busy intervals
    is named by the innermost host event open on the step thread when the
    gap began, and the device op that ended it."""
    steps = sorted((s, s + d) for t, n, s, d in events["host"]
                   if n == STEP_SPAN)
    if not steps:
        raise ValueError(f"no {STEP_SPAN!r} span in the trace")
    t0, t1 = steps[0][0], steps[-1][1]
    window = t1 - t0
    planes = sorted({p for *_, p in events["device"]})
    if not planes:
        raise ValueError("no device op in the traced window")
    ops = [(n, max(s, t0), min(s + d, t1), p)
           for n, s, d, p in events["device"] if s < t1 and s + d > t0]
    per_op: Dict[str, float] = defaultdict(float)
    for n, s, e, _ in ops:
        per_op[op_name(n)] += (e - s) / len(planes)
    busy = 0.0
    gaps: List[Tuple[float, float, float]] = []
    first: list = []
    step_thread = next(t for t, n, *_ in events["host"] if n == STEP_SPAN)
    host = sorted((s, s + d, n) for t, n, s, d in events["host"]
                  if t == step_thread)
    starts = [h[0] for h in host]
    for plane in planes:
        mine = sorted((s, e, n) for n, s, e, p in ops if p == plane)
        busy_iv = _union([(s, e) for s, e, _ in mine])
        busy += sum(e - s for s, e in busy_iv) / len(planes)
        if plane != planes[0]:
            continue
        first = mine
        edges = [(t0, t0)] + busy_iv + [(t1, t1)]
        gaps = [(b - a, a, b) for (_, a), (b, _) in zip(edges, edges[1:])
                if b > a]
    named = []
    for length, at, end in sorted(gaps, reverse=True)[:top]:
        j = bisect.bisect_left(first, (end,))
        nxt = op_name(first[j][2]) if j < len(first) else "end of window"
        i = bisect.bisect_right(starts, at)
        doing = [h for h in host[:i] if h[1] > at]
        what = max(doing, key=lambda h: h[0])[2] if doing else "no host event"
        named.append([f"{what} -> {nxt}", length * 1e-9])
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window * 1e-9,
        "busy_s": busy * 1e-9,
        "steps": len(steps),
        "device_ops": len(ops) / len(planes),
        "breakdown": {
            "device_ops": [[n, t * 1e-9] for n, t in device_ops],
            "idle_gaps": named,
        },
    }
