"""Runtime telemetry: a per-call ring buffer with a strict overhead budget.

The contract, enforced by ``tests/test_obs.py`` and ``benchmarks/
obs_bench.py``: with telemetry *disabled* the dispatch hot path pays one
attribute load and an ``is None`` test — no allocation, no locking, no
dict probe — and stays within 2% of the uninstrumented baseline on the
exec_bench dispatch-chain microbench.  With telemetry *enabled*, each
call appends one :class:`CallRecord` to a fixed-capacity ring.

The ring takes one mutex per push: a serving deployment drives a single
``DynamicShapeFunction`` from many request threads (see the chaos suite),
so the write index must move atomically or concurrent pushes overwrite
one slot and double-count another.  The lock lives on the *enabled* path
only — the disabled path never reaches it — and is uncontended in
single-threaded use.  Readers (``records()``) snapshot under the same
lock; slots are replaced wholesale, so a reader can never observe a
partial record.

A second, process-wide ring, :data:`PROFILE_SINK`, takes one record per
call made under a ``jax.profiler`` session, whatever the function's own
telemetry says (:mod:`.profile`): its phase times, binds, GC pauses and
HBM held above the plan are what the benchmark's per-layer metrics read.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Tuple


class CallRecord(NamedTuple):
    """One dispatched call, as the ring stores it (flat, allocation-light)."""

    seq: int                            # 0-based call number
    bucket_key: Optional[Tuple]         # specialization bucket; None = unbucketed
    env: Tuple[Tuple[str, int], ...]    # sorted dim binding
    wall_s: float
    dispatch_ns: int                    # this call's dispatch overhead
    device_peak: int
    arena_bytes: int
    evictions: int
    recomputes: int
    reloads: int
    donated_reuses: int
    loop_trips: Tuple[int, ...]         # per rolled loop, program order
    # profiled calls only (obs.profile; 0 otherwise): host ns in the env
    # solve + declared-range check, Program.resolve, the instruction
    # stream, and the whole call (flatten to unflatten); primitive binds
    # executed; jitted op-group runs dispatched, and the binds inside them;
    # Python GC pauses inside the call; the largest excess of the device's
    # bytes in use over the plan's occupancy at an op-group boundary
    solve_ns: int = 0
    resolve_ns: int = 0
    stream_ns: int = 0
    call_ns: int = 0
    binds: int = 0
    segments: int = 0
    fused_binds: int = 0
    gc_ns: int = 0
    gc_collections: int = 0
    hbm_over_plan_bytes: int = 0


class TelemetryRing:
    """Fixed-capacity ring of :class:`CallRecord` (thread-safe)."""

    def __init__(self, capacity: int = 256):
        if capacity < 1:
            raise ValueError("ring capacity must be >= 1")
        self.capacity = capacity
        self._slots: List[Optional[CallRecord]] = [None] * capacity
        self._count = 0                 # monotonic; next write position
        self._lock = threading.Lock()

    def push(self, rec: CallRecord) -> None:
        with self._lock:
            self._slots[self._count % self.capacity] = rec
            self._count += 1

    def __len__(self) -> int:
        return min(self._count, self.capacity)

    @property
    def total_pushed(self) -> int:
        return self._count

    @property
    def dropped(self) -> int:
        """Records overwritten because the ring wrapped."""
        return max(0, self._count - self.capacity)

    def records(self) -> List[CallRecord]:
        """Oldest-to-newest snapshot of the retained records."""
        with self._lock:
            n, cap = self._count, self.capacity
            if n <= cap:
                return [r for r in self._slots[:n] if r is not None]
            start = n % cap
            out = self._slots[start:] + self._slots[:start]
            return [r for r in out if r is not None]


@dataclass(frozen=True)
class AdmissionEvent:
    """One admission-control decision by the serving batcher.

    ``outcome`` distinguishes what happened to the group/request:
    ``"held"`` (over-budget group deferred to a later drain),
    ``"shed-capacity"`` (queue full, request refused at submit),
    ``"shed-deadline"`` (request's deadline expired in queue),
    ``"shed-aged"`` (held group exceeded its max hold cycles)."""

    key: Tuple                          # bucket key (dim upper bounds)
    label: str                          # human-readable bucket label
    required_bytes: int                 # the group's arena_bound_bytes
    available_bytes: int                # the batcher's memory_budget
    queue_depth: int                    # requests held in this group
    outcome: str = "held"               # held | shed-capacity |
    #                                     shed-deadline | shed-aged


class Telemetry:
    """Telemetry aggregate: ring + running totals.  Created per function by
    ``DynamicShapeFunction.enable_telemetry()``, and once per process as
    :data:`PROFILE_SINK`.  Counter updates are lock-protected — concurrent
    request threads must not lose increments (the lock is on the enabled
    path only)."""

    def __init__(self, capacity: int = 256):
        self.ring = TelemetryRing(capacity)
        self.n_calls = 0
        self.wall_s_total = 0.0
        self.dispatch_ns_total = 0
        self.calls_by_bucket: Dict[Optional[Tuple], int] = {}
        self._lock = threading.Lock()

    def on_call(self, bucket_key: Optional[Tuple], report: Any, *,
                loop_trips: Tuple[int, ...] = (),
                profile: Any = None) -> None:
        """Record one dispatched call.  Runs only when telemetry is
        enabled or the call was profiled — the disabled path never reaches
        this method.  ``profile`` (a :class:`.profile.CallProfile`) adds
        the call's phase times and counters, under the call span's seq."""
        st = report.stats
        with self._lock:
            seq = self.n_calls if profile is None else profile.seq
            self.n_calls += 1
            self.wall_s_total += report.wall_s
            self.dispatch_ns_total += st.last_dispatch_ns
            self.calls_by_bucket[bucket_key] = \
                self.calls_by_bucket.get(bucket_key, 0) + 1
        self.ring.push(CallRecord(
            seq=seq, bucket_key=bucket_key,
            env=tuple(sorted(report.env.items())),
            wall_s=report.wall_s, dispatch_ns=st.last_dispatch_ns,
            device_peak=st.device_peak, arena_bytes=st.arena_bytes,
            evictions=st.evictions, recomputes=st.recomputes,
            reloads=st.reloads, donated_reuses=st.donated_reuses,
            loop_trips=loop_trips,
            **({} if profile is None else profile.counters())))

    def summary(self) -> Dict[str, Any]:
        return dict(
            n_calls=self.n_calls,
            wall_s_total=self.wall_s_total,
            dispatch_ns_total=self.dispatch_ns_total,
            ring_retained=len(self.ring),
            ring_dropped=self.ring.dropped,
            calls_by_bucket={str(k): v
                             for k, v in self.calls_by_bucket.items()})


# every call made under a jax.profiler session, process-wide (obs.profile);
# the benchmark's readers take its newest records
PROFILE_SINK = Telemetry(capacity=256)
