"""Spans and counters inside a call, written into the JAX profiler's trace.

Tracing is on exactly when a ``jax.profiler`` session is active
(``TraceAnnotation.is_enabled()``); there is no option.  The spans share
the trace, and so the clock, of the device's ``XLA Ops``.  A profiled call
of a ``DynamicShapeFunction`` records, on the calling thread::

    dsf.call (seq, then the env: b, s, ...)   flatten to unflatten
      dsf.solve_env                           env solve + declared ranges
      vm.resolve                              Program.resolve for the env
      vm.stream                               the instruction stream
        <op-group label> (seq, hbm_bytes, plan_bytes)   one per run
          vm.loop (trips)                     a rolled loop's run
      python.gc (generation)                  a collection inside the call

and pushes one :class:`.telemetry.CallRecord` with its phase times and
counters to :data:`.telemetry.PROFILE_SINK`.  Op-group runs exist on the
fast stream (``Program.groups``); the dynamic (eviction) stream is one
``vm.stream`` span.

At the end of every run the device's ``bytes_in_use`` is read and set
beside the plan's occupancy at that instruction (the exact replay of
:func:`.timeline.actual_timeline`, cached per env): the excess is memory
the plan has freed but the allocator still holds, because the host runs
ahead of the device.  Where the device keeps no allocator statistics (the
CPU), the bytes of all live arrays stand in.
"""
from __future__ import annotations

import functools
import gc
import itertools
import time
from typing import Any, Callable, Dict, List, Sequence

import jax
from jax.profiler import TraceAnnotation

from ..lowering.program import OP_COMPUTE, OP_LOOP
from .telemetry import PROFILE_SINK
from .timeline import actual_timeline

_SEQ = itertools.count()


@functools.lru_cache(maxsize=None)
def _in_use_reader() -> Callable[[], int]:
    """Reads the device's bytes in use (live arrays' bytes on the CPU)."""
    dev = jax.local_devices()[0]
    if dev.memory_stats() is not None:
        return lambda: dev.memory_stats()["bytes_in_use"]
    return lambda: sum(a.nbytes for a in jax.live_arrays())


def _binds(prog: Any, resolved: Any) -> int:
    """Primitive binds one fast-stream run executes (rolled loops: per
    trip)."""
    n = sum(inst.op == OP_COMPUTE for inst in prog.fast_instructions)
    for info, rl in zip(prog.loops, resolved.loops):
        n += rl.trip * _binds(info.body_program, rl.rbody)
    return n


class CallProfile:
    """Phase times and counters of one profiled call (see module doc)."""

    __slots__ = ("seq", "solve_ns", "resolve_ns", "stream_ns", "call_ns",
                 "binds", "gc_ns", "gc_collections", "hbm_over_plan_bytes",
                 "report", "program", "_gc_span", "_gc_t0", "on_gc")

    def __init__(self):
        self.seq = next(_SEQ)
        self.solve_ns = self.resolve_ns = self.stream_ns = self.call_ns = 0
        self.binds = self.gc_ns = self.gc_collections = 0
        self.hbm_over_plan_bytes = 0
        # set by the dispatch that served the call
        self.report = self.program = None
        self._gc_span = None
        self._gc_t0 = 0
        self.on_gc = self._on_gc     # one bound method: registered, removed

    def counters(self) -> Dict[str, int]:
        return dict(solve_ns=self.solve_ns, resolve_ns=self.resolve_ns,
                    stream_ns=self.stream_ns, call_ns=self.call_ns,
                    binds=self.binds, gc_ns=self.gc_ns,
                    gc_collections=self.gc_collections,
                    hbm_over_plan_bytes=self.hbm_over_plan_bytes)

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        """``gc.callbacks`` entry: a ``python.gc`` span per collection."""
        if phase == "start":
            self._gc_span = TraceAnnotation("python.gc",
                                            generation=info["generation"])
            self._gc_span.__enter__()
            self._gc_t0 = time.perf_counter_ns()
        elif self._gc_span is not None:
            self.gc_ns += time.perf_counter_ns() - self._gc_t0
            self.gc_collections += 1
            self._gc_span.__exit__(None, None, None)
            self._gc_span = None

    def run_groups(self, vm: Any, storage: List[Any],
                   flat_args: Sequence[Any], resolved: Any) -> None:
        """The fast stream, one ``vm._run_range`` per op-group run, each
        inside its span, with the device's bytes in use read at its end."""
        prog = vm.program
        insts = prog.fast_instructions
        planned = resolved.group_plan_bytes
        if planned is None:
            points = actual_timeline(prog, resolved.env).points
            planned = resolved.group_plan_bytes = [
                points[g.hi - 1].device_used for g in prog.groups]
        in_use = _in_use_reader()
        worst = None
        for g, plan_bytes in zip(prog.groups, planned):
            run = insts[g.lo:g.hi]
            with TraceAnnotation(g.label or "vm.ops", seq=self.seq) as span:
                if g.loop:
                    lidx = next(i.lidx for i in run if i.op == OP_LOOP)
                    with TraceAnnotation("vm.loop",
                                         trips=resolved.loops[lidx].trip):
                        vm._run_range(storage, flat_args, resolved, run)
                else:
                    vm._run_range(storage, flat_args, resolved, run)
                used = in_use()
                span.set_metadata(hbm_bytes=used, plan_bytes=plan_bytes)
            if worst is None or used - plan_bytes > worst:
                worst = used - plan_bytes
        if worst is not None:
            self.hbm_over_plan_bytes = worst
        self.binds += _binds(prog, resolved)


def profile_call(fn: Any, args: tuple, kwargs: dict) -> Any:
    """Run ``fn(*args, **kwargs)`` (a ``DynamicShapeFunction``) inside a
    ``dsf.call`` span with GC watched, then push its record to
    :data:`PROFILE_SINK`."""
    prof = CallProfile()
    gc.callbacks.append(prof.on_gc)
    try:
        with TraceAnnotation("dsf.call", seq=prof.seq) as span:
            t0 = time.perf_counter_ns()
            out = fn._call(args, kwargs, prof)
            prof.call_ns = time.perf_counter_ns() - t0
            span.set_metadata(**prof.report.env)
    finally:
        gc.callbacks.remove(prof.on_gc)
    fn._record_call(PROFILE_SINK, prof.report, prof.program, prof)
    return out
