"""Slim register VM over a lowered :class:`~repro.core.lowering.Program`.

The default executor.  Two regimes, chosen per dim binding by
``Program.resolve``:

* **fast stream** — when no ``MaybeEvict`` can fire at this env (no
  memory limit, or the replayed peak fits under it), each op-group run
  of the stream (``Program.groups``, ``Program.segments``) is one
  ``jax.jit`` executable: the per-op loop below traced once per env, a
  rolled loop as one ``lax.scan`` of its body.  XLA fuses the run's ops,
  and the call is one dispatch per run.  JAX caches the executables per
  env (a static argument), apart from ``Program.resolve``'s cache.  All
  sizes/params were resolved once per env and the call's complete
  ``MemoryStats`` was precomputed by the resolve replay.  Outputs equal
  the reference ``PlanInterpreter``'s within f32 round-off, not bitwise
  (fusion reorders and fuses the arithmetic).
* **dynamic stream** — under real memory pressure the full instruction
  stream runs one bind per op: ``MaybeEvict`` triggers the runtime remat
  policy at the op boundaries the lowering marked, ``Regen``
  rematerializes evicted registers through reload or the candidate's
  lowered sub-program, and frees honor regeneration holds.  Outputs are
  bitwise-identical to the reference ``PlanInterpreter``; eviction
  counters can differ only when victim scores tie exactly after remat
  churn (the interpreter's storage-dict iteration order mutates on
  reinsertion, the VM's candidate order is static).

A call with an armed kernel fault runs the fast stream per op too
(``_run_fast_faulted``: a fault probe before every bind, a rolled loop's
body per trip), bitwise equal to the interpreter like the dynamic
stream.
"""
from __future__ import annotations

import contextlib
import re
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from ..ir.trace import solve_checked_env
from ..lowering.program import (OP_BIND_ARG, OP_BIND_DIM, OP_COMPUTE,
                                OP_DONATE, OP_FREE_SLOT, OP_LOOP,
                                OP_MAYBE_EVICT, OP_REGEN, Program,
                                ResolvedProgram)
from ..memplan.arena import ArenaAllocator, ArenaExhausted
from ..remat.runtime import RuntimeRematPolicy
from .interpreter import RunReport
from .memory import MemoryManager, MemoryStats

# entered in place of a span when no profiler session is active (reusable,
# allocates nothing)
_NO_SPAN = contextlib.nullcontext()


class ProgramVM:
    """Executes a lowered Program; drop-in for ``PlanInterpreter.run``."""

    def __init__(self, program: Program, *,
                 size_cache: Optional[Dict[Tuple, Dict[int, int]]] = None,
                 params_cache: Optional[
                     Dict[Tuple, Dict[int, Dict[str, Any]]]] = None,
                 arena_hard_cap: Optional[int] = None):
        self.program = program
        self.plan = program.plan
        # shared per-env caches (bucketed dispatch passes one pair to every
        # bucket executor; keys are namespaced by graph uid inside resolve)
        self._size_cache = size_cache
        self._params_cache = params_cache
        # resilience.enforce_arena_bound: the plan's guaranteed arena bound
        # as a runtime hard cap — a resolve (or runtime growth) that would
        # exceed it raises ArenaExhausted instead of silently growing
        self.arena_hard_cap = arena_hard_cap
        # optional live-occupancy probe, dynamic (eviction) stream only:
        # called as hook(idx, inst, mm) after every executed instruction.
        # The fast stream is never instrumented — its occupancy curve is
        # exactly reconstructible off the hot path (obs.timeline)
        self.timeline_hook = None
        # per op group: its run's jitted executable (built on first use;
        # JAX keeps one compiled program per env under it)
        self._segment_fns: List[Any] = [None] * len(program.groups)
        self._consts = tuple(i for i in program.fast_instructions
                             if i.op == OP_BIND_ARG and i.index < 0)

    # knobs live on the lowered artifact (they shaped the emission)
    @property
    def memory_limit(self) -> Optional[int]:
        return self.program.memory_limit

    @property
    def donate_inputs(self) -> bool:
        return self.program.donate_inputs

    @property
    def count_inputs(self) -> bool:
        return self.program.count_inputs

    # ---------------------------------------------------------------- run --
    def run(self, flat_args: Sequence[Any],
            env: Optional[Dict[str, int]] = None,
            faults: Any = None, prof: Any = None
            ) -> Tuple[List[Any], RunReport]:
        """Execute once.  ``prof`` is the call's
        :class:`~repro.core.obs.profile.CallProfile` when a profiler
        session is active (``None`` otherwise): the resolve and the stream
        then run inside ``vm.resolve`` / ``vm.stream`` spans, timed."""
        t0 = time.perf_counter_ns()
        prog = self.program
        if env is None:
            # pre-solved envs (bucketed dispatch hot path) skip both steps
            env = solve_checked_env(prog.graph, prog.plan.shape_graph,
                                    flat_args)
        t1 = time.perf_counter_ns()
        with (_NO_SPAN if prof is None else TraceAnnotation("vm.resolve")):
            resolved = prog.resolve(env, self._size_cache,
                                    self._params_cache)
        t2 = time.perf_counter_ns()
        cap = self.arena_hard_cap
        if cap is not None and resolved.arena is not None \
                and resolved.arena.arena_bytes > cap:
            # the resolve replay is exact for this env: catching the breach
            # here covers the fast stream without instrumenting its loop
            raise ArenaExhausted(
                f"resolved arena reserve {resolved.arena.arena_bytes} "
                f"exceeds the enforced bound of {cap} bytes")
        if prof is None:
            outs, stats = self._execute(flat_args, resolved, env, faults)
        else:
            with TraceAnnotation("vm.stream"):
                outs, stats = self._execute(flat_args, resolved, env, faults,
                                            prof)
            prof.resolve_ns += t2 - t1
            prof.stream_ns += time.perf_counter_ns() - t2
        # the VM's share of this call's dispatch overhead (env solve when
        # not given, resolve); the caller adds its own solve and lookup
        stats.last_dispatch_ns = t2 - t0
        if stats.measured_dims:
            # surface the measured (not cap) bound dims in the report env
            env = {**resolved.env, **stats.measured_dims}
        wall = (time.perf_counter_ns() - t0) * 1e-9
        return outs, RunReport(stats=stats, wall_s=wall, env=env)

    def _execute(self, flat_args: Sequence[Any], resolved: ResolvedProgram,
                 env: Dict[str, int], faults: Any, prof: Any = None
                 ) -> Tuple[List[Any], MemoryStats]:
        if faults is None:
            if resolved.fast_ok:
                return self._run_fast(flat_args, resolved, prof)
            return self._run_dynamic(flat_args, resolved, env)
        if resolved.fast_ok and not faults.needs_memory:
            return self._run_fast_faulted(flat_args, resolved, faults)
        # a memory-kind fault needs the allocation stream: the dynamic
        # regime runs the full instruction list (bitwise-identical outputs
        # — it is the generic path the fast stream specializes)
        return self._run_dynamic(flat_args, resolved, env, faults=faults)

    # ------------------------------------------------------------- loops --
    def _exec_loop(self, info, rl, ins: Sequence[Any],
                   env: Dict[str, int]) -> List[Any]:
        """Run one rolled loop per op (the dynamic and faulted streams): the
        lowered body sub-Program per iteration with registers rebound
        (carries from the previous iteration's output registers, ``xs``
        slices by index).

        Pure execution — memory accounting happens through the shared
        ``LoopPlanInfo.account`` engine (dynamic path) or the resolve-time
        stats replay (faulted path).  The body runs the same nodes in the
        same order with the same refined params as the reference
        interpreter's op-by-op loop, so outputs are bitwise-identical."""
        body, lp = info.body, info.lp
        bprog = info.body_program
        nc, nk = body.num_consts, body.num_carry
        consts_args = list(ins[:nc])
        carries = list(ins[nc:nc + nk])
        # one unstack dispatch per used xs, not one slice per iteration
        xs = [list(x) if lp.x_used[j] else None
              for j, x in enumerate(ins[nc + nk:])]
        out_regs = bprog.out_regs          # carries then ys
        ys: List[List[Any]] = [[] for _ in lp.y_out]
        for i in range(rl.trip):
            flat = consts_args + carries + [
                xs[j][i] if lp.x_used[j] else None for j in range(len(xs))]
            storage: List[Any] = [None] * bprog.n_regs
            # a body holds no rolled loop (bodies trace with roll_loops off)
            self._run_range(storage, flat, rl.rbody, bprog.fast_instructions)
            carries = [storage[r] for r in out_regs[:nk]]
            for j, r in enumerate(out_regs[nk:]):
                ys[j].append(storage[r])
        if rl.trip > 0:
            # lax.concatenate over expanded slices: bitwise-identical to
            # jnp.stack at a fraction of its dispatch cost
            stacked = [
                lax.concatenate([lax.expand_dims(y, (0,)) for y in col], 0)
                for col in ys]
        else:
            stacked = [jnp.zeros((0,) + tuple(int(d.evaluate(env))
                                              for d in v.dims), v.dtype)
                       for v in lp.y_out]
        return carries + stacked

    def _scan_loop(self, info, rl, ins: Sequence[Any],
                   env: Dict[str, int]) -> List[Any]:
        """``_exec_loop`` inside a traced op-group run: the body, traced
        once, as a ``lax.scan`` of ``rl.trip`` iterations."""
        body, lp = info.body, info.lp
        bprog = info.body_program
        nc, nk = body.num_consts, body.num_carry
        consts_args = list(ins[:nc])
        xs = [x if lp.x_used[j] else None
              for j, x in enumerate(ins[nc + nk:])]
        out_regs = bprog.out_regs          # carries then ys

        def step(carries, x):
            storage: List[Any] = [None] * bprog.n_regs
            self._run_range(storage, consts_args + list(carries) + list(x),
                            rl.rbody, bprog.fast_instructions)
            return ([storage[r] for r in out_regs[:nk]],
                    [storage[r] for r in out_regs[nk:]])

        carries, ys = lax.scan(step, list(ins[nc:nc + nk]), xs,
                               length=rl.trip)
        return list(carries) + list(ys)

    # ------------------------------------------------------------ fast path
    def _run_fast(self, flat_args: Sequence[Any], resolved: ResolvedProgram,
                  prof: Any = None) -> Tuple[List[Any], MemoryStats]:
        """The fast stream: one jitted executable per op-group run, in a
        profiled call each inside its span."""
        prog = self.program
        storage: List[Any] = [None] * prog.n_regs
        if prof is None:
            for gi in range(len(prog.groups)):
                self._run_segment(storage, flat_args, resolved, gi)
        else:
            prof.run_groups(self, storage, flat_args, resolved)
        outputs = [storage[r] for r in prog.out_regs]
        return outputs, prog.stats_for(resolved)

    def _run_segment(self, storage: List[Any], flat_args: Sequence[Any],
                     resolved: ResolvedProgram, gi: int) -> None:
        """Op group ``gi``'s run: its BindArgs on the host, then one call of
        its executable at this env (traced and compiled on the env's first
        call), its live outputs stored and what it freed nulled."""
        prog = self.program
        g, seg = prog.groups[gi], prog.segments[gi]
        self._run_range(storage, flat_args, resolved,
                        prog.fast_instructions[g.lo:seg.lo])
        if seg.lo == g.hi:
            return
        fn = self._segment_fns[gi]
        if fn is None:
            fn = self._segment_fns[gi] = self._segment_fn(gi)
        outs = fn(resolved.env_key, *[storage[r] for r in seg.in_regs])
        for r, out in zip(seg.out_regs, outs):
            storage[r] = out
        for r in seg.frees:
            storage[r] = None

    def _segment_fn(self, gi: int) -> Any:
        """Op group ``gi``'s run as a ``jax.jit`` function of the env key
        (static: JAX keeps one executable per env) and its input
        registers, donating the dead ones ``Segment.donate`` names."""
        prog = self.program
        g, seg = prog.groups[gi], prog.segments[gi]
        insts = prog.fast_instructions[seg.lo:g.hi]

        def run(env_key, *ins):
            resolved = prog.resolve(dict(env_key), self._size_cache,
                                    self._params_cache)
            storage: List[Any] = [None] * prog.n_regs
            self._run_range(storage, (), resolved, self._consts)
            for r, x in zip(seg.in_regs, ins):
                storage[r] = x
            self._run_range(storage, (), resolved, insts, traced=True)
            return [storage[r] for r in seg.out_regs]

        # the executable's name in the device's trace
        run.__name__ = run.__qualname__ = "vm_" + re.sub(
            r"\W", "_", g.label or "ops")
        return jax.jit(run, static_argnums=0,
                       donate_argnums=tuple(1 + k for k in seg.donate))

    def _run_range(self, storage: List[Any], flat_args: Sequence[Any],
                   resolved: ResolvedProgram, insts: Sequence[Any],
                   traced: bool = False) -> None:
        """The fast stream's loop body (its only copy) over ``insts``: gather
        input registers, bind, store outputs, null dead registers.  Eager
        per op (the faulted stream, a run's BindArgs), or ``traced`` inside
        an op-group executable, where a rolled loop is a ``lax.scan``."""
        prog = self.program
        params = resolved.params
        for inst in insts:
            op = inst.op
            if op == OP_COMPUTE:
                ins = [storage[r] for r in inst.in_regs]
                if inst.dim_as_value:
                    out = jnp.asarray(params[inst.cidx]["dim"], jnp.int32)
                    for _oi, r in inst.store:
                        storage[r] = out
                elif inst.multi:
                    outs = inst.prim.bind(*ins, **params[inst.cidx])
                    for oi, r in inst.store:
                        storage[r] = outs[oi]
                else:
                    out = inst.prim.bind(*ins, **params[inst.cidx])
                    for _oi, r in inst.store:
                        storage[r] = out
            elif op == OP_BIND_ARG:
                storage[inst.reg] = (flat_args[inst.index]
                                     if inst.index >= 0 else inst.const)
            elif op == OP_FREE_SLOT or op == OP_DONATE:
                storage[inst.reg] = None
            elif op == OP_LOOP:
                run_loop = self._scan_loop if traced else self._exec_loop
                outs = run_loop(
                    prog.loops[inst.lidx], resolved.loops[inst.lidx],
                    [storage[r] for r in inst.in_regs], resolved.env)
                for oi, r in inst.store:
                    storage[r] = outs[oi]

    # -------------------------------------------------- fast path, faulted
    def _run_fast_faulted(self, flat_args: Sequence[Any],
                          resolved: ResolvedProgram,
                          faults: Any) -> Tuple[List[Any], MemoryStats]:
        """The fast stream one instruction at a time, with a fault probe
        ahead of every kernel bind (a rolled loop counts as one step).

        Only runs when a kernel fault is armed for the call, so the clean
        stream's loop stays branch-free."""
        prog = self.program
        storage: List[Any] = [None] * prog.n_regs
        for inst in prog.fast_instructions:
            if inst.op == OP_COMPUTE or inst.op == OP_LOOP:
                faults.before_compute()
            self._run_range(storage, flat_args, resolved, (inst,))
        outputs = [storage[r] for r in prog.out_regs]
        return outputs, prog.stats_for(resolved)

    # --------------------------------------------------------- dynamic path
    def _run_dynamic(self, flat_args: Sequence[Any],
                     resolved: ResolvedProgram,
                     env: Dict[str, int],
                     faults: Any = None) -> Tuple[List[Any], MemoryStats]:
        prog = self.program
        plan = prog.plan
        vid_of = prog.vid_of
        reg_of = prog.reg_of
        nbytes = resolved.nbytes
        params = resolved.params
        ensure_bytes = resolved.ensure_bytes
        death = prog.death_step

        # value-dependent bounded dims: per-call overlays.  ``env_run``
        # starts at the cap-completed resolve env and is rebound by each
        # BindDim; ``nbytes`` becomes a private copy so measured sizes
        # never leak into the shared resolve (cap) tables.
        bound = prog.has_bound_dims
        env_run = resolved.env
        if bound:
            env_run = dict(env_run)
            nbytes = list(nbytes)

        # the policy's candidate flops expressions may mention bound
        # symbols (a recompute over a padded payload): evaluate at the
        # complete resolve env, never the bare declared env
        policy = RuntimeRematPolicy(plan, resolved.env)
        arena = None
        if resolved.arena is not None:
            arena = ArenaAllocator(plan.arena_plan, resolved.arena,
                                   hard_cap=self.arena_hard_cap)
        mm = MemoryManager(prog.memory_limit, arena=arena)
        if faults is not None:
            mm.fault_hook = faults.on_memory

        storage: List[Any] = [None] * prog.n_regs
        host_storage: Dict[int, Any] = {}     # reg -> host (numpy) array
        evicted_recompute: set = set()        # regs dropped, regenerable
        holds: Dict[int, int] = {}            # regen source pins
        pending_free: Dict[int, bool] = {}    # dead-but-held: reg -> counted
        state = {"step": 0, "pinned": frozenset()}

        def is_materializable(reg: int) -> bool:
            return storage[reg] is not None or reg in host_storage \
                or reg in evicted_recompute

        def free_reg(reg: int, counted: bool) -> None:
            was_tracked = is_materializable(reg)
            storage[reg] = None
            host_storage.pop(reg, None)
            evicted_recompute.discard(reg)
            if not was_tracked:
                return
            if counted:
                mm.free(vid_of[reg])
            else:
                # uncounted donated input: still release its arena slot
                mm.arena_release(vid_of[reg])

        # -- eviction callback (the folded RuntimeRematPolicy check) ---------
        def evict(need: int) -> int:
            live: Dict[int, int] = {}
            for reg in prog.candidate_regs:
                if storage[reg] is None:
                    continue
                if death[reg] >= state["step"] or holds.get(reg, 0) > 0:
                    live[vid_of[reg]] = mm.device_bytes(vid_of[reg])
            decisions = policy.choose_victims(need, live, state["pinned"],
                                              state["step"])
            freed = 0
            for dec in decisions:
                reg = reg_of[dec.vid]
                arr = storage[reg]
                if arr is None:
                    continue
                storage[reg] = None
                method = dec.method
                sub = prog.regen.get(reg)
                if method == "recompute":
                    # recompute is only safe if every source is materializable
                    if sub is None or not all(is_materializable(s)
                                              for s in sub.source_regs):
                        method = "offload"
                if method == "offload":
                    host_storage[reg] = np.asarray(arr)
                    mm.evict_to_host(dec.vid)
                else:
                    for s in sub.source_regs:
                        holds[s] = holds.get(s, 0) + 1
                    evicted_recompute.add(reg)
                    mm.evict_drop(dec.vid)
                del arr
                freed += dec.bytes_freed
            return freed

        mm.evict_callback = evict

        # -- materialize-on-demand (Regen instruction body) ------------------
        def materialize(reg: int) -> Any:
            arr = storage[reg]
            if arr is not None:
                return arr
            vid = vid_of[reg]
            if reg in host_storage:  # reload path (H2D)
                mm.ensure(nbytes[reg])
                arr = jnp.asarray(host_storage.pop(reg))
                mm.reload(vid)
                storage[reg] = arr
                return arr
            if reg in evicted_recompute:  # recompute sub-program
                sub = prog.regen[reg]
                evicted_recompute.discard(reg)
                for s in sub.source_regs:  # recursion strictly moves up-graph
                    materialize(s)
                temps: List[Any] = [None] * sub.n_temps
                for st in sub.steps:
                    ins = [temps[idx] if is_temp else materialize(idx)
                           for is_temp, idx in st.in_refs]
                    p = params[st.params_cidx]
                    if st.dim_as_value:
                        outs = [jnp.asarray(p["dim"], jnp.int32)]
                    elif st.multi:
                        outs = st.prim.bind(*ins, **p)
                    else:
                        outs = [st.prim.bind(*ins, **p)]
                    for oi, ti in st.writes:
                        temps[ti] = outs[oi]
                out_arr = temps[sub.target_temp]
                mm.ensure(nbytes[reg])
                mm.restore(vid, nbytes[reg])
                mm.stats.recompute_flops += resolved.regen_flops[reg]
                storage[reg] = out_arr
                # release regen holds on sources
                for s in sub.source_regs:
                    holds[s] = holds.get(s, 0) - 1
                    if holds[s] <= 0:
                        holds.pop(s, None)
                        counted = pending_free.pop(s, None)
                        if counted is not None:
                            free_reg(s, counted)
                return out_arr
            raise KeyError(f"value {vid} is not materializable")

        # -- instruction loop -------------------------------------------------
        outputs: List[Any] = []
        hook = self.timeline_hook
        for idx, inst in enumerate(prog.instructions):
            op = inst.op
            if op == OP_COMPUTE:
                if faults is not None:
                    faults.before_compute()
                ins = [storage[r] if storage[r] is not None else materialize(r)
                       for r in inst.in_regs]
                p = params[inst.cidx]
                if inst.dim_as_value:
                    out = jnp.asarray(p["dim"], jnp.int32)
                    for _oi, r in inst.store:
                        storage[r] = out
                        mm.alloc(vid_of[r], nbytes[r])
                elif inst.multi:
                    outs = inst.prim.bind(*ins, **p)
                    if inst.defer_regs or inst.extra_store:
                        # introducing op: payload alloc waits for the
                        # BindDim (tight size); count scalar reaches its
                        # register unaccounted when nothing consumes it
                        for oi, r in inst.store:
                            storage[r] = outs[oi]
                            if r not in inst.defer_regs:
                                mm.alloc(vid_of[r], nbytes[r])
                        for oi, r in inst.extra_store:
                            storage[r] = outs[oi]
                    else:
                        for oi, r in inst.store:
                            storage[r] = outs[oi]
                            mm.alloc(vid_of[r], nbytes[r])
                else:
                    out = inst.prim.bind(*ins, **p)
                    for _oi, r in inst.store:
                        storage[r] = out
                        mm.alloc(vid_of[r], nbytes[r])
                del ins
            elif op == OP_BIND_DIM:
                # measure the just-computed extent, clamp to the cap at
                # the current env (chained introducers can match padding
                # rows), publish it, refresh bound-dependent sizes, then
                # run the deferred payload alloc at the tight size
                measured = int(storage[inst.count_reg])
                cap_val = int(inst.cap_expr.evaluate(env_run))
                measured = min(max(measured, 0), cap_val)
                env_run[inst.name] = measured
                mm.stats.measured_dims[inst.name] = measured
                exprs = prog.nbytes_exprs
                for r in prog.bound_dep_regs[inst.name]:
                    nbytes[r] = exprs[r].evaluate(env_run)
                for _oi, r in inst.alloc_store:
                    mm.alloc(vid_of[r], nbytes[r])
                if inst.drop_count:
                    storage[inst.count_reg] = None
            elif op == OP_REGEN:
                state["step"] = inst.step
                state["pinned"] = inst.pinned
                for r in inst.regs:
                    materialize(r)
            elif op == OP_MAYBE_EVICT:   # Remat::EvictOp check
                state["step"] = inst.step
                state["pinned"] = inst.pinned
                if bound:
                    # the resolved ensure table holds cap sizes; sum the
                    # live overlay so pressure checks see measured sizes
                    comp = prog.computes[inst.cidx]
                    mm.ensure(sum(nbytes[r] for _oi, r in comp.store))
                else:
                    mm.ensure(ensure_bytes[inst.cidx])
            elif op == OP_BIND_ARG:
                storage[inst.reg] = (flat_args[inst.index]
                                     if inst.index >= 0 else inst.const)
                if arena is not None:
                    arena.place_external(inst.vid, nbytes[inst.reg])
                if prog.count_inputs:
                    mm.alloc(inst.vid, nbytes[inst.reg])
            elif op == OP_LOOP:
                # rolled loop under the dynamic regime: the evict check is
                # hoisted — one ensure() for the loop's exact internal peak
                # delta — then the shared account() engine drives the
                # MemoryManager while execution runs the body sub-Program
                state["step"] = inst.step
                state["pinned"] = inst.pinned
                if faults is not None:
                    faults.before_compute()   # one step per rolled loop
                ins = [storage[r] if storage[r] is not None else materialize(r)
                       for r in inst.in_regs]
                rl = resolved.loops[inst.lidx]
                info = prog.loops[inst.lidx]
                mm.ensure(rl.extra_bytes)
                info.lp.account(mm, info.node.id, rl.trip,
                                rl.sizes.__getitem__, rl.outer_y,
                                rl.outer_carry)
                outs = self._exec_loop(info, rl, ins, env)
                del ins
                for oi, r in inst.store:   # account() allocated the kept outs
                    storage[r] = outs[oi]
            elif op == OP_FREE_SLOT:
                if holds.get(inst.reg, 0) > 0:
                    pending_free[inst.reg] = True
                else:
                    free_reg(inst.reg, True)
            elif op == OP_DONATE:
                if holds.get(inst.reg, 0) > 0:
                    pending_free[inst.reg] = inst.counted
                else:
                    free_reg(inst.reg, inst.counted)
            else:  # OP_RETURN
                outputs = [materialize(r) for r in inst.regs]
            if hook is not None:
                hook(idx, inst, mm)
        if arena is not None:
            arena.write_stats(mm.stats)
        return outputs, mm.stats
