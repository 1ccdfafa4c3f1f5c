"""ExecutionPlan -> Program compilation (the lowering pass).

One linear walk over the scheduled order turns every per-call decision
the ``PlanInterpreter`` re-derives op-by-op into static instruction
structure:

* **registers** — value ids renumbered densely in first-store order
  (inputs, consts, then scheduled outputs), so the VM indexes lists;
* **death points** — each value's last consumer position is known from
  the schedule, so frees become ``FreeSlot``/``Donate`` instructions
  instead of per-op refcount bookkeeping;
* **evict/regen guards** — ``MaybeEvict``/``Regen`` instructions are
  emitted only when eviction is actually possible: there is a memory
  limit, and the guaranteed worst-case peak (interval bounds over the
  declared dim ranges) does not already prove every in-range env fits
  under it.  With no limit — or a proven-safe one — the stream contains
  no runtime remat machinery at all;
* **regen sub-programs** — candidates' recompute subgraphs are lowered
  inline by ``repro.core.remat.export.export_regen_programs``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from ..ir.graph import Value
from ..ir.loop import loop_body_of
from ..ir.trace import _contains_symbolic
from ..remat.export import export_regen_programs
from ..remat.planner import ExecutionPlan
from .program import (OP_COMPUTE, OP_LOOP, BindArg, BindDim, Compute, Donate,
                      FreeSlot, Loop, LoopInfo, MaybeEvict, OpGroup, Program,
                      Regen, Return)


def op_groups(fast: List[Any], loops: List[LoopInfo]) -> List[OpGroup]:
    """Cut the fast stream into contiguous runs of one op-group label.

    A Compute or Loop whose label differs from the open run's starts a new
    run (a Loop always does); other instructions (BindArg, frees, Return)
    join the open run, and those before the first Compute join the first."""
    starts: List[Tuple[int, str, bool]] = []
    for i, inst in enumerate(fast):
        if inst.op == OP_COMPUTE:
            label, loop = inst.node.label, False
        elif inst.op == OP_LOOP:
            label, loop = loops[inst.lidx].node.label, True
        else:
            continue
        if starts and not loop and not starts[-1][2] \
                and label == starts[-1][1]:
            continue
        starts.append((i if starts else 0, label, loop))
    if not starts:
        return [OpGroup("", 0, len(fast), False)] if fast else []
    ends = [lo for lo, _, _ in starts[1:]] + [len(fast)]
    return [OpGroup(label, lo, hi, loop)
            for (lo, label, loop), hi in zip(starts, ends)]


def lower_plan(plan: ExecutionPlan, *,
               memory_limit: Optional[int] = None,
               donate_inputs: bool = False,
               count_inputs: bool = True,
               peak_bound_bytes: Optional[int] = None) -> Program:
    """Compile ``plan`` into a flat :class:`Program`.

    ``peak_bound_bytes`` is the guaranteed worst-case free-run peak over
    the declared dim ranges (from ``simulate_peak_bound``); when it is
    known and ``<= memory_limit``, eviction is provably impossible and
    the evict path is not emitted.
    """
    g = plan.graph
    output_ids = {v.id for v in g.outputs}

    reg_of: Dict[int, int] = {}
    vid_of: List[int] = []
    nbytes_exprs = []

    def new_reg(v: Value) -> int:
        r = reg_of.get(v.id)
        if r is None:
            r = len(vid_of)
            reg_of[v.id] = r
            vid_of.append(v.id)
            nbytes_exprs.append(v.nbytes_expr)
        return r

    # eviction is possible only under a limit the bounds cannot clear
    has_evict_path = memory_limit is not None and (
        peak_bound_bytes is None or peak_bound_bytes > memory_limit)

    instructions: List[Any] = []
    for i, v in enumerate(g.inputs):
        instructions.append(BindArg(reg=new_reg(v), index=i, kind="input",
                                    const=None, vid=v.id))
    for v in g.consts:
        instructions.append(BindArg(reg=new_reg(v), index=-1, kind="const",
                                    const=v.const_val, vid=v.id))

    # death point = last consumer position in the scheduled order
    death_pos: Dict[int, int] = {
        vid: uses[-1] for vid, uses in plan.use_positions.items() if uses}

    computes: List[Compute] = []
    static_params: List[Optional[Dict[str, Any]]] = []
    params_cidx_of: Dict[int, int] = {}
    loops: List[LoopInfo] = []
    for step, node in enumerate(plan.order):
        body = loop_body_of(node)
        pinned = frozenset(
            [iv.id for iv in node.invals] + [ov.id for ov in node.outvals])
        if has_evict_path:
            cand_in = tuple(dict.fromkeys(
                reg_of[iv.id] for iv in node.invals
                if iv.id in plan.candidates))
            if cand_in:
                instructions.append(Regen(regs=cand_in, step=step,
                                          pinned=pinned))
            if body is None:
                # a rolled loop does its own hoisted ensure (the resolved
                # internal peak delta) inside the Loop handler; plain
                # computes get the MaybeEvict guard here
                instructions.append(MaybeEvict(cidx=len(computes), step=step,
                                               pinned=pinned))
        store = tuple((oi, new_reg(ov)) for oi, ov in enumerate(node.outvals)
                      if ov.consumers or ov.id in output_ids)
        if body is not None:
            # rolled loop: lower the traced body ONCE as a sub-Program —
            # the outer stream stays O(body) regardless of the trip count
            lp = body.plan(plan.shape_graph)
            body_plan = ExecutionPlan(graph=body.graph, order=list(lp.order),
                                      shape_graph=plan.shape_graph,
                                      candidates={})
            body_program = lower_plan(body_plan, memory_limit=None,
                                      donate_inputs=False, count_inputs=True)
            kept = tuple(bool(ov.consumers) or ov.id in output_ids
                         for ov in node.outvals)
            instructions.append(Loop(
                lidx=len(loops),
                in_regs=tuple(reg_of[iv.id] for iv in node.invals),
                store=store, step=step, pinned=pinned))
            loops.append(LoopInfo(node=node, body=body, lp=lp,
                                  body_program=body_program, kept=kept))
        else:
            cidx = len(computes)
            intro = g.bound_intros.get(node.id)
            defer_regs: Tuple[int, ...] = ()
            extra_store: Tuple[Tuple[int, int], ...] = ()
            if intro is not None:
                # the padded payload's accounting alloc moves to the
                # BindDim below (its tight size needs the measured count);
                # the count scalar must reach a register either way
                defer_regs = tuple(r for oi, r in store
                                   if oi == intro.padded_out)
                count_reg = new_reg(node.outvals[intro.count_out])
                count_kept = any(oi == intro.count_out for oi, _r in store)
                if not count_kept:
                    extra_store = ((intro.count_out, count_reg),)
            comp = Compute(cidx=cidx, node=node, prim=node.prim,
                           multi=bool(node.prim is not None
                                      and node.prim.multiple_results),
                           dim_as_value=node.prim_name == "dim_as_value",
                           in_regs=tuple(reg_of[iv.id] for iv in node.invals),
                           store=store, step=step, defer_regs=defer_regs,
                           extra_store=extra_store)
            instructions.append(comp)
            computes.append(comp)
            # kernel-variant selection bakes its param overrides here, so
            # the VM hot path replays the chosen configuration with no
            # shape branch; the shared ``node.params`` stay untouched
            # (other buckets' plans merge their own choices)
            ov = plan.kernel_overrides.get(node.id)
            params = node.params if ov is None else {**node.params, **ov}
            static_params.append(
                None if _contains_symbolic(params) else params)
            params_cidx_of[node.id] = cidx
            if intro is not None:
                instructions.append(BindDim(
                    name=intro.name, cap_expr=intro.cap, count_reg=count_reg,
                    alloc_store=tuple((oi, r) for oi, r in store
                                      if r in defer_regs),
                    drop_count=not count_kept, step=step))

        # frees, in the interpreter's first-occurrence order
        seen = set()
        for iv in node.invals:
            if iv.id in seen:
                continue
            seen.add(iv.id)
            if death_pos.get(iv.id) != step or iv.id in output_ids:
                continue
            if iv.is_materialized_input():
                if donate_inputs:
                    instructions.append(Donate(reg=reg_of[iv.id], vid=iv.id,
                                               counted=count_inputs))
            else:
                instructions.append(FreeSlot(reg=reg_of[iv.id], vid=iv.id))

    out_regs = tuple(reg_of[v.id] for v in g.outputs)
    instructions.append(Return(regs=out_regs))

    regen = {}
    candidate_regs: Tuple[int, ...] = ()
    if has_evict_path:
        regen = export_regen_programs(plan, reg_of, params_cidx_of)
        # first-store order (the interpreter iterates its storage dict,
        # whose order additionally mutates on reload/recompute reinsertion
        # — so on *exact* victim-score ties after remat churn the two
        # executors may evict different victims; outputs stay identical,
        # only eviction counters can differ)
        candidate_regs = tuple(sorted(
            (reg_of[vid] for vid in plan.candidates if vid in reg_of)))

    death_step = [-1] * len(vid_of)
    for vid, pos in death_pos.items():
        r = reg_of.get(vid)
        if r is not None:
            death_step[r] = pos

    fast = [inst for inst in instructions
            if inst.op not in (Regen.op, MaybeEvict.op)]

    # bounded dim -> every register whose byte size mentions it; the
    # BindDim publishing that dim refreshes exactly these sizes
    bound_dep_regs: Dict[str, Tuple[int, ...]] = {}
    if g.bound_dims:
        dep_lists: Dict[str, List[int]] = {name: [] for name in g.bound_dims}
        for r, expr in enumerate(nbytes_exprs):
            for name in expr.free_vars() & set(g.bound_dims):
                dep_lists[name].append(r)
        bound_dep_regs = {name: tuple(rs) for name, rs in dep_lists.items()}

    return Program(plan=plan, graph=g, n_regs=len(vid_of), reg_of=reg_of,
                   vid_of=vid_of, nbytes_exprs=nbytes_exprs,
                   instructions=instructions, fast_instructions=fast,
                   computes=computes, static_params=static_params,
                   regen=regen, out_regs=out_regs, death_step=death_step,
                   candidate_regs=candidate_regs,
                   has_evict_path=has_evict_path,
                   memory_limit=memory_limit, donate_inputs=donate_inputs,
                   count_inputs=count_inputs, loops=loops,
                   bound_dep_regs=bound_dep_regs,
                   groups=op_groups(fast, loops))
