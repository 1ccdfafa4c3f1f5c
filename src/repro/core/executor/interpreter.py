"""Op-by-op executor of an ExecutionPlan — the BladeDISC++ runtime analogue.

Executes the scheduled graph on concrete arrays of *any* shape matching the
symbolic trace (one compilation, no padding, no recompile), with:

  * exact memory accounting through ``MemoryManager``;
  * the evict check at op boundaries (paper's ``Remat::EvictOp``);
  * materialize-on-demand regeneration (paper's ``Remat::RegenerateOp``),
    by recompute subgraph or host reload, chosen by the runtime policy.

Recompute-evicted tensors place a *hold* on each source of their recompute
subgraph, so sources stay materializable (alive, offloaded, or recursively
recomputable) until regeneration releases the hold.  This realises the
compile-time impact accounting (bytes(target) − bytes(kept sources)) at
runtime.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..ir.dynamism import complete_bound_env
from ..ir.graph import Graph, Node, Value
from ..ir.loop import loop_body_of
from ..ir.trace import refine_params, solve_checked_env
from ..memplan.arena import ArenaAllocator
from ..remat.planner import ExecutionPlan
from ..remat.runtime import RuntimeRematPolicy
from .memory import MemoryManager, MemoryStats


def _bind_node(node: Node, ins: Sequence[Any], params: Dict[str, Any]) -> List[Any]:
    """Execute one primitive with refined (concrete) params.

    A few shape-polymorphism helper primitives have no eager impl and are
    evaluated directly from their params.
    """
    if node.prim_name == "dim_as_value":
        # params['dim'] was already refined to a concrete int
        return [jnp.asarray(params["dim"], jnp.int32)]
    outs = node.prim.bind(*ins, **params)
    return list(outs) if node.prim.multiple_results else [outs]


@dataclass
class RunReport:
    stats: MemoryStats
    wall_s: float
    env: Dict[str, int]


class PlanInterpreter:
    def __init__(self, plan: ExecutionPlan, *,
                 memory_limit: Optional[int] = None,
                 donate_inputs: bool = False,
                 count_inputs: bool = True,
                 size_cache: Optional[Dict[Tuple, Dict[int, int]]] = None,
                 params_cache: Optional[
                     Dict[Tuple, Dict[int, Dict[str, Any]]]] = None,
                 arena_hard_cap: Optional[int] = None):
        self.plan = plan
        self.g = plan.graph
        self.memory_limit = memory_limit
        # resilience.enforce_arena_bound (see ProgramVM.arena_hard_cap)
        self.arena_hard_cap = arena_hard_cap
        self.donate_inputs = donate_inputs
        self.count_inputs = count_inputs
        self._output_ids = {v.id for v in self.g.outputs}
        self._value_by_id = {v.id: v for v in self.g.values}
        self._remaining_template: Dict[int, int] = {
            v.id: len([c for c in v.consumers if c.id in plan.pos])
            for v in self.g.values
        }
        # values whose byte size mentions a bounded dim: their sizes are
        # re-evaluated per call at the live env (the measured value) and
        # never enter the shared (cap-valued) size cache
        self._bound_dep_vids: set = set()
        if self.g.bound_dims:
            names = frozenset(self.g.bound_dims)
            self._bound_dep_vids = {v.id for v in self.g.values
                                    if v.nbytes_expr.free_vars() & names}
        # per-env caches reused across calls (training repeats shapes).
        # Both depend only on graph + env — never on the op order — so
        # bucketed dispatch passes one shared pair to every per-bucket
        # interpreter: swapping plans between calls re-derives nothing.
        self._size_cache: Dict[Tuple, Dict[int, int]] = \
            size_cache if size_cache is not None else {}
        self._params_cache: Dict[Tuple, Dict[int, Dict[str, Any]]] = \
            params_cache if params_cache is not None else {}
        # optional live-occupancy probe (see ProgramVM.timeline_hook):
        # called as hook(step, node, mm) after every executed node
        self.timeline_hook = None

    # ---------------------------------------------------------------- run --
    def run(self, flat_args: Sequence[Any],
            env: Optional[Dict[str, int]] = None,
            faults: Any = None, prof: Any = None
            ) -> Tuple[List[Any], RunReport]:
        # ``prof``: accepted for the executor interface; the reference
        # interpreter records no spans
        t0 = time.perf_counter()
        g, plan = self.g, self.plan
        if env is None:
            # a caller passing a pre-solved env (the bucketed dispatch hot
            # path) has already validated it and skips both steps
            env = solve_checked_env(g, plan.shape_graph, flat_args)
        # namespaced by graph uid: node/value ids restart at 0 per graph,
        # so a cache injected across interpreters must never let one
        # graph's refined params/sizes answer for another's same-id node.
        # Keyed by the *declared* env: bounded dims complete to caps
        # deterministically, and measured values stay out of shared caches.
        env_key = (g.uid,) + tuple(sorted(env.items()))
        env_decl = env
        env = complete_bound_env(g, env) if g.bound_dims else env
        # the live env: BindDim-equivalent measuring rebinds bounded dims
        # here mid-call (a private copy; ``env`` keeps the caps)
        env_run = dict(env) if g.bound_dims else env
        bound_dep = self._bound_dep_vids
        policy = RuntimeRematPolicy(plan, env)
        nbytes = self._size_cache.setdefault(env_key, {})
        refined = self._params_cache.setdefault(env_key, {})
        if len(self._size_cache) > 64:  # bound the per-shape caches
            self._size_cache.clear()
            self._params_cache.clear()
            nbytes = self._size_cache.setdefault(env_key, {})
            refined = self._params_cache.setdefault(env_key, {})
        arena = None
        if plan.arena_plan is not None:
            # symbolic slot sizes evaluate + carve once per env (cached
            # inside the plan, like the size/params caches above)
            arena = ArenaAllocator(plan.arena_plan,
                                   plan.arena_plan.resolve(env),
                                   hard_cap=self.arena_hard_cap)
        mm = MemoryManager(self.memory_limit, arena=arena)
        if faults is not None:
            mm.fault_hook = faults.on_memory

        def bytes_of(v: Value) -> int:
            if v.id in bound_dep:
                # tight size at the live env; bypasses the shared cache
                return v.nbytes_expr.evaluate(env_run)
            b = nbytes.get(v.id)
            if b is None:
                b = v.nbytes_expr.evaluate(env)
                nbytes[v.id] = b
            return b

        overrides = plan.kernel_overrides
        local_refined: Dict[int, Dict[str, Any]] = {}

        def params_of(node: Node) -> Dict[str, Any]:
            ov = overrides.get(node.id)
            if ov is not None:
                # kernel-variant override: merge per plan, cached per run —
                # the shared cross-bucket cache keys only (graph uid, env)
                # and other buckets' plans merge different choices
                p = local_refined.get(node.id)
                if p is None:
                    p = {**refine_params(node.params, env), **ov}
                    local_refined[node.id] = p
                return p
            p = refined.get(node.id)
            if p is None:
                p = refine_params(node.params, env)
                refined[node.id] = p
            return p

        storage: Dict[int, Any] = {}          # vid -> device array
        host_storage: Dict[int, Any] = {}     # vid -> host (numpy) array
        evicted_recompute: set = set()        # vids dropped, regenerable
        remaining = dict(self._remaining_template)
        holds: Dict[int, int] = {}            # regen source pins
        step_holder = {"i": 0}
        pinned_holder = {"s": frozenset()}

        def is_materializable(vid: int) -> bool:
            return vid in storage or vid in host_storage or vid in evicted_recompute

        def maybe_free(vid: int) -> None:
            if remaining.get(vid, 0) == 0 and holds.get(vid, 0) == 0 \
                    and vid not in self._output_ids:
                v = self._value_by_id[vid]
                if v.is_materialized_input() and not self.donate_inputs:
                    return
                was_tracked = vid in storage or vid in host_storage \
                    or vid in evicted_recompute
                storage.pop(vid, None)
                host_storage.pop(vid, None)
                evicted_recompute.discard(vid)
                if was_tracked and (self.count_inputs or not v.is_materialized_input()):
                    mm.free(vid)
                elif was_tracked:
                    # uncounted donated input: still release its arena slot
                    mm.arena_release(vid)

        # -- eviction callback wired into the memory manager ------------------
        def evict(need: int) -> int:
            live = {vid: mm.device_bytes(vid) for vid in list(storage)
                    if vid in plan.candidates
                    and (remaining.get(vid, 0) > 0 or holds.get(vid, 0) > 0)}
            decisions = policy.choose_victims(need, live, pinned_holder["s"],
                                              step_holder["i"])
            freed = 0
            for dec in decisions:
                arr = storage.pop(dec.vid, None)
                if arr is None:
                    continue
                method = dec.method
                if method == "recompute":
                    rp = plan.candidates[dec.vid].recompute
                    # recompute is only safe if every source is materializable
                    if rp is None or not all(is_materializable(s)
                                             for s in rp.source_ids):
                        method = "offload"
                if method == "offload":
                    host_storage[dec.vid] = np.asarray(arr)
                    mm.evict_to_host(dec.vid)
                else:
                    rp = plan.candidates[dec.vid].recompute
                    for sid in rp.source_ids:
                        holds[sid] = holds.get(sid, 0) + 1
                    evicted_recompute.add(dec.vid)
                    mm.evict_drop(dec.vid)
                del arr
                freed += dec.bytes_freed
            return freed

        mm.evict_callback = evict

        # -- registration of inputs & consts ---------------------------------
        # caller-provided buffers occupy external arena slots (registered
        # before mm.alloc so the arena does not treat them as fresh allocs)
        for val, arr in zip(g.inputs, flat_args):
            storage[val.id] = arr
            if arena is not None:
                arena.place_external(val.id, bytes_of(val))
            if self.count_inputs:
                mm.alloc(val.id, bytes_of(val))
        for val in g.consts:
            storage[val.id] = val.const_val
            if arena is not None:
                arena.place_external(val.id, bytes_of(val))
            if self.count_inputs:
                mm.alloc(val.id, bytes_of(val))

        # -- materialize-on-demand (Remat::RegenerateOp) -----------------------
        def materialize(v: Value) -> Any:
            arr = storage.get(v.id)
            if arr is not None:
                return arr
            if v.id in host_storage:  # reload path (H2D)
                mm.ensure(bytes_of(v))
                arr = jnp.asarray(host_storage.pop(v.id))
                mm.reload(v.id)
                storage[v.id] = arr
                return arr
            if v.id in evicted_recompute:  # recompute path
                cand = plan.candidates[v.id]
                rp = cand.recompute
                assert rp is not None
                evicted_recompute.discard(v.id)
                for sid in rp.source_ids:  # recursion strictly moves up-graph
                    materialize(self._value_by_id[sid])
                temps: Dict[int, Any] = {}

                def read_local(x: Value) -> Any:
                    if x.id in temps:
                        return temps[x.id]
                    return materialize(x)

                out_arr = None
                for nid in rp.node_ids:
                    node = plan.node_by_id[nid]
                    ins = [read_local(iv) for iv in node.invals]
                    outs = _bind_node(node, ins, params_of(node))
                    for ov, oa in zip(node.outvals, outs):
                        temps[ov.id] = oa
                        if ov.id == v.id:
                            out_arr = oa
                assert out_arr is not None, "recompute plan missed its target"
                mm.ensure(bytes_of(v))
                mm.restore(v.id, bytes_of(v))
                mm.stats.recompute_flops += rp.flops.evaluate(env)
                storage[v.id] = out_arr
                # release regen holds on sources
                for sid in rp.source_ids:
                    holds[sid] = holds.get(sid, 0) - 1
                    if holds[sid] <= 0:
                        holds.pop(sid, None)
                        maybe_free(sid)
                return out_arr
            raise KeyError(f"value {v} is not materializable")

        # -- main loop ----------------------------------------------------------
        order = plan.order
        hook = self.timeline_hook
        for i, node in enumerate(order):
            step_holder["i"] = i
            pinned_holder["s"] = frozenset(
                [iv.id for iv in node.invals] + [ov.id for ov in node.outvals])
            if faults is not None:
                faults.before_compute()
            ins = [materialize(iv) for iv in node.invals]
            body = loop_body_of(node)
            if body is not None:
                # rolled loop: one ensure for the loop's whole internal peak
                # (Remat::EvictOp hoisted out of the body), then the shared
                # account() event replay drives the MemoryManager — the same
                # engine the VM and the resolve-time stats replay use, so
                # every executor reports identical loop accounting
                lp = body.plan(plan.shape_graph)
                trip = body.length_expr.evaluate(env)
                kept = [bool(ov.consumers) or ov.id in self._output_ids
                        for ov in node.outvals]
                nk = body.num_carry
                outer_y = [(ov.id, bytes_of(ov))
                           for ov, k in zip(node.outvals[nk:], kept[nk:]) if k]
                outer_carry = [(ov.id, bytes_of(ov)) if k else None
                               for ov, k in zip(node.outvals[:nk], kept[:nk])]
                # body-side caches namespaced by the body graph's uid — body
                # value/node ids restart at 0 and must not collide with the
                # outer graph's entries
                bkey = (body.graph.uid,) + tuple(sorted(env.items()))
                bsizes = self._size_cache.setdefault(bkey, {})
                bparams = self._params_cache.setdefault(bkey, {})

                def bsize_of(bvid: int) -> int:
                    b = bsizes.get(bvid)
                    if b is None:
                        b = lp.sizes[bvid].evaluate(env)
                        bsizes[bvid] = b
                    return b

                def bparams_of(bn: Node) -> Dict[str, Any]:
                    p = bparams.get(bn.id)
                    if p is None:
                        p = refine_params(bn.params, env)
                        bparams[bn.id] = p
                    return p

                mm.ensure(lp.peak_expr_for(node, kept, trip).evaluate(env))
                lp.account(mm, node.id, trip, bsize_of, outer_y, outer_carry)
                outs = lp.execute(ins, trip, env, bparams_of, bind=_bind_node)
                del ins
                for ov, oa, k in zip(node.outvals, outs, kept):
                    if k:   # account() already allocated the kept outputs
                        storage[ov.id] = oa
            else:
                out_bytes = sum(bytes_of(ov) for ov in node.outvals
                                if ov.consumers or ov.id in self._output_ids)
                mm.ensure(out_bytes)  # Remat::EvictOp check
                outs = _bind_node(node, ins, params_of(node))
                del ins
                intro = g.bound_intros.get(node.id)
                if intro is not None:
                    # the BindDim step: measure, clamp to the cap at the
                    # live env (chained introducers can match padding
                    # rows), publish — the kept-output allocs below then
                    # see the tight size through bytes_of
                    measured = int(outs[intro.count_out])
                    cap_val = int(intro.cap.evaluate(env_run))
                    measured = min(max(measured, 0), cap_val)
                    env_run[intro.name] = measured
                    mm.stats.measured_dims[intro.name] = measured
                for ov, oa in zip(node.outvals, outs):
                    if ov.consumers or ov.id in self._output_ids:
                        storage[ov.id] = oa
                        mm.alloc(ov.id, bytes_of(ov))
            # free dead values (buffer lifetime = last consumer)
            seen = set()
            for iv in node.invals:
                if iv.id in seen:
                    continue
                seen.add(iv.id)
                remaining[iv.id] -= sum(1 for x in node.invals if x.id == iv.id)
                maybe_free(iv.id)
            if hook is not None:
                hook(i, node, mm)

        outputs = [materialize(v) for v in g.outputs]
        if arena is not None:
            arena.write_stats(mm.stats)
        wall = time.perf_counter() - t0
        # bound graphs report the live env (measured extents, not caps);
        # range-dynamic graphs report the declared env unchanged
        report_env = env_run if g.bound_dims else env_decl
        return outputs, RunReport(stats=mm.stats, wall_s=wall, env=report_env)
