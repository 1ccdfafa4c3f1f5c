"""The lowered executable artifact: a flat ``Program`` of typed instructions.

``lower_plan`` (see :mod:`.lower`) compiles each (schedule, remat plan,
arena plan) triple into a :class:`Program` — the runtime analogue of
Relax's VM executable and SoD²'s pre-derived dynamic decisions: every
decision the compile half *can* fix is burned into the instruction
stream, so the per-call work left is binding primitives.

* value ids are renumbered to **dense registers** (list indices, not
  dict probes);
* buffer frees happen at statically-known death points
  (:class:`FreeSlot` / :class:`Donate` instructions) instead of runtime
  refcounting;
* the evict check and regeneration guards exist only as explicit
  :class:`MaybeEvict` / :class:`Regen` instructions, emitted solely when
  the compile-time interval bounds cannot rule eviction out;
* regeneration subgraphs are lowered inline as register-addressed
  sub-programs (:class:`RegenProgram`, exported by
  ``repro.core.remat.export.export_regen_programs``);
* every symbolic quantity (buffer sizes, evict thresholds, recompute
  FLOPs, arena slot sizes/offsets) is attached as a precompiled
  expression, and :meth:`Program.resolve` evaluates them all for one dim
  binding in a single pass — including a replay of the static alloc/free
  sequence that precomputes the call's entire :class:`MemoryStats` when
  eviction is provably off the table for that env.

The instruction set:

========== =================================================================
BindArg     place a caller input / trace constant into its register
Compute     bind one primitive: gather input registers, store outputs
MaybeEvict  the paper's ``Remat::EvictOp`` — ensure the op's output bytes
            fit the limit, evicting victims chosen by the runtime policy
Regen       the paper's ``Remat::RegenerateOp`` guard — rematerialize the
            listed registers (reload or sub-program recompute) if evicted
FreeSlot    release a dead intermediate's buffer (statically placed)
Donate      release a dead caller buffer (only under ``donate_inputs``)
Return      gather the output registers
========== =================================================================
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from ..executor.memory import MemoryManager, MemoryStats
from ..ir.graph import Graph, Node
from ..ir.trace import refine_params
from ..memplan.arena import ArenaAllocator
from ..remat.planner import ExecutionPlan
from ..symbolic.expr import SymbolicExpr

# instruction opcodes (small ints: the VM dispatches on them)
OP_BIND_ARG = 0
OP_COMPUTE = 1
OP_MAYBE_EVICT = 2
OP_REGEN = 3
OP_FREE_SLOT = 4
OP_DONATE = 5
OP_RETURN = 6
OP_LOOP = 7
OP_BIND_DIM = 8


@dataclass(frozen=True)
class BindArg:
    """Place flat input ``index`` (or a trace constant) into ``reg``."""
    reg: int
    index: int                 # flat-input position; -1 for consts
    kind: str                  # 'input' | 'const'
    const: Any                 # the constant array (kind='const' only)
    vid: int                   # original value id (memory accounting key)
    op: int = OP_BIND_ARG


@dataclass(frozen=True)
class Compute:
    """Bind one primitive over input registers, store selected outputs."""
    cidx: int                  # index into resolved params / ensure tables
    node: Node
    prim: Any
    multi: bool                # prim.multiple_results
    dim_as_value: bool         # shape-poly helper: emit params['dim'] directly
    in_regs: Tuple[int, ...]
    # (output position, destination register) for outputs that are kept
    # (consumed later or returned); unkept outputs are simply dropped
    store: Tuple[Tuple[int, int], ...]
    step: int                  # schedule position (victim scoring distance)
    # value-dependent bounded ops only: registers in ``store`` whose
    # accounting alloc is deferred to the following BindDim (the padded
    # payload — its tight size is only known after measuring), and
    # outputs stored for the BindDim to read but never accounted (the
    # i32 count scalar when nothing downstream consumes it)
    defer_regs: Tuple[int, ...] = ()
    extra_store: Tuple[Tuple[int, int], ...] = ()
    op: int = OP_COMPUTE


@dataclass(frozen=True)
class MaybeEvict:
    """Ensure the next Compute's output bytes fit the memory limit.

    Emitted only when lowering cannot prove eviction impossible (no
    limit, or guaranteed peak <= limit).  ``pinned`` are the value ids
    the in-flight op needs live (its inputs + outputs)."""
    cidx: int
    step: int
    pinned: frozenset
    op: int = OP_MAYBE_EVICT


@dataclass(frozen=True)
class Regen:
    """Rematerialize ``regs`` (reload or recompute) if they were evicted.

    Emitted before a Compute only for inputs that are remat candidates —
    the only values an eviction can ever drop."""
    regs: Tuple[int, ...]
    step: int
    pinned: frozenset
    op: int = OP_REGEN


@dataclass(frozen=True)
class FreeSlot:
    """Release a dead intermediate at its statically-known death point."""
    reg: int
    vid: int
    op: int = OP_FREE_SLOT


@dataclass(frozen=True)
class Donate:
    """Release a dead caller buffer (input/const) under ``donate_inputs``.

    ``counted`` mirrors ``count_inputs``: counted buffers leave through
    the memory manager, uncounted ones only release their arena slot."""
    reg: int
    vid: int
    counted: bool
    op: int = OP_DONATE


@dataclass(frozen=True)
class Return:
    """Gather the output registers (rematerializing evicted ones)."""
    regs: Tuple[int, ...]
    op: int = OP_RETURN


@dataclass(frozen=True)
class BindDim:
    """Publish a just-measured bounded dim into the call env (mid-call).

    Emitted immediately after the Compute that *introduces* bounded dim
    ``name`` (``ir.dynamism``): read the i32 count from ``count_reg``,
    clamp it to the cap evaluated at the current env (chained introducers
    can match padding rows, so the raw count may exceed a chained cap),
    rebind ``name`` in the per-call env, refresh the byte sizes of every
    bound-dependent register, and only *then* run the deferred accounting
    alloc of the padded payload (``alloc_store``) — so the arena records
    the tight size and every later fit/free/peak sees it.  With
    ``drop_count`` the count scalar's register is nulled after reading
    (nothing downstream consumes it)."""
    name: str
    cap_expr: SymbolicExpr
    count_reg: int
    alloc_store: Tuple[Tuple[int, int], ...]   # deferred (out pos, reg)
    drop_count: bool
    step: int
    op: int = OP_BIND_DIM


@dataclass(frozen=True)
class Loop:
    """Run a rolled ``scan`` loop: one instruction for the whole trip.

    The body is a lowered sub-:class:`Program` executed once per
    iteration with registers rebound (carries from the previous
    iteration's outputs, ``xs`` slices by index); ``lidx`` indexes the
    owning Program's ``loops`` table.  ``store`` routes the loop's kept
    outer outputs (final carries, stacked ``ys``); ``pinned`` mirrors
    ``MaybeEvict.pinned`` for the hoisted evict check."""
    lidx: int
    in_regs: Tuple[int, ...]
    store: Tuple[Tuple[int, int], ...]
    step: int
    pinned: frozenset
    op: int = OP_LOOP


class OpGroup(NamedTuple):
    """A contiguous run ``[lo, hi)`` of the fast stream whose Computes share
    one op-group label (``Node.label``).  A rolled Loop is a run of its own
    (``loop``).  The scheduler may interleave groups, so one label can give
    several runs; the runs cover the stream exactly once, in order."""
    label: str
    lo: int
    hi: int
    loop: bool


@dataclass
class LoopInfo:
    """Compile-time half of one rolled loop inside a Program."""
    node: Node                 # the outer loop node
    body: Any                  # ir.loop.LoopBody
    lp: Any                    # ir.loop.LoopPlanInfo (schedule + events)
    body_program: "Program"    # the body lowered once (O(body) size)
    kept: Tuple[bool, ...]     # per outer output: consumed or returned


@dataclass
class ResolvedLoop:
    """One rolled loop realized for a concrete env."""
    trip: int                               # trip count t at this env
    rbody: ResolvedProgram                  # body program resolve (cached)
    extra_bytes: int                        # exact internal peak delta
    sizes: Dict[int, int]                   # body value id -> bytes
    outer_y: List[Tuple[int, int]]          # kept stacked ys: (vid, bytes)
    outer_carry: List[Optional[Tuple[int, int]]]   # per carry slot


@dataclass(frozen=True)
class RegenStep:
    """One lowered node of a regeneration sub-program.

    ``in_refs`` entries are ``(is_temp, index)``: a sub-program temp
    produced by an earlier step, or a main-program register (materialized
    recursively).  ``writes`` routes outputs into temp slots."""
    node: Node
    prim: Any
    multi: bool
    dim_as_value: bool
    params_cidx: int           # the node's main-program params entry
    in_refs: Tuple[Tuple[bool, int], ...]
    writes: Tuple[Tuple[int, int], ...]


@dataclass(frozen=True)
class RegenProgram:
    """A remat candidate's recompute subgraph, lowered over registers."""
    target_reg: int
    target_vid: int
    source_regs: Tuple[int, ...]
    n_temps: int
    steps: Tuple[RegenStep, ...]
    target_temp: int
    flops_expr: SymbolicExpr


@dataclass
class ResolvedProgram:
    """A :class:`Program` realized for one concrete dim binding.

    Everything symbolic is now a plain int or dict: per-register byte
    sizes, per-Compute ensure thresholds and refined params, per-regen
    FLOPs, the resolved arena (with concrete per-value offsets), and —
    when ``fast_ok`` — the complete :class:`MemoryStats` of a run, so
    the hot path copies a template instead of accounting per op."""

    env: Dict[str, int]
    nbytes: List[int]                       # per register
    ensure_bytes: List[int]                 # per Compute (cidx)
    params: List[Dict[str, Any]]            # per Compute (cidx)
    regen_flops: Dict[int, int]             # target reg -> FLOPs at env
    arena: Optional[Any] = None             # memplan ResolvedArena
    value_offsets: Dict[int, int] = field(default_factory=dict)
    # replay results: the exact free-run stats of this env's call
    stats_template: Optional[MemoryStats] = None
    peak_bytes: int = 0
    # True when no MaybeEvict can fire at this env (no limit, or the
    # replayed peak fits it): the VM may run the fast stream
    fast_ok: bool = True
    # per rolled loop (index = Loop.lidx): trip count, body resolve,
    # exact internal peak delta, and the accounting size tables
    loops: List[ResolvedLoop] = field(default_factory=list)
    # the plan's device occupancy at the end of each op group
    # (``Program.groups``), filled on a profiled call's first use of this
    # env (``obs.profile``)
    group_plan_bytes: Optional[List[int]] = None


@dataclass
class Program:
    """Flat lowered executable for one ExecutionPlan (see module doc)."""

    plan: ExecutionPlan
    graph: Graph
    n_regs: int
    reg_of: Dict[int, int]                  # value id -> register
    vid_of: List[int]                       # register -> value id
    nbytes_exprs: List[SymbolicExpr]        # per register
    instructions: List[Any]                 # full stream (evict path included)
    fast_instructions: List[Any]            # stream without MaybeEvict/Regen
    computes: List[Compute]
    # per Compute: the node's params when they contain nothing symbolic
    # (used as-is), else None -> refined per env in resolve()
    static_params: List[Optional[Dict[str, Any]]]
    regen: Dict[int, RegenProgram]          # target reg -> sub-program
    out_regs: Tuple[int, ...]
    death_step: List[int]                   # per register; -1 = never freed
    candidate_regs: Tuple[int, ...]         # remat candidates, producer order
    has_evict_path: bool
    memory_limit: Optional[int]
    donate_inputs: bool
    count_inputs: bool
    # rolled loops (index = Loop.lidx); each body is itself a Program,
    # lowered once — the stream stays O(body), not O(t·body)
    loops: List[LoopInfo] = field(default_factory=list)
    # bounded dim name -> registers whose byte size mentions it (refreshed
    # by the BindDim that publishes the measured value)
    bound_dep_regs: Dict[str, Tuple[int, ...]] = field(default_factory=dict)
    # the fast stream cut into op-group runs (a profiled call opens one
    # span per run)
    groups: List[OpGroup] = field(default_factory=list)

    def __post_init__(self):
        self._resolve_cache: Dict[Tuple, ResolvedProgram] = {}

    @property
    def has_bound_dims(self) -> bool:
        return bool(self.graph.bound_dims)

    @property
    def n_instructions(self) -> int:
        return len(self.instructions)

    def counts(self) -> Dict[str, int]:
        """Instruction histogram (docs/tests introspection)."""
        names = {OP_BIND_ARG: "BindArg", OP_COMPUTE: "Compute",
                 OP_MAYBE_EVICT: "MaybeEvict", OP_REGEN: "Regen",
                 OP_FREE_SLOT: "FreeSlot", OP_DONATE: "Donate",
                 OP_RETURN: "Return", OP_LOOP: "Loop",
                 OP_BIND_DIM: "BindDim"}
        out = {name: 0 for name in names.values()}
        for inst in self.instructions:
            out[names[inst.op]] += 1
        return out

    # ---------------------------------------------------------------- resolve
    def resolve(self, env: Dict[str, int],
                size_cache: Optional[Dict[Tuple, Dict[int, int]]] = None,
                params_cache: Optional[
                    Dict[Tuple, Dict[int, Dict[str, Any]]]] = None,
                ) -> ResolvedProgram:
        """Evaluate every attached expression for ``env`` in one pass.

        Cached per env (training repeats shapes).  ``size_cache`` /
        ``params_cache`` are the same shared per-env dicts the reference
        interpreter uses (keyed by graph uid + env, then value/node id),
        so bucketed dispatch re-derives nothing when plans swap."""
        key = (self.graph.uid,) + tuple(sorted(env.items()))
        out = self._resolve_cache.get(key)
        if out is not None:
            return out
        if len(self._resolve_cache) > 64:
            self._resolve_cache.clear()

        # value-dependent bounded dims absent from the env evaluate at
        # their cap.  Completion is deterministic in the declared env, so
        # the declared-env cache key stays sound: cached sizes are cap
        # sizes, and measured values live only in per-call overlays (the
        # VM's nbytes_run / the interpreter's fresh evaluations) — two
        # calls with equal declared dims but different measured bounds
        # can never alias each other's resolve.
        if self.graph.bound_dims:
            from ..ir.dynamism import complete_bound_env
            env = complete_bound_env(self.graph, env)

        sizes: Dict[int, int] = {}
        if size_cache is not None:
            if len(size_cache) > 64:
                size_cache.clear()
            sizes = size_cache.setdefault(key, {})
        nbytes = [0] * self.n_regs
        for reg, expr in enumerate(self.nbytes_exprs):
            vid = self.vid_of[reg]
            b = sizes.get(vid)
            if b is None:
                b = expr.evaluate(env)
                sizes[vid] = b
            nbytes[reg] = b

        refined: Dict[int, Dict[str, Any]] = {}
        if params_cache is not None:
            if len(params_cache) > 64:
                params_cache.clear()
            refined = params_cache.setdefault(key, {})
        overrides = self.plan.kernel_overrides if self.plan is not None else {}
        params: List[Dict[str, Any]] = []
        for comp, static in zip(self.computes, self.static_params):
            if static is not None:
                params.append(static)
                continue
            ov = overrides.get(comp.node.id)
            if ov is not None:
                # kernel-variant override on symbolic params: resolve
                # outside the shared cache — other buckets' programs key
                # the same (graph uid, env) but merge different choices
                params.append({**refine_params(comp.node.params, env), **ov})
                continue
            p = refined.get(comp.node.id)
            if p is None:
                p = refine_params(comp.node.params, env)
                refined[comp.node.id] = p
            params.append(p)

        ensure = [sum(nbytes[r] for _oi, r in comp.store)
                  for comp in self.computes]
        regen_flops = {reg: max(1, rp.flops_expr.evaluate(env))
                       for reg, rp in self.regen.items()}

        arena = offsets = None
        if self.plan.arena_plan is not None:
            arena = self.plan.arena_plan.resolve(env)
            offsets = arena.offsets

        # rolled loops: resolve each body sub-program (its own cache entry,
        # keyed by the body graph's uid) and evaluate the loop's trip count,
        # exact internal peak delta, and accounting size tables
        rloops: List[ResolvedLoop] = []
        for info in self.loops:
            trip = info.body.length_expr.evaluate(env)
            rbody = info.body_program.resolve(env, size_cache, params_cache)
            bsizes = {bvid: e.evaluate(env)
                      for bvid, e in info.lp.sizes.items()}
            nk = info.body.num_carry
            node = info.node
            outer_y = [(ov.id, nbytes[self.reg_of[ov.id]])
                       for ov, k in zip(node.outvals[nk:], info.kept[nk:])
                       if k]
            outer_carry = [(ov.id, nbytes[self.reg_of[ov.id]]) if k else None
                           for ov, k in zip(node.outvals[:nk],
                                            info.kept[:nk])]
            extra = info.lp.peak_expr_for(node, info.kept,
                                          trip).evaluate(env)
            rloops.append(ResolvedLoop(trip=trip, rbody=rbody,
                                       extra_bytes=extra, sizes=bsizes,
                                       outer_y=outer_y,
                                       outer_carry=outer_carry))

        out = ResolvedProgram(env=dict(env), nbytes=nbytes,
                              ensure_bytes=ensure, params=params,
                              regen_flops=regen_flops, arena=arena,
                              value_offsets=offsets or {}, loops=rloops)
        out.stats_template, out.peak_bytes = self._replay_stats(
            nbytes, arena, rloops)
        # bound programs measure sizes mid-call, so the precomputed stats
        # template (cap sizes) is not this call's truth: force the
        # dynamic path
        out.fast_ok = ((self.memory_limit is None
                        or out.peak_bytes <= self.memory_limit)
                       and not self.graph.bound_dims)
        self._resolve_cache[key] = out
        return out

    def _replay_stats(self, nbytes: List[int], arena_resolved,
                      rloops: List[ResolvedLoop] = ()) -> Tuple[MemoryStats, int]:
        """Replay the static alloc/free sequence once for this env.

        The fast stream's memory traffic is fully determined by the env
        (no eviction can reorder it), so the whole run's MemoryStats —
        device peak, arena size, reuse ratio, fragmentation — is a
        compile-side fact the hot path copies instead of recomputing."""
        arena = None
        if arena_resolved is not None:
            arena = ArenaAllocator(self.plan.arena_plan, arena_resolved)
        mm = MemoryManager(None, arena=arena)
        vid_of = self.vid_of
        for inst in self.fast_instructions:
            op = inst.op
            if op == OP_COMPUTE:
                for _oi, r in inst.store:
                    if r not in inst.defer_regs:
                        mm.alloc(vid_of[r], nbytes[r])
            elif op == OP_BIND_DIM:
                # the replay has no measurement: the deferred payload
                # alloc lands at whatever the resolving env said (cap for
                # a declared env, measured for a report env)
                for _oi, r in inst.alloc_store:
                    mm.alloc(vid_of[r], nbytes[r])
            elif op == OP_BIND_ARG:
                if arena is not None:
                    arena.place_external(inst.vid, nbytes[inst.reg])
                if self.count_inputs:
                    mm.alloc(inst.vid, nbytes[inst.reg])
            elif op == OP_FREE_SLOT:
                mm.free(inst.vid)
            elif op == OP_DONATE:
                if inst.counted:
                    mm.free(inst.vid)
                else:
                    mm.arena_release(inst.vid)
            elif op == OP_LOOP:
                # the shared event engine replays the loop's alloc/free
                # sequence — identical to what the interpreter and the
                # VM dynamic path drive through their MemoryManagers
                rl = rloops[inst.lidx]
                info = self.loops[inst.lidx]
                info.lp.account(mm, info.node.id, rl.trip,
                                rl.sizes.__getitem__, rl.outer_y,
                                rl.outer_carry)
        if arena is not None:
            arena.write_stats(mm.stats)
        return mm.stats, mm.stats.device_peak

    def stats_for(self, resolved: ResolvedProgram) -> MemoryStats:
        """A fresh per-call copy of the precomputed stats template."""
        return replace(resolved.stats_template,
                       measured_dims=dict(
                           resolved.stats_template.measured_dims))
