"""Kernel-variant registry + the hardware-aware analytical cost model.

Buckets specialize *memory plans*; this module is what lets them
specialize *kernels* too.  Every selectable primitive registers a table
of variants — Pallas block configurations at several sizes and pipeline
depths, plus the dense reference implementation — and a cost function
that prices one variant at one concrete shape from the
:class:`~repro.kernels.hw_model.HardwareModel` constants:

* **MXU / VPU time** — FLOPs over the sustained rate, discounted by
  :func:`~repro.kernels.hw_model.mxu_efficiency` for tiles below the
  128-wide systolic edge;
* **HBM time** — bytes moved, including padding copies and the
  K/V-revisit traffic that shrinks as blocks grow;
* **fixed overhead** — per-``pallas_call`` launch vs per-XLA-dispatch
  cost, the term that makes the reference implementation win degenerate
  shapes (Vortex's sample-free, hierarchized strategy space: prune by
  hardware constraints, rank analytically, never autotune on-device);
* **VMEM footprint** — the *validity* constraint: a variant whose
  double-buffered working set cannot fit VMEM at any in-range shape is
  never selected for that range.

Selection happens per compiled plan (:func:`select_kernels`): a kernel
node's dims are bounded by the plan's ``ShapeGraph`` intervals — a
bucket's narrowed ranges, or the whole declared range for the fallback
plan — the cost model scores every valid variant at the range's lo /
geometric-mid / hi corners, and the cheapest total wins.  Validity is
judged at the range's *upper* corner (footprints are monotone in every
dim), so the whole-range fallback can never adopt a variant that some
in-range shape would overflow; an unbounded dim that a Pallas footprint
depends on simply rules the Pallas variants out, leaving the always-valid
reference implementation.

The winning variant's parameter overrides are baked into the lowered
``Compute`` instruction at lowering time — the VM hot path never
branches on shape — and the scores surface as ``kernel-select`` entries
in the :class:`~repro.core.obs.DecisionLog`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, \
    Tuple

from .hw_model import HardwareModel, hardware_for, mxu_efficiency

# dims the cost model probes when a range has no upper bound: a heuristic
# *pricing* point only — validity never relies on it (unbounded Pallas
# footprints are simply invalid)
_UNBOUNDED_PROBE = 4096


# ---------------------------------------------------------------------------
# variant + cost containers
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class KernelVariant:
    """One selectable configuration of a kernel primitive.

    ``block`` holds the primitive's block-size parameters as sorted
    name/value pairs (hashable); ``pipeline_depth`` is the multiple-
    buffering factor the cost model charges VMEM for (Pallas TPU double
    buffers in/out blocks by default — depth 1 models the serial
    fallback that halves the footprint when depth 2 cannot fit)."""

    name: str
    impl: str                                   # 'pallas' | 'ref'
    block: Tuple[Tuple[str, int], ...] = ()
    pipeline_depth: int = 2

    def overrides(self) -> Dict[str, Any]:
        """The node-param overrides that realize this variant."""
        return {"impl": self.impl, "pipeline_depth": self.pipeline_depth,
                **dict(self.block)}

    def block_of(self, name: str, default: int = 0) -> int:
        return dict(self.block).get(name, default)


@dataclass(frozen=True)
class VariantCost:
    """One variant priced at one concrete shape."""

    time_s: float
    flops: float
    hbm_bytes: float
    vmem_bytes: int          # working-set footprint (0 for HBM-resident ref)
    util: float              # sustained fraction of the unit's peak


@dataclass
class KernelSelection:
    """The outcome of selecting one kernel node over one shape range."""

    node_id: int
    prim_name: str
    variant: KernelVariant
    default: KernelVariant
    scores: Dict[str, float]                 # variant name -> summed time_s
    bounds: Dict[str, Tuple[int, Optional[int]]]  # dim label -> (lo, hi)
    probes: List[Dict[str, int]] = field(default_factory=list)
    invalid: Tuple[str, ...] = ()            # variants VMEM ruled out
    measured: bool = False                   # True after a measured re-select

    @property
    def is_default(self) -> bool:
        return self.variant.name == self.default.name

    @property
    def model_speedup(self) -> float:
        """Predicted default-time / selected-time over the probe corners."""
        sel = self.scores.get(self.variant.name, 0.0)
        def_ = self.scores.get(self.default.name, sel)
        return def_ / sel if sel > 0 else 1.0

    def describe_bounds(self) -> str:
        parts = []
        for name, (lo, hi) in self.bounds.items():
            parts.append(f"{name}∈[{lo},{'∞' if hi is None else hi}]")
        return " ".join(parts)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

# prim name -> (variants, default, cost_fn, shape_fn)
#   cost_fn(variant, shapes, itemsize, params, hw) -> VariantCost
#   shape_fn(node_dims) -> the dim-label map the cost model prices
_REGISTRY: Dict[str, Dict[str, Any]] = {}


def register_kernel(prim_name: str, variants: Sequence[KernelVariant],
                    default: KernelVariant,
                    cost_fn: Callable[..., VariantCost]) -> None:
    if default.name not in {v.name for v in variants}:
        raise ValueError(f"default variant {default.name!r} not in the "
                         f"{prim_name} registry")
    _REGISTRY[prim_name] = dict(variants=tuple(variants), default=default,
                                cost=cost_fn)


def variants_for(prim_name: str) -> Tuple[KernelVariant, ...]:
    return _REGISTRY[prim_name]["variants"]


def default_variant(prim_name: str) -> KernelVariant:
    return _REGISTRY[prim_name]["default"]


def is_selectable(prim_name: str) -> bool:
    return prim_name in _REGISTRY


def registered_kernels() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


# ---------------------------------------------------------------------------
# tile / footprint helpers
# ---------------------------------------------------------------------------


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_bytes(rows: int, cols: int, itemsize: int,
                hw: HardwareModel) -> int:
    """VMEM bytes of one (rows, cols) tile after min-tile padding.

    The second-minor dim pads to the sublane count, the minor dim to the
    128-lane width — a (block_q, 1) f32 accumulator still occupies
    (block_q, 128) lanes of VMEM."""
    return (_ceil_to(max(rows, 1), hw.vpu_sublanes)
            * _ceil_to(max(cols, 1), hw.vpu_lanes) * itemsize)


def flash_vmem_bytes(variant: KernelVariant, s_hi: Optional[int],
                     t_hi: Optional[int], hd: Optional[int], itemsize: int,
                     hw: HardwareModel) -> Optional[int]:
    """Worst-case VMEM working set of a flash-attention variant.

    Block dims self-bound (``min(block, s)`` never exceeds the block), so
    unbounded s/t stay sound; an unbounded head dim cannot be bounded at
    all — ``None`` (treated as invalid for Pallas)."""
    if variant.impl == "ref":
        return 0
    if hd is None:
        return None
    bq = variant.block_of("block_q", 128)
    bkv = variant.block_of("block_kv", 128)
    if s_hi is not None:
        bq = min(bq, max(s_hi, 1))
    if t_hi is not None:
        bkv = min(bkv, max(t_hi, 1))
    io = (_tile_bytes(bq, hd, itemsize, hw)          # Q block
          + 2 * _tile_bytes(bkv, hd, itemsize, hw)   # K + V blocks
          + _tile_bytes(bq, hd, itemsize, hw))       # O block
    scratch = (2 * _tile_bytes(bq, 1, 4, hw)         # m, l (f32)
               + _tile_bytes(bq, hd, 4, hw))         # acc (f32)
    return variant.pipeline_depth * io + scratch


def rmsnorm_vmem_bytes(variant: KernelVariant, n_hi: Optional[int],
                       d: Optional[int], itemsize: int,
                       hw: HardwareModel) -> Optional[int]:
    if variant.impl == "ref":
        return 0
    if d is None:
        return None
    br = variant.block_of("block_rows", 256)
    if n_hi is not None:
        br = min(br, max(n_hi, 1))
    d_pad = _ceil_to(d, hw.vpu_lanes)
    io = 2 * _tile_bytes(br, d_pad, itemsize, hw)    # x + out blocks
    scratch = (_tile_bytes(1, d_pad, itemsize, hw)   # scale row
               + _tile_bytes(br, d_pad, 4, hw))      # f32 working copy
    return variant.pipeline_depth * io + scratch


def variant_vmem_bytes(prim_name: str, variant: KernelVariant,
                       hi_shape: Mapping[str, Optional[int]], itemsize: int,
                       hw: Optional[HardwareModel] = None) -> Optional[int]:
    """Worst-case footprint over a range's upper corner (``None`` dims =
    unbounded).  The validity predicate is ``footprint <= hw.vmem_bytes``
    with ``None`` meaning unboundable → invalid."""
    hw = hw or hardware_for()
    if prim_name == "flash_attention":
        return flash_vmem_bytes(variant, hi_shape.get("s"), hi_shape.get("t"),
                                hi_shape.get("hd"), itemsize, hw)
    if prim_name == "rmsnorm":
        return rmsnorm_vmem_bytes(variant, hi_shape.get("n"),
                                  hi_shape.get("d"), itemsize, hw)
    raise KeyError(prim_name)


def variant_valid(prim_name: str, variant: KernelVariant,
                  hi_shape: Mapping[str, Optional[int]], itemsize: int,
                  hw: Optional[HardwareModel] = None) -> bool:
    hw = hw or hardware_for()
    vm = variant_vmem_bytes(prim_name, variant, hi_shape, itemsize, hw)
    return vm is not None and vm <= hw.vmem_bytes


# ---------------------------------------------------------------------------
# cost models
# ---------------------------------------------------------------------------


def _causal_block_pairs(nq: int, nk: int, bq: int, bkv: int) -> int:
    """Blocks the causal kernel actually runs: for q block ``qi`` only kv
    blocks at or below the diagonal contribute (the kernel's ``pl.when``
    skip), ≈ half the grid for square shapes."""
    total = 0
    for qi in range(nq):
        total += min(nk, (qi * bq + bq - 1) // bkv + 1)
    return total


def flash_cost(variant: KernelVariant, shape: Mapping[str, int],
               itemsize: int, params: Mapping[str, Any],
               hw: HardwareModel) -> VariantCost:
    """Price one flash-attention variant at one concrete shape."""
    b, hq = shape["b"], shape["hq"]
    s, t, hd = shape["s"], shape["t"], shape["hd"]
    causal = bool(params.get("causal", True))
    if variant.impl == "ref":
        # dense: full S×T scores, no causal block skipping; the score
        # matrix round-trips HBM only once it outgrows VMEM — below that
        # it stays on-chip and the dense path is pure fixed-cost
        flops_mxu = b * hq * 4.0 * s * t * hd
        flops_vpu = b * hq * 8.0 * s * t
        eff = mxu_efficiency(hw, hd, t)
        scores_b = b * hq * s * t * 4
        hbm = ((b * hq * 2 * s * hd + 2 * b * hq * t * hd) * itemsize
               + (3 * scores_b if scores_b > hw.vmem_bytes else 0))
        compute_s = flops_mxu / (hw.peak_flops * eff) + flops_vpu / hw.vpu_flops
        time = max(compute_s, hbm / hw.hbm_bw) + 3 * hw.xla_dispatch_s
        util = compute_s / time if time > 0 else 0.0
        return VariantCost(time, flops_mxu + flops_vpu, hbm, 0, util)

    bq = min(variant.block_of("block_q", 128), s)
    bkv = min(variant.block_of("block_kv", 128), t)
    s_pad, t_pad = _ceil_to(s, bq), _ceil_to(t, bkv)
    nq, nk = s_pad // bq, t_pad // bkv
    pairs = _causal_block_pairs(nq, nk, bq, bkv) if causal else nq * nk
    flops_mxu = b * hq * pairs * 4.0 * bq * bkv * hd
    flops_vpu = b * hq * pairs * 6.0 * bq * bkv
    eff = mxu_efficiency(hw, hd, bkv)
    # Q/O stream once; K/V tiles re-stream once per visiting q block —
    # the revisit traffic is what larger q blocks buy down
    hbm = (2 * b * hq * s_pad * hd + b * hq * pairs * 2 * bkv * hd) * itemsize
    compute_s = flops_mxu / (hw.peak_flops * eff) + flops_vpu / hw.vpu_flops
    grid = b * hq * nq * nk
    time = max(compute_s, hbm / hw.hbm_bw) \
        + hw.kernel_launch_s + grid * hw.grid_step_s
    util = compute_s / time if time > 0 else 0.0
    vm = flash_vmem_bytes(variant, s, t, hd, itemsize, hw) or 0
    return VariantCost(time, flops_mxu + flops_vpu, hbm, vm, util)


def rmsnorm_cost(variant: KernelVariant, shape: Mapping[str, int],
                 itemsize: int, params: Mapping[str, Any],
                 hw: HardwareModel) -> VariantCost:
    """Price one rmsnorm variant at one concrete shape (n rows × d)."""
    n, d = shape["n"], shape["d"]
    if variant.impl == "ref":
        # unfused jnp: ~3 passes over the (n, d) activation, no padding
        flops = 4.0 * n * d
        hbm = 6 * n * d * itemsize
        compute_s = flops / hw.vpu_flops
        time = max(compute_s, hbm / hw.hbm_bw) + 3 * hw.xla_dispatch_s
        return VariantCost(time, flops, hbm, 0,
                           compute_s / time if time > 0 else 0.0)

    br = min(variant.block_of("block_rows", 256), n)
    d_pad = _ceil_to(d, hw.vpu_lanes)
    n_pad = _ceil_to(n, br)
    flops = 4.0 * n_pad * d_pad
    # fused kernel: one read + one write per (padded) element — plus the
    # wrapper's pad/unpad copies whenever d or n is not tile-aligned,
    # the traffic that makes tiny-d Pallas strictly worse than ref
    hbm = 2 * n_pad * d_pad * itemsize
    if d_pad != d or n_pad != n:
        hbm += (n * d + n_pad * d_pad) * itemsize      # pad copy
        hbm += (n_pad * d_pad + n * d) * itemsize      # unpad slice
    compute_s = flops / hw.vpu_flops
    grid = n_pad // br
    time = max(compute_s, hbm / hw.hbm_bw) \
        + hw.kernel_launch_s + grid * hw.grid_step_s
    vm = rmsnorm_vmem_bytes(variant, n, d, itemsize, hw) or 0
    return VariantCost(time, flops, hbm, vm,
                       compute_s / time if time > 0 else 0.0)


# ---------------------------------------------------------------------------
# the built-in variant tables
# ---------------------------------------------------------------------------


def _fa_variant(bq: int, bkv: int, depth: int = 2) -> KernelVariant:
    suffix = "" if depth == 2 else f"_d{depth}"
    return KernelVariant(name=f"pallas_{bq}x{bkv}{suffix}", impl="pallas",
                         block=(("block_kv", bkv), ("block_q", bq)),
                         pipeline_depth=depth)


FLASH_DEFAULT = _fa_variant(128, 128)
FLASH_VARIANTS: Tuple[KernelVariant, ...] = (
    FLASH_DEFAULT,
    _fa_variant(256, 256),
    _fa_variant(512, 256),
    _fa_variant(64, 64),
    _fa_variant(128, 128, depth=1),     # halved buffering for fat head dims
    KernelVariant(name="ref_dense", impl="ref"),
)


def _rn_variant(rows: int, depth: int = 2) -> KernelVariant:
    suffix = "" if depth == 2 else f"_d{depth}"
    return KernelVariant(name=f"pallas_r{rows}{suffix}", impl="pallas",
                         block=(("block_rows", rows),), pipeline_depth=depth)


RMSNORM_DEFAULT = _rn_variant(256)
RMSNORM_VARIANTS: Tuple[KernelVariant, ...] = (
    RMSNORM_DEFAULT,
    _rn_variant(1024),
    _rn_variant(64),
    _rn_variant(256, depth=1),
    KernelVariant(name="ref_unfused", impl="ref"),
)

register_kernel("flash_attention", FLASH_VARIANTS, FLASH_DEFAULT, flash_cost)
register_kernel("rmsnorm", RMSNORM_VARIANTS, RMSNORM_DEFAULT, rmsnorm_cost)


# ---------------------------------------------------------------------------
# shape extraction: kernel node dims -> the labels the cost model prices
# ---------------------------------------------------------------------------


def _node_dim_exprs(prim_name: str, node) -> Dict[str, Any]:
    """Map a kernel node's input dim exprs to cost-model labels."""
    if prim_name == "flash_attention":
        q, k = node.invals[0], node.invals[1]
        b, hq, s, hd = q.dims
        t = k.dims[2]
        return {"b": b, "hq": hq, "s": s, "t": t, "hd": hd}
    if prim_name == "rmsnorm":
        x = node.invals[0]
        lead, d = x.dims[:-1], x.dims[-1]
        n = None
        for e in lead:
            n = e if n is None else n * e
        return {"n": n if n is not None else 1, "d": d}
    raise KeyError(prim_name)


def _expr_bounds(expr, sg) -> Tuple[int, Optional[int]]:
    """(lo, hi) of one dim expression under the plan's shape graph."""
    if isinstance(expr, int):
        return expr, expr
    iv = sg.interval_of(expr)
    lo = iv.lo if iv.lo is not None and iv.lo >= 1 else 1
    return lo, iv.hi


def _probe_shapes(bounds: Mapping[str, Tuple[int, Optional[int]]]
                  ) -> List[Dict[str, int]]:
    """lo / geometric-mid / hi pricing corners (deduplicated)."""
    los = {k: lo for k, (lo, _hi) in bounds.items()}
    his = {k: hi if hi is not None else max(lo, _UNBOUNDED_PROBE)
           for k, (lo, hi) in bounds.items()}
    mids = {k: max(1, int(math.isqrt(los[k] * his[k]))) for k in bounds}
    probes, seen = [], set()
    for p in (los, mids, his):
        key = tuple(sorted(p.items()))
        if key not in seen:
            seen.add(key)
            probes.append(dict(p))
    return probes


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def select_variant(prim_name: str,
                   bounds: Mapping[str, Tuple[int, Optional[int]]],
                   itemsize: int, params: Mapping[str, Any],
                   hw: Optional[HardwareModel] = None,
                   forced: Optional[str] = None
                   ) -> Tuple[KernelVariant, Dict[str, float], List[Dict[str, int]], Tuple[str, ...]]:
    """Pick the cheapest VMEM-valid variant over one shape range.

    Returns ``(variant, scores, probes, invalid_names)``.  Validity is
    judged at the range's hi corner (``None`` = unbounded, sound because
    footprints are monotone in every dim); scores sum the model time over
    the lo/mid/hi pricing corners.  ``forced`` pins a variant by name
    (measured re-selection) — it must still be valid."""
    hw = hw or hardware_for()
    entry = _REGISTRY[prim_name]
    hi_shape = {k: hi for k, (_lo, hi) in bounds.items()}
    probes = _probe_shapes(bounds)
    scores: Dict[str, float] = {}
    invalid: List[str] = []
    valid: List[KernelVariant] = []
    for v in entry["variants"]:
        if not variant_valid(prim_name, v, hi_shape, itemsize, hw):
            invalid.append(v.name)
            continue
        valid.append(v)
        scores[v.name] = sum(
            entry["cost"](v, p, itemsize, params, hw).time_s for p in probes)
    if not valid:  # unreachable with a ref variant registered; be safe
        raise RuntimeError(
            f"no VMEM-valid {prim_name} variant over bounds {dict(bounds)}")
    if forced is not None:
        chosen = next((v for v in valid if v.name == forced), None)
        if chosen is None:
            raise ValueError(
                f"forced variant {forced!r} is not valid for {prim_name} "
                f"over bounds {dict(bounds)} (valid: "
                f"{[v.name for v in valid]})")
        return chosen, scores, probes, tuple(invalid)
    default = entry["default"]
    best = min(valid, key=lambda v: (scores[v.name],
                                     v.name != default.name, v.name))
    return best, scores, probes, tuple(invalid)


def node_bounds(node, sg) -> Dict[str, Tuple[int, Optional[int]]]:
    """A kernel node's cost-model dim bounds under one shape graph."""
    exprs = _node_dim_exprs(node.prim_name, node)
    return {k: _expr_bounds(e, sg) for k, e in exprs.items()}


def select_for_node(node, sg, hw: Optional[HardwareModel] = None,
                    forced: Optional[str] = None) -> KernelSelection:
    """Select a variant for one kernel node under a plan's shape graph."""
    prim_name = node.prim_name
    bounds = node_bounds(node, sg)
    itemsize = int(node.invals[0].dtype.itemsize)
    variant, scores, probes, invalid = select_variant(
        prim_name, bounds, itemsize, node.params, hw, forced=forced)
    return KernelSelection(node_id=node.id, prim_name=prim_name,
                           variant=variant,
                           default=default_variant(prim_name),
                           scores=scores, bounds=bounds, probes=probes,
                           invalid=invalid, measured=forced is not None)


def select_kernels(graph, sg, hw: Optional[HardwareModel] = None,
                   forced: Optional[Mapping[int, str]] = None,
                   decisions=None) -> Dict[int, KernelSelection]:
    """Select a variant for every registered kernel node in ``graph``.

    ``forced`` maps node id -> variant name (the measured-fallback path).
    Returns node id -> :class:`KernelSelection`; logs one
    ``kernel-select`` decision per node when a ``DecisionLog`` is given.
    """
    out: Dict[int, KernelSelection] = {}
    for node in graph.nodes:
        if node.prim_name not in _REGISTRY:
            continue
        sel = select_for_node(node, sg, hw,
                              forced=(forced or {}).get(node.id))
        out[node.id] = sel
        if decisions is not None:
            sel_us = sel.scores.get(sel.variant.name, 0.0) * 1e6
            def_us = sel.scores.get(sel.default.name, sel_us) * 1e6
            why = (f"measured re-selection over {sel.describe_bounds()}"
                   if sel.measured else
                   f"model {sel_us:.1f}us vs default {def_us:.1f}us "
                   f"over {sel.describe_bounds()}")
            decisions.add("kernel-select", f"%{node.id} {node.prim_name}",
                          sel.variant.name, why,
                          model_speedup=round(sel.model_speedup, 3),
                          n_scored=len(sel.scores),
                          invalid=list(sel.invalid))
    return out


def select_eager(prim_name: str, shape: Mapping[str, int], itemsize: int,
                 params: Mapping[str, Any],
                 hw: Optional[HardwareModel] = None) -> KernelVariant:
    """Cost-model choice at one *concrete* shape (the eager-call path:
    ``kernels.rmsnorm(x, scale)`` with no explicit impl)."""
    bounds = {k: (int(v), int(v)) for k, v in shape.items()}
    variant, _scores, _probes, _invalid = select_variant(
        prim_name, bounds, itemsize, params, hw)
    return variant


# ---------------------------------------------------------------------------
# measured fallback: time the candidates at a representative shape
# ---------------------------------------------------------------------------


def measure_variants(prim_name: str, node, env: Mapping[str, int],
                     hw: Optional[HardwareModel] = None, repeats: int = 3
                     ) -> Dict[str, float]:
    """Wall-time every VMEM-valid variant of ``node`` at ``env``.

    Builds random inputs at the node's concrete shapes (values are
    irrelevant to timing), runs each valid variant once to warm the jit
    cache, then takes the best of ``repeats`` timed calls.  Returns
    variant name -> seconds."""
    hw = hw or hardware_for()
    import time as _time

    import jax
    import numpy as np

    from . import ops as _ops

    def _dim(e):
        return int(e) if isinstance(e, int) else int(e.evaluate(dict(env)))

    arrays = []
    rng = np.random.default_rng(0)
    for i, v in enumerate(node.invals):
        shape = tuple(_dim(d) for d in v.dims)
        if np.issubdtype(v.dtype, np.floating):
            arr = rng.standard_normal(shape, dtype=np.float32).astype(v.dtype)
        else:
            arr = rng.integers(0, 8, size=shape).astype(v.dtype)
        arrays.append(jax.numpy.asarray(arr))
    exprs = _node_dim_exprs(prim_name, node)
    hi_shape = {k: _dim(e) for k, e in exprs.items()}
    itemsize = int(node.invals[0].dtype.itemsize)
    timings: Dict[str, float] = {}
    for variant in variants_for(prim_name):
        if not variant_valid(prim_name, variant, hi_shape, itemsize, hw):
            continue
        merged = {**node.params, **variant.overrides()}
        run = lambda: _ops.run_kernel(prim_name, arrays, merged)
        jax.block_until_ready(run())            # warm the jit cache
        best = float("inf")
        for _ in range(max(1, repeats)):
            t0 = _time.perf_counter()
            jax.block_until_ready(run())
            best = min(best, _time.perf_counter() - t0)
        timings[variant.name] = best
    return timings
