"""Per-bucket kernel-variant selection: cost model + wiring contracts.

Four contract groups:

* **cost-model properties** — VMEM footprints are monotone in block size
  and pipeline depth; shrinking the VMEM budget only ever *shrinks* the
  valid variant set (the reference implementation never leaves it); an
  unbounded dim a Pallas footprint depends on rules every Pallas variant
  out.  Property-tested (hypothesis): over random shape ranges —
  unbounded corners included — the selected variant is always valid at
  the range's upper corner, so the whole-range fallback can never adopt
  a variant some in-range shape would overflow.
* **ref-vs-pallas crossovers** — the tiny-``d`` rmsnorm regression: the
  cost model sends sub-tile feature dims to the unfused reference path
  (pad/unpad copy traffic swamps the fused kernel) and tile-aligned fat
  dims to Pallas, and the eager auto-dispatch path actually routes there.
* **differential** — with selection on, the ProgramVM and the reference
  interpreter agree *bitwise* and on memory stats in every bucket, on
  the plain path, through value-dependent bounded dims, and inside
  rolled ``scan`` bodies; memory stats are identical across variant
  choices (selection changes kernel params, never the memory plan).
* **measured fallback** — ``remeasure_kernels`` wall-times the valid
  candidates, swaps the plan (bucket recompile or monolithic rebuild),
  marks the selections ``measured``, logs ``kernel-measure`` decisions,
  and only ever forces winners that stay valid over the whole target
  range.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import optimize, symbolic_dim, symbolic_dims
from repro.core.ir.trace import solve_checked_env
from repro.kernels import flash_attention, masked_select, rmsnorm
from repro.kernels.hw_model import TPU_V5E
from repro.kernels.ref import reference_attention, reference_rmsnorm
from repro.kernels.variants import (default_variant, node_bounds,
                                    registered_kernels, select_eager,
                                    select_variant, variant_valid,
                                    variant_vmem_bytes, variants_for)

# tiny bench-like geometry: small enough for interpret-mode Pallas
HQ, HKV, HD, D = 2, 1, 16, 64
B_RANGE, S_RANGE, EDGES = (1, 4), (1, 512), [64]
SMALL_ENV, LARGE_ENV = (2, 16), (1, 128)


def _fwd(impl=None):
    def fwd(q, k, v, x, scale):
        o = flash_attention(q, k, v, causal=True, impl=impl)
        h = rmsnorm(x, scale, impl=impl)
        return o, h
    return fwd


def _specs():
    B, S = symbolic_dims("b, s")
    f32 = jnp.float32
    return (jax.ShapeDtypeStruct((B, HQ, S, HD), f32),
            jax.ShapeDtypeStruct((B, HKV, S, HD), f32),
            jax.ShapeDtypeStruct((B, HKV, S, HD), f32),
            jax.ShapeDtypeStruct((B, S, D), f32),
            jax.ShapeDtypeStruct((D,), f32))


def _args(b, s, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *sh: jnp.asarray(rng.standard_normal(sh, dtype=np.float32))
    return (f(b, HQ, s, HD), f(b, HKV, s, HD), f(b, HKV, s, HD),
            f(b, s, D), f(D,))


def _compile(executor="vm", impl=None, **kw):
    return optimize(_fwd(impl), *_specs(),
                    dynamic_dims={"b": B_RANGE, "s": S_RANGE},
                    buckets={"s": EDGES}, executor=executor, **kw)


def _stats(fn):
    d = fn.last_report.stats.as_dict()
    d.pop("last_dispatch_ns", None)
    d.pop("dispatch_ns_total", None)
    return d


def _bucket_plan(fn, env):
    table = fn.specialization_table
    return table.peek(table.key_of(env)).plan


# -- cost-model properties -----------------------------------------------------

def test_flash_vmem_monotone_in_block_size():
    hi = {"s": 4096, "t": 4096, "hd": 64}
    names = ["pallas_64x64", "pallas_128x128", "pallas_256x256",
             "pallas_512x256"]
    by_name = {v.name: v for v in variants_for("flash_attention")}
    fps = [variant_vmem_bytes("flash_attention", by_name[n], hi, 4)
           for n in names]
    assert all(a <= b for a, b in zip(fps, fps[1:])), dict(zip(names, fps))
    # halved pipelining shrinks the footprint at the same block size
    assert (variant_vmem_bytes("flash_attention",
                               by_name["pallas_128x128_d1"], hi, 4)
            < variant_vmem_bytes("flash_attention",
                                 by_name["pallas_128x128"], hi, 4))
    # the reference path is HBM-resident: zero VMEM working set
    assert variant_vmem_bytes("flash_attention", by_name["ref_dense"],
                              hi, 4) == 0


def test_rmsnorm_vmem_monotone_in_block_rows():
    hi = {"n": 1 << 16, "d": 1024}
    by_name = {v.name: v for v in variants_for("rmsnorm")}
    fps = [variant_vmem_bytes("rmsnorm", by_name[n], hi, 4)
           for n in ("pallas_r64", "pallas_r256", "pallas_r1024")]
    assert fps[0] <= fps[1] <= fps[2], fps
    assert (variant_vmem_bytes("rmsnorm", by_name["pallas_r256_d1"], hi, 4)
            < variant_vmem_bytes("rmsnorm", by_name["pallas_r256"], hi, 4))


@pytest.mark.parametrize("prim", ["flash_attention", "rmsnorm"])
def test_valid_set_shrinks_with_vmem_budget(prim):
    """A smaller VMEM budget can only remove variants, and the reference
    implementation (footprint 0) survives every budget."""
    hi = ({"s": 4096, "t": 4096, "hd": 128} if prim == "flash_attention"
          else {"n": 1 << 16, "d": 4096})
    budgets = [TPU_V5E.vmem_bytes, 4 << 20, 1 << 20, 256 << 10,
               32 << 10, 1]
    prev = None
    for budget in budgets:
        hw = TPU_V5E.with_vmem(budget)
        valid = {v.name for v in variants_for(prim)
                 if variant_valid(prim, v, hi, 4, hw)}
        ref = {v.name for v in variants_for(prim) if v.impl == "ref"}
        assert ref <= valid
        if prev is not None:
            assert valid <= prev, (budget, valid - prev)
        prev = valid


def test_unbounded_footprint_dim_rules_out_pallas():
    """A dim the Pallas footprint cannot self-bound (the head dim / the
    feature dim) being unbounded invalidates every Pallas variant; the
    selector falls back to the reference implementation."""
    bounds = {"b": (1, None), "hq": (4, 4), "s": (1, None), "t": (1, None),
              "hd": (1, None)}
    variant, _scores, _probes, invalid = select_variant(
        "flash_attention", bounds, 4, {"causal": True})
    assert variant.impl == "ref"
    assert set(invalid) == {v.name for v in variants_for("flash_attention")
                            if v.impl == "pallas"}


def _rand_bounds(prim, rng):
    def one(lo_hi, unbounded_ok=True):
        lo = int(rng.integers(1, lo_hi))
        if unbounded_ok and rng.random() < 0.25:
            return (lo, None)
        return (lo, lo + int(rng.integers(0, 8192)))
    if prim == "flash_attention":
        return {"b": one(16), "hq": one(16), "s": one(64), "t": one(64),
                "hd": one(256)}
    return {"n": one(64), "d": one(4096)}


@settings(max_examples=40, deadline=None)
@given(prim=st.sampled_from(["flash_attention", "rmsnorm"]),
       itemsize=st.sampled_from([2, 4]),
       seed=st.integers(0, 10**6))
def test_whole_range_fallback_never_selects_invalid(prim, itemsize, seed):
    """Acceptance property: over arbitrary shape ranges — unbounded
    corners included — selection succeeds and the winner's footprint fits
    VMEM at the range's upper corner, so no in-range shape can overflow
    it (footprints are monotone in every dim)."""
    bounds = _rand_bounds(prim, np.random.default_rng(seed))
    variant, scores, _probes, invalid = select_variant(
        prim, bounds, itemsize, {})
    hi = {k: h for k, (_lo, h) in bounds.items()}
    assert variant_valid(prim, variant, hi, itemsize)
    assert variant.name in scores
    for name in invalid:
        bad = next(v for v in variants_for(prim) if v.name == name)
        assert not variant_valid(prim, bad, hi, itemsize)
        assert name not in scores


# -- ref-vs-pallas crossovers (the tiny-d rmsnorm regression) ------------------

def test_rmsnorm_tiny_d_crossover_in_the_model():
    for d in (8, 16, 32):
        v = select_eager("rmsnorm", {"n": 256, "d": d}, 4, {})
        assert v.impl == "ref", (d, v.name)
    for d in (512, 2048):
        v = select_eager("rmsnorm", {"n": 256, "d": d}, 4, {})
        assert v.impl == "pallas", (d, v.name)


def test_rmsnorm_tiny_d_eager_call_routes_to_ref():
    """The no-impl eager call actually dispatches where the model points:
    bitwise equal to the explicit ref call at tiny d, to the explicit
    Pallas call at fat d."""
    rng = np.random.default_rng(3)
    x8 = jnp.asarray(rng.standard_normal((16, 8), dtype=np.float32))
    s8 = jnp.asarray(rng.standard_normal((8,), dtype=np.float32))
    auto = rmsnorm(x8, s8)
    assert np.array_equal(np.asarray(auto), np.asarray(
        rmsnorm(x8, s8, impl="ref")))

    x2k = jnp.asarray(rng.standard_normal((16, 2048), dtype=np.float32))
    s2k = jnp.asarray(rng.standard_normal((2048,), dtype=np.float32))
    auto = rmsnorm(x2k, s2k)
    assert np.array_equal(np.asarray(auto), np.asarray(
        rmsnorm(x2k, s2k, impl="pallas")))


def test_flash_small_seq_crossover_in_the_model():
    """Degenerate sequence lengths route attention to the dense reference
    path (launch overhead + on-chip score matrix), long ones to Pallas."""
    small = {"b": 2, "hq": 4, "s": 16, "t": 16, "hd": 64}
    large = {"b": 2, "hq": 4, "s": 2048, "t": 2048, "hd": 64}
    assert select_eager("flash_attention", small, 4, {}).impl == "ref"
    assert select_eager("flash_attention", large, 4, {}).impl == "pallas"


# -- differential: selection wiring, per bucket --------------------------------

def test_per_bucket_selection_and_explain():
    fn = _compile()
    fn(*_args(*SMALL_ENV))
    small = {s.prim_name: s.variant
             for s in _bucket_plan(fn, {"b": SMALL_ENV[0],
                                        "s": SMALL_ENV[1]}).kernel_selections.values()}
    fn(*_args(*LARGE_ENV))
    large = {s.prim_name: s.variant
             for s in _bucket_plan(fn, {"b": LARGE_ENV[0],
                                        "s": LARGE_ENV[1]}).kernel_selections.values()}
    # the small bucket crosses attention over to the dense reference path;
    # the large bucket stays on (bigger-block) Pallas — buckets genuinely
    # specialize kernels, not just memory plans
    assert small["flash_attention"].impl == "ref"
    assert large["flash_attention"].impl == "pallas"
    assert small["flash_attention"].name != large["flash_attention"].name
    # the whole-range fallback plan carries its own selections
    assert fn.plan.kernel_selections
    # decisions + explain surface the choices
    kinds = {d.kind for d in fn.decisions.entries()}
    assert "kernel-select" in kinds
    report = fn.explain()
    assert "kernel selection" in report
    assert small["flash_attention"].name in report
    assert large["flash_attention"].name in report


def test_vm_matches_interpreter_bitwise_per_bucket():
    fn_vm = _compile("vm")
    fn_ref = _compile("reference")
    for b, s in (SMALL_ENV, LARGE_ENV):
        args = _args(b, s, seed=b + s)
        out_vm, out_ref = fn_vm(*args), fn_ref(*args)
        for x, y in zip(jax.tree_util.tree_leaves(out_vm),
                        jax.tree_util.tree_leaves(out_ref)):
            assert np.array_equal(np.asarray(x), np.asarray(y)), (b, s)
        assert _stats(fn_vm) == _stats(fn_ref), (b, s)


def test_memory_stats_identical_across_variants():
    """Variant choice changes kernel params only — the memory plan, the
    arena, and the guaranteed bounds are byte-identical whether the node
    runs ref, default Pallas, or the selected variant."""
    fns = [_compile(impl=None, kernel_select=True),
           _compile(impl="pallas", kernel_select=False),
           _compile(impl="ref", kernel_select=False)]
    assert len({fn.guaranteed_peak_bytes for fn in fns}) == 1
    assert len({fn.arena_bound_bytes for fn in fns}) == 1
    for b, s in (SMALL_ENV, LARGE_ENV):
        stats = []
        for fn in fns:
            fn(*_args(b, s))
            stats.append(_stats(fn))
        assert stats[0] == stats[1] == stats[2], (b, s)


def test_bounded_dims_path_vm_eq_interpreter():
    """Kernels downstream of a value-dependent bounded dim still agree
    bitwise across executors (the row count is decided by input values)."""
    def f(x, mask, scale):
        y, cnt = masked_select(x, mask)
        return jnp.sum(rmsnorm(y, scale), axis=0), cnt

    s = symbolic_dim("s")
    specs = (jax.ShapeDtypeStruct((s, D), jnp.float32),
             jax.ShapeDtypeStruct((s,), jnp.bool_),
             jax.ShapeDtypeStruct((D,), jnp.float32))
    kw = dict(dynamic_dims={"s": (1, 64)})
    vm = optimize(f, *specs, executor="vm", **kw)
    ref = optimize(f, *specs, executor="reference", **kw)
    rng = np.random.RandomState(0)
    n = 24
    x = jnp.asarray(rng.randn(n, D), jnp.float32)
    scale = jnp.asarray(rng.randn(D), jnp.float32)
    for occ in (1.0, 0.5):
        mask = jnp.asarray(rng.rand(n) < occ)
        for a, b in zip(jax.tree_util.tree_leaves(vm(x, mask, scale)),
                        jax.tree_util.tree_leaves(ref(x, mask, scale))):
            assert np.array_equal(np.asarray(a), np.asarray(b)), occ
        assert _stats(vm) == _stats(ref), occ


def test_rolled_scan_body_kernels_vm_eq_interpreter():
    """A kernel inside a rolled scan body auto-selects at the concrete
    per-step shape — identically under both executors: the VM's per-op
    stream bitwise, its fused fast stream (the loop one ``lax.scan``
    inside its run's executable) within f32 round-off."""
    def f(xs, scale):
        def body(c, x):
            h = rmsnorm(x, scale)
            return c + h, h
        out, ys = jax.lax.scan(body, jnp.zeros((8, D), jnp.float32), xs)
        return out, ys

    t = symbolic_dim("t")
    specs = (jax.ShapeDtypeStruct((t, 8, D), jnp.float32),
             jax.ShapeDtypeStruct((D,), jnp.float32))
    kw = dict(dynamic_dims={"t": (1, 16)})
    vm = optimize(f, *specs, executor="vm", **kw)
    ref = optimize(f, *specs, executor="reference", **kw)
    rng = np.random.RandomState(1)
    for steps in (1, 5):
        xs = jnp.asarray(rng.randn(steps, 8, D), jnp.float32)
        scale = jnp.asarray(rng.randn(D), jnp.float32)
        outs_ref = jax.tree_util.tree_leaves(ref(xs, scale))
        fused = jax.tree_util.tree_leaves(vm(xs, scale))
        stats_fused = _stats(vm)
        flat = [xs, scale]
        prog = vm.program
        env = solve_checked_env(prog.graph, prog.plan.shape_graph, flat)
        per_op, _ = vm.interp._run_dynamic(flat, prog.resolve(env), env)
        for a, o, b in zip(fused, per_op, outs_ref):
            b = np.asarray(b)
            assert np.array_equal(np.asarray(o), b), steps
            np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                                       atol=1e-5 * np.max(np.abs(b)))
        assert stats_fused == _stats(ref), steps


# -- measured fallback ---------------------------------------------------------

def _oracle(args):
    q, k, v, x, scale = args
    return (reference_attention(q, k, v, causal=True),
            reference_rmsnorm(x, scale))


def test_remeasure_swaps_monolithic_plan():
    fn = optimize(_fwd(None), *_specs(),
                  dynamic_dims={"b": (1, 2), "s": (1, 64)})
    args = _args(1, 32)
    fn(*args)
    forced = fn.remeasure_kernels(repeats=1)
    assert set(forced) == set(fn.plan.kernel_selections)
    assert all(s.measured for s in fn.plan.kernel_selections.values())
    kinds = {d.kind for d in fn.decisions.entries()}
    assert "kernel-measure" in kinds
    # the swapped plan still computes attention + rmsnorm
    out = fn(*args)
    for got, want in zip(out, _oracle(args)):
        assert np.allclose(np.asarray(got), np.asarray(want), atol=5e-2)
    assert "[measured" in fn.explain()


def test_remeasure_bucketed_recompiles_bucket_only():
    fn = _compile()
    env = {"b": SMALL_ENV[0], "s": SMALL_ENV[1]}
    args = _args(*SMALL_ENV)
    fn(*args)
    forced = fn.remeasure_kernels(repeats=1)
    bp_plan = _bucket_plan(fn, env)
    assert all(s.measured for s in bp_plan.kernel_selections.values())
    # the whole-range fallback plan keeps its model-based selections
    assert not any(s.measured for s in fn.plan.kernel_selections.values())
    # fallback safety survives measurement: every forced winner fits VMEM
    # at the bucket range's upper corner
    table = fn.specialization_table
    sg = fn.plan.shape_graph.specialized(
        table.space.ranges_of(table.key_of(env)))
    by_prim = {}
    for nid, name in forced.items():
        node = fn.plan.node_by_id[nid]
        hi = {k: h for k, (_lo, h) in node_bounds(node, sg).items()}
        variant = next(v for v in variants_for(node.prim_name)
                       if v.name == name)
        assert variant_valid(node.prim_name, variant, hi,
                             int(node.invals[0].dtype.itemsize))
        by_prim[node.prim_name] = name
    assert set(by_prim) == set(registered_kernels())
    out = fn(*args)
    for got, want in zip(out, _oracle(args)):
        assert np.allclose(np.asarray(got), np.asarray(want), atol=5e-2)


def test_kernel_remeasure_after_autotriggers_once():
    fn = _compile(kernel_remeasure_after=2)
    env = {"b": SMALL_ENV[0], "s": SMALL_ENV[1]}
    args = _args(*SMALL_ENV)
    fn(*args)
    assert not any(s.measured
                   for s in _bucket_plan(fn, env).kernel_selections.values())
    fn(*args)
    fn.drain_specializations()
    assert all(s.measured
               for s in _bucket_plan(fn, env).kernel_selections.values())
    n_measure = sum(1 for d in fn.decisions.entries()
                    if d.kind == "kernel-measure")
    # fires once per bucket, not per call
    fn(*args)
    fn.drain_specializations()
    assert sum(1 for d in fn.decisions.entries()
               if d.kind == "kernel-measure") == n_measure
