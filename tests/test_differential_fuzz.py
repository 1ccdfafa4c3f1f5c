"""Property-based pipeline fuzzer: VM ≡ interpreter and rolled ≡ unrolled.

Hypothesis (or the deterministic shim from ``conftest.py`` when the real
package is absent) generates small random graphs — an op-chain drawn from
a fixed vocabulary, random declared trip-count ranges, random loop bodies
with one or two carries, optional passthrough carries, kept or dropped
stacked outputs, optional input donation — and every example is pushed
through the *whole* pipeline four ways:

  * the rolled ``scan`` under the ProgramVM,
  * the rolled ``scan`` under the reference interpreter,
  * the mechanically unrolled DAG (Python loop at the concrete trip
    count) under the ProgramVM,

asserting the two rolled executors agree bitwise *and* on memory stats,
and that the rolled form equals the unrolled oracle bitwise.  A second
loop-free fuzzer covers plain DAG chains the same way.  Bitwise holds for
the VM's per-op stream (``_run_dynamic``); its fused fast stream (one
jitted executable per op-group run) agrees within f32 round-off.
Failures print the drawn spec, which is the whole reproducer.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import optimize, symbolic_dim
from repro.core.ir.trace import solve_checked_env
from repro.kernels import (masked_select, nonzero_pad, topk_dynamic,
                           unique_bounded)

R = 3          # fixed leading dim of the carry block


def _apply_op(oc, h, params, x):
    """One vocabulary op; every op preserves the (R, d) carry shape."""
    if oc == 0:
        return jnp.tanh(h)
    if oc == 1:
        return h * params["w"]
    if oc == 2:
        return h + x
    if oc == 3:
        return h @ params["wm"]
    return h - 0.25 * h * h


def _build_fns(opcodes, two_carry, passthrough, return_ys, T):
    """(rolled_fn, unrolled_fn) tracing the identical op sequence."""

    def body(params, c, x):
        c1, c2 = c
        h = c1
        for oc in opcodes:
            h = _apply_op(oc, h, params, x)
        if two_carry:
            n2 = c2 if passthrough else c2 * 0.9 + h * 0.1
        else:
            n2 = c2
        return (h, n2), h * 2.0

    def rolled(params, c1, c2, xs):
        def f(c, x):
            return body(params, c, x)
        (h, n2), ys = jax.lax.scan(f, (c1, c2), xs)
        outs = (h, n2) if two_carry else (h,)
        return outs + ((ys,) if return_ys else ())

    def unrolled(params, c1, c2, xs):
        c = (c1, c2)
        ys = []
        for i in range(T):
            c, y = body(params, c, xs[i])
            ys.append(y)
        outs = (c[0], c[1]) if two_carry else (c[0],)
        return outs + ((jnp.stack(ys),) if return_ys else ())

    return rolled, unrolled


def _specs(d, t):
    p = {"w": jax.ShapeDtypeStruct((d,), jnp.float32),
         "wm": jax.ShapeDtypeStruct((d, d), jnp.float32)}
    c = jax.ShapeDtypeStruct((R, d), jnp.float32)
    xs = jax.ShapeDtypeStruct((t, R, d), jnp.float32)
    return p, c, c, xs


def _concrete(d, T, seed):
    rng = np.random.RandomState(seed)
    arr = lambda *s: jnp.asarray(rng.randn(*s) * 0.1, jnp.float32)
    params = {"w": arr(d), "wm": arr(d, d)}
    return params, arr(R, d), arr(R, d), arr(T, R, d)


def _assert_bitwise(a, b, spec):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            f"bitwise divergence for {spec}"


def _assert_close(a, b, spec, rel=1e-5):
    """Within ``rel`` of each output's largest magnitude (the fused fast
    stream against a per-op stream); integer outputs stay exact."""
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype, spec
        if not jnp.issubdtype(y.dtype, jnp.inexact):
            assert np.array_equal(x, y), f"divergence for {spec}"
            continue
        x, y = x.astype(np.float64), y.astype(np.float64)
        assert np.max(np.abs(x - y), initial=0.0) <= rel * np.max(
            np.abs(y), initial=0.0), f"divergence past round-off for {spec}"


def _per_op(fn, *args):
    """``fn``'s VM run per op (the dynamic stream) at the args' env."""
    vm = fn.interp
    prog = vm.program
    flat = jax.tree_util.tree_leaves((args, {}))
    env = solve_checked_env(prog.graph, prog.plan.shape_graph, flat)
    outs, _ = vm._run_dynamic(flat, prog.resolve(env), env)
    return outs


def _stats(fn):
    d = fn.last_report.stats.as_dict()
    d.pop("last_dispatch_ns", None)
    d.pop("dispatch_ns_total", None)
    return d


@settings(max_examples=12, deadline=None)
@given(opcodes=st.lists(st.integers(0, 4), min_size=1, max_size=4),
       d=st.integers(2, 5),
       hi=st.sampled_from([4, 16, 64]),
       T=st.integers(1, 5),
       two_carry=st.booleans(),
       passthrough=st.booleans(),
       return_ys=st.booleans(),
       donate=st.booleans())
def test_rolled_loop_pipeline_fuzz(opcodes, d, hi, T, two_carry,
                                   passthrough, return_ys, donate):
    T = min(T, hi)
    spec = dict(opcodes=opcodes, d=d, hi=hi, T=T, two_carry=two_carry,
                passthrough=passthrough, return_ys=return_ys, donate=donate)
    rolled, unrolled = _build_fns(opcodes, two_carry, passthrough,
                                  return_ys, T)
    t = symbolic_dim("t")
    kw = dict(donate_inputs=True) if donate else {}
    vm = optimize(rolled, *_specs(d, t), dynamic_dims={"t": (1, hi)},
                  executor="vm", **kw)
    ref = optimize(rolled, *_specs(d, t), dynamic_dims={"t": (1, hi)},
                   executor="reference", **kw)
    oracle = optimize(unrolled, *_specs(d, T), **kw)

    args = _concrete(d, T, seed=sum(opcodes) + d + T)
    v_out = vm(*args)
    v_stats = _stats(vm)
    r_out = ref(*args)
    r_stats = _stats(ref)
    o_out = oracle(*args)

    v_op = _per_op(vm, *args)
    _assert_bitwise(v_op, r_out, spec)
    _assert_close(v_out, r_out, spec)
    assert v_stats == r_stats, f"stats diverge for {spec}: " + str({
        k: (v_stats[k], r_stats[k]) for k in v_stats
        if v_stats[k] != r_stats[k]})
    _assert_bitwise(v_op, _per_op(oracle, *args), spec)
    _assert_close(v_out, o_out, spec)
    # the rolled program must contain the loop as a single instruction
    assert vm.program.counts()["Loop"] == 1, spec


@settings(max_examples=20, deadline=None)
@given(opcodes=st.lists(st.integers(0, 4), min_size=1, max_size=6),
       d=st.integers(2, 6),
       hi=st.sampled_from([8, 64, 512]),
       donate=st.booleans())
def test_plain_dag_vm_vs_interpreter_fuzz(opcodes, d, hi, donate):
    spec = dict(opcodes=opcodes, d=d, hi=hi, donate=donate)

    def f(params, a, x):
        h = jnp.tanh(a)
        for oc in opcodes:
            h = _apply_op(oc, h, params, x)
        return h, jnp.sum(h, axis=-1)

    s = symbolic_dim("s")
    p = {"w": jax.ShapeDtypeStruct((d,), jnp.float32),
         "wm": jax.ShapeDtypeStruct((d, d), jnp.float32)}
    a = jax.ShapeDtypeStruct((s, d), jnp.float32)
    kw = dict(donate_inputs=True) if donate else {}
    vm = optimize(f, p, a, a, dynamic_dims={"s": (1, hi)},
                  executor="vm", **kw)
    ref = optimize(f, p, a, a, dynamic_dims={"s": (1, hi)},
                   executor="reference", **kw)

    n = min(hi, 1 + sum(opcodes))
    rng = np.random.RandomState(d + n)
    arr = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.1, jnp.float32)
    args = ({"w": arr(d), "wm": arr(d, d)}, arr(n, d), arr(n, d))
    v_out = vm(*args)
    v_stats = _stats(vm)
    r_out = ref(*args)
    r_stats = _stats(ref)
    _assert_bitwise(_per_op(vm, *args), r_out, spec)
    _assert_close(v_out, r_out, spec)
    assert v_stats == r_stats, f"stats diverge for {spec}"


# -- value-dependent bounded dims ----------------------------------------------
#
# Random DAGs mixing the SoD² op classes: *introduce* ops mint a fresh
# bounded dim whose extent only the input values decide (masked_select /
# nonzero_pad / topk_dynamic / unique_bounded), *propagate* ops (the
# elementwise vocabulary) carry it along.  Occupancy is driven through a
# value threshold so the 0%-fill and 100%-fill edges are exact.  Contract
# per drawn program, at every probed env:
#
#   * ProgramVM per op ≡ PlanInterpreter bitwise on outputs (the fused
#     fast stream, where no bounded dim forces the per-op one: round-off),
#   * memory stats identical dict-for-dict (measured_dims included),
#   * the runtime arena (tight, measured sizes) never exceeds the plan's
#     ``arena_bound_bytes`` reserve computed from the caps.

# threshold on h > t realizes the drawn occupancy exactly at the edges
_OCC_THRESHOLD = {0.0: 1e9, 0.5: 0.0, 1.0: -1e9}


def _build_bounded_fn(opcodes, occ):
    t = _OCC_THRESHOLD[occ]

    def f(x, k):
        h = x
        total = k * 0
        for oc in opcodes:
            if oc == 0:
                h = jnp.tanh(h)
            elif oc == 1:
                h = h * 2.0 + 0.25
            elif oc == 2:
                h = h - 0.5 * h * h
            elif oc == 3:
                h, c = masked_select(h, h > t)
                total = total + c
            elif oc == 4:
                idx, c = nonzero_pad(h)
                h = idx.astype(jnp.float32)
                total = total + c
            elif oc == 5:
                h, c = topk_dynamic(h, k)
                total = total + c
            else:
                h, c = unique_bounded(h)
                total = total + c
        return jnp.sum(h), total

    return f


@settings(max_examples=10, deadline=None)
@given(opcodes=st.lists(st.integers(0, 6), min_size=1, max_size=5),
       occ=st.sampled_from([0.0, 0.5, 1.0]),
       n=st.sampled_from([4, 13, 32]),
       hi=st.sampled_from([32, 64]))
def test_value_dependent_bounded_fuzz(opcodes, occ, n, hi):
    n = min(n, hi)
    spec = dict(opcodes=opcodes, occ=occ, n=n, hi=hi)
    f = _build_bounded_fn(opcodes, occ)
    s = symbolic_dim("s")
    specs = (jax.ShapeDtypeStruct((s,), jnp.float32),
             jax.ShapeDtypeStruct((), jnp.int32))
    vm = optimize(f, *specs, dynamic_dims={"s": (1, hi)}, executor="vm")
    ref = optimize(f, *specs, dynamic_dims={"s": (1, hi)},
                   executor="reference")

    n_intro = sum(1 for oc in opcodes if oc >= 3)
    assert len(vm.plan.graph.bound_dims) == n_intro, spec
    assert vm.program.counts()["BindDim"] == n_intro, spec

    for env_n in (n, max(1, n // 2)):
        rng = np.random.RandomState(env_n + sum(opcodes))
        x = jnp.asarray(rng.randn(env_n), jnp.float32)
        k = jnp.int32(max(1, env_n // 3))
        v_out = vm(x, k)
        v_stats = _stats(vm)
        r_out = ref(x, k)
        r_stats = _stats(ref)
        v_op = _per_op(vm, x, k)
        _assert_bitwise(v_op, r_out, spec)
        _assert_close(v_out, r_out, spec)
        assert v_stats == r_stats, f"stats diverge for {spec}: " + str({
            kk: (v_stats[kk], r_stats[kk]) for kk in v_stats
            if v_stats[kk] != r_stats.get(kk)})
        assert len(v_stats["measured_dims"]) == n_intro, spec
        # tight runtime accounting must stay under the cap-sized reserve
        bound = vm.report.arena_bound_bytes
        if bound is not None:
            assert v_stats["arena_bytes"] <= bound, (spec, env_n)
        # oracle: the eager impls compute the exact same padded values
        _assert_bitwise(v_op, f(x, k), spec)
        _assert_close(v_out, f(x, k), spec)
