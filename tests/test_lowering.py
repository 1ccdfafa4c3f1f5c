"""Lowering & ProgramVM: instruction emission, differential execution,
per-env resolve, and the shared-cache keying regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import tree_util

from repro.core import lower_plan, optimize, symbolic_dims
from repro.core.executor.interpreter import PlanInterpreter
from repro.core.executor.memory import MemoryLimitExceeded
from repro.core.executor.vm import ProgramVM
from repro.core.ir import trace_to_graph
from repro.core.ir.trace import solve_checked_env
from repro.core.lowering.program import (OP_COMPUTE, OP_MAYBE_EVICT,
                                         OP_REGEN)
from repro.core.remat.planner import build_plan
from repro.core.scheduling.scheduler import ScheduleResult
from repro.core.symbolic import ShapeGraph

B, S = symbolic_dims("b, s")
V, D, F = 300, 32, 64


def loss_fn(params, tokens, labels):
    emb = params["emb"][tokens]
    h = jax.nn.gelu(emb @ params["w1"])
    h2 = h @ params["w2"]
    logits = h2 @ params["emb"].T
    logp = jax.nn.log_softmax(logits)
    oh = jax.nn.one_hot(labels, logits.shape[-1])
    return -(oh * logp).sum() / (1.0 * tokens.shape[0] * tokens.shape[1])


def train_step(params, tokens, labels):
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, labels)
    return loss, jax.tree.map(lambda p, g: p - 1e-3 * g, params, grads)


def specs():
    p = {"emb": jax.ShapeDtypeStruct((V, D), jnp.float32),
         "w1": jax.ShapeDtypeStruct((D, F), jnp.float32),
         "w2": jax.ShapeDtypeStruct((F, D), jnp.float32)}
    t = jax.ShapeDtypeStruct((B, S), jnp.int32)
    return p, t, t


def concrete_params(seed=0):
    rng = np.random.RandomState(seed)
    return {"emb": jnp.asarray(rng.randn(V, D), jnp.float32),
            "w1": jnp.asarray(rng.randn(D, F) * 0.05, jnp.float32),
            "w2": jnp.asarray(rng.randn(F, D) * 0.05, jnp.float32)}


def _assert_trees_equal(a, b):
    la = tree_util.tree_leaves(a)
    lb = tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert np.array_equal(np.asarray(x), np.asarray(y)), \
            "executors disagree bitwise"


def _assert_trees_close(a, b, rel=1e-5):
    """Equal within ``rel`` of each output's largest magnitude: the fused
    fast stream against a per-op reference (XLA fuses the ops, so the f32
    round-off differs); integer and boolean outputs stay exact."""
    la = tree_util.tree_leaves(a)
    lb = tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.shape == y.shape and x.dtype == y.dtype
        if not jnp.issubdtype(y.dtype, jnp.inexact):
            assert np.array_equal(x, y), "executors disagree"
            continue
        x, y = x.astype(np.float64), y.astype(np.float64)
        scale = np.max(np.abs(y), initial=0.0)
        assert np.max(np.abs(x - y), initial=0.0) <= rel * scale, \
            "executors disagree beyond round-off"


def _run_per_op(vm, flat):
    """``vm``'s per-op stream at ``flat``'s env: the dynamic stream, which
    a memory cap takes; with no cap it runs the fast stream's ops one bind
    at a time.  Returns (outputs, stats)."""
    prog = vm.program
    env = solve_checked_env(prog.graph, prog.plan.shape_graph, flat)
    return vm._run_dynamic(flat, prog.resolve(env), env)


# -- the differential harness: every bench arch, both executors ---------------

BENCH_ARCHS = ["llama2_1b", "gemma_2b", "granite_8b", "musicgen_medium"]
PROBE_ENVS = [{"b": 1, "s": 16}, {"b": 2, "s": 40}, {"b": 3, "s": 96}]


@pytest.mark.parametrize("arch", BENCH_ARCHS)
def test_differential_vm_vs_reference_on_bench_arch(arch):
    """The VM's per-op stream and the reference interpreter agree bitwise on
    every bench arch at >=3 probe envs, the fused fast stream within
    round-off, and the VM's peak bytes never exceed the reference's."""
    from benchmarks.memplan_bench import _step_and_specs, concretize_spec

    r = _step_and_specs(arch)
    assert r is not None, f"{arch} missing from the bench arch set"
    step, args = r
    fn = optimize(step, *args,
                  dynamic_dims={"b": (1, 8), "s": (8, 128)})
    assert fn.program is not None
    ref = PlanInterpreter(fn.plan)          # same plan, reference executor
    flat_specs, _ = tree_util.tree_flatten((args, {}))
    rng = np.random.RandomState(0)
    for env in PROBE_ENVS:
        flat = [concretize_spec(s, env, rng) for s in flat_specs]
        outs_vm, rep_vm = fn.interp.run(flat)
        outs_op, stats_op = _run_per_op(fn.interp, flat)
        outs_ref, rep_ref = ref.run(flat)
        _assert_trees_equal(outs_op, outs_ref)
        _assert_trees_close(outs_vm, outs_ref)
        assert stats_op.device_peak == rep_ref.stats.device_peak
        assert rep_vm.env == env and rep_ref.env == env
        assert rep_vm.stats.device_peak <= rep_ref.stats.device_peak
        # the fast path precomputes the whole stats template — it must
        # match the reference's per-op accounting exactly
        assert rep_vm.stats.device_peak == rep_ref.stats.device_peak
        assert rep_vm.stats.arena_bytes == rep_ref.stats.arena_bytes
        assert rep_vm.stats.reuse_ratio == rep_ref.stats.reuse_ratio


class TestInstructionEmission:
    def test_no_evict_path_without_limit(self):
        fn = optimize(train_step, *specs())
        counts = fn.program.counts()
        assert counts["MaybeEvict"] == 0 and counts["Regen"] == 0
        assert counts["Compute"] == len(fn.plan.order)
        assert counts["Return"] == 1
        assert not fn.program.has_evict_path

    def test_no_evict_path_when_bound_fits_limit(self):
        """Guaranteed peak <= limit proves eviction impossible: the
        compile-time analysis strips the whole runtime remat machinery."""
        probe = optimize(train_step, *specs(),
                         dynamic_dims={"b": (1, 4), "s": (8, 64)})
        bound = probe.guaranteed_peak_bytes
        assert bound is not None
        fn = optimize(train_step, *specs(),
                      dynamic_dims={"b": (1, 4), "s": (8, 64)},
                      memory_limit=bound)
        counts = fn.program.counts()
        assert counts["MaybeEvict"] == 0 and counts["Regen"] == 0

    def test_evict_path_under_pressure(self):
        fn = optimize(train_step, *specs(), memory_limit=1 << 20)
        counts = fn.program.counts()
        assert counts["MaybeEvict"] == len(fn.plan.order)
        assert counts["Regen"] > 0
        assert fn.program.regen, "recompute sub-programs must be exported"
        for sub in fn.program.regen.values():
            assert sub.n_temps >= 1 and sub.steps
            # the target is produced by the sub-program, not a source
            assert sub.target_reg not in sub.source_regs

    def test_registers_dense_and_frees_static(self):
        fn = optimize(train_step, *specs())
        prog = fn.program
        assert sorted(prog.reg_of.values()) == list(range(prog.n_regs))
        assert len(prog.vid_of) == prog.n_regs
        # every FreeSlot frees a distinct register, none of them outputs
        freed = [i.reg for i in prog.instructions if type(i).__name__ == "FreeSlot"]
        assert len(freed) == len(set(freed))
        assert not set(freed) & set(prog.out_regs)

    def test_donate_instructions_only_when_donating(self):
        plain = optimize(train_step, *specs())
        donating = optimize(train_step, *specs(), donate_inputs=True)
        assert plain.program.counts()["Donate"] == 0
        assert donating.program.counts()["Donate"] > 0

    def test_fast_stream_strips_guards(self):
        fn = optimize(train_step, *specs(), memory_limit=1 << 20)
        ops = {inst.op for inst in fn.program.fast_instructions}
        assert OP_MAYBE_EVICT not in ops and OP_REGEN not in ops
        assert OP_COMPUTE in ops


class TestVMExecution:
    def test_memory_limit_identical_numerics_and_evictions(self):
        vm = optimize(train_step, *specs())
        ref = optimize(train_step, *specs(), executor="reference")
        params = concrete_params()
        rng = np.random.RandomState(2)
        t = jnp.asarray(rng.randint(0, V, (6, 50)), jnp.int32)
        vm(params, t, t)
        free_peak = vm.last_report.stats.device_peak
        for frac in (0.8, 0.6):
            limit = int(free_peak * frac)
            lv, pv = vm.with_memory_limit(limit)(params, t, t)
            lr, pr = ref.with_memory_limit(limit)(params, t, t)
            _assert_trees_equal((lv, pv), (lr, pr))

    def test_vm_limit_respected_with_evictions(self):
        vm = optimize(train_step, *specs())
        params = concrete_params()
        rng = np.random.RandomState(3)
        t = jnp.asarray(rng.randint(0, V, (6, 50)), jnp.int32)
        vm(params, t, t)
        free_peak = vm.last_report.stats.device_peak
        limited = vm.with_memory_limit(int(free_peak * 0.6))
        limited(params, t, t)
        st = limited.last_report.stats
        assert st.device_peak <= int(free_peak * 0.6)
        assert st.evictions > 0

    def test_impossible_limit_raises(self):
        vm = optimize(train_step, *specs(), memory_limit=1000)
        params = concrete_params()
        t = jnp.zeros((2, 8), jnp.int32)
        with pytest.raises(MemoryLimitExceeded):
            vm(params, t, t)

    def test_offload_fallback(self):
        vm = optimize(train_step, *specs(), max_subgraph=1)
        params = concrete_params()
        rng = np.random.RandomState(4)
        t = jnp.asarray(rng.randint(0, V, (6, 50)), jnp.int32)
        vm(params, t, t)
        peak = vm.last_report.stats.device_peak
        limited = vm.with_memory_limit(int(peak * 0.6))
        limited(params, t, t)
        st = limited.last_report.stats
        assert st.offloads > 0 and st.reloads > 0

    def test_donated_run_matches_reference(self):
        vm = optimize(train_step, *specs(), donate_inputs=True)
        ref = optimize(train_step, *specs(), donate_inputs=True,
                       executor="reference")
        params = concrete_params()
        t = jnp.asarray(np.random.RandomState(5).randint(0, V, (3, 20)),
                        jnp.int32)
        ov = vm(params, t, t)
        orf = ref(params, t, t)
        flat = tree_util.tree_leaves(((params, t, t), {}))
        op, _ = _run_per_op(vm.interp, flat)
        _assert_trees_equal(op, tree_util.tree_leaves(orf))
        _assert_trees_close(ov, orf)
        assert vm.last_report.stats.device_peak \
            == ref.last_report.stats.device_peak
        assert vm.last_report.stats.donated_reuses \
            == ref.last_report.stats.donated_reuses

    def test_bad_executor_name_rejected(self):
        with pytest.raises(ValueError, match="executor"):
            optimize(train_step, *specs(), executor="jit")


class TestResolve:
    def test_resolved_program_cached_per_env(self):
        fn = optimize(train_step, *specs())
        prog = fn.program
        r1 = prog.resolve({"b": 2, "s": 16})
        r2 = prog.resolve({"b": 2, "s": 16})
        assert r1 is r2
        r3 = prog.resolve({"b": 2, "s": 17})
        assert r3 is not r1

    def test_resolve_produces_offsets_and_stats(self):
        fn = optimize(train_step, *specs())
        r = fn.program.resolve({"b": 2, "s": 16})
        assert r.fast_ok and r.stats_template is not None
        assert r.peak_bytes == r.stats_template.device_peak > 0
        assert r.value_offsets, "arena-served values must get offsets"
        assert all(off >= 0 for off in r.value_offsets.values())
        assert max(off + 1 for off in r.value_offsets.values()) \
            <= r.arena.packed_height
        # calling through the VM at this env reports the template's stats
        params = concrete_params()
        t = jnp.zeros((2, 16), jnp.int32)
        fn(params, t, t)
        assert fn.last_report.stats.device_peak == r.peak_bytes

    def test_program_surfaces_on_buckets(self):
        fn = optimize(train_step, *specs(),
                      dynamic_dims={"b": (1, 8), "s": (8, 64)},
                      buckets={"s": [16]})
        params = concrete_params()
        t = jnp.zeros((2, 12), jnp.int32)
        fn(params, t, t)
        bp = fn.specialization_table.peek(fn.last_bucket)
        assert bp.program is not None
        assert bp.n_instructions == bp.program.n_instructions > 0


class TestSharedCacheKeying:
    """Regression: a size/params cache shared across executors of two
    *different* graphs must never alias same-id nodes (ids restart at 0
    per graph).  Before the graph-uid namespacing, the second run below
    picked up the first graph's refined broadcast shape for node 0."""

    @staticmethod
    def _plan_for(fn, spec):
        g, _ = trace_to_graph(fn, spec)
        return build_plan(g, ScheduleResult(list(g.nodes), 0, 0),
                          ShapeGraph(), enable_remat=False)

    def test_interpreters_with_shared_caches_do_not_alias(self):
        n, = symbolic_dims("n")
        spec = jax.ShapeDtypeStruct((n,), jnp.float32)
        plan4 = self._plan_for(lambda x: jnp.broadcast_to(x, (4, x.shape[0])),
                               spec)
        plan8 = self._plan_for(lambda x: jnp.broadcast_to(x, (8, x.shape[0])),
                               spec)
        size_cache, params_cache = {}, {}
        i4 = PlanInterpreter(plan4, size_cache=size_cache,
                             params_cache=params_cache)
        i8 = PlanInterpreter(plan8, size_cache=size_cache,
                             params_cache=params_cache)
        x = jnp.arange(5, dtype=jnp.float32)
        (o4,), _ = i4.run([x])
        (o8,), _ = i8.run([x])     # same env {'n': 5}, different graph
        assert o4.shape == (4, 5)
        assert o8.shape == (8, 5)

    def test_vms_with_shared_caches_do_not_alias(self):
        n, = symbolic_dims("n")
        spec = jax.ShapeDtypeStruct((n,), jnp.float32)
        plan4 = self._plan_for(lambda x: jnp.broadcast_to(x, (4, x.shape[0])),
                               spec)
        plan8 = self._plan_for(lambda x: jnp.broadcast_to(x, (8, x.shape[0])),
                               spec)
        size_cache, params_cache = {}, {}
        v4 = ProgramVM(lower_plan(plan4), size_cache=size_cache,
                       params_cache=params_cache)
        v8 = ProgramVM(lower_plan(plan8), size_cache=size_cache,
                       params_cache=params_cache)
        x = jnp.arange(5, dtype=jnp.float32)
        (o4,), _ = v4.run([x])
        (o8,), _ = v8.run([x])
        assert o4.shape == (4, 5)
        assert o8.shape == (8, 5)
