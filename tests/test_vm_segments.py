"""The fast stream as one jitted executable per op-group run.

``Program.segments`` cuts each run of ``Program.groups`` into a function
of its registers; the VM compiles it once per resolved env and calls it.
These tests pin what that changes and what it must not: one executable
per run, no compile on a repeated env (also past ``Program.resolve``'s
cache), donation of the VM's own dead registers and never of a caller's,
the engagement counters a profiled call records, a rolled loop as one
scan inside its run, and the per-op paths (dynamic, faulted) left as
they were.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import optimize, symbolic_dim
from repro.core.executor.vm import ProgramVM
from repro.core.lowering.program import OP_BIND_ARG, OP_COMPUTE
from repro.core.obs.telemetry import PROFILE_SINK
from repro.core.resilience import (FaultPlan, FaultSpec, ResilienceConfig,
                                   RetryPolicy)
from test_lowering import (V, _run_per_op, concrete_params, specs,
                          train_step)

@pytest.fixture()
def compiles():
    """``compiles(fn, *args)``: XLA programs compiled (or loaded) while
    ``fn(*args)`` ran."""
    n = [0]

    def count(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    def run(fn, *args):
        before = n[0]
        jax.block_until_ready(fn(*args))
        return n[0] - before

    jax.monitoring.register_event_duration_secs_listener(count)
    yield run
    jax.monitoring.unregister_event_duration_listener(count)


def scoped(x, w):
    """Three op groups: ``a`` feeds ``b``, whose output has ``a``'s shape."""
    with jax.named_scope("a"):
        h = jnp.tanh(x @ w)
    with jax.named_scope("b"):
        y = h * 2.0 + 1.0
    with jax.named_scope("c"):
        s = jnp.sum(y * y)
    return y, s


def _scoped_fn(n_max=64, **kw):
    n = symbolic_dim("n")
    return optimize(scoped, jax.ShapeDtypeStruct((n, 8), jnp.float32),
                    jax.ShapeDtypeStruct((8, 8), jnp.float32),
                    dynamic_dims={"n": (1, n_max)}, **kw)


def _args(n, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, 8), jnp.float32),
            jnp.asarray(rng.randn(8, 8) * 0.3, jnp.float32))


def _per_op(fn, *args):
    """``fn``'s VM run per op (the dynamic stream) at the args' env."""
    return _run_per_op(fn.interp, jax.tree_util.tree_leaves((args, {})))[0]


def _profiled(fn, tmp_path, *args):
    with jax.profiler.trace(str(tmp_path)):
        out = fn(*args)
        jax.block_until_ready(out)
    return out, PROFILE_SINK.ring.records()[-1]


def _binds(prog):
    return sum(i.op == OP_COMPUTE for i in prog.fast_instructions)


class TestOneExecutablePerRun:
    def test_each_group_is_one_segment(self):
        fn = _scoped_fn()
        prog = fn.program
        assert [g.label for g in prog.groups] == ["a", "b", "c"]
        assert len(prog.segments) == len(prog.groups)
        # every Compute lies in some run's replayed range
        replayed = sum(
            i.op == OP_COMPUTE for g, s in zip(prog.groups, prog.segments)
            for i in prog.fast_instructions[s.lo:g.hi])
        assert all(s.lo < g.hi for g, s in zip(prog.groups, prog.segments))
        assert replayed == _binds(prog) > 0
        # only BindArgs run on the host ahead of a run's executable
        assert all(i.op == OP_BIND_ARG
                   for g, s in zip(prog.groups, prog.segments)
                   for i in prog.fast_instructions[g.lo:s.lo])

    def test_repeated_env_compiles_nothing(self, compiles):
        fn = _scoped_fn()
        n_groups = len(fn.program.groups)
        args = _args(5)
        assert 0 < compiles(fn, *args) <= n_groups
        assert all(callable(f) for f in fn.interp._segment_fns)
        assert compiles(fn, *args) == 0
        assert compiles(fn, *_args(5, seed=1)) == 0
        assert compiles(fn, *_args(9)) <= n_groups

    def test_executables_outlive_the_resolve_cache(self, compiles):
        """Past 64 envs ``Program.resolve`` drops its cache; the op-group
        executables stay in JAX's, so a revisited env compiles nothing."""
        fn = _scoped_fn(n_max=128)
        prog = fn.program
        assert compiles(fn, *_args(1)) > 0
        for n in range(2, 70):
            fn(*_args(n))
        assert len(prog._resolve_cache) < 69      # it was cleared
        assert ("n", 1) not in {k[1] for k in prog._resolve_cache
                                if len(k) > 1}
        assert compiles(fn, *_args(1, seed=3)) == 0

    def test_outputs_match_the_per_op_stream(self):
        fn = _scoped_fn()
        for n in (1, 7, 64):
            args = _args(n, seed=n)
            fused = fn(*args)
            per_op = _per_op(fn, *args)
            for a, b in zip(jax.tree_util.tree_leaves(fused), per_op):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=0, atol=1e-5 * float(
                                               np.max(np.abs(b))))


class TestDonation:
    def test_dead_register_is_donated_to_its_run(self, monkeypatch):
        fn = _scoped_fn()
        prog = fn.program
        seg_b = prog.segments[1]
        assert prog.groups[1].label == "b" and seg_b.donate
        h_reg = seg_b.in_regs[seg_b.donate[0]]
        held = {}
        run_segment = ProgramVM._run_segment

        def spy(self, storage, flat_args, resolved, gi):
            if gi == 1:
                held["h"] = storage[h_reg]
            return run_segment(self, storage, flat_args, resolved, gi)

        monkeypatch.setattr(ProgramVM, "_run_segment", spy)
        y, _ = fn(*_args(6))
        assert held["h"].is_deleted()
        assert np.isfinite(np.asarray(y)).all()

    def test_caller_arguments_are_never_donated(self):
        fn = _scoped_fn(donate_inputs=True)
        assert fn.program.counts()["Donate"] > 0
        x, w = _args(6)
        caller = {i.reg for i in fn.program.fast_instructions
                  if i.op == OP_BIND_ARG}
        for seg in fn.program.segments:
            assert not {seg.in_regs[k] for k in seg.donate} & caller
        y, _ = fn(x, w)
        assert not x.is_deleted() and not w.is_deleted()
        np.testing.assert_array_equal(np.asarray(x), np.asarray(_args(6)[0]))
        y2, _ = fn(x, w)          # the same arguments again
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y2))


class TestCounters:
    def test_every_bind_is_fused_without_a_limit(self, tmp_path):
        fn = _scoped_fn()
        args = _args(4)
        fn(*args)
        _, rec = _profiled(fn, tmp_path, *args)
        assert rec.binds == _binds(fn.program) > 0
        assert rec.fused_binds == rec.binds
        assert rec.segments == len(fn.program.groups)

    def test_dynamic_stream_fuses_nothing(self, tmp_path):
        fn = optimize(train_step, *specs())
        rng = np.random.RandomState(2)
        t = jnp.asarray(rng.randint(0, V, (6, 50)), jnp.int32)
        args = (concrete_params(), t, t)
        fn(*args)
        limited = fn.with_memory_limit(
            int(fn.last_report.stats.device_peak * 0.8))
        out, rec = _profiled(limited, tmp_path, *args)
        assert not limited.program.resolve({"b": 6, "s": 50}).fast_ok
        assert limited.last_report.stats.evictions > 0
        assert rec.fused_binds == 0 and rec.segments == 0
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        _per_op(limited, *args)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_faulted_stream_fuses_nothing(self, tmp_path, monkeypatch):
        fn = _scoped_fn(resilience=ResilienceConfig(
            retry=RetryPolicy(max_retries=2, backoff_base_s=0.0)))
        args = _args(4)
        fn(*args)
        faulted = []
        run_faulted = ProgramVM._run_fast_faulted

        def spy(self, *a):
            faulted.append(1)
            return run_faulted(self, *a)

        monkeypatch.setattr(ProgramVM, "_run_fast_faulted", spy)
        # armed for the call, past its last bind: the call runs the
        # faulted stream from end to end and nothing fires
        fp = FaultPlan([FaultSpec("kernel", call=1, step=10**6)])
        with fn.inject_faults(fp) as res:
            out, rec = _profiled(fn, tmp_path, *args)
        assert fp.fired == [] and res.counters()["degraded_calls"] == 0
        assert faulted == [1]
        assert rec.fused_binds == 0 and rec.segments == 0
        for a, b in zip(jax.tree_util.tree_leaves(out), _per_op(fn, *args)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestRolledLoop:
    @pytest.fixture(scope="class")
    def loop_fn(self):
        t = symbolic_dim("t")

        def f(h0, xs):
            return jax.lax.scan(lambda c, x: (jnp.tanh(c * x + 0.5), None),
                                h0, xs)[0]

        return optimize(f, jax.ShapeDtypeStruct((4,), jnp.float32),
                        jax.ShapeDtypeStruct((t, 4), jnp.float32),
                        dynamic_dims={"t": (1, 32)})

    def test_loop_run_is_one_scan(self, loop_fn, monkeypatch, compiles):
        prog = loop_fn.program
        assert prog.counts()["Loop"] == 1
        loops = [gi for gi, g in enumerate(prog.groups) if g.loop]
        assert len(loops) == 1
        g, seg = prog.groups[loops[0]], prog.segments[loops[0]]
        assert seg.lo < g.hi
        traced = []
        scan_loop = ProgramVM._scan_loop

        def spy(self, *a):
            traced.append(1)
            return scan_loop(self, *a)

        monkeypatch.setattr(ProgramVM, "_scan_loop", spy)
        monkeypatch.setattr(ProgramVM, "_exec_loop", None)  # never per op
        rng = np.random.RandomState(3)
        h0 = jnp.asarray(rng.randn(4), jnp.float32)
        xs = jnp.asarray(rng.randn(7, 4), jnp.float32)
        assert 0 < compiles(loop_fn, h0, xs) <= len(prog.groups)
        assert traced == [1]          # the body traced once, not per trip
        out = loop_fn(h0, xs)
        assert traced == [1] and compiles(loop_fn, h0, xs) == 0
        monkeypatch.undo()
        per_op = np.asarray(_per_op(loop_fn, h0, xs)[0])
        np.testing.assert_allclose(np.asarray(out), per_op, rtol=0,
                                   atol=1e-5 * float(np.max(np.abs(per_op))))

    def test_loop_with_stacked_outputs_matches_per_op(self):
        """A decode-style scan: weights closed over, a carry, a stacked
        ``ys``; fused within round-off of the per-op stream at two trip
        counts."""
        t = symbolic_dim("t")

        def f(w, h0, xs):
            def step(c, x):
                h = jnp.tanh(c @ w + x)
                return h, jnp.sum(h, axis=-1)
            return jax.lax.scan(step, jnp.tanh(h0), xs)

        fn = optimize(f, jax.ShapeDtypeStruct((8, 8), jnp.float32),
                      jax.ShapeDtypeStruct((2, 8), jnp.float32),
                      jax.ShapeDtypeStruct((t, 2, 8), jnp.float32),
                      dynamic_dims={"t": (1, 64)})
        rng = np.random.RandomState(4)
        w = jnp.asarray(rng.randn(8, 8) * 0.3, jnp.float32)
        h0 = jnp.asarray(rng.randn(2, 8), jnp.float32)
        for trips in (1, 17):
            xs = jnp.asarray(rng.randn(trips, 2, 8), jnp.float32)
            fused = jax.tree_util.tree_leaves(fn(w, h0, xs))
            per_op = _per_op(fn, w, h0, xs)
            assert fused[1].shape == (trips, 2)
            for a, b in zip(fused, per_op):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=0,
                    atol=1e-5 * float(np.max(np.abs(np.asarray(b)))))
