"""Fault-tolerant serving runtime: chaos, ladder, quarantine, hardening.

Contract families over :mod:`repro.core.resilience` and its wiring:

  * **fault plans** — seeded schedules are reproducible, per-attempt
    arming spends the firing budget exactly once, and every firing lands
    in the ``fired`` audit record;
  * **circuit breaker** — the closed / open / half-open state machine on
    an injectable clock: threshold trips, backoff gating, the single
    half-open probe, exponential re-open growth, full reset on success;
  * **degradation ladder** — transient faults retry in place, memory
    pressure and quarantined compiles retry on the whole-range fallback
    (bitwise-identical outputs), retries are bounded with exponential
    backoff, exhaustion raises a structured ``RequestFailed``, malformed
    requests never retry;
  * **chaos** — randomized fault schedules across the bench archs: no
    uncaught exception escapes, surviving requests match the fault-free
    run (within f32 round-off: a fault can move a request from the fused
    fast stream to a per-op one), every fired fault maps to a structured event/error or a
    breaker transition, quarantined buckets heal after faults clear, and
    arena occupancy stays under the active plan's guaranteed bound;
  * **zero overhead disabled** — with resilience off, a call allocates
    nothing from resilience code (the telemetry tracemalloc discipline);
  * **thread safety** — telemetry counters and the specialization table
    survive concurrent request threads plus background swaps;
  * **serve hardening** — bounded queue shed policies, deadlines,
    held-group aging/backoff (the unbounded-requeue bugfix), and the
    structured ``process`` loop.
"""
import os
import threading
import time
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import tree_util

import repro.core.resilience as res_pkg
from repro.core import optimize, symbolic_dims
from repro.core.resilience import (BreakerConfig, BucketQuarantined,
                                   CircuitBreaker, FaultPlan, FaultSpec,
                                   RequestFailed, RequestRejected,
                                   ResilienceConfig, RetryPolicy)
from repro.core.resilience.degrade import ResilienceController
from repro.launch.serve import BucketBatcher

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


def tokens_of(b, s, seed=1):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, V, (b, s)), jnp.int32)


def _flat(tree):
    return [np.asarray(x) for x in tree_util.tree_leaves(tree)]


def _trees_equal(a, b):
    fa, fb = _flat(a), _flat(b)
    return len(fa) == len(fb) and all(
        np.array_equal(x, y) for x, y in zip(fa, fb))


def _close(x, y, rel):
    if x.shape != y.shape or x.dtype != y.dtype:
        return False
    if not jnp.issubdtype(y.dtype, jnp.inexact):
        return np.array_equal(x, y)
    x, y = x.astype(np.float64), y.astype(np.float64)
    return np.max(np.abs(x - y), initial=0.0) <= rel * np.max(
        np.abs(y), initial=0.0)


def _trees_close(a, b, rel=1e-5):
    """Equal within ``rel`` of each output's largest magnitude: a run that
    a fault moved to another stream (per op, or another plan's fused
    executables) against the fault-free fused run."""
    fa, fb = _flat(a), _flat(b)
    return len(fa) == len(fb) and all(
        _close(x, y, rel) for x, y in zip(fa, fb))


FAST_RETRY = RetryPolicy(max_retries=2, backoff_base_s=0.0)


@pytest.fixture()
def resilient_fn():
    """Whole-range fn with the ladder attached (fresh per test — the
    controller and fault bookkeeping are the object under test)."""
    return optimize(train_step, *specs(),
                    dynamic_dims={"b": (1, 16), "s": (8, 256)},
                    resilience=ResilienceConfig(retry=FAST_RETRY))


@pytest.fixture()
def bucketed_resilient_fn():
    return optimize(train_step, *specs(),
                    dynamic_dims={"b": (1, 16), "s": (8, 256)},
                    buckets={"s": [32, 256]},
                    resilience=ResilienceConfig(
                        retry=FAST_RETRY,
                        breaker=BreakerConfig(backoff_s=0.02),
                        enforce_arena_bound=True))


# -- fault plans ---------------------------------------------------------------


class TestFaultPlan:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError):
            FaultSpec("meteor-strike")
        with pytest.raises(ValueError):
            FaultSpec("kernel", times=0)

    def test_random_is_reproducible(self):
        a = FaultPlan.random(7, buckets=[(0,), (1,)])
        b = FaultPlan.random(7, buckets=[(0,), (1,)])
        assert [vars(s) for s in a.specs] == [vars(s) for s in b.specs]
        c = FaultPlan.random(8, buckets=[(0,), (1,)])
        assert [vars(s) for s in a.specs] != [vars(s) for s in c.specs]

    def test_arm_call_matches_ordinal(self):
        fp = FaultPlan([FaultSpec("kernel", call=3)])
        assert fp.arm_call(0) is None
        armed = fp.arm_call(3)
        assert armed is not None and not armed.needs_memory

    def test_budget_spent_once(self):
        fp = FaultPlan([FaultSpec("kernel", call=0, step=0, times=1)])
        armed = fp.arm_call(0)
        from repro.core.resilience import TransientKernelError
        with pytest.raises(TransientKernelError):
            armed.before_compute()
        assert fp.remaining() == 0
        # re-arming after the budget is spent: nothing left to fire
        assert fp.arm_call(0) is None
        assert [f.kind for f in fp.fired] == ["kernel"]
        assert fp.fired[0].call == 0 and fp.fired[0].seq == 0

    def test_compile_fault_targets_bucket(self):
        from repro.core.resilience import CompileFault
        fp = FaultPlan([FaultSpec("compile", bucket=(1,))])
        fp.check_compile((0,))          # other bucket: nothing fires
        with pytest.raises(CompileFault):
            fp.check_compile((1,))
        fp.check_compile((1,))          # budget spent
        assert fp.fired[0].bucket == (1,)


# -- the circuit breaker -------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestCircuitBreaker:
    def test_trip_backoff_halfopen_close(self):
        clk = FakeClock()
        br = CircuitBreaker(BreakerConfig(backoff_s=1.0), clock=clk)
        key = (0,)
        assert br.allow(key)
        br.record_failure(key, RuntimeError("boom"))
        assert br.state(key) == "open"
        assert not br.allow(key)                  # inside the backoff
        assert br.retry_in_s(key) == pytest.approx(1.0)
        clk.t = 1.5
        assert br.allow(key)                      # open -> half-open probe
        assert br.state(key) == "half-open"
        assert not br.allow(key)                  # one probe at a time
        br.record_success(key)
        assert br.state(key) == "closed"
        assert br.allow(key)
        assert br.quarantined_keys() == []

    def test_failed_probe_reopens_with_doubled_backoff(self):
        clk = FakeClock()
        br = CircuitBreaker(BreakerConfig(backoff_s=1.0, backoff_factor=2.0,
                                          max_backoff_s=3.0), clock=clk)
        key = (1,)
        br.record_failure(key, RuntimeError("one"))
        clk.t = 1.0
        assert br.allow(key)
        br.record_failure(key, RuntimeError("two"))   # probe fails
        assert br.state(key) == "open"
        assert br.retry_in_s(key) == pytest.approx(2.0)
        clk.t = 3.0
        assert br.allow(key)
        br.record_failure(key, RuntimeError("three"))
        # growth capped at max_backoff_s
        assert br.retry_in_s(key) == pytest.approx(3.0)

    def test_backoff_restarts_once_the_fallback_served(self):
        clk = FakeClock()
        br = CircuitBreaker(BreakerConfig(backoff_s=1.0), clock=clk)
        key = (3,)
        br.restart_backoff(key)                   # unknown key: no-op
        br.record_failure(key, RuntimeError("boom"))
        clk.t = 0.8                               # a slow fallback call
        br.restart_backoff(key)
        assert br.retry_in_s(key) == pytest.approx(1.0)
        clk.t = 1.5
        assert not br.allow(key)                  # the window runs anew
        clk.t = 1.8
        assert br.allow(key) and br.state(key) == "half-open"
        br.restart_backoff(key)                   # only an open key moves
        assert br.state(key) == "half-open"

    def test_failure_threshold(self):
        br = CircuitBreaker(BreakerConfig(failure_threshold=3),
                            clock=FakeClock())
        key = (2,)
        br.record_failure(key, RuntimeError("a"))
        br.record_failure(key, RuntimeError("b"))
        assert br.state(key) == "closed" and br.allow(key)
        br.record_failure(key, RuntimeError("c"))
        assert br.state(key) == "open"

    def test_transition_log_and_stats(self):
        clk = FakeClock()
        br = CircuitBreaker(BreakerConfig(backoff_s=1.0), clock=clk)
        br.record_failure((0,), RuntimeError("x"))
        clk.t = 2.0
        br.allow((0,))
        br.record_success((0,))
        states = [t["state"] for t in br.transitions if t["key"] == (0,)]
        assert states == ["open", "half-open", "closed"]
        assert br.stats()["by_state"] == {"closed": 1}


# -- the degradation ladder ----------------------------------------------------


class TestDegradationLadder:
    def test_no_fault_path_matches_plain(self, resilient_fn):
        plain = optimize(train_step, *specs(),
                         dynamic_dims={"b": (1, 16), "s": (8, 256)})
        args = (concrete_params(), tokens_of(4, 32), tokens_of(4, 32))
        assert _trees_equal(resilient_fn(*args), plain(*args))
        assert resilient_fn.resilience.counters()["degraded_calls"] == 0

    def test_transient_kernel_fault_retries_in_place(self, resilient_fn):
        args = (concrete_params(), tokens_of(4, 32), tokens_of(4, 32))
        ref = resilient_fn(*args)
        fp = FaultPlan([FaultSpec("kernel", call=1, step=2)])
        with resilient_fn.inject_faults(fp) as res:
            out = resilient_fn(*args)
        assert _trees_equal(out, ref)
        c = res.counters()
        assert c["retries_transient"] == 1 and c["degraded_calls"] == 1
        assert c["failures"] == 0
        evs = list(res.events)
        assert [e.rung for e in evs] == ["retry-transient"]
        assert "kernel" in evs[0].cause
        assert [f.kind for f in fp.fired] == ["kernel"]

    def test_alloc_fault_falls_back_bitwise(self, bucketed_resilient_fn):
        fn = bucketed_resilient_fn
        args = (concrete_params(), tokens_of(2, 24), tokens_of(2, 24))
        ref = fn(*args)
        assert fn.last_bucket is not None
        fp = FaultPlan([FaultSpec("alloc", call=1, step=0)])
        with fn.inject_faults(fp) as res:
            out = fn(*args)
        assert _trees_equal(out, ref)       # fallback is bitwise-identical
        c = res.counters()
        assert c["retries_fallback"] == 1 and c["failures"] == 0
        assert [e.rung for e in res.events] == ["retry-fallback"]

    def test_backoff_is_exponential_and_injectable(self, resilient_fn):
        slept = []
        res = resilient_fn.enable_resilience(ResilienceConfig(
            retry=RetryPolicy(max_retries=2, backoff_base_s=0.01,
                              backoff_factor=4.0)))
        res.sleep = slept.append
        resilient_fn._fault_ref.plan = FaultPlan(
            [FaultSpec("kernel", call=0, step=0, times=2)])
        args = (concrete_params(), tokens_of(2, 16), tokens_of(2, 16))
        resilient_fn(*args)
        assert slept == [pytest.approx(0.01), pytest.approx(0.04)]

    def test_retries_exhausted_raises_structured(self, resilient_fn):
        args = (concrete_params(), tokens_of(3, 16), tokens_of(3, 16))
        fp = FaultPlan([FaultSpec("kernel", call=0, step=0, times=5)])
        with resilient_fn.inject_faults(fp) as res:
            with pytest.raises(RequestFailed) as ei:
                resilient_fn(*args)
        e = ei.value
        assert e.attempts == 3               # max_retries=2 -> 3 attempts
        assert e.env == {"b": 3, "s": 16}
        assert e.cause is not None and "kernel" in repr(e.cause)
        assert [ev.rung for ev in e.events] == \
            ["retry-transient", "retry-transient", "reject"]
        assert [ev.attempt for ev in e.events] == [0, 1, 2]
        assert res.counters()["failures"] == 1
        # the next call is healthy again (budget spent on the failed one)
        resilient_fn(*args)
        assert res.counters()["failures"] == 1

    def test_malformed_request_rejected_without_retry(self, resilient_fn):
        args = (concrete_params(), tokens_of(2, 16), tokens_of(2, 16))
        fp = FaultPlan([FaultSpec("malformed-env", call=0)])
        with resilient_fn.inject_faults(fp) as res:
            with pytest.raises(RequestFailed) as ei:
                resilient_fn(*args)
        assert ei.value.attempts == 0
        assert [ev.rung for ev in ei.value.events] == ["reject-malformed"]
        c = res.counters()
        assert c["malformed"] == 1 and c["failures"] == 1
        assert c["retries_transient"] == 0 and c["retries_fallback"] == 0

    def test_degrade_events_land_in_decision_log(self, resilient_fn):
        fp = FaultPlan([FaultSpec("kernel", call=0, step=0)])
        args = (concrete_params(), tokens_of(2, 16), tokens_of(2, 16))
        with resilient_fn.inject_faults(fp):
            resilient_fn(*args)
        degrades = resilient_fn.decisions.entries("degrade")
        assert len(degrades) == 1
        assert degrades[0].choice == "retry-transient"

    def test_enable_disable_roundtrip(self, resilient_fn):
        res = resilient_fn.disable_resilience()
        assert res is not None and resilient_fn.resilience is None
        args = (concrete_params(), tokens_of(2, 16), tokens_of(2, 16))
        resilient_fn(*args)                 # plain path works
        res2 = resilient_fn.enable_resilience()
        assert resilient_fn.resilience is res2


# -- quarantined specialization ------------------------------------------------


class TestQuarantine:
    def test_compile_fault_quarantines_then_heals(self):
        fn = optimize(train_step, *specs(),
                      dynamic_dims={"b": (1, 16), "s": (8, 256)},
                      buckets={"s": [32, 256]},
                      resilience=ResilienceConfig(
                          retry=FAST_RETRY,
                          breaker=BreakerConfig(backoff_s=0.05)))
        args = (concrete_params(), tokens_of(2, 24), tokens_of(2, 24))
        ref = optimize(train_step, *specs(),
                       dynamic_dims={"b": (1, 16), "s": (8, 256)})(*args)
        table = fn.specialization_table
        fp = FaultPlan([FaultSpec("compile")])
        with fn.inject_faults(fp) as res:
            out = fn(*args)                 # compile fails -> fallback
            assert _trees_equal(out, ref)
            assert res.counters()["retries_fallback"] == 1
            key = fp.fired[0].bucket
            assert table.breaker.state(key) == "open"
            assert table.quarantined() == [key]
            # while quarantined: served by the fallback, no new compile
            out2 = fn(*args)
            assert _trees_equal(out2, ref)
            assert table.stats()["specialize_count"] == 0
        # faults cleared; after the backoff the next miss re-probes
        time.sleep(0.06)
        out3 = fn(*args)
        assert _trees_equal(out3, ref)
        assert table.breaker.state(key) == "closed"
        assert table.quarantined() == []
        assert table.stats()["specialize_count"] == 1
        assert table.peek(key) is not None

    def test_compile_timeout_detected_and_quarantined(self):
        fn = optimize(train_step, *specs(),
                      dynamic_dims={"b": (1, 16), "s": (8, 256)},
                      buckets={"s": [32, 256]},
                      resilience=ResilienceConfig(
                          retry=FAST_RETRY,
                          breaker=BreakerConfig(backoff_s=5.0),
                          compile_timeout_s=0.001))
        args = (concrete_params(), tokens_of(2, 24), tokens_of(2, 24))
        fp = FaultPlan([FaultSpec("compile-timeout", delay_s=0.01)])
        with fn.inject_faults(fp) as res:
            fn(*args)                       # slow compile -> fallback
        table = fn.specialization_table
        key = fp.fired[0].bucket
        assert table.breaker.state(key) == "open"
        cause = table.breaker.cause(key)
        from repro.core.resilience import CompileTimeout
        assert isinstance(cause, CompileTimeout)
        assert res.counters()["retries_fallback"] == 1

    def test_quarantine_visible_in_exports(self):
        fn = optimize(train_step, *specs(),
                      dynamic_dims={"b": (1, 16), "s": (8, 256)},
                      buckets={"s": [32, 256]},
                      resilience=ResilienceConfig(
                          retry=FAST_RETRY,
                          breaker=BreakerConfig(backoff_s=5.0)))
        args = (concrete_params(), tokens_of(2, 24), tokens_of(2, 24))
        with fn.inject_faults(FaultPlan([FaultSpec("compile")])):
            fn(*args)
        from repro.core.obs import prometheus_text
        text = prometheus_text(fn=fn)
        assert "repro_quarantined_buckets 1" in text
        assert "repro_retries_total" in text
        report = fn.explain()
        assert "resilience" in report and "quarantined" in report


# -- chaos ---------------------------------------------------------------------

BENCH_ARCHS = ["llama2_1b", "gemma_2b", "granite_8b", "musicgen_medium"]
CHAOS_SEEDS = [0, 1, 2]
CHAOS_ENVS = [{"b": 1, "s": 16}, {"b": 2, "s": 40}, {"b": 3, "s": 64}]


@pytest.fixture(scope="module")
def chaos_arch_fn():
    """Per-arch compiled pair: (resilient bucketed fn, concrete args per
    env, fault-free reference outputs per env).  Compiled once per arch —
    the three chaos seeds reuse it with fresh controllers."""
    from benchmarks.memplan_bench import _step_and_specs, concretize_spec
    cache = {}

    def build(arch):
        if arch in cache:
            return cache[arch]
        r = _step_and_specs(arch)
        assert r is not None, f"{arch} missing from the bench arch set"
        step, args = r
        fn = optimize(step, *args,
                      dynamic_dims={"b": (1, 4), "s": (8, 64)},
                      buckets={"s": [16, 64]},
                      resilience=ResilienceConfig(
                          retry=RetryPolicy(max_retries=3,
                                            backoff_base_s=0.0),
                          breaker=BreakerConfig(backoff_s=0.01),
                          enforce_arena_bound=True))
        flat_specs, treedef = tree_util.tree_flatten((args, {}))
        rng = np.random.RandomState(0)
        calls, refs = {}, {}
        for env in CHAOS_ENVS:
            flat = [concretize_spec(s, env, rng) for s in flat_specs]
            cargs, _ = tree_util.tree_unflatten(treedef, flat)
            calls[tuple(sorted(env.items()))] = cargs
        # fault-free reference pass (also makes bucket plans resident)
        for env in CHAOS_ENVS:
            k = tuple(sorted(env.items()))
            refs[k] = fn(*calls[k])
        cache[arch] = (fn, calls, refs)
        return cache[arch]

    return build


@pytest.mark.parametrize("arch", BENCH_ARCHS)
@pytest.mark.parametrize("seed", CHAOS_SEEDS)
def test_chaos_schedule_no_crash_and_bitwise_survivors(
        chaos_arch_fn, arch, seed):
    """The acceptance chaos property, per (arch, seed): a randomized
    fault schedule crashes nothing, surviving requests match the
    fault-free run within round-off, every fired fault maps to a structured
    event/error or breaker transition, quarantined buckets heal after
    the schedule clears, and arena occupancy respects the active bound.
    """
    fn, calls, refs = chaos_arch_fn(arch)
    table = fn.specialization_table
    keys = sorted({table.key_of(env) for env in CHAOS_ENVS})
    # evict resident plans so compile faults have compiles to hit
    # (bounds survive eviction; the next miss recompiles)
    with table._lock:
        for key in keys:
            table._plans.pop(key, None)
    res = fn.enable_resilience(ResilienceConfig(
        retry=RetryPolicy(max_retries=3, backoff_base_s=0.0),
        breaker=BreakerConfig(backoff_s=0.01),
        enforce_arena_bound=True))
    plan = FaultPlan.random(seed, n_faults=4, max_call=8, max_step=3,
                            buckets=keys, timeout_delay_s=0.0)
    failures = []
    with fn.inject_faults(plan):
        for i in range(8):
            env = CHAOS_ENVS[i % len(CHAOS_ENVS)]
            k = tuple(sorted(env.items()))
            try:
                out = fn(*calls[k])
            except RequestFailed as e:
                failures.append((i, e))     # structured: fine
                continue
            # survivor: the fault-free run's outputs, within round-off
            assert _trees_close(out, refs[k]), \
                f"{arch} seed {seed} call {i}: outputs diverged"
            bound = fn.last_arena_bound
            if bound is not None:
                assert fn.last_report.stats.arena_bytes <= bound
    # every failure is structured and self-describing
    for i, e in failures:
        assert isinstance(e, RequestFailed)
        assert e.events, f"failure at call {i} carries no events"
    # every fired fault maps to a structured record
    evs = list(res.events)
    for f in plan.fired:
        if f.kind in ("compile", "compile-timeout"):
            assert any(t["key"] == f.bucket and t["state"] == "open"
                       for t in table.breaker.transitions), \
                f"compile fault on {f.bucket} left no breaker transition"
        else:
            assert any(e.seq == f.call for e in evs), \
                f"{f.kind} fault on call {f.call} left no event"
    # recovery: schedule cleared -> every bucket heals once its breaker
    # backoff elapses and the next miss re-probes
    deadline = time.monotonic() + 5.0
    while table.quarantined() and time.monotonic() < deadline:
        time.sleep(0.02)
        for env in CHAOS_ENVS:
            k = tuple(sorted(env.items()))
            out = fn(*calls[k])
            assert _trees_close(out, refs[k])
    assert table.quarantined() == [], \
        f"{arch} seed {seed}: buckets still quarantined after recovery"
    for env in CHAOS_ENVS:
        k = tuple(sorted(env.items()))
        assert _trees_equal(fn(*calls[k]), refs[k])
        assert table.peek(table.key_of(env)) is not None, \
            "bucket did not return to its specialized plan"


def test_chaos_through_serve_loop():
    """The serve loop itself: RequestFailed becomes a structured outcome,
    nothing escapes ``process``."""
    fn = optimize(train_step, *specs(),
                  dynamic_dims={"b": (1, 16), "s": (8, 256)},
                  buckets={"s": [32, 256]},
                  resilience=ResilienceConfig(retry=FAST_RETRY))
    bat = BucketBatcher(fn)
    args = (concrete_params(), tokens_of(2, 24), tokens_of(2, 24))
    ref = fn(*args)
    for _ in range(3):
        bat.submit({"b": 2, "s": 24}, payload=args)
    # the reference call above was resilient seq 0; the three queued
    # requests dispatch as seqs 1..3 — fault the middle one
    fp = FaultPlan([FaultSpec("malformed-env", call=2)])
    with fn.inject_faults(fp):
        outcomes = bat.process()
    assert [o["ok"] for o in outcomes] == [True, False, True]
    for o in outcomes:
        if o["ok"]:
            assert _trees_equal(o["value"], ref)
            assert o["report"] is not None
        else:
            assert isinstance(o["error"], RequestFailed)
            assert o["error"].attempts == 0


# -- zero overhead when disabled -----------------------------------------------


class TestZeroOverheadDisabled:
    def test_disabled_path_allocates_nothing_from_resilience(self):
        """The structural <=2% contract (wall-clock form lives in
        ``benchmarks/resilience_bench.py``): with resilience off, a call
        touches no resilience code at all."""
        fn = optimize(train_step, *specs(),
                      dynamic_dims={"b": (1, 16), "s": (8, 256)})
        assert fn.resilience is None
        res_dir = os.path.dirname(res_pkg.__file__)
        args = (concrete_params(), tokens_of(2, 16), tokens_of(2, 16))
        fn(*args)                                 # warm every cache
        flt = tracemalloc.Filter(True, os.path.join(res_dir, "*"))
        tracemalloc.start(5)
        try:
            before = tracemalloc.take_snapshot().filter_traces([flt])
            for _ in range(5):
                fn(*args)
            after = tracemalloc.take_snapshot().filter_traces([flt])
        finally:
            tracemalloc.stop()
        diff = after.compare_to(before, "lineno")
        grew = [d for d in diff if d.size_diff > 0]
        assert not grew, \
            f"resilience code allocated on the disabled path: {grew}"


# -- thread safety -------------------------------------------------------------


class TestThreadSafety:
    def test_telemetry_ring_concurrent_pushes(self):
        from repro.core.obs import CallRecord, TelemetryRing
        ring = TelemetryRing(capacity=64)
        N, T = 500, 8

        def rec(i):
            return CallRecord(seq=i, bucket_key=None, env=(), wall_s=0.0,
                              dispatch_ns=0, device_peak=0, arena_bytes=0,
                              evictions=0, recomputes=0, reloads=0,
                              donated_reuses=0, loop_trips=())

        def work():
            for i in range(N):
                ring.push(rec(i))

        threads = [threading.Thread(target=work) for _ in range(T)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        # no lost increments: the monotonic write index moved atomically
        assert ring.total_pushed == N * T
        assert len(ring.records()) == 64

    def test_concurrent_calls_lose_no_counts(self):
        """Satellite regression: many request threads + background swaps
        hammer telemetry counters and the table at once."""
        fn = optimize(train_step, *specs(),
                      dynamic_dims={"b": (1, 16), "s": (8, 256)},
                      buckets={"s": [32, 256]})
        tel = fn.enable_telemetry(capacity=1024)
        envs = [(2, 24), (3, 48), (2, 16)]
        per_thread, T = 6, 6
        errs = []

        def work(tid):
            try:
                for i in range(per_thread):
                    b, s = envs[(tid + i) % len(envs)]
                    fn(concrete_params(), tokens_of(b, s), tokens_of(b, s))
            except Exception as e:        # surface, don't deadlock
                errs.append(e)

        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(T)]
        for t in threads:
            t.start()
        # concurrent background churn: recompiles swap plans mid-traffic
        for key in list(fn.specialization_table._plans):
            fn.specialization_table.recompile(key)
        for t in threads:
            t.join()
        fn.specialization_table.drain_background()
        assert errs == []
        total = per_thread * T
        assert tel.n_calls == total
        assert sum(tel.calls_by_bucket.values()) == total
        assert tel.ring.total_pushed == total
        st = fn.specialization_table.stats()
        assert st["hits"] + st["misses"] == total


# -- serve hardening -----------------------------------------------------------


@pytest.fixture(scope="module")
def serve_fn():
    return optimize(train_step, *specs(),
                    dynamic_dims={"b": (1, 16), "s": (8, 256)},
                    buckets={"s": [32, 256]})


class TestBatcherHardening:
    def test_defaults_preserve_plain_behavior(self, serve_fn):
        """Knobs off: held groups persist forever, nothing sheds (the
        pre-hardening contract ``test_dispatch.py`` pins)."""
        bat = BucketBatcher(serve_fn, memory_budget=1)
        bat.submit({"b": 2, "s": 24})
        for _ in range(5):
            assert bat.drain() == []
        assert bat.pending() == 1 and bat.shed_count == 0

    def test_aged_group_is_shed_structurally(self, serve_fn):
        """The drain bugfix: an over-budget group ages out after
        ``max_hold_cycles`` instead of re-enqueueing indefinitely."""
        bat = BucketBatcher(serve_fn, memory_budget=1, max_hold_cycles=2)
        bat.submit({"b": 2, "s": 24}, payload="r0")
        bat.submit({"b": 3, "s": 24}, payload="r1")
        assert bat.drain() == [] and bat.pending() == 2   # hold 1
        assert bat.drain() == [] and bat.pending() == 2   # hold 2
        assert bat.drain() == []                          # aged out
        assert bat.pending() == 0
        assert bat.held_count == 2
        assert bat.shed_count == 2
        assert bat.shed_by_outcome == {"shed-aged": 2}
        shed = bat.take_shed()
        assert [p for _, _, p, _ in shed] == ["r0", "r1"]
        assert all(o == "shed-aged" for _, _, _, o in shed)
        assert bat.take_shed() == []                      # drained once
        evs = [e for e in bat.admission_events if e.outcome == "shed-aged"]
        assert len(evs) == 1 and evs[0].queue_depth == 2
        assert evs[0].required_bytes > evs[0].available_bytes

    def test_hold_backoff_skips_rechecks(self, serve_fn):
        clk = FakeClock()
        bat = BucketBatcher(serve_fn, memory_budget=1, hold_backoff_s=10.0,
                            clock=clk)
        bat.submit({"b": 2, "s": 24})
        assert bat.drain() == [] and bat.held_count == 1
        clk.t = 5.0                        # inside the backoff window
        assert bat.drain() == []
        assert bat.held_count == 1         # silent: no re-check, no event
        clk.t = 11.0                       # window over: re-check happens
        assert bat.drain() == []
        assert bat.held_count == 2
        # second consecutive hold: window doubles (10 * 2**1)
        clk.t = 30.0
        bat.memory_budget = None
        groups = bat.drain()
        assert sum(len(g) for g in groups) == 1

    def test_bounded_queue_reject_new(self, serve_fn):
        bat = BucketBatcher(serve_fn, max_queue=2)
        bat.submit({"b": 2, "s": 24}, payload="a")
        bat.submit({"b": 3, "s": 24}, payload="b")
        with pytest.raises(RequestRejected) as ei:
            bat.submit({"b": 4, "s": 24}, payload="c")
        assert ei.value.reason == "shed-capacity"
        assert ei.value.env == {"b": 4, "s": 24}
        assert bat.pending() == 2
        assert bat.shed_by_outcome == {"shed-capacity": 1}
        evs = [e for e in bat.admission_events
               if e.outcome == "shed-capacity"]
        assert len(evs) == 1

    def test_bounded_queue_drop_oldest(self, serve_fn):
        bat = BucketBatcher(serve_fn, max_queue=2,
                            shed_policy="drop-oldest")
        bat.submit({"b": 2, "s": 24}, payload="a")
        bat.submit({"b": 3, "s": 24}, payload="b")
        bat.submit({"b": 4, "s": 24}, payload="c")   # evicts "a"
        assert bat.pending() == 2
        shed = bat.take_shed()
        assert len(shed) == 1 and shed[0][2] == "a"
        assert shed[0][3] == "shed-capacity"
        groups = bat.drain()
        payloads = sorted(p for g in groups for p in g.payloads)
        assert payloads == ["b", "c"]

    def test_invalid_shed_policy_rejected(self, serve_fn):
        with pytest.raises(ValueError):
            BucketBatcher(serve_fn, shed_policy="yolo")

    def test_deadline_expired_requests_shed(self, serve_fn):
        clk = FakeClock()
        bat = BucketBatcher(serve_fn, clock=clk)
        bat.submit({"b": 2, "s": 24}, payload="slow", deadline_s=1.0)
        bat.submit({"b": 2, "s": 24}, payload="patient")
        clk.t = 2.0
        groups = bat.drain()
        assert [p for g in groups for p in g.payloads] == ["patient"]
        shed = bat.take_shed()
        assert len(shed) == 1 and shed[0][2] == "slow"
        assert shed[0][3] == "shed-deadline"
        assert bat.shed_by_outcome == {"shed-deadline": 1}

    def test_default_deadline_applies(self, serve_fn):
        clk = FakeClock()
        bat = BucketBatcher(serve_fn, default_deadline_s=1.0, clock=clk)
        bat.submit({"b": 2, "s": 24})
        clk.t = 2.0
        assert bat.drain() == []
        assert bat.shed_by_outcome == {"shed-deadline": 1}
        assert bat.pending() == 0

    def test_intake_validation_still_at_submit(self, serve_fn):
        bat = BucketBatcher(serve_fn, max_queue=1)
        with pytest.raises(ValueError):
            bat.submit({"b": 2, "s": 10_000})

    def test_shed_metrics_exported(self, serve_fn):
        bat = BucketBatcher(serve_fn, memory_budget=1, max_hold_cycles=1)
        bat.submit({"b": 2, "s": 24})
        bat.drain()
        bat.drain()
        text = bat.metrics_text()
        assert 'repro_batcher_shed_total{outcome="shed-aged"} 1' in text
