"""Value-dependent bounded dims: property tests over the cap contract.

A bounded dim ``b`` is introduced by an op whose output extent only the
input *values* decide (``masked_select`` et al.); the trace mints a fresh
symbol with a cap expression ``b <= f(input dims)``.  Three contracts are
exercised here (hypothesis, or the deterministic shim from
``conftest.py``):

* **cap monotonicity** — ``ShapeGraph.bounds_of`` answered through
  ``declare_bound`` is never tighter than any value the runtime can
  measure: every measured extent lies in ``[0, cap(env)]`` and the
  declared interval contains that whole span, at every env in range.
* **plan invariance under rebinding** — re-running the same declared env
  with different input values (hence different measured bounds) changes
  nothing about the compiled artifact: same plan, same reserve, same
  cached ``Program.resolve`` object, while each call's stats are tight
  for *its* measured value (the satellite-3 cache-alias regression).
* **measured == 0** — a bounded dim that measures empty allocates
  zero-byte buffers, frees them, and the slot is reusable by the next
  call at full occupancy.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import optimize, symbolic_dim
from repro.core.symbolic import Interval, ShapeGraph, SymbolicExpr
from repro.kernels import masked_select

V = SymbolicExpr.var


def _mask(n, occ, seed=0):
    if occ == 0.0:
        return jnp.zeros((n,), bool)
    if occ == 1.0:
        return jnp.ones((n,), bool)
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.rand(n) < occ)


def _select_fn():
    def f(x, mask):
        y, cnt = masked_select(x * 2.0, mask)
        return jnp.sum(y, axis=0), cnt
    return f


def _specs(cols=4):
    s = symbolic_dim("s")
    return (jax.ShapeDtypeStruct((s, cols), jnp.float32),
            jax.ShapeDtypeStruct((s,), jnp.bool_))


# -- cap monotonicity ----------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(lo=st.integers(1, 8),
       span=st.integers(0, 60),
       shape=st.sampled_from(["n", "2n", "n+3", "3n+1"]),
       probe=st.integers(0, 7))
def test_declared_bounds_never_tighter_than_measurable(lo, span, shape,
                                                       probe):
    hi = lo + span
    cap = {"n": V("n"), "2n": V("n") * 2, "n+3": V("n") + 3,
           "3n+1": V("n") * 3 + 1}[shape]
    sg = ShapeGraph()
    sg.declare_range("n", lo, hi)
    sg.declare_bound("b", cap)

    blo, bhi = sg.bounds_of(V("b"))
    assert blo is not None and bhi is not None
    # pick an in-range env, then any measurable value m in [0, cap(env)]
    env = {"n": lo + probe % (span + 1)}
    cap_val = cap.evaluate(env)
    for m in (0, cap_val // 2, cap_val):
        assert blo <= m <= bhi, (
            f"measured {m} escapes declared [{blo}, {bhi}] "
            f"(cap {cap} at {env})")
    # the declared interval is exactly the measurable span at the widest env
    assert blo == 0
    assert bhi == cap.evaluate({"n": hi})
    # interval queries compose through the cap: a size expression over b
    # is bounded without b ever being user-declared
    iv = sg.interval_of(V("b") * 4 + 8)
    assert iv.lo == 8 and iv.hi == 4 * bhi + 8


def test_declare_bound_tightens_monotonically():
    """Re-declaring through a narrower cap can only shrink the interval
    (specialization re-derives caps after range narrowing)."""
    sg = ShapeGraph()
    sg.declare_range("n", 1, 100)
    sg.declare_bound("b", V("n"))
    assert sg.bounds_of(V("b")) == (0, 100)
    sub = sg.specialized({"n": Interval(1, 10)})
    assert sub.bounds_of(V("b")) == (0, 10)
    # the parent is untouched
    assert sg.bounds_of(V("b")) == (0, 100)


# -- plan invariance under rebinding (satellite-3 regression) ------------------

def test_rebinding_same_env_cannot_alias_caches():
    """Two calls with identical declared dims but different measured
    bounds must not alias each other's cached ``Program.resolve`` or the
    interpreter's per-env size cache: each call's peak is tight for its
    own occupancy, and the cached resolve keeps cap sizes throughout."""
    fn = optimize(_select_fn(), *_specs(), dynamic_dims={"s": (1, 64)})
    n = 16
    x = jnp.asarray(np.random.RandomState(0).randn(n, 4), jnp.float32)

    resolved_before = fn.program.resolve({"s": n})
    cap_nbytes = list(resolved_before.nbytes)

    peaks = {}
    for occ in (1.0, 0.0, 0.5):
        fn(x, _mask(n, occ))
        st_ = fn.last_report.stats
        peaks[occ] = st_.device_peak
        assert st_.measured_dims == {
            name: int(np.sum(np.asarray(_mask(n, occ))))
            for name in fn.plan.graph.bound_dims}

    # tight accounting per call: an empty selection peaks strictly below
    # a full one — a cache alias would make these equal
    assert peaks[0.0] < peaks[0.5] < peaks[1.0], peaks
    # the declared-env resolve cache still holds cap sizes (same object,
    # unmutated by the measured overlays)
    resolved_after = fn.program.resolve({"s": n})
    assert resolved_after is resolved_before
    assert list(resolved_after.nbytes) == cap_nbytes


def test_rebinding_shared_size_cache_bucketed():
    """The bucketed path injects one shared size/params cache across all
    bucket executors — measured bounds must not leak into it either."""
    fn = optimize(_select_fn(), *_specs(), dynamic_dims={"s": (1, 64)},
                  buckets="geometric")
    ref = optimize(_select_fn(), *_specs(), dynamic_dims={"s": (1, 64)},
                   buckets="geometric", executor="reference")
    n = 24
    x = jnp.asarray(np.random.RandomState(1).randn(n, 4), jnp.float32)
    for occ in (1.0, 0.0, 1.0):
        o_vm = fn(x, _mask(n, occ))
        o_ref = ref(x, _mask(n, occ))
        for a, b in zip(o_vm, o_ref):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        sv = fn.last_report.stats
        sr = ref.last_report.stats
        assert sv.measured_dims == sr.measured_dims
        assert sv.device_peak == sr.device_peak
        want = n if occ == 1.0 else 0
        assert list(sv.measured_dims.values()) == [want]


def test_plan_artifacts_invariant_under_rebinding():
    fn = optimize(_select_fn(), *_specs(), dynamic_dims={"s": (1, 64)})
    n = 12
    x = jnp.asarray(np.random.RandomState(2).randn(n, 4), jnp.float32)
    bound = fn.report.arena_bound_bytes
    prog = fn.program
    outs = []
    for occ in (0.5, 0.5):
        outs.append(fn(x, _mask(n, occ, seed=7)))
        assert fn.report.arena_bound_bytes == bound
        assert fn.program is prog
        assert fn.last_report.stats.arena_bytes <= bound
    for a, b in zip(*outs):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- measured == 0 -------------------------------------------------------------

def test_measured_zero_frees_and_reuses():
    """A 0%-fill call allocates a zero-byte bounded buffer, frees it, and
    the arena slot serves the next full-occupancy call unharmed."""
    fn = optimize(_select_fn(), *_specs(), dynamic_dims={"s": (1, 64)})
    ref = optimize(_select_fn(), *_specs(), dynamic_dims={"s": (1, 64)},
                   executor="reference")
    n = 10
    x = jnp.asarray(np.random.RandomState(3).randn(n, 4), jnp.float32)

    for occ in (0.0, 1.0, 0.0):
        o_vm, o_ref = fn(x, _mask(n, occ)), ref(x, _mask(n, occ))
        for a, b in zip(o_vm, o_ref):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        sv, sr = fn.last_report.stats, ref.last_report.stats
        # every counter but the dispatch wall times (not semantics)
        timing = ("last_dispatch_ns", "dispatch_ns_total")
        assert {k: v for k, v in sv.as_dict().items() if k not in timing} \
            == {k: v for k, v in sr.as_dict().items() if k not in timing}
        if occ == 0.0:
            assert list(sv.measured_dims.values()) == [0]
            # eager oracle agrees the selection is empty
            assert float(o_vm[1]) == 0.0
    # everything freed at the end of each call: no residual growth
    assert fn.last_report.stats.arena_growth_bytes == 0
