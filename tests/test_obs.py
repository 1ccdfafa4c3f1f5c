"""Observability contracts: tracing, telemetry, timelines, exports.

Five contract families over :mod:`repro.core.obs`:

  * **tracing** — ``optimize`` records a span forest (phases nested under
    their parent, attributes attached) and a decision log; the
    Chrome-trace export is valid Trace Event JSON with children inside
    their parent's time window;
  * **telemetry** — the ring is an exact bounded FIFO (property test over
    capacity x push-count), per-call records carry the stats split
    (``last_dispatch_ns`` per call, ``dispatch_ns_total`` cumulative),
    and the *disabled* hot path allocates nothing from obs code — the
    structural form of the <=2% overhead contract (its wall-clock form
    lives in ``benchmarks/obs_bench.py``);
  * **timelines** — the replayed per-instruction occupancy agrees with
    the compile-time plan: actual arena under the guaranteed bound, zero
    unexplained allocations, device peak exactly the plan's prediction —
    including across a rolled ``lax.scan`` loop;
  * **serve surfaces** — admission control emits structured events and
    the Prometheus export renders well-formed metric families;
  * **profiled calls** — under a ``jax.profiler`` session a call writes
    its spans (call, solve, resolve, one per op group, GC) into the
    profiler's trace and one record to the process-wide sink; outside a
    session it records nothing, and its outputs are the same bits.
"""
import gc
import json
import os
import threading
import tracemalloc
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.core.obs as obs_pkg
from repro.core import optimize, symbolic_dim, symbolic_dims
from repro.core.lowering.program import OP_COMPUTE
from repro.core.obs import (CallRecord, DecisionLog, NullTracer,
                            TelemetryRing, Tracer, chrome_trace_json,
                            prometheus_text)
from repro.core.obs.telemetry import PROFILE_SINK
from repro.launch.serve import BucketBatcher


# -- shared compiled functions (compile once per module) -----------------------

@pytest.fixture(scope="module")
def chain_fn():
    n, = symbolic_dims("n")

    def chain(x):
        for _ in range(8):
            x = jnp.tanh(x * 1.5 + 0.25)
        return x.sum()

    return optimize(chain, jax.ShapeDtypeStruct((n, 4), jnp.float32),
                    dynamic_dims={"n": (2, 256)})


@pytest.fixture(scope="module")
def bucketed_fn():
    b, = symbolic_dims("b")

    def f(w, x):
        h = jnp.tanh(x @ w)
        return (h * h).sum()

    return optimize(f,
                    jax.ShapeDtypeStruct((8, 8), jnp.float32),
                    jax.ShapeDtypeStruct((b, 8), jnp.float32),
                    dynamic_dims={"b": (1, 512)},
                    buckets={"b": [8, 64, 512]})


@pytest.fixture(scope="module")
def loop_fn():
    t = symbolic_dim("t")

    def f(h0, xs):
        c0 = jnp.tanh(h0)
        cN, ys = jax.lax.scan(lambda c, x: (jnp.tanh(c + x), c.sum()),
                              c0, xs)
        return cN.sum() + ys.sum()

    return optimize(f,
                    jax.ShapeDtypeStruct((4,), jnp.float32),
                    jax.ShapeDtypeStruct((t, 4), jnp.float32),
                    dynamic_dims={"t": (1, 64)})


# -- tracing -------------------------------------------------------------------

class TestTracer:
    def test_nesting_and_attrs(self):
        tr = Tracer()
        with tr.span("outer", kind="test") as o:
            with tr.span("inner") as i:
                i.attrs["n"] = 3
            o.attrs["done"] = True
        assert [r.name for r in tr.roots] == ["outer"]
        assert [s.name for s in tr.spans()] == ["outer", "inner"]
        outer = tr.roots[0]
        assert outer.attrs == {"kind": "test", "done": True}
        assert [c.name for c in outer.children] == ["inner"]
        inner = outer.children[0]
        assert inner.attrs["n"] == 3
        # children close inside their parent's window
        assert outer.t0_ns <= inner.t0_ns <= inner.t1_ns <= outer.t1_ns
        assert tr.find("inner") == [inner]

    def test_thread_spans_are_separate_roots(self):
        tr = Tracer()

        def work():
            with tr.span("bg"):
                pass

        th = threading.Thread(target=work, name="specialize_0")
        with tr.span("fg"):
            th.start()
            th.join()
        names = {s.name for s in tr.spans()}
        assert names == {"fg", "bg"}
        bg, = tr.find("bg")
        assert bg.thread_name == "specialize_0"

    def test_null_tracer_absorbs(self):
        tr = NullTracer()
        with tr.span("x", a=1) as sp:
            sp.attrs["b"] = 2          # must not raise
        assert tr.spans() == []

    def test_optimize_records_phases(self, chain_fn):
        names = [s.name for s in chain_fn.trace.spans()]
        assert "trace" in names
        # find() searches the whole span forest, nested or not
        for phase in ("schedule", "remat", "memplan", "lower"):
            assert chain_fn.trace.find(phase), phase
        mem = chain_fn.trace.find("memplan")[0]
        assert mem.attrs["n_slots"] >= 1
        assert mem.duration_ns >= 0

    def test_decision_log_records_slot_pack(self, chain_fn):
        kinds = {d.kind for d in chain_fn.decisions.entries()}
        assert "slot-pack" in kinds
        packs = chain_fn.decisions.entries(kind="slot-pack")
        assert all(d.kind == "slot-pack" for d in packs)

    def test_bucketed_compile_spans_and_decisions(self, bucketed_fn):
        bucketed_fn(np.ones((8, 8), np.float32),
                    np.ones((4, 8), np.float32))
        spans = [s for s in _walk_all(bucketed_fn.trace)
                 if s.name == "specialize"]
        assert spans, "bucket compile recorded no specialize span"
        assert any("bucket" in s.attrs for s in spans)


def _walk_all(tracer):
    out = []
    for r in tracer.spans():
        out.extend(r.walk())
    return out


class TestChromeTrace:
    def test_export_is_valid_and_nested(self, chain_fn):
        text = chrome_trace_json(chain_fn.trace)
        data = json.loads(text)
        events = data["traceEvents"]
        spans = [e for e in events if e["ph"] == "X"]
        assert spans
        for e in spans:
            assert e["dur"] >= 0
            assert isinstance(e["name"], str)
            for v in e["args"].values():   # JSON-safe attrs only
                assert isinstance(v, (int, float, str, bool, type(None)))


# -- telemetry -----------------------------------------------------------------

def _rec(seq):
    return CallRecord(seq=seq, bucket_key=None, env=(("n", 8),),
                      wall_s=0.0, dispatch_ns=0, device_peak=0,
                      arena_bytes=0, evictions=0, recomputes=0,
                      reloads=0, donated_reuses=0, loop_trips=())


# module-level: the conftest hypothesis shim drives @given tests without
# pytest fixtures, so property tests cannot take self
@settings(max_examples=40, deadline=None)
@given(cap=st.integers(1, 8), n=st.integers(0, 30))
def test_ring_is_exact_bounded_fifo(cap, n):
    ring = TelemetryRing(cap)
    for i in range(n):
        ring.push(_rec(i))
    recs = ring.records()
    assert len(ring) == min(n, cap)
    assert ring.total_pushed == n
    assert ring.dropped == max(0, n - cap)
    # exactly the newest min(n, cap) records, oldest first
    assert [r.seq for r in recs] == list(range(max(0, n - cap), n))


def test_ring_rejects_zero_capacity():
    with pytest.raises(ValueError):
        TelemetryRing(0)


class TestTelemetry:
    def test_enable_record_disable(self, bucketed_fn):
        w = np.ones((8, 8), np.float32)
        tel = bucketed_fn.enable_telemetry(capacity=4)
        try:
            for b in (2, 2, 30):
                bucketed_fn(w, np.ones((b, 8), np.float32))
            assert tel.n_calls == 3
            recs = tel.ring.records()
            assert [r.seq for r in recs] == [0, 1, 2]
            assert recs[0].env == (("b", 2),)
            assert recs[2].env == (("b", 30),)
            assert recs[0].bucket_key is not None
            assert recs[0].bucket_key == recs[1].bucket_key
            assert recs[2].bucket_key != recs[0].bucket_key
            assert tel.summary()["n_calls"] == 3
        finally:
            got = bucketed_fn.disable_telemetry()
        assert got is tel
        assert bucketed_fn.telemetry is None

    def test_stats_split_semantics(self, bucketed_fn):
        w = np.ones((8, 8), np.float32)
        x = np.ones((2, 8), np.float32)
        bucketed_fn(w, x)
        st1 = bucketed_fn.last_report.stats
        total1 = st1.dispatch_ns_total
        assert st1.last_dispatch_ns > 0
        assert total1 >= st1.last_dispatch_ns
        bucketed_fn(w, x)
        st2 = bucketed_fn.last_report.stats
        assert st2.dispatch_ns_total >= total1 + st2.last_dispatch_ns
        d = st2.as_dict()
        assert "last_dispatch_ns" in d and "dispatch_ns_total" in d
        assert "dispatch_ns" not in d

    def test_unbucketed_dispatch_is_timed(self, chain_fn):
        """The monolithic path times its env solve, range check and
        resolve, so every dispatch counter reads non-zero there too."""
        x = np.ones((4, 4), np.float32)
        tel = chain_fn.enable_telemetry()
        try:
            chain_fn(x)
            st1 = chain_fn.last_report.stats
            assert chain_fn.last_bucket is None
            assert st1.last_dispatch_ns > 0
            chain_fn(x)
            st2 = chain_fn.last_report.stats
            assert st2.dispatch_ns_total >= (st1.dispatch_ns_total
                                             + st2.last_dispatch_ns)
            assert tel.dispatch_ns_total == (st1.last_dispatch_ns
                                             + st2.last_dispatch_ns)
            text = prometheus_text(chain_fn)
            line, = [ln for ln in text.splitlines()
                     if ln.startswith("repro_dispatch_ns_total ")]
            assert int(line.split()[1]) > 0
        finally:
            chain_fn.disable_telemetry()

    def test_loop_trips_recorded(self, loop_fn):
        tel = loop_fn.enable_telemetry()
        try:
            loop_fn(np.ones(4, np.float32), np.ones((5, 4), np.float32))
            recs = tel.ring.records()
            assert recs[-1].loop_trips == (5,)
        finally:
            loop_fn.disable_telemetry()

    def test_disabled_path_allocates_nothing_from_obs(self, chain_fn):
        """The structural <=2% contract: with telemetry off, a call
        touches no obs code at all (one attribute test, no allocation)."""
        obs_dir = os.path.dirname(obs_pkg.__file__)
        x = np.ones((4, 4), np.float32)
        chain_fn(x)                               # warm every cache
        flt = tracemalloc.Filter(True, os.path.join(obs_dir, "*"))
        tracemalloc.start(5)
        try:
            before = tracemalloc.take_snapshot().filter_traces([flt])
            for _ in range(5):
                chain_fn(x)
            after = tracemalloc.take_snapshot().filter_traces([flt])
        finally:
            tracemalloc.stop()
        diff = after.compare_to(before, "lineno")
        grew = [d for d in diff if d.size_diff > 0]
        assert not grew, f"obs code allocated on the disabled path: {grew}"


# -- profiled calls (a jax.profiler session on the CPU) -------------------------

def _trace_events(log_dir):
    """(name, metadata) of every host event of the trace under log_dir."""
    from jax.profiler import ProfileData
    path, = [os.path.join(d, f) for d, _, fs in os.walk(log_dir)
             for f in fs if f.endswith(".xplane.pb")]
    out = []
    with warnings.catch_warnings():      # jaxlib's stats type warns on use
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    out += [(e.name, dict(e.stats)) for e in line.events]
    return out


def _computes(prog):
    return sum(i.op == OP_COMPUTE for i in prog.fast_instructions)


@pytest.fixture(scope="module")
def train_fn():
    """The trainer's symbolic step on a two-layer model at tiny widths."""
    from repro.configs.base import ModelConfig
    from repro.launch.steps import adamw_config_for
    from repro.launch.train import build_dynamic_step
    from repro.models import init_params
    from repro.optim import init_state
    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=64,
                      ffn_kind="swiglu", rope_theta=1e4,
                      tie_embeddings=False, dtype="bfloat16",
                      optimizer_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_state(params, adamw_config_for(cfg))
    batch = {"tokens": jnp.ones((2, 12), jnp.int32),
             "labels": jnp.ones((2, 12), jnp.int32),
             "mask": jnp.ones((2, 12), jnp.float32)}
    return build_dynamic_step(cfg, params, opt), (params, opt, batch)


class TestProfiledCall:
    def test_a_session_records_one_call(self, chain_fn, tmp_path):
        x = np.ones((6, 4), np.float32)
        chain_fn(x)
        before = PROFILE_SINK.ring.total_pushed
        with jax.profiler.trace(str(tmp_path)):
            chain_fn(x)
        assert PROFILE_SINK.ring.total_pushed == before + 1
        rec = PROFILE_SINK.ring.records()[-1]
        assert rec.env == (("n", 6),)
        assert rec.binds == _computes(chain_fn.program) > 0
        for f in ("solve_ns", "resolve_ns", "stream_ns", "call_ns",
                  "dispatch_ns"):
            assert getattr(rec, f) > 0, f
        assert rec.call_ns >= rec.stream_ns + rec.resolve_ns
        names = {n for n, _ in _trace_events(tmp_path)}
        assert {"dsf.call", "dsf.solve_env", "vm.resolve", "vm.stream",
                "vm.ops"} <= names
        call, = [m for n, m in _trace_events(tmp_path) if n == "dsf.call"]
        assert call["seq"] == rec.seq and call["n"] == 6

    def test_rolled_loop_runs_in_a_loop_span(self, loop_fn, tmp_path):
        h0, xs = np.ones(4, np.float32), np.ones((5, 4), np.float32)
        plain = loop_fn(h0, xs)
        with jax.profiler.trace(str(tmp_path)):
            traced = loop_fn(h0, xs)
        np.testing.assert_array_equal(np.asarray(plain), np.asarray(traced))
        prog = loop_fn.program
        body, = [info.body_program for info in prog.loops]
        rec = PROFILE_SINK.ring.records()[-1]
        assert rec.loop_trips == (5,)
        assert rec.binds == _computes(prog) + 5 * _computes(body)
        loops = [m for n, m in _trace_events(tmp_path) if n == "vm.loop"]
        assert loops == [{"trips": 5}]

    def test_no_session_records_nothing(self, chain_fn):
        from jax.profiler import TraceAnnotation
        assert not TraceAnnotation.is_enabled()
        before = PROFILE_SINK.ring.total_pushed
        chain_fn(np.ones((6, 4), np.float32))
        assert PROFILE_SINK.ring.total_pushed == before

    def test_groups_cover_the_stream_and_change_no_output(self, train_fn,
                                                          tmp_path):
        fn, (params, opt, batch) = train_fn
        prog = fn.program
        edges = [(g.lo, g.hi) for g in prog.groups]
        assert edges[0][0] == 0 and edges[-1][1] == len(
            prog.fast_instructions)
        assert all(a[1] == b[0] and a[0] < a[1]
                   for a, b in zip(edges, edges[1:]))
        plain = fn(params, opt, batch)
        with jax.profiler.trace(str(tmp_path)):
            traced = fn(params, opt, batch)
        for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(traced)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        rec = PROFILE_SINK.ring.records()[-1]
        assert rec.binds == _computes(prog)
        spans = [m for n, m in _trace_events(tmp_path)
                 if n in {g.label for g in prog.groups}]
        assert len(spans) == len(prog.groups)
        assert max(m["hbm_bytes"] - m["plan_bytes"] for m in spans) \
            == rec.hbm_over_plan_bytes

    def test_named_scopes_label_forward_and_backward(self, train_fn):
        labels = {g.label for g in train_fn[0].program.groups}
        for want in ("fwd/embed", "fwd/layer0/attn", "fwd/layer1/mlp",
                     "fwd/head_loss", "bwd/head_loss", "bwd/layer0/attn",
                     "bwd/layer1/mlp", "adamw"):
            assert want in labels, (want, sorted(labels))

    def test_gc_callbacks_balance_and_count(self, chain_fn, monkeypatch,
                                            tmp_path):
        from repro.core.executor.vm import ProgramVM
        run_segment = ProgramVM._run_segment

        def collecting(self, *a):
            gc.collect()
            return run_segment(self, *a)

        monkeypatch.setattr(ProgramVM, "_run_segment", collecting)
        x = np.ones((6, 4), np.float32)
        n_callbacks = len(gc.callbacks)
        with jax.profiler.trace(str(tmp_path)):
            chain_fn(x)
        assert len(gc.callbacks) == n_callbacks
        rec = PROFILE_SINK.ring.records()[-1]
        assert rec.gc_collections >= 1 and rec.gc_ns > 0
        gcs = [m for n, m in _trace_events(tmp_path) if n == "python.gc"]
        assert len(gcs) == rec.gc_collections
        assert all(m["generation"] == 2 for m in gcs)

    def test_compile_spans_reach_the_profile(self, tmp_path):
        tr = Tracer()
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("memplan"):
                with tr.span("lower"):
                    pass
        names = [n for n, _ in _trace_events(tmp_path)]
        assert "memplan" in names and "lower" in names
        assert [s.name for s in tr.spans()] == ["memplan", "lower"]


# -- timelines -----------------------------------------------------------------

class TestTimeline:
    def test_plan_vs_actual_agree(self, chain_fn):
        for n in (2, 32, 256):
            diff = chain_fn.memory_timeline({"n": n})
            assert diff.ok, diff.summary()
            assert diff.unexplained == []
            assert diff.within_bound
            # the fast stream's traffic is fully determined by the env,
            # so the replayed peak must hit the plan's prediction exactly
            assert diff.actual.peak_device == diff.predicted_peak_device
            assert len(diff.actual.points) > 0

    def test_loop_timeline_audits_clean(self, loop_fn):
        for t in (1, 5, 64):
            diff = loop_fn.memory_timeline({"t": t})
            assert diff.ok, diff.summary()
            assert diff.unexplained == []
            opnames = {p.opname for p in diff.actual.points}
            assert "Loop" in opnames

    def test_bounded_dims_timeline_stays_ok(self):
        """With value-dependent bounded ops in the graph the plan-vs-actual
        diff still audits clean: the replay completes missing bound dims to
        their caps, every allocation is explained by a planned liveness
        interval, and a measured env (from a real call) reconstructs that
        call's tight curve — still within the cap-sized reserve."""
        from repro.kernels import masked_select
        s = symbolic_dim("s")

        def f(x, mask):
            y, cnt = masked_select(jnp.tanh(x), mask)
            return (y * y).sum(), cnt

        fn = optimize(f, jax.ShapeDtypeStruct((s, 4), jnp.float32),
                      jax.ShapeDtypeStruct((s,), jnp.bool_),
                      dynamic_dims={"s": (1, 64)})
        for s_val in (2, 16, 64):
            diff = fn.memory_timeline({"s": s_val})
            assert diff.ok, diff.summary()
            assert diff.unexplained == []
            assert "BindDim" in {p.opname for p in diff.actual.points}
        # a real call's measured env replays the tight curve
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(16, 4), jnp.float32)
        fn(x, jnp.asarray(rng.rand(16) < 0.5))
        rep = fn.last_report
        assert rep.stats.measured_dims
        tight = fn.memory_timeline(rep.env)
        assert tight.ok, tight.summary()
        assert tight.actual.peak_device == rep.stats.device_peak
        cap = fn.memory_timeline({"s": 16})
        assert tight.actual.peak_device < cap.actual.peak_device
        # explain() reports reserved-cap vs measured-size per bounded slot
        text = fn.explain(env=rep.env)
        assert "value-dependent bounded dims" in text
        assert "measured" in text and "reserved" in text

    def test_bucketed_timeline_uses_resident_bucket(self, bucketed_fn):
        w = np.ones((8, 8), np.float32)
        bucketed_fn(w, np.ones((4, 8), np.float32))
        diff = bucketed_fn.memory_timeline({"b": 4})
        assert diff.ok, diff.summary()
        # the bucket plan's bound (b<=8), far below the whole range's
        assert diff.arena_bound_bytes is not None
        mono = bucketed_fn.arena_bound_bytes
        assert diff.arena_bound_bytes <= mono

    def test_reference_executor_has_no_timeline(self):
        n, = symbolic_dims("n")
        fn = optimize(lambda x: (x * x).sum(),
                      jax.ShapeDtypeStruct((n,), jnp.float32),
                      dynamic_dims={"n": (2, 16)}, executor="reference")
        with pytest.raises(ValueError):
            fn.memory_timeline({"n": 4})


# -- explain + serve surfaces --------------------------------------------------

class TestExplain:
    def test_report_sections(self, bucketed_fn):
        w = np.ones((8, 8), np.float32)
        bucketed_fn(w, np.ones((4, 8), np.float32))
        text = bucketed_fn.explain(env={"b": 4})
        for needle in ("compile phases", "decisions", "arena slots",
                       "rematerialization", "bucket dispatch",
                       "plan vs actual", "verdict: OK"):
            assert needle in text, f"explain() lacks {needle!r}"

    def test_explain_without_env(self, chain_fn):
        text = chain_fn.explain()
        assert "compile phases" in text
        assert "plan vs actual" not in text


class TestServeSurfaces:
    def test_admission_events(self, bucketed_fn):
        bat = BucketBatcher(bucketed_fn, memory_budget=1)
        bat.submit({"b": 2})
        bat.submit({"b": 2})
        bat.submit({"b": 100})
        assert bat.drain() == []
        assert bat.held_count == 2                 # two distinct groups held
        assert bat.pending() == 3                  # requests stay queued
        evs = list(bat.admission_events)
        assert len(evs) == 2
        by_depth = {e.queue_depth for e in evs}
        assert by_depth == {1, 2}
        for e in evs:
            assert e.required_bytes > e.available_bytes
            assert "b" in e.label
        bat.memory_budget = None                   # lift the budget
        groups = bat.drain()
        assert sum(len(g) for g in groups) == 3
        assert bat.pending() == 0

    def test_prometheus_text(self, bucketed_fn):
        w = np.ones((8, 8), np.float32)
        bucketed_fn(w, np.ones((4, 8), np.float32))
        bat = BucketBatcher(bucketed_fn, memory_budget=1)
        bat.submit({"b": 2})
        bat.drain()
        text = bat.metrics_text()
        lines = [ln for ln in text.splitlines() if ln]
        families = {}
        for ln in lines:
            if ln.startswith("# TYPE"):
                _, _, name, kind = ln.split()
                families[name] = kind
            elif not ln.startswith("#"):
                name = ln.split("{")[0].split(" ")[0]
                assert name in families, f"sample before TYPE: {ln}"
                float(ln.rsplit(" ", 1)[1])        # value parses
        assert families["repro_bucket_hits_total"] == "counter"
        assert families["repro_batcher_held_total"] == "counter"
        assert families["repro_bucket_arena_bound_bytes"] == "gauge"

    def test_prometheus_with_telemetry(self, bucketed_fn):
        tel = bucketed_fn.enable_telemetry()
        try:
            bucketed_fn(np.ones((8, 8), np.float32),
                        np.ones((4, 8), np.float32))
            text = prometheus_text(fn=bucketed_fn)
            assert "repro_calls_total 1" in text
            assert "repro_dispatch_ns_total" in text
        finally:
            bucketed_fn.disable_telemetry()
        assert tel.n_calls == 1
