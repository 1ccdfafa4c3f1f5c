"""CPU tests of the per-layer metrics read from the program's own records
of the traced cycle (``traced_calls.py``): a tiny cell's cycle run under a
CPU profiler session gives each a number, and records that are not the
cycle's shapes give none."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import pytest

from chip_bench_support import tiny_cell

import run
import traffic
from reference import Weights, seed_key

READERS = ["vm_us_per_op", "call_overhead_ms", "gc_pause_ms",
           "hbm_over_plan_gib"]
SEED = 2**31 + 777


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """The run object a ``--trace 1`` run hands the readers, for a tiny
    cell whose cycle ran once under a CPU profiler session."""
    from repro.launch.steps import adamw_config_for
    from repro.launch.train import build_dynamic_step
    from repro.optim import init_state
    cell = tiny_cell()
    c, t = cell.config, cell.traffic
    cfg = run.model_config(c)
    cycle = traffic.make_cycle(t, c["vocab_size"], SEED)
    batches = [{k: jnp.asarray(b[k]) for k in ("tokens", "labels", "mask")}
               for b in cycle]
    params = Weights(c).init(seed_key(SEED))
    opt = init_state(params, adamw_config_for(cfg))
    step = build_dynamic_step(cfg, params, opt)
    for b in batches:                         # warm every shape
        jax.block_until_ready(step(params, opt, b))
    with jax.profiler.trace(str(tmp_path_factory.mktemp("trace"))):
        for b in batches:
            _, params, opt = step(params, opt, b)
        jax.block_until_ready(params)
    shapes = [b["tokens"].shape for b in cycle]
    return {"trace": {"steps": len(batches)},
            "window": {"steps": [{"shape": s} for s in shapes]}}


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_a_number_for_the_traced_cycle(traced_run, name):
    v = run.load_reader(name)(traced_run)
    assert isinstance(v, float) and math.isfinite(v)
    if name in ("vm_us_per_op", "call_overhead_ms"):
        assert v > 0
    if name == "gc_pause_ms":
        assert v >= 0


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_for_other_shapes(traced_run, name):
    steps = traced_run["window"]["steps"]
    other = dict(traced_run, window={"steps": [
        {"shape": (s["shape"][0], s["shape"][1] + 1)} for s in steps]})
    assert run.load_reader(name)(other) is None
    assert run.load_reader(name)(dict(traced_run, trace=None)) is None
