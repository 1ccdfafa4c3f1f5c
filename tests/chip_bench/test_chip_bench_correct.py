"""CPU tests of what decides ``correct``: a whole run of a tiny cell with
the harness's look for a chip skipped, sound and with the timed step
broken underneath, and the float8 control against the reference.

The tiny cell is a real cell's files at CPU widths (one layer of width
128, 4-row batches of short documents); its limits are the tiny widths'
own (``chip_bench_support.TINY_LIMITS``)."""
from __future__ import annotations

import pytest

from chip_bench_support import tiny_cell

import compare
import run
import traffic
from reference import Reference, seed_key

SEED = 2**31 + 12345


def _run(wrap_step=None, workload="granite-8b.varlen"):
    return run.run_cell(tiny_cell(workload), SEED, 0.05, False,
                        chip_check=False, wrap_step=wrap_step)


def _unchanged(dyn):
    """A step that returns its state unchanged."""
    def step(params, opt, batch):
        loss, _, _ = dyn(params, opt, batch)
        return loss, params, opt
    return step


def _half_batch(dyn):
    """Half of the batch left out, the mean taken over the rest."""
    def step(params, opt, batch):
        return dyn(params, opt, {k: v[:v.shape[0] // 2]
                                 for k, v in batch.items()})
    return step


def _stale_in_the_window(dyn):
    """A warm-loop fault: the set-up steps are sound, and from the
    window's first call on the step hands back the state it was given
    (what a step that reuses a buffer it has handed out would do)."""
    cell = tiny_cell()
    warm, compared = traffic.setup_order(
        traffic.cycle_shapes(cell.traffic), run.N_COMPARED)
    calls = [0]

    def step(params, opt, batch):
        calls[0] += 1
        loss, new_params, new_opt = dyn(params, opt, batch)
        if calls[0] > len(warm) + len(compared):
            return loss, params, opt
        return loss, new_params, new_opt
    return step


@pytest.mark.parametrize("workload", ["granite-8b.varlen",
                                      "internlm2-1.8b.varlen"])
def test_a_sound_run_is_correct(workload):
    res = _run(workload=workload)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] >= 3
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                   "step_p95_s", "setup_s"}
    assert set(res["compared"]) == set(tiny_cell(workload).limits)
    for v in res["compared"].values():
        assert v["value"] <= v["limit"]
    # the end steps repeat the set-up steps, from the same weights
    for k in compare.NUMBERS:
        assert res["compared"][f"{k}_end"]["value"] == pytest.approx(
            res["compared"][k]["value"], rel=1e-6)


@pytest.mark.parametrize("fault", [_unchanged, _half_batch,
                                   _stale_in_the_window],
                         ids=["state_unchanged", "half_batch",
                              "stale_in_the_window"])
def test_a_broken_step_is_not_correct(fault):
    res = _run(wrap_step=fault)
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for v in res["compared"].values())


def test_a_fault_only_in_the_window_shows_in_the_end_readings():
    res = _run(wrap_step=_stale_in_the_window)
    shown = res["compared"]
    assert all(shown[k]["value"] <= shown[k]["limit"]
               for k in compare.NUMBERS)
    assert shown["update_end"]["value"] == pytest.approx(1.0)


def test_the_float8_control_fails_the_limits():
    cell = tiny_cell()
    t, c = cell.traffic, cell.config
    shapes = traffic.cycle_shapes(t)
    _, compared = traffic.setup_order(shapes, run.N_COMPARED)
    limits = {k: cell.limits[k] for k in compare.NUMBERS}
    for seed in (3, 2**31 + 7, 99):
        cycle = traffic.make_cycle(t, c["vocab_size"], seed)
        batches = [cycle[i] for i in compared]
        key = seed_key(seed)
        ref = Reference(c).run(key, batches)
        ctl = Reference(c, "fp8").run(key, batches)
        ok, shown = compare.judge({"": compare.gaps(ctl, ref)}, limits)
        assert not ok, shown
