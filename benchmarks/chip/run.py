#!/usr/bin/env python3
"""One run of one benchmark cell: variable-length fine-tuning through the
symbolic-shape trainer, on the chip.

    python3 benchmarks/chip/run.py --workload granite-8b.varlen \\
        --seed 1234 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` and the files under this directory, see
``spec.py``) fixes the model and a cycle of batch shapes.  The run:

1. fails, printing no result, unless JAX finds a TPU and as many chips
   as the cell asks for;
2. set-up: makes the weights and Adam state on the device from
   ``--seed`` in one jitted call, builds the trainer's dynamic step
   (``launch.train.build_dynamic_step``: ``optimize()`` of the train step
   with symbolic ``(b, s)``), runs one step at each distinct shape of the
   cycle, largest first, from the initial state and drops its outputs
   (these compile or load the per-op programs), then runs the three steps
   that are compared with the reference from that state, on other batches
   of the same shapes, on the window's own warm path;
3. the window: replays the cycle, whole cycles, until ``--seconds`` have
   passed, blocking on every step's outputs; then reads the device's
   ``peak_bytes_in_use`` before anything else allocates;
4. with ``--trace 1``, one more cycle under the profiler;
5. the end steps: drops the window's state, makes the seed's weights
   again and runs the three compared steps once more through the same
   step, which has by then served every call of the window;
6. frees the program's state and runs the plain reference
   (``reference.py``) over the three compared steps once, and judges the
   set-up steps and the end steps against it (``compare.py``) and the
   cell's limits.

Earlier lines of standard error log every step (shape, times, memory);
its last lines are the numbers compared beside their limits.  The last
line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``compared``.

JAX's persistent compilation cache is ``.jax_cache`` at the root of the
checkout, whatever the environment says, so that only a checkout's first
run compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# steps of set-up compared with the reference
N_COMPARED = 3
# JAX records this for every executable it compiles or loads from the
# persistent cache
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


class CompileCounter:
    """Counts XLA programs built (compiled or loaded from disk)."""

    def __init__(self):
        import jax
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            self.programs += 1


def model_config(c: Dict[str, Any]):
    """The trainer's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], ffn_kind="swiglu", rope_theta=c["rope_theta"],
        tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"],
        optimizer_dtype=c["optimizer"]["state_dtype"])


def memory(dev) -> Dict[str, int]:
    st = dev.memory_stats() or {}
    return {"in_use": st.get("bytes_in_use", 0),
            "peak": st.get("peak_bytes_in_use", 0),
            "limit": st.get("bytes_limit", 0)}


def gib(n: float) -> str:
    return f"{n / 2**30:.4f} GiB"


def load_reader(name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end(name: str, run: Dict[str, Any]) -> float:
    w = run["window"]
    walls = sorted(s["wall_s"] for s in w["steps"])
    if name == "tokens_per_s":
        return w["useful_tokens"] / w["seconds"]
    if name == "peak_hbm_gib":
        return run["peak_bytes"] / 2**30
    if name == "step_p95_s":
        # linear interpolation between closest ranks
        x = 0.95 * (len(walls) - 1)
        lo = int(math.floor(x))
        hi = min(lo + 1, len(walls) - 1)
        return walls[lo] + (walls[hi] - walls[lo]) * (x - lo)
    if name == "setup_s":
        return run["setup_s"]
    raise KeyError(f"no end-to-end metric {name!r} in this harness")


def setup_steps(step, dyn, state, batches, shapes, warm, compared, weights,
                key, counter, dev, label="compared"):
    """Run the set-up steps and take the program's readings.

    The ``warm`` steps run from the initial ``state`` and their outputs are
    dropped; the ``compared`` steps then run from it in turn.  ``state`` is
    ``[params, opt]``, replaced in place after every compared step so that
    nothing keeps an old state alive.  ``label`` names the compared steps
    in the log.  Returns (readings, seconds in all the steps, the plan's
    peak at the largest shape, if a step had it)."""
    import jax
    from reference import leaf_norms
    largest = max(shapes)
    prog = {"loss": [], "first_moment": None, "change": None}
    secs, plan_peak = 0.0, None
    for j, i in enumerate(warm + compared):
        n0 = counter.programs
        t0 = time.perf_counter()
        out = step(state[0], state[1], batches[i])
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        secs += wall
        st = dyn.last_report.stats
        if shapes[i] == largest:
            plan_peak = st.device_peak
        kind = "warm" if j < len(warm) else label
        say(f"setup step {j} ({kind}): batch {i} shape {shapes[i]} loss "
            f"{float(out[0]):.6f} wall {wall:.3f} s xla_programs "
            f"{counter.programs - n0} plan_peak {gib(st.device_peak)} "
            f"evictions {st.evictions} recomputes {st.recomputes} "
            f"offloads {st.offloads} reloads {st.reloads} memory "
            f"{memory(dev)}")
        if kind == "warm":
            del out
            continue
        prog["loss"].append(float(out[0]))
        state[0], state[1] = out[1], out[2]
        del out
        if len(prog["loss"]) == 1:
            prog["first_moment"] = leaf_norms(state[1].m)
    prog["change"] = weights.change_norms(key, state[0])
    return prog, secs, plan_peak


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             chip_check: bool = True,
             wrap_step: Optional[Callable] = None) -> Dict[str, Any]:
    """One run; returns the result object (see the module docstring).

    ``chip_check=False`` and ``wrap_step`` exist for the tests, which run
    a tiny cell on the CPU with the timed step broken underneath."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import compare
    import devtrace
    import flops
    import traffic
    from reference import Reference, Weights, seed_key

    devs = jax.devices()
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    say(f"device: {json.dumps(device)}")
    if chip_check and (dev.platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(f"the cell needs {cell.chips} TPU chip(s); JAX has "
                     f"{len(devs)} {dev.platform!r} device(s)")
    peaks = flops.peaks_for(dev.device_kind if dev.platform == "tpu"
                            else "TPU v5 lite")

    from repro.launch.steps import adamw_config_for
    from repro.launch.train import build_dynamic_step
    from repro.optim import init_state

    c, t = cell.config, cell.traffic
    cfg = model_config(c)
    counter = CompileCounter()
    say(f"cell {cell.name}: {c['name']} layers={c['num_hidden_layers']} "
        f"d={c['hidden_size']} heads={c['num_attention_heads']}/"
        f"{c['num_key_value_heads']} ff={c['intermediate_size']} "
        f"vocab={c['vocab_size']} {c['torch_dtype']}; traffic "
        f"{t['layout']}")

    # -- set-up: inputs and weights from the seed ---------------------------
    cycle = traffic.make_cycle(t, c["vocab_size"], seed)
    shapes = [b["tokens"].shape for b in cycle]
    for b in cycle:
        b["flops"] = flops.step_flops(c, b["lengths"])
    batches = [{k: jnp.asarray(b[k]) for k in ("tokens", "labels", "mask")}
               for b in cycle]
    key = seed_key(seed)
    weights = Weights(c)
    ocfg = adamw_config_for(cfg)
    make_state = jax.jit(lambda k: (lambda p: (p, init_state(p, ocfg)))(
        weights.init(k)))
    state = list(make_state(key))
    jax.block_until_ready(state)
    say(f"memory after weights: {memory(dev)}")

    t0 = time.perf_counter()
    dyn = build_dynamic_step(cfg, *state)
    optimize_s = time.perf_counter() - t0
    say(f"optimize: {optimize_s:.3f} s (host); memory {memory(dev)}")
    step = dyn if wrap_step is None else wrap_step(dyn)

    warm, compared = traffic.setup_order(shapes, N_COMPARED)
    prog, shape_warm_s, plan_peak = setup_steps(
        step, dyn, state, batches, shapes, warm, compared, weights, key,
        counter, dev)
    params, opt = state
    state.clear()

    # -- the window: whole cycles until `seconds` have passed ----------------
    gc.collect()
    n0 = counter.programs
    steps: List[Dict[str, Any]] = []
    failed = 0
    t_w0 = time.perf_counter()
    setup_s = t_w0 - T_START
    while True:
        for i, batch in enumerate(batches):
            t0 = time.perf_counter()
            out = step(params, opt, batch)
            t1 = time.perf_counter()
            jax.block_until_ready(out)
            t2 = time.perf_counter()
            loss, params, opt = out
            del out
            lv = float(loss)
            failed += not math.isfinite(lv)
            st = dyn.last_report.stats
            mem = memory(dev)
            steps.append({"shape": shapes[i], "enqueue_s": t1 - t0,
                          "wall_s": t2 - t0, "useful": cycle[i]["useful"],
                          "flops": cycle[i]["flops"]})
            say(f"step {len(steps) - 1}: shape {shapes[i]} enqueue "
                f"{t1 - t0:.4f} s wall {t2 - t0:.4f} s loss {lv:.5f} "
                f"evictions {st.evictions} recompute_flops "
                f"{st.recompute_flops} in_use {mem['in_use']} peak "
                f"{mem['peak']}")
        if time.perf_counter() - t_w0 >= seconds:
            break
    window_s = time.perf_counter() - t_w0
    peak_bytes = memory(dev)["peak"]
    window = {"steps": steps, "seconds": window_s,
              "xla_programs": counter.programs - n0,
              "useful_tokens": sum(s["useful"] for s in steps),
              "flops": sum(s["flops"] for s in steps)}
    say(f"window: {len(steps)} steps in {window_s:.3f} s, "
        f"{window['xla_programs']} XLA programs, peak_bytes_in_use "
        f"{peak_bytes} ({gib(peak_bytes)}), plan peak at {max(shapes)} "
        f"{plan_peak}")

    run = {"optimize_s": optimize_s, "shape_warm_s": shape_warm_s,
           "setup_s": setup_s, "window": window, "peak_bytes": peak_bytes,
           "plan_peak_bytes": plan_peak, "peaks": peaks, "trace": None}

    # -- with --trace 1: one more cycle under the profiler ------------------
    if trace:
        state = [params, opt]

        def traced_cycle():
            for batch in batches:
                with jax.profiler.TraceAnnotation(devtrace.STEP_SPAN):
                    out = step(state[0], state[1], batch)
                    jax.block_until_ready(out)
                state[0], state[1] = out[1], out[2]

        tr = devtrace.reduce(devtrace.capture(traced_cycle))
        params, opt = state
        state.clear()
        least = [max(b["flops"] / peaks["flops"],
                     flops.step_bytes(c) / peaks["hbm_bytes_per_s"])
                 for b in cycle]
        bound = sum(b["flops"] / peaks["flops"] >= flops.step_bytes(c)
                    / peaks["hbm_bytes_per_s"] for b in cycle)
        run["trace"] = tr
        run["trace_least_s"] = sum(least)
        run["trace_bound"] = ("compute" if 2 * bound >= len(cycle)
                              else "memory")
        say(f"trace: {tr['steps']} steps, window {tr['window_s']:.4f} s, "
            f"device busy {tr['busy_s']:.4f} s, {tr['device_ops']:.0f} "
            f"device ops; least time {sum(least):.4f} s "
            f"({run['trace_bound']}-bound)")
        for name, secs in tr["breakdown"]["device_ops"]:
            say(f"  device op {name}: {secs:.6f} s")
        for name, secs in tr["breakdown"]["idle_gaps"]:
            say(f"  idle gap {name}: {secs:.6f} s")

    # -- the end steps: the compared steps again from the seed's weights,
    # through the step the window drove (a fault that shows only after
    # many calls shows here)
    del params, opt
    gc.collect()
    state = list(make_state(key))
    prog_end, _, _ = setup_steps(step, dyn, state, batches, shapes, [],
                                 compared, weights, key, counter, dev, "end")

    # -- correctness: the plain reference, once the program's state is freed
    del state, batches, dyn, step
    gc.collect()
    say(f"memory before the reference: {memory(dev)}")
    t0 = time.perf_counter()
    ref = Reference(c).run(key, [cycle[i] for i in compared])
    say(f"reference: {time.perf_counter() - t0:.2f} s, losses "
        f"{ref['loss']} (program {prog['loss']}, at the end "
        f"{prog_end['loss']}); memory {memory(dev)}")
    g = {"": compare.gaps(prog, ref), "_end": compare.gaps(prog_end, ref)}
    correct, shown = compare.judge(g, cell.limits)
    correct = correct and failed == 0

    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": end_to_end(m["name"], run),
                               "unit": m["unit"]} for m in cell.end_to_end}
    device["memory_peak_bytes"] = peak_bytes
    result: Dict[str, Any] = {"correct": correct, "attempted": len(steps),
                              "failed": failed, "metrics": metrics,
                              "device": device}
    if trace:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        result["breakdown"] = run["trace"]["breakdown"]
    for when, x in g.items():
        say(f"compared{when} at: grad {x['grad_at']}, update "
            f"{x['update_at']}; leaves left out of update: {x['left_out']}")
    for name, v in shown.items():
        say(f"compared {name}: {v['value']:.6e} limit {v['limit']:.6e}")
    result["compared"] = {k: {"value": (v["value"] if math.isfinite(
        v["value"]) else None), "limit": v["limit"]}
        for k, v in shown.items()}
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import spec
    from repro.launch.compile_cache import configure_compile_cache
    cell = spec.load_cell(args.workload)
    configure_compile_cache()
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        say(f"FAIL: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
