#!/usr/bin/env python3
"""The readings that a cell's limits of ``correct`` are set from.

    python3 benchmarks/chip/calibrate.py --workload granite-8b.varlen \\
        --seeds 12 --faulted-seeds 3 --first-seed 5000

In one process, at the cell's own size and traffic, with the trainer
built once (``optimize()``) and reused:

* for each of ``--seeds`` seeds: the program's readings of the compared
  set-up steps, exactly as ``run.py`` takes them, then the float32
  reference's; their gaps are the *lower* readings;
* for the first ``--faulted-seeds`` of them, two stand-ins put in the
  program's place and compared with the same reference: the control, the
  reference computed with float8 (e4m3) matmul operands, and the planted
  fault "half of the batch left out, the mean taken over the rest".
  Their gaps are the *upper* readings.  (A step that returns its state
  unchanged reads 1 on ``grad`` and ``update`` by construction.)

Standard error logs each seed; the last line of standard output is a
JSON object with every reading and, per number, the largest lower and
the smallest upper reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

NUMBERS = ("loss", "grad", "update")


def calibrate(cell, seeds, faulted, *, chip_check=True):
    import jax
    import jax.numpy as jnp

    import compare
    import traffic
    from reference import Reference, Weights, seed_key
    from repro.launch.steps import adamw_config_for
    from repro.launch.train import build_dynamic_step
    from repro.optim import init_state

    dev = jax.devices()[0]
    if chip_check and dev.platform != "tpu":
        raise run.NoChip(f"JAX runs on {dev.platform!r}, not a TPU")
    c, t = cell.config, cell.traffic
    cfg = run.model_config(c)
    counter = run.CompileCounter()
    weights = Weights(c)
    ocfg = adamw_config_for(cfg)
    make = jax.jit(lambda k: (lambda p: (p, init_state(p, ocfg)))(
        weights.init(k)))
    shapes = traffic.cycle_shapes(t)
    warm, compared_idx = traffic.setup_order(shapes, run.N_COMPARED)
    dyn = None
    out = {"program": [], "control": [], "half_batch": []}
    for n, seed in enumerate(seeds):
        key = seed_key(seed)
        cycle = traffic.make_cycle(t, c["vocab_size"], seed)
        batches = [{k: jnp.asarray(b[k]) for k in ("tokens", "labels",
                                                   "mask")}
                   for b in cycle]
        state = list(make(key))
        if dyn is None:
            dyn = build_dynamic_step(cfg, *state)
        prog, _, _ = run.setup_steps(dyn, dyn, state, batches, shapes, warm,
                                     compared_idx, weights, key, counter, dev)
        del state, batches
        gc.collect()
        compared = [cycle[i] for i in compared_idx]
        t0 = time.perf_counter()
        ref = Reference(c).run(key, compared)
        ref_s = time.perf_counter() - t0
        g = compare.gaps(prog, ref)
        out["program"].append({"seed": seed, **g})
        run.say(f"seed {seed}: program loss {g['loss']:.3e} grad "
                f"{g['grad']:.3e} ({g['grad_at']}) update {g['update']:.3e} "
                f"({g['update_at']}); reference {ref_s:.1f} s")
        if n < faulted:
            for name, stand_in in (("control", Reference(c, "fp8")),
                                   ("half_batch",
                                    Reference(c, half_batch=True))):
                g = compare.gaps(stand_in.run(key, compared), ref)
                out[name].append({"seed": seed, **g})
                run.say(f"seed {seed}: {name} loss {g['loss']:.3e} grad "
                        f"{g['grad']:.3e} ({g['grad_at']}) update "
                        f"{g['update']:.3e} ({g['update_at']})")
    out["lower"] = {k: max(r[k] for r in out["program"]) for k in NUMBERS}
    for name in ("control", "half_batch"):
        if out[name]:
            out[f"upper_{name}"] = {k: min(r[k] for r in out[name])
                                    for k in NUMBERS}
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faulted-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=1)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import spec
    from repro.launch.compile_cache import configure_compile_cache
    cell = spec.load_cell(args.workload)
    configure_compile_cache()
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    try:
        out = calibrate(cell, seeds, args.faulted_seeds)
    except run.NoChip as e:
        run.say(f"FAIL: {e}")
        return 1
    for k in NUMBERS:
        run.say(f"{k}: lower {out['lower'][k]:.3e}, upper control "
                f"{out.get('upper_control', {}).get(k, float('nan')):.3e}, "
                f"upper half_batch "
                f"{out.get('upper_half_batch', {}).get(k, float('nan')):.3e}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
