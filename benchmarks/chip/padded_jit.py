#!/usr/bin/env python3
"""The row to beat: a cell's cycle through plain ``jax.jit``, every batch
padded to the cycle's largest shape.

    python3 benchmarks/chip/padded_jit.py --workload granite-8b.varlen \\
        --seed 1234 --seconds 30

What a user gets without this system: the trainer's own
``make_train_step`` (layers scanned, block remat) under ``jax.jit`` with
the parameters and optimizer state donated, one compiled shape.  The
window is ``run.py``'s (whole cycles, every step's outputs blocked on,
useful tokens counted), so the numbers sit beside the cell's.  Run it in
a process of its own: its compile and memory would otherwise land in the
cell's set-up and peak.  It prints one JSON line; it is not a cell.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import spec
    from repro.launch.compile_cache import configure_compile_cache
    cell = spec.load_cell(args.workload)
    configure_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import traffic
    from reference import Weights, seed_key
    from repro.launch.steps import adamw_config_for, make_train_step
    from repro.optim import init_state

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        run.say(f"FAIL: JAX runs on {dev.platform!r}, not a TPU")
        return 1
    c, t = cell.config, cell.traffic
    cfg = run.model_config(c)
    cycle = traffic.make_cycle(t, c["vocab_size"], args.seed)
    b, s = max(x["tokens"].shape for x in cycle)

    def pad(x):
        return np.pad(x, [(0, b - x.shape[0]), (0, s - x.shape[1])])

    batches = [{k: jnp.asarray(pad(x[k])) for k in ("tokens", "labels",
                                                      "mask")}
               for x in cycle]
    weights = Weights(c)
    ocfg = adamw_config_for(cfg)
    params, opt = jax.jit(lambda k: (lambda p: (p, init_state(p, ocfg)))(
        weights.init(k)))(seed_key(args.seed))
    step = jax.jit(make_train_step(cfg), donate_argnums=(0, 1))
    t0 = time.perf_counter()
    loss, params, opt = step(params, opt, batches[0])
    jax.block_until_ready((loss, params, opt))
    compile_s = time.perf_counter() - t0
    walls, useful = [], 0
    t_w0 = time.perf_counter()
    setup_s = t_w0 - T_START
    while True:
        for i, batch in enumerate(batches):
            t0 = time.perf_counter()
            loss, params, opt = step(params, opt, batch)
            jax.block_until_ready((loss, params, opt))
            walls.append(time.perf_counter() - t0)
            useful += cycle[i]["useful"]
            if not math.isfinite(float(loss)):
                run.say(f"FAIL: step {len(walls)} loss {float(loss)}")
                return 1
        if time.perf_counter() - t_w0 >= args.seconds:
            break
    window_s = time.perf_counter() - t_w0
    walls.sort()
    x = 0.95 * (len(walls) - 1)
    lo = int(x)
    p95 = walls[lo] + (walls[min(lo + 1, len(walls) - 1)] - walls[lo]) \
        * (x - lo)
    out = {"workload": cell.name, "padded_to": [b, s],
           "steps": len(walls), "tokens_per_s": useful / window_s,
           "peak_hbm_gib": run.memory(dev)["peak"] / 2**30,
           "step_p95_s": p95, "step_median_s": walls[len(walls) // 2],
           "setup_s": setup_s, "compile_s": compile_s,
           "device": {"platform": dev.platform, "kind": dev.device_kind}}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
