"""End-to-end trainer.

Two execution paths share the data pipeline / optimizer / checkpointing:

  * ``compiled``  — jit + mesh sharding (production; dry-run lowers this);
  * ``dynamic``   — the BladeDISC++ path: one symbolic trace, the op
    scheduler + runtime remat execute every variable-shape batch without
    recompilation or padding (paper §2/§3).

Usage (CPU scale-down):
    PYTHONPATH=src python -m repro.launch.train --arch llama2-1b --smoke \
        --steps 50 --mode dynamic --memory-limit-mb 200
"""
from __future__ import annotations

import argparse
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import Checkpointer
from ..configs import get_config, get_smoke_config
from ..core import optimize, symbolic_dims
from ..data import DataPipeline, PipelineConfig
from ..distributed import StragglerMonitor
from ..models import init_params
from ..optim import init_state
from .compile_cache import configure_compile_cache
from .steps import adamw_config_for, make_train_step


def build_dynamic_step(cfg, params, opt_state):
    """Symbolically trace the train step once; runs any (B, S)."""
    import dataclasses
    cfg = dataclasses.replace(cfg, scan_layers=False)  # flat graph for the
    # symbolic optimizer (scheduling + remat own the memory plan)
    B, S = symbolic_dims("b, s")
    step = make_train_step(cfg)
    p_spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
    o_spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), opt_state)
    batch_spec = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                  "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
                  "mask": jax.ShapeDtypeStruct((B, S), jnp.float32)}
    return optimize(step, p_spec, o_spec, batch_spec, donate_inputs=True)


def train(cfg, *, steps: int = 50, batch_size: int = 8, mode: str = "dynamic",
          memory_limit: Optional[int] = None, ckpt_dir: Optional[str] = None,
          ckpt_every: int = 25, resume: bool = True, data_mode: str = "dynamic",
          log_every: int = 10, seed: int = 0) -> Dict[str, Any]:
    params = init_params(cfg, jax.random.PRNGKey(seed))
    opt_state = init_state(params, adamw_config_for(cfg))
    pipe = DataPipeline(PipelineConfig(vocab=cfg.vocab, batch_size=batch_size,
                                       seed=seed, mode=data_mode,
                                       min_tokens=16, max_tokens=96))
    ck = Checkpointer(ckpt_dir, keep=3) if ckpt_dir else None
    start_step = 0
    if ck is not None and resume and ck.latest_step() is not None:
        start_step, state, extra = ck.restore()
        params, opt_state = state["params"], state["opt_state"]
        pipe.restore(extra["pipeline"])
        print(f"[train] resumed from step {start_step}")

    monitor = StragglerMonitor()
    stats: Dict[str, Any] = {"losses": [], "tokens": 0, "peak_bytes": 0,
                             "recompilations": 0}

    if mode == "dynamic":
        dyn = build_dynamic_step(cfg, params, opt_state)
        if memory_limit:
            dyn = dyn.with_memory_limit(memory_limit)
        step_fn = None
    else:
        jit_cache: Dict[Any, Any] = {}
        base_step = make_train_step(cfg)

        def step_fn(params, opt_state, batch):
            key = batch["tokens"].shape
            if key not in jit_cache:
                jit_cache[key] = jax.jit(base_step, donate_argnums=(0, 1))
                stats["recompilations"] += 1
            return jit_cache[key](params, opt_state, batch)

    t0 = time.time()
    for step in range(start_step, steps):
        raw = pipe.next_batch()
        batch = {"tokens": jnp.asarray(raw["tokens"]),
                 "labels": jnp.asarray(raw["labels"]),
                 "mask": jnp.asarray(raw["mask"])}
        ts = time.time()
        if mode == "dynamic":
            loss, params, opt_state = dyn(params, opt_state, batch)
            rep = dyn.last_report
            stats["peak_bytes"] = max(stats["peak_bytes"],
                                      rep.stats.device_peak)
        else:
            loss, params, opt_state = step_fn(params, opt_state, batch)
            loss.block_until_ready()
        dt = time.time() - ts
        monitor.record_step({0: dt})
        stats["losses"].append(float(loss))
        stats["tokens"] += int(raw["mask"].sum())
        if ck is not None and (step + 1) % ckpt_every == 0:
            ck.save(step + 1, {"params": params, "opt_state": opt_state},
                    extra={"pipeline": pipe.state()}, blocking=False)
        if (step + 1) % log_every == 0:
            print(f"[train] step {step+1} loss={float(loss):.4f} "
                  f"({dt*1000:.0f} ms)", flush=True)
    if ck is not None:
        ck.wait()
    wall = time.time() - t0
    stats["wall_s"] = wall
    stats["tokens_per_s"] = stats["tokens"] / max(wall, 1e-9)
    return stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--mode", choices=["dynamic", "compiled"], default="dynamic")
    ap.add_argument("--data-mode", choices=["dynamic", "bucketed"],
                    default="dynamic")
    ap.add_argument("--memory-limit-mb", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    configure_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    stats = train(cfg, steps=args.steps, batch_size=args.batch_size,
                  mode=args.mode, data_mode=args.data_mode,
                  memory_limit=args.memory_limit_mb * 2**20 or None,
                  ckpt_dir=args.ckpt_dir)
    print(f"[train] done: {stats['tokens_per_s']:.0f} tokens/s, "
          f"final loss {stats['losses'][-1]:.4f}, "
          f"peak {stats['peak_bytes']/2**20:.1f} MiB, "
          f"recompiles {stats['recompilations']}")


if __name__ == "__main__":
    main()
