#!/usr/bin/env python3
"""Smoke run of the symbolic-shape training path on one TPU.

Drives the trainer's dynamic path once, in this one process:
``launch.train.build_dynamic_step`` traces the train step with symbolic
``(b, s)`` through ``optimize()``, and the ProgramVM runs a few batches
of different shapes from ``data.DataPipeline``.  The model is
``llama2-1b`` at its published widths (d_model 4096, 32 heads of 128,
d_ff 11008, vocab 32000), cut in depth to what one chip holds, with
random weights from ``--seed``.

Checks, each fatal:

* the device is a TPU;
* on the first batch the VM's loss and per-leaf update summaries agree
  with the same ``make_train_step`` under plain ``jax.jit`` on the chip,
  within the tolerances stated below;
* every step's loss is finite;
* the Pallas ``flash_attention`` and ``rmsnorm`` kernels, compiled (not
  interpreted), agree with ``kernels/ref.py``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.

Usage::

    python chip_smoke.py                             # on a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --smoke   # CPU rehearsal

``--smoke`` runs every phase at the smoke widths, with Pallas in
interpret mode, and always exits non-zero: the ok line stands for the
published widths with compiled kernels on a TPU.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))
# the TPU library logs under /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, get_smoke_config  # noqa: E402
from repro.data import DataPipeline, PipelineConfig  # noqa: E402
from repro.kernels import flash_attention, hardware_for, rmsnorm  # noqa: E402
from repro.kernels.ref import reference_attention, reference_rmsnorm  # noqa: E402
from repro.launch.compile_cache import (cache_entries,  # noqa: E402
                                        configure_compile_cache)
from repro.launch.steps import adamw_config_for, make_train_step  # noqa: E402
from repro.launch.train import build_dynamic_step  # noqa: E402
from repro.models import init_params  # noqa: E402
from repro.optim import init_state  # noqa: E402

ARCH = "llama2-1b"
STEPS = 4
# Share of HBM the training state may take; the rest is left for
# activations, XLA's workspace and the plain-jit reference step.
STATE_SHARE = 0.75
# VM-vs-jit tolerances (relative).  The VM binds one primitive at a
# time, so every bf16 intermediate is rounded to bf16; under jax.jit XLA
# fuses elementwise chains and rounds only at fusion outputs.  The two
# therefore differ by a few bf16 ulps (2^-8 = 3.9e-3) per activation,
# and the aggregates below average most of that out.  A dropped layer
# update moves its leaf's update norm by 100%; rounding every bf16
# intermediate to fp8 (e4m3, 2^-4) moves gradient norms by tens of
# percent.  The loss alone is a weak check — near ln(vocab) at random
# init whatever the precision — so the per-leaf norms carry it.
LOSS_RTOL = 2e-3
GRAD_RTOL = 2e-2
UPDATE_RTOL = 2e-2
# Pallas kernels vs the f32 reference, on bf16 inputs: the outputs are
# bf16, whose ulp at |x| ~ 4 is 2^-6; the test suite's bf16 tolerance.
KERNEL_TOL = 5e-2
# JAX records the first for every executable it compiles *or* loads from
# the persistent cache, the second for each such load.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def state_bytes(cfg) -> tuple:
    """(params, reckoned bytes while the VM runs one step).

    The VM cannot donate device buffers, so a step holds the old
    parameters and Adam state, the new ones, and the gradients: for bf16
    parameters with f32 moments that is 10 + 10 + 2 = 22 B/param."""
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: init_state(p, adamw_config_for(cfg)),
                         shapes)
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    p, o = nbytes(shapes), nbytes(opt)
    return n, 2 * (p + o) + p


def cut_depth(cfg, hbm_bytes: int):
    """The deepest n_layers whose reckoned step state fits the budget."""
    budget = STATE_SHARE * hbm_bytes
    best = None
    for n_layers in range(1, cfg.n_layers + 1):
        c = dataclasses.replace(cfg, n_layers=n_layers)
        n, need = state_bytes(c)
        if need > budget:
            break
        best = (c, n, need)
    if best is None:
        fail(f"even one layer of {cfg.name} reckons more than "
             f"{budget / 2**30:.1f} GiB")
    return best


def leaf_summaries(old_params, loss, new_params, new_opt):
    """Loss plus, per parameter leaf, the norm of the new first moment
    (0.1 x the clipped gradient) and the norm of the applied update."""
    f32 = lambda x: x.astype(jnp.float32)
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(f32(x))))
    grad = jax.tree.map(norm, new_opt.m)
    upd = jax.tree.map(lambda a, b: norm(f32(a) - f32(b)),
                       new_params, old_params)
    return loss, grad, upd


def rel_err(a, b) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def compare(vm, ref) -> None:
    loss_vm, grad_vm, upd_vm = vm
    loss_ref, grad_ref, upd_ref = ref
    e = rel_err(loss_vm, loss_ref)
    say(f"check loss: vm {float(loss_vm):.6f} jit {float(loss_ref):.6f} "
        f"rel err {e:.3e} (tol {LOSS_RTOL:.0e})")
    if not e <= LOSS_RTOL:
        fail("VM loss differs from the jit step")
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(grad_ref)[0]]
    for name, tol, a, b in (("grad norm", GRAD_RTOL, grad_vm, grad_ref),
                            ("update norm", UPDATE_RTOL, upd_vm, upd_ref)):
        errs = [rel_err(x, y) for x, y in
                zip(jax.tree.leaves(a), jax.tree.leaves(b))]
        worst = int(np.argmax(errs))
        say(f"check {name}: {len(errs)} leaves, max rel err "
            f"{errs[worst]:.3e} at {paths[worst]} (tol {tol:.0e})")
        if not errs[worst] <= tol:
            fail(f"VM {name} of {paths[worst]} differs from the jit step")
        if not all(float(y) > 0 for y in jax.tree.leaves(b)):
            fail(f"the jit step left a leaf with a zero {name}")


def kernel_phase(smoke: bool) -> None:
    """Compiled Pallas kernels vs the f32 references."""
    if smoke:
        b, hq, hkv, s, hd, n, d = 1, 8, 2, 256, 128, 64, 256
    else:
        b, hq, hkv, s, hd, n, d = 1, 32, 8, 2048, 128, 2048, 4096
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    bf16 = jnp.bfloat16
    q = jax.random.normal(keys[0], (b, hq, s, hd), bf16)
    k = jax.random.normal(keys[1], (b, hkv, s, hd), bf16)
    v = jax.random.normal(keys[2], (b, hkv, s, hd), bf16)
    x = jax.random.normal(keys[3], (n, d), bf16)
    scale = (0.1 * jax.random.normal(keys[4], (d,))).astype(bf16)
    interpret = smoke   # compiled on the chip; interpreted on the CPU
    with jax.default_matmul_precision("highest"):
        o_ref = reference_attention(q, k, v, causal=True)
        y_ref = reference_rmsnorm(x, scale)
    t0 = time.perf_counter()
    o = flash_attention(q, k, v, causal=True, impl="pallas",
                        interpret=interpret)
    y = rmsnorm(x, scale, impl="pallas", interpret=interpret)
    jax.block_until_ready((o, y))
    wall = time.perf_counter() - t0
    for name, got, want, shape in (
            ("flash_attention", o, o_ref,
             f"GQA {hq}/{hkv} s={s} hd={hd} causal"),
            ("rmsnorm", y, y_ref, f"n={n} d={d}")):
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        say(f"kernel {name} ({shape}, bf16, interpret={interpret}): "
            f"max abs err {err:.3e} (tol {KERNEL_TOL:.0e})")
        if not err <= KERNEL_TOL:
            fail(f"Pallas {name} differs from its reference")
    say(f"kernels: compile + run {wall:.2f} s")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smoke widths, Pallas interpreted (CPU rehearsal)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    t_start = time.perf_counter()

    cache_dir = configure_compile_cache()
    entries_before = cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({entries_before} entries before)")

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    say(f"device: {json.dumps(device)}")
    if dev.platform != "tpu" and not args.smoke:
        fail(f"no TPU: JAX runs on {dev.platform!r}")
    hw = hardware_for(dev)

    built = {"programs": 0, "from_disk": 0}

    def on_duration(event, _secs, **_kw):
        if event == BACKEND_COMPILE_EVENT:
            built["programs"] += 1

    def on_event(event, **_kw):
        if event == CACHE_HIT_EVENT:
            built["from_disk"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    # -- config: published widths, depth cut to one chip ------------------
    full = get_config(ARCH)
    base = (dataclasses.replace(get_smoke_config(ARCH), dtype=full.dtype)
            if args.smoke else full)
    cfg, n_params, need = cut_depth(base, hw.hbm_bytes)
    say(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.n_heads}x"
        f"{cfg.resolved_head_dim} kv_heads={cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} dtype={cfg.dtype}")
    say(f"depth: n_layers {base.n_layers} -> {cfg.n_layers} (the VM cannot "
        f"donate, so a step holds old + new params and Adam state plus "
        f"grads, 22 B/param; budget {STATE_SHARE:.0%} of "
        f"{hw.hbm_bytes / 2**30:.0f} GiB HBM); params {n_params:,}, "
        f"reckoned step state {need / 2**30:.2f} GiB")

    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    opt_state = init_state(params, adamw_config_for(cfg))

    # -- the trainer's dynamic step: optimize() with symbolic (b, s) -------
    t0 = time.perf_counter()
    dyn = build_dynamic_step(cfg, params, opt_state)
    say(f"optimize: traced + planned in {time.perf_counter() - t0:.2f} s "
        f"(host)")

    if args.smoke:
        pipe_cfg = PipelineConfig(vocab=cfg.vocab, batch_size=2,
                                  seed=args.seed, min_tokens=16,
                                  max_tokens=64, align=16)
    else:
        pipe_cfg = PipelineConfig(vocab=cfg.vocab, batch_size=4,
                                  seed=args.seed, min_tokens=128,
                                  max_tokens=512, align=128)
    pipe = DataPipeline(pipe_cfg)
    batches = []
    for _ in range(STEPS):
        raw = pipe.next_batch()
        batches.append({k: jnp.asarray(raw[k])
                        for k in ("tokens", "labels", "mask")})
    shapes = [b["tokens"].shape for b in batches]
    if len(set(shapes)) < 2 or len(set(shapes)) == len(shapes):
        fail(f"batch shapes {shapes} need two distinct and one repeated")

    # -- reference: the same step under plain jax.jit, first batch --------
    ref_cfg = dataclasses.replace(cfg, scan_layers=False)
    summarize = jax.jit(leaf_summaries)
    t0 = time.perf_counter()
    out = jax.jit(make_train_step(ref_cfg))(params, opt_state, batches[0])
    ref = jax.device_get(summarize(params, *out))
    del out
    say(f"reference: jax.jit step on {shapes[0]} in "
        f"{time.perf_counter() - t0:.2f} s (compile included)")
    mem = dev.memory_stats() or {}
    peak_ref = mem.get("peak_bytes_in_use")

    # -- training steps through the VM -------------------------------------
    seen = set()
    for i, batch in enumerate(batches):
        shape = batch["tokens"].shape
        new = shape not in seen
        seen.add(shape)
        b0 = dict(built)
        t0 = time.perf_counter()
        loss, new_params, new_opt = dyn(params, opt_state, batch)
        jax.block_until_ready((loss, new_params, new_opt))
        wall = time.perf_counter() - t0
        programs = built["programs"] - b0["programs"]
        from_disk = built["from_disk"] - b0["from_disk"]
        say(f"step {i}: (b, s) = {shape} loss {float(loss):.6f} "
            f"wall {wall:.3f} s new_shape={new} xla_programs={programs} "
            f"(compiled {programs - from_disk}, from disk cache "
            f"{from_disk})")
        if not math.isfinite(float(loss)):
            fail(f"step {i}: loss is not finite")
        if i == 0:
            compare(jax.device_get(summarize(params, loss, new_params,
                                             new_opt)), ref)
        params, opt_state = new_params, new_opt
        del new_params, new_opt

    # -- memory: the device's own counter beside the plan's bookkeeping ----
    stats = dyn.last_report.stats
    mem = dev.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    say(f"memory: device peak_bytes_in_use {peak} (after the jit "
        f"reference: {peak_ref}); host bookkeeping: plan device_peak "
        f"{stats.device_peak}, guaranteed_peak_bytes "
        f"{dyn.guaranteed_peak_bytes}")

    kernel_phase(args.smoke)

    say(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries "
        f"after, {entries_before} before)")
    say(f"total: {time.perf_counter() - t_start:.1f} s")
    if args.smoke:
        fail("every phase ran, but at smoke widths (--smoke)")
    if dev.platform != "tpu":
        fail(f"every phase ran, but on {dev.platform!r}, not a TPU")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
