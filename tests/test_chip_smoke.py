"""The VM-versus-jit check of ``chip_smoke.py``, held to planted faults.

``chip_smoke.compare`` holds the ProgramVM's first training step to the
same step under plain ``jax.jit``: relative tolerances on the loss and
on each parameter leaf's gradient and update norms.  Here, on the CPU
at the smoke widths (one layer, bf16), the VM's step must pass it, and
two faults must fail it: every bf16 intermediate rounded through fp8
(e4m3), and the embedding's update dropped.  The fp8 case keeps the
reference's loss, so the per-leaf norms alone have to catch it.
"""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.extend.core import Literal

from repro.configs import get_smoke_config
from repro.data import DataPipeline, PipelineConfig
from repro.launch.steps import adamw_config_for, make_train_step
from repro.launch.train import build_dynamic_step
from repro.models import init_params
from repro.optim import init_state

_SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def cs():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run(cs):
    """Config, state, first batch, and the jit step's summaries."""
    cfg = dataclasses.replace(get_smoke_config(cs.ARCH), n_layers=1,
                              dtype="bfloat16")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_state(params, adamw_config_for(cfg))
    raw = DataPipeline(PipelineConfig(vocab=cfg.vocab, batch_size=2, seed=0,
                                      min_tokens=16, max_tokens=64,
                                      align=16)).next_batch()
    batch = {k: jnp.asarray(raw[k]) for k in ("tokens", "labels", "mask")}
    step = make_train_step(dataclasses.replace(cfg, scan_layers=False))
    summarize = jax.jit(cs.leaf_summaries)
    ref = jax.device_get(summarize(params, *jax.jit(step)(params, opt,
                                                          batch)))
    return dict(cfg=cfg, params=params, opt=opt, batch=batch, step=step,
                summarize=summarize, ref=ref)


def _fp8_intermediates(fn, *args):
    """Evaluate ``fn`` one primitive at a time, as the VM does, rounding
    every bf16 result through float8_e4m3fn."""
    closed = jax.make_jaxpr(fn)(*args)
    env = dict(zip(closed.jaxpr.constvars, closed.consts))
    env.update(zip(closed.jaxpr.invars, jax.tree.leaves(args)))
    read = lambda v: v.val if isinstance(v, Literal) else env[v]
    for eqn in closed.jaxpr.eqns:
        outs = eqn.primitive.bind(*map(read, eqn.invars), **eqn.params)
        if not eqn.primitive.multiple_results:
            outs = [outs]
        for v, o in zip(eqn.outvars, outs):
            env[v] = (o.astype(jnp.float8_e4m3fn).astype(o.dtype)
                      if o.dtype == jnp.bfloat16 else o)
    treedef = jax.tree.structure(jax.eval_shape(fn, *args))
    return jax.tree.unflatten(treedef, map(read, closed.jaxpr.outvars))


def test_vm_step_agrees_with_jit(cs, run):
    dyn = build_dynamic_step(run["cfg"], run["params"], run["opt"])
    out = dyn(run["params"], run["opt"], run["batch"])
    cs.compare(jax.device_get(run["summarize"](run["params"], *out)),
               run["ref"])


def _fp8(run):
    _, grad, upd = jax.device_get(run["summarize"](
        run["params"], *_fp8_intermediates(run["step"], run["params"],
                                           run["opt"], run["batch"])))
    return run["ref"][0], grad, upd


def _dropped_embed_update(run):
    loss, new_params, new_opt = jax.jit(run["step"])(
        run["params"], run["opt"], run["batch"])
    new_params = dict(new_params, embed=run["params"]["embed"])
    return jax.device_get(run["summarize"](run["params"], loss, new_params,
                                           new_opt))


@pytest.mark.parametrize("fault, caught_by", [
    (_fp8, "grad norm"),
    (_dropped_embed_update, "update norm of ['embed']"),
], ids=["fp8_intermediates", "dropped_embed_update"])
def test_planted_fault_fails_the_check(cs, run, capsys, fault, caught_by):
    with pytest.raises(SystemExit) as exc:
        cs.compare(fault(run), run["ref"])
    assert exc.value.code == 1
    assert caught_by in capsys.readouterr().err
