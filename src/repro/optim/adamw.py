"""AdamW with decoupled weight decay and global-norm clipping (pure JAX)."""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWConfig(NamedTuple):
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    state_dtype: Any = jnp.float32   # bf16 option for trillion-class configs


class AdamWState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any


def init_state(params, cfg: AdamWConfig = AdamWConfig()) -> AdamWState:
    zeros = lambda p: jnp.zeros_like(p, dtype=cfg.state_dtype)
    return AdamWState(step=jnp.zeros((), jnp.int32),
                      m=jax.tree.map(zeros, params),
                      v=jax.tree.map(zeros, params))


def global_norm(tree) -> jax.Array:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in leaves))


@jax.named_scope("adamw")
def apply_updates(params, grads, state: AdamWState,
                  cfg: AdamWConfig = AdamWConfig()) -> Tuple[Any, AdamWState]:
    step = state.step + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m_new = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * g
        v_new = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * g * g
        mhat = m_new / (1 - cfg.b1 ** step.astype(jnp.float32))
        vhat = v_new / (1 - cfg.b2 ** step.astype(jnp.float32))
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * \
            p.astype(jnp.float32)
        p_new = p.astype(jnp.float32) - cfg.lr * delta
        return (p_new.astype(p.dtype), m_new.astype(cfg.state_dtype),
                v_new.astype(cfg.state_dtype))

    out = jax.tree.map(upd, params, grads, state.m, state.v)
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda x: isinstance(x, tuple))
    new_m = jax.tree.map(lambda t: t[1], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    new_v = jax.tree.map(lambda t: t[2], out,
                         is_leaf=lambda x: isinstance(x, tuple))
    return new_params, AdamWState(step=step, m=new_m, v=new_v)
