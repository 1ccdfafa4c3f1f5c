"""Top-level language model: init / forward / loss / prefill / decode.

One code path serves all ten assigned architectures, driven by
``ModelConfig``.  Layers are stacked pytrees scanned with ``lax.scan`` (so
the compiled HLO is one block, not n_layers copies) with a configurable
remat policy.  xLSTM uses grouped stacks (runs of mLSTM + periodic sLSTM).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from .attention import KVCache
from .blocks import BlockCache, block_apply, block_decode, block_init
from .common import KeyGen, dense_init, embed_init, rms_norm, softmax_cross_entropy
from .mla import MLACache
from .ssm import ssm_init_cache
from .xlstm import (MLSTMCache, SLSTMCache, mlstm_apply, mlstm_decode_step,
                    mlstm_init, mlstm_init_cache, slstm_apply,
                    slstm_decode_step, slstm_init, slstm_init_cache)


# -- per-layer window schedule (hybrid archs) -------------------------------------


def layer_windows(cfg: ModelConfig) -> Optional[np.ndarray]:
    """hymba-style: sliding window everywhere except {first, middle, last}."""
    if cfg.window is None:
        return None
    big = np.int32(2 ** 30)  # "global" == effectively unbounded window
    w = np.full((cfg.n_layers,), cfg.window, np.int32)
    for g in {0, cfg.n_layers // 2, cfg.n_layers - 1}:
        w[g] = big
    return w


def xlstm_meta(cfg: ModelConfig) -> Dict[str, int]:
    n_s = cfg.n_layers // cfg.slstm_every if cfg.slstm_every else 0
    n_groups = max(n_s, 1)
    m_per_group = (cfg.n_layers - n_s) // n_groups
    return dict(n_groups=n_groups, m_per_group=m_per_group, n_s=n_s)


# -- init --------------------------------------------------------------------------


def init_params(cfg: ModelConfig, key) -> Dict:
    kg = KeyGen(key)
    dtype = cfg.jax_dtype
    p: Dict[str, Any] = {}
    vpad = cfg.padded_vocab
    if cfg.input_mode in ("tokens", "vlm"):
        p["embed"] = embed_init(kg(), (vpad, cfg.d_model), dtype=dtype)
    if not cfg.tie_embeddings or cfg.input_mode == "embeddings":
        if cfg.n_codebooks:
            p["lm_head"] = dense_init(kg(), (cfg.d_model,
                                             cfg.n_codebooks * vpad),
                                      dtype=dtype)
        else:
            p["lm_head"] = dense_init(kg(), (cfg.d_model, vpad), dtype=dtype)
    p["final_norm"] = jnp.zeros((cfg.d_model,), dtype)

    if cfg.block_kind == "xlstm":
        xc = cfg.xlstm_config()
        meta = xlstm_meta(cfg)
        base = kg()
        mkeys = jnp.stack([jnp.stack([jax.random.fold_in(base, i * 100 + j)
                                      for j in range(meta["m_per_group"])])
                           for i in range(meta["n_groups"])])
        p["mlstm"] = jax.vmap(jax.vmap(lambda k: mlstm_init(k, xc, dtype)))(mkeys)
        if meta["n_s"]:
            skeys = jnp.stack([jax.random.fold_in(base, 10_000 + i)
                               for i in range(meta["n_s"])])
            p["slstm"] = jax.vmap(lambda k: slstm_init(k, xc, dtype))(skeys)
        return p

    keys = jnp.stack([jax.random.fold_in(kg(), i) for i in range(cfg.n_layers)])
    p["layers"] = jax.vmap(lambda k: block_init(k, cfg, dtype))(keys)
    return p


# -- forward (training / prefill path) ----------------------------------------------


def _embed_inputs(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    dtype = cfg.jax_dtype
    if cfg.input_mode == "tokens":
        return params["embed"][batch["tokens"]].astype(dtype)
    if cfg.input_mode == "embeddings":
        return batch["frame_embed"].astype(dtype)
    if cfg.input_mode == "vlm":
        txt = params["embed"][batch["tokens"]].astype(dtype)
        vis = batch["vis_embed"].astype(dtype)
        return jnp.concatenate([vis, txt], axis=1)
    raise ValueError(cfg.input_mode)


def _mask_pad_vocab(cfg: ModelConfig, logits: jax.Array) -> jax.Array:
    """Pad-vocab columns (table padded to a tile boundary) never win."""
    if cfg.padded_vocab == cfg.vocab:
        return logits
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    len(logits.shape) - 1)
    return jnp.where(iota < cfg.vocab, logits,
                     jnp.asarray(-1e30, logits.dtype))


def _lm_logits(cfg: ModelConfig, params: Dict, h: jax.Array) -> jax.Array:
    from .common import DP, shard_hint
    h = rms_norm(h, params["final_norm"])
    if cfg.n_codebooks:
        logits = h @ params["lm_head"]
        b, s, _ = h.shape
        logits = shard_hint(
            logits.reshape(b, s, cfg.n_codebooks, cfg.padded_vocab),
            DP, None, None, "model")
        return _mask_pad_vocab(cfg, logits)
    if cfg.tie_embeddings and cfg.input_mode != "embeddings":
        logits = h @ params["embed"].T.astype(h.dtype)
    else:
        logits = h @ params["lm_head"]
    return _mask_pad_vocab(cfg, shard_hint(logits, DP, None, "model"))


def _scan_blocks(cfg: ModelConfig, params: Dict, x: jax.Array,
                 q_offset=0) -> Tuple[jax.Array, jax.Array]:
    windows = layer_windows(cfg)

    if not cfg.scan_layers:
        # python-unrolled: the flat graph the dynamic-shape optimizer
        # schedules / rematerializes (remat is *its* job, not jax.checkpoint's)
        aux = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layers):
            with jax.named_scope(f"layer{i}"):
                layer_p = jax.tree.map(lambda a: a[i], params["layers"])
                w = None if windows is None else int(windows[i])
                x, a = block_apply(layer_p, cfg, x, window=w,
                                   q_offset=q_offset)
                aux = aux + a
        return x, aux

    def body(carry, xs):
        h, aux = carry
        if windows is None:
            layer_p = xs
            w = None
        else:
            layer_p, w = xs
        y, a = block_apply(layer_p, cfg, h, window=w, q_offset=q_offset)
        return (y, aux + a), None

    body_fn = body
    if cfg.remat_policy != "none":
        body_fn = jax.checkpoint(body, prevent_cse=False)
    xs = params["layers"] if windows is None else (params["layers"],
                                                   jnp.asarray(windows))
    (h, aux), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)), xs)
    return h, aux


def _xlstm_forward(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    xc = cfg.xlstm_config()
    meta = xlstm_meta(cfg)

    def m_body(h, layer_p):
        return h + mlstm_apply(layer_p, xc, rms_norm(h, layer_p["ln"])), None

    m_fn = jax.checkpoint(m_body, prevent_cse=False) \
        if cfg.remat_policy != "none" else m_body
    for g in range(meta["n_groups"]):
        group_p = jax.tree.map(lambda a: a[g], params["mlstm"])
        x, _ = jax.lax.scan(m_fn, x, group_p)
        if meta["n_s"]:
            sp = jax.tree.map(lambda a: a[g], params["slstm"])
            x = x + slstm_apply(sp, xc, rms_norm(x, sp["ln"]))
    return x


def _forward_hidden(cfg: ModelConfig, params: Dict,
                    batch: Dict) -> Tuple[jax.Array, jax.Array]:
    """The final hidden states and the MoE aux loss, under the ``embed``
    and ``layer{i}`` name scopes."""
    with jax.named_scope("embed"):
        x = _embed_inputs(cfg, params, batch)
    if cfg.block_kind == "xlstm":
        return _xlstm_forward(cfg, params, x), jnp.zeros((), jnp.float32)
    return _scan_blocks(cfg, params, x)


def forward(cfg: ModelConfig, params: Dict, batch: Dict) -> Tuple[jax.Array, jax.Array]:
    """Returns (logits, moe_aux_loss)."""
    h, aux = _forward_hidden(cfg, params, batch)
    return _lm_logits(cfg, params, h), aux


def loss_fn(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    h, aux = _forward_hidden(cfg, params, batch)
    with jax.named_scope("head_loss"):
        logits = _lm_logits(cfg, params, h)
        labels = batch["labels"]
        if cfg.input_mode == "vlm":  # loss only over text positions
            logits = logits[:, -labels.shape[1]:]
        if cfg.n_codebooks:
            loss = softmax_cross_entropy(logits, labels)  # labels (B,S,K)
        else:
            loss = softmax_cross_entropy(logits, labels, batch.get("mask"))
        return loss + 0.01 * aux


# -- serving: prefill + single-token decode -------------------------------------------


class DecodeState(NamedTuple):
    caches: Any          # stacked per-layer caches
    xlstm: Any = None    # xlstm grouped caches


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> DecodeState:
    dtype = cfg.jax_dtype
    hd = cfg.resolved_head_dim
    if cfg.block_kind == "xlstm":
        xc = cfg.xlstm_config()
        meta_groups = max(cfg.n_layers // cfg.slstm_every, 1)
        m_per = (cfg.n_layers - cfg.n_layers // cfg.slstm_every) // meta_groups
        m_cache = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (meta_groups, m_per) + a.shape),
            mlstm_init_cache(xc, batch, dtype))
        s_cache = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (meta_groups,) + a.shape),
            slstm_init_cache(xc, batch))
        return DecodeState(caches=None, xlstm=(m_cache, s_cache))
    L = cfg.n_layers
    if cfg.attn_kind == "mla":
        kv = MLACache(
            c_kv=jnp.zeros((L, batch, max_len, cfg.r_kv), dtype),
            k_rope=jnp.zeros((L, batch, max_len, cfg.qk_rope), dtype),
            length=jnp.zeros((L,), jnp.int32))
    else:
        kv = KVCache(
            k=jnp.zeros((L, batch, max_len, cfg.n_kv_heads, hd), dtype),
            v=jnp.zeros((L, batch, max_len, cfg.n_kv_heads, hd), dtype),
            length=jnp.zeros((L,), jnp.int32))
    ssm = None
    if cfg.family == "hybrid":
        ssm = jax.tree.map(lambda a: jnp.broadcast_to(a, (L,) + a.shape),
                           ssm_init_cache(cfg.ssm_config(), batch, dtype))
    return DecodeState(caches=BlockCache(kv=kv, ssm=ssm))


def prefill(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    """Forward over the prompt; returns last-position logits.

    (Cache-filling prefill for serving reuses forward() compute; for the
    dry-run cells the compiled artifact of interest is this forward.)
    """
    logits, _ = forward(cfg, params, batch)
    return logits[:, -1]


def decode_step(cfg: ModelConfig, params: Dict, state: DecodeState,
                inp: Dict) -> Tuple[jax.Array, DecodeState]:
    """One new token against the running cache.

    inp: {'token': (B,1)} or {'frame_embed': (B,1,D)} per input_mode.
    """
    dtype = cfg.jax_dtype
    if cfg.input_mode in ("tokens", "vlm"):
        x = params["embed"][inp["token"]].astype(dtype)
    else:
        x = inp["frame_embed"].astype(dtype)

    if cfg.block_kind == "xlstm":
        xc = cfg.xlstm_config()
        meta = xlstm_meta(cfg)
        m_cache, s_cache = state.xlstm
        meta_groups = m_cache.c.shape[0]

        def m_body(h, xs):
            layer_p, cache = xs
            y, new_cache = mlstm_decode_step(
                layer_p, xc, rms_norm(h, layer_p["ln"]), cache)
            return h + y, new_cache

        new_m, new_s = [], []
        for g in range(meta_groups):
            gp = jax.tree.map(lambda a: a[g], params["mlstm"])
            gc = jax.tree.map(lambda a: a[g], m_cache)
            x, nc = jax.lax.scan(m_body, x, (gp, gc))
            new_m.append(nc)
            if meta["n_s"]:
                sp = jax.tree.map(lambda a: a[g], params["slstm"])
                sc = jax.tree.map(lambda a: a[g], s_cache)
                y, nsc = slstm_decode_step(sp, xc, rms_norm(x, sp["ln"]), sc)
                x = x + y
                new_s.append(nsc)
        m_stack = jax.tree.map(lambda *a: jnp.stack(a), *new_m)
        s_stack = jax.tree.map(lambda *a: jnp.stack(a), *new_s) if new_s else s_cache
        logits = _lm_logits(cfg, params, x)[:, -1:]
        return logits, DecodeState(caches=None, xlstm=(m_stack, s_stack))

    windows = layer_windows(cfg)

    def body(h, xs):
        if windows is None:
            layer_p, cache = xs
            w = None
        else:
            layer_p, cache, w = xs
        y, new_cache = block_decode(layer_p, cfg, h, cache, window=w)
        return y, new_cache

    xs = (params["layers"], state.caches) if windows is None else \
        (params["layers"], state.caches, jnp.asarray(windows))
    x, new_caches = jax.lax.scan(body, x, xs)
    logits = _lm_logits(cfg, params, x)[:, -1:]
    return logits, DecodeState(caches=new_caches)
