"""Transformer / hybrid / xLSTM blocks assembled from the mixer modules."""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import KVCache, attention, decode_attention, kv_cache_update
from .common import DP, KeyGen, apply_rope, dense_init, rms_norm, shard_hint
from .ffn import ffn_apply, ffn_init, moe_apply, moe_init
from .mla import MLACache, MLAConfig, mla_decode, mla_init, mla_prefill
from .ssm import SSMCache, SSMConfig, ssm_apply, ssm_decode_step, ssm_init, ssm_init_cache


# -- GQA attention sub-block -----------------------------------------------------


def gqa_init(key, d_model: int, n_heads: int, n_kv_heads: int, head_dim: int,
             dtype=jnp.float32) -> Dict:
    kg = KeyGen(key)
    return {
        "wq": dense_init(kg(), (d_model, n_heads * head_dim), dtype=dtype),
        "wk": dense_init(kg(), (d_model, n_kv_heads * head_dim), dtype=dtype),
        "wv": dense_init(kg(), (d_model, n_kv_heads * head_dim), dtype=dtype),
        "wo": dense_init(kg(), (n_heads * head_dim, d_model), dtype=dtype),
    }


def gqa_qkv(params: Dict, x: jax.Array, n_heads: int, n_kv_heads: int,
            head_dim: int, positions, rope_theta: float):
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, n_heads, head_dim)
    k = (x @ params["wk"]).reshape(b, s, n_kv_heads, head_dim)
    v = (x @ params["wv"]).reshape(b, s, n_kv_heads, head_dim)
    q = shard_hint(apply_rope(q, positions, rope_theta), DP, None, "model", None)
    k = shard_hint(apply_rope(k, positions, rope_theta), DP, None, "model", None)
    v = shard_hint(v, DP, None, "model", None)
    return q, k, v


def gqa_full(params: Dict, x: jax.Array, *, n_heads, n_kv_heads, head_dim,
             rope_theta, q_offset=0, window=None) -> jax.Array:
    b, s, _ = x.shape
    positions = q_offset + jnp.arange(s)[None, :]
    q, k, v = gqa_qkv(params, x, n_heads, n_kv_heads, head_dim, positions,
                      rope_theta)
    out = attention(q, k, v, causal=True, q_offset=q_offset, window=window)
    return out.reshape(b, s, n_heads * head_dim) @ params["wo"]


def gqa_decode(params: Dict, x: jax.Array, cache: KVCache, *, n_heads,
               n_kv_heads, head_dim, rope_theta, window=None
               ) -> Tuple[jax.Array, KVCache]:
    b, s, _ = x.shape  # s == 1
    positions = jnp.broadcast_to(cache.length[None, None], (b, s))
    q, k, v = gqa_qkv(params, x, n_heads, n_kv_heads, head_dim, positions,
                      rope_theta)
    cache = kv_cache_update(cache, k, v)
    out = decode_attention(q, cache, window=window)
    return out.reshape(b, s, n_heads * head_dim) @ params["wo"], cache


# -- Transformer block (dense or MoE FFN; GQA or MLA attention) -------------------


def block_init(key, cfg, dtype=jnp.float32) -> Dict:
    kg = KeyGen(key)
    p: Dict[str, Any] = {
        "ln1": jnp.zeros((cfg.d_model,), dtype),
        "ln2": jnp.zeros((cfg.d_model,), dtype),
    }
    if cfg.attn_kind == "mla":
        p["attn"] = mla_init(kg(), cfg.mla_config(), dtype=dtype)
    else:
        p["attn"] = gqa_init(kg(), cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                             cfg.resolved_head_dim, dtype=dtype)
    if cfg.n_experts:
        p["moe"] = moe_init(kg(), cfg.d_model, cfg.d_ff, cfg.n_experts,
                            cfg.n_shared, dtype=dtype)
    elif cfg.d_ff:
        p["ffn"] = ffn_init(kg(), cfg.d_model, cfg.d_ff, dtype=dtype)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_init(kg(), cfg.ssm_config(), dtype=dtype)
        p["ln_ssm"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def block_apply(params: Dict, cfg, x: jax.Array, *, window=None,
                q_offset=0) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence block forward. Returns (y, moe_aux).

    The two halves, residual add included, run under the ``attn`` and
    ``mlp`` name scopes: the op groups a profile of the step shows."""
    aux = jnp.zeros((), jnp.float32)
    x = shard_hint(x, DP, None, None)
    with jax.named_scope("attn"):
        h = rms_norm(x, params["ln1"])
        if cfg.attn_kind == "mla":
            attn_out, _ = mla_prefill(params["attn"], cfg.mla_config(), h,
                                      q_offset=q_offset)
        else:
            attn_out = gqa_full(params["attn"], h, n_heads=cfg.n_heads,
                                n_kv_heads=cfg.n_kv_heads,
                                head_dim=cfg.resolved_head_dim,
                                rope_theta=cfg.rope_theta, q_offset=q_offset,
                                window=window)
        if cfg.family == "hybrid":  # parallel attn + SSM heads (hymba)
            ssm_out = ssm_apply(params["ssm"], cfg.ssm_config(),
                                rms_norm(x, params["ln_ssm"]))
            attn_out = 0.5 * (attn_out + ssm_out)
        x = x + attn_out
    with jax.named_scope("mlp"):
        if cfg.n_experts:
            y, aux = moe_apply(params["moe"], rms_norm(x, params["ln2"]),
                               top_k=cfg.top_k,
                               capacity_factor=cfg.moe_capacity,
                               ffn_kind=cfg.ffn_kind)
            x = x + y
        elif cfg.d_ff:
            x = x + ffn_apply(params["ffn"], rms_norm(x, params["ln2"]),
                              kind=cfg.ffn_kind)
    return x, aux


class BlockCache(NamedTuple):
    kv: Optional[Any] = None      # KVCache or MLACache
    ssm: Optional[SSMCache] = None


def block_decode(params: Dict, cfg, x: jax.Array, cache: BlockCache, *,
                 window=None) -> Tuple[jax.Array, BlockCache]:
    h = rms_norm(x, params["ln1"])
    if cfg.attn_kind == "mla":
        attn_out, kv = mla_decode(params["attn"], cfg.mla_config(), h, cache.kv)
    else:
        attn_out, kv = gqa_decode(params["attn"], h, cache.kv,
                                  n_heads=cfg.n_heads,
                                  n_kv_heads=cfg.n_kv_heads,
                                  head_dim=cfg.resolved_head_dim,
                                  rope_theta=cfg.rope_theta, window=window)
    new_ssm = cache.ssm
    if cfg.family == "hybrid":
        ssm_out, new_ssm = ssm_decode_step(params["ssm"], cfg.ssm_config(),
                                           rms_norm(x, params["ln_ssm"]),
                                           cache.ssm)
        attn_out = 0.5 * (attn_out + ssm_out)
    x = x + attn_out
    if cfg.n_experts:
        y, _ = moe_apply(params["moe"], rms_norm(x, params["ln2"]),
                         top_k=cfg.top_k, capacity_factor=cfg.moe_capacity,
                         ffn_kind=cfg.ffn_kind)
        x = x + y
    elif cfg.d_ff:
        x = x + ffn_apply(params["ffn"], rms_norm(x, params["ln2"]),
                          kind=cfg.ffn_kind)
    return x, BlockCache(kv=kv, ssm=new_ssm)
