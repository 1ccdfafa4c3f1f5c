"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import flash_attention, rmsnorm
from repro.kernels.ref import reference_attention, reference_rmsnorm

RNG = np.random.RandomState(0)


def _tol(dt):
    return 5e-2 if dt == jnp.bfloat16 else 2e-4


@pytest.mark.parametrize("b,hq,hkv,s,hd", [
    (2, 4, 2, 256, 64),    # GQA
    (1, 8, 1, 128, 128),   # MQA, MXU-aligned head
    (2, 4, 4, 100, 64),    # MHA, ragged seq (padding path)
    (1, 6, 2, 384, 32),    # narrow head
    (3, 2, 1, 64, 64),     # small batch of rows
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_reference(b, hq, hkv, s, hd, dtype):
    q = jnp.asarray(RNG.randn(b, hq, s, hd), dtype)
    k = jnp.asarray(RNG.randn(b, hkv, s, hd), dtype)
    v = jnp.asarray(RNG.randn(b, hkv, s, hd), dtype)
    o = flash_attention(q, k, v, causal=True, interpret=True)
    r = reference_attention(q, k, v, causal=True)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    assert err < _tol(dtype), err


def test_flash_attention_noncausal():
    q = jnp.asarray(RNG.randn(1, 2, 128, 64), jnp.float32)
    k = jnp.asarray(RNG.randn(1, 2, 128, 64), jnp.float32)
    v = jnp.asarray(RNG.randn(1, 2, 128, 64), jnp.float32)
    o = flash_attention(q, k, v, causal=False, interpret=True)
    r = reference_attention(q, k, v, causal=False)
    assert float(jnp.max(jnp.abs(o - r))) < 2e-4


def test_flash_attention_block_shape_sweep():
    q = jnp.asarray(RNG.randn(1, 2, 256, 64), jnp.float32)
    k = jnp.asarray(RNG.randn(1, 2, 256, 64), jnp.float32)
    v = jnp.asarray(RNG.randn(1, 2, 256, 64), jnp.float32)
    r = reference_attention(q, k, v, causal=True)
    for bq, bkv in [(64, 64), (128, 64), (64, 128), (128, 128)]:
        o = flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bkv,
                            interpret=True)
        assert float(jnp.max(jnp.abs(o - r))) < 2e-4, (bq, bkv)


@pytest.mark.parametrize("n,d", [(64, 256), (100, 300), (32, 2048), (7, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_reference(n, d, dtype):
    x = jnp.asarray(RNG.randn(n, d), dtype)
    s = jnp.asarray(RNG.randn(d) * 0.1, dtype)
    o = rmsnorm(x, s, interpret=True)
    r = reference_rmsnorm(x, s)
    err = float(jnp.max(jnp.abs(o.astype(jnp.float32) - r.astype(jnp.float32))))
    assert err < _tol(dtype), err


def test_rmsnorm_3d_input():
    x = jnp.asarray(RNG.randn(2, 33, 160), jnp.float32)
    s = jnp.asarray(RNG.randn(160) * 0.1, jnp.float32)
    o = rmsnorm(x, s, interpret=True)
    r = reference_rmsnorm(x, s)
    assert o.shape == x.shape
    assert float(jnp.max(jnp.abs(o - r))) < 1e-4


def test_interpret_mode_only_by_default_on_the_cpu(monkeypatch):
    """Unset ``interpret`` compiles on a TPU, interprets on the CPU, and
    refuses any other backend instead of silently interpreting there."""
    from repro.kernels import ops
    assert ops._default_interpret() is True
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._default_interpret() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no path on backend 'gpu'"):
        ops._default_interpret()
