"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds for a ``v5e:2x2`` topology
that is described, not attached, and refuses what the chip would refuse
(misaligned tiles, too much VMEM, a program over HBM).  Each kernel
compiles at the main path's real widths — GQA 32/8 attention with
head_dim 128 at s=2048, rmsnorm at d=4096 — once per registered block
configuration: what the cost model calls VMEM-valid must compile, and
what it rules out the compiler must refuse as well.

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process at a time may load the TPU
library, and under pytest-xdist every worker imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention, rmsnorm
from repro.kernels.hw_model import TPU_V5E
from repro.kernels.variants import (FLASH_VARIANTS, RMSNORM_VARIANTS,
                                    variant_valid)

PALLAS_FLASH = [v for v in FLASH_VARIANTS if v.impl == "pallas"]
PALLAS_RMSNORM = [v for v in RMSNORM_VARIANTS if v.impl == "pallas"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        # the TPU library logs under /tmp/tpu_logs unless told otherwise
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _spec(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= TPU_V5E.hbm_bytes, used


@pytest.mark.parametrize("variant", PALLAS_FLASH, ids=lambda v: v.name)
def test_flash_attention_compiles_for_v5e(one_chip, variant):
    b, hq, hkv, s, hd = 1, 32, 8, 2048, 128
    assert variant_valid("flash_attention", variant,
                         {"s": s, "t": s, "hd": hd}, 2, TPU_V5E)
    fn = functools.partial(flash_attention, causal=True, impl="pallas",
                           interpret=False,
                           block_q=variant.block_of("block_q"),
                           block_kv=variant.block_of("block_kv"))
    compiled = jax.jit(fn).lower(
        _spec((b, hq, s, hd), one_chip), _spec((b, hkv, s, hd), one_chip),
        _spec((b, hkv, s, hd), one_chip)).compile()
    _check(compiled)


@pytest.mark.parametrize("variant", PALLAS_RMSNORM, ids=lambda v: v.name)
def test_rmsnorm_compiles_for_v5e(one_chip, variant):
    """Variants the cost model calls VMEM-valid compile; the ones it
    rules out (1024-row blocks at d=4096) the compiler refuses too."""
    n, d = 4096, 4096
    fn = functools.partial(rmsnorm, impl="pallas", interpret=False,
                           block_rows=variant.block_of("block_rows"))
    lowered = jax.jit(fn).lower(_spec((n, d), one_chip),
                                _spec((d,), one_chip))
    if variant_valid("rmsnorm", variant, {"n": n, "d": d}, 2, TPU_V5E):
        _check(lowered.compile())
    else:
        with pytest.raises(jax.errors.JaxRuntimeError, match="vmem"):
            lowered.compile()
