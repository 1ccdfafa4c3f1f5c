"""Public kernel entry points, registered as first-class primitives.

``flash_attention`` and ``rmsnorm`` used to be plain jit'd wrappers — a
fixed Pallas configuration fused into whatever jaxpr traced them.  They
are now JAX primitives, so a traced graph carries one node per kernel
call and the compiler can *select* a configuration for it: the variant
registry + cost model in :mod:`repro.kernels.variants` pick block sizes,
pipeline depth, and the ref-vs-pallas crossover per compiled plan, and
the choice is baked into the lowered ``Compute`` instruction.

Dispatch rules of the wrappers:

* an explicit ``impl=`` always wins ('pallas' | 'ref');
* passing any Pallas-specific argument (``block_q``/``block_kv``/
  ``block_rows``/``interpret``) implies ``impl='pallas'`` — existing
  call sites keep their exact behavior;
* otherwise ``impl`` stays ``None`` — *auto*: an eager call resolves it
  through the cost model at the concrete shape (tiny-d ``rmsnorm`` hits
  the reference implementation instead of padding d up to 128), while a
  call under ``repro.optimize`` tracing leaves the sentinel in the node
  params for plan-time per-bucket selection to overwrite.

``interpret`` left unset resolves from the backend: ``False`` on a TPU
(the compiled Mosaic kernel), ``True`` on the CPU (the kernel body runs
in Python — how the test suite checks it).  Any other backend raises: a
kernel never runs interpreted on a device that was not asked for.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from jax.extend.core import Primitive
from jax.interpreters import mlir

from repro.core.ir.dynamism import DimIntroSpec, register_introduces_dim

from . import flash_attention as _fa
from . import ref as _ref
from . import rmsnorm as _rn
from . import variants as _variants


def _default_interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas TPU kernels have no path on backend {backend!r}: run "
        f"on a TPU, or on the CPU (interpret mode), or pass impl='ref'")


# jit'd workers — every knob static so each resolved configuration
# compiles once and replays from cache
_fa_pallas = partial(jax.jit, static_argnames=(
    "causal", "softmax_scale", "block_q", "block_kv", "interpret"))(
        _fa.flash_attention)
_fa_ref = partial(jax.jit, static_argnames=("causal", "softmax_scale"))(
    _ref.reference_attention)
_rn_pallas = partial(jax.jit, static_argnames=(
    "eps", "block_rows", "interpret"))(_rn.rmsnorm)
_rn_ref = partial(jax.jit, static_argnames=("eps",))(_ref.reference_rmsnorm)


def _flash_run(q, k, v, *, causal: bool = True,
               softmax_scale: Optional[float] = None,
               block_q: Optional[int] = None, block_kv: Optional[int] = None,
               interpret: Optional[bool] = None, impl: Optional[str] = None,
               pipeline_depth: int = 2):
    """Concrete-shape dispatcher behind the flash_attention primitive."""
    del pipeline_depth  # VMEM-accounting knob only; Pallas double buffers
    if impl is None:
        b, hq, s, hd = q.shape
        t = k.shape[2]
        chosen = _variants.select_eager(
            "flash_attention", {"b": b, "hq": hq, "s": s, "t": t, "hd": hd},
            jnp.dtype(q.dtype).itemsize, {"causal": causal})
        impl = chosen.impl
        if impl == "pallas":
            block_q = block_q or chosen.block_of("block_q", 128)
            block_kv = block_kv or chosen.block_of("block_kv", 128)
    if impl == "ref":
        return _fa_ref(q, k, v, causal=causal, softmax_scale=softmax_scale)
    interp = _default_interpret() if interpret is None else interpret
    return _fa_pallas(q, k, v, causal=causal, softmax_scale=softmax_scale,
                      block_q=block_q or 128, block_kv=block_kv or 128,
                      interpret=interp)


def _rmsnorm_run(x, scale, *, eps: float = 1e-6,
                 block_rows: Optional[int] = None,
                 interpret: Optional[bool] = None, impl: Optional[str] = None,
                 pipeline_depth: int = 2):
    """Concrete-shape dispatcher behind the rmsnorm primitive."""
    del pipeline_depth
    if impl is None:
        d = x.shape[-1]
        n = 1
        for s in x.shape[:-1]:
            n *= s
        chosen = _variants.select_eager(
            "rmsnorm", {"n": n, "d": d}, jnp.dtype(x.dtype).itemsize, {})
        impl = chosen.impl
        if impl == "pallas":
            block_rows = block_rows or chosen.block_of("block_rows", 256)
    if impl == "ref":
        return _rn_ref(x, scale, eps=eps)
    interp = _default_interpret() if interpret is None else interpret
    return _rn_pallas(x, scale, eps=eps, block_rows=block_rows or 256,
                      interpret=interp)


def _kernel_primitive(name: str, run) -> Primitive:
    p = Primitive(name)
    p.def_impl(run)

    def abse(*avals, **params):
        from jax.core import ShapedArray
        a = avals[0]
        return ShapedArray(a.shape, a.dtype)

    p.def_abstract_eval(abse)
    # usable under an outer jax.jit
    mlir.register_lowering(p, mlir.lower_fun(run, multiple_results=False))
    return p


_flash_attention_p = _kernel_primitive("flash_attention", _flash_run)
_rmsnorm_p = _kernel_primitive("rmsnorm", _rmsnorm_run)


def run_kernel(prim_name: str, arrays: Sequence[Any],
               params: Dict[str, Any]):
    """Invoke a kernel dispatcher directly (the measured-fallback timer)."""
    if prim_name == "flash_attention":
        return _flash_run(*arrays, **params)
    if prim_name == "rmsnorm":
        return _rmsnorm_run(*arrays, **params)
    raise KeyError(prim_name)


def flash_attention(q, k, v, *, causal: bool = True,
                    softmax_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_kv: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    impl: Optional[str] = None):
    """q: (B, Hq, S, hd); k/v: (B, Hkv, T, hd)."""
    if impl is None and (block_q is not None or block_kv is not None
                         or interpret is not None):
        impl = "pallas"
    return _flash_attention_p.bind(q, k, v, causal=causal,
                                   softmax_scale=softmax_scale,
                                   block_q=block_q, block_kv=block_kv,
                                   interpret=interpret, impl=impl,
                                   pipeline_depth=2)


def rmsnorm(x, scale, *, eps: float = 1e-6, block_rows: Optional[int] = None,
            interpret: Optional[bool] = None, impl: Optional[str] = None):
    if impl is None and (block_rows is not None or interpret is not None):
        impl = "pallas"
    return _rmsnorm_p.bind(x, scale, eps=eps, block_rows=block_rows,
                           interpret=interpret, impl=impl, pipeline_depth=2)


# ---------------------------------------------------------------------------
# Value-dependent bounded ops (dynamism *introducers*, SoD² taxonomy).
#
# Each primitive returns ``(payload, count)``: the payload is padded to
# its symbolic bound (the input's static/cap shape) with zeros past the
# valid prefix, and ``count`` is the measured i32 extent.  Registering
# with ``register_introduces_dim`` makes the tracer rewrite the payload's
# leading dim to a fresh bounded symbol ``__b<k> <= cap``, which the
# planner reserves at the cap and the runtime re-binds tight (``BindDim``).
# The eager impls are the padded-to-bound oracles in ``kernels.ref`` —
# both executors run the identical impl, keeping the differential
# contract bitwise.
# ---------------------------------------------------------------------------


def _i32_scalar(_: object = None):
    from jax.core import ShapedArray
    return ShapedArray((), jnp.int32)


def _bounded_primitive(name: str, impl, abstract_eval,
                       spec: Optional[DimIntroSpec] = None) -> Primitive:
    p = Primitive(name)
    p.multiple_results = True
    p.def_impl(lambda *xs, **kw: list(impl(*xs, **kw)))
    p.def_abstract_eval(abstract_eval)
    register_introduces_dim(name, spec)
    return p


def _abse_like(i):
    """Payload aval == input ``i``'s aval; plus the i32 count scalar."""
    def abse(*avals):
        from jax.core import ShapedArray
        a = avals[i]
        return [ShapedArray(a.shape, a.dtype), _i32_scalar()]
    return abse


def _abse_idx(*avals):
    from jax.core import ShapedArray
    return [ShapedArray(avals[0].shape, jnp.int32), _i32_scalar()]


_nonzero_pad_p = _bounded_primitive(
    "nonzero_pad", _ref.reference_nonzero_pad, _abse_idx)
_masked_select_p = _bounded_primitive(
    "masked_select", _ref.reference_masked_select, _abse_like(0))
_topk_dynamic_p = _bounded_primitive(
    "topk_dynamic", _ref.reference_topk_dynamic, _abse_like(0))
_unique_bounded_p = _bounded_primitive(
    "unique_bounded", _ref.reference_unique_bounded, _abse_like(0))


def nonzero_pad(x):
    """Indices of nonzero entries of 1-D ``x`` -> ``(idx_padded, count)``.

    ``idx_padded`` is i32 with the same length as ``x``; entries past
    ``count`` are zero.  Under ``optimize`` the output length becomes a
    bounded dim ``b <= len(x)``."""
    a, c = _nonzero_pad_p.bind(x)
    return a, c


def masked_select(x, mask):
    """Rows of ``x`` (leading axis) where 1-D ``mask`` holds, compacted
    to the front -> ``(rows_padded, count)``."""
    a, c = _masked_select_p.bind(x, mask)
    return a, c


def topk_dynamic(x, k):
    """Largest ``k`` values of 1-D ``x`` with a *data-dependent* ``k``
    (i32 scalar array), descending -> ``(vals_padded, count)``."""
    a, c = _topk_dynamic_p.bind(x, k)
    return a, c


def unique_bounded(x):
    """Sorted distinct values of 1-D ``x`` -> ``(unique_padded, count)``."""
    a, c = _unique_bounded_p.bind(x)
    return a, c
