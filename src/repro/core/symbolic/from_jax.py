"""Conversion between JAX shape-polymorphism dims and our SymbolicExpr.

JAX's ``jax.export.symbolic_shape`` dims are ``_DimExpr`` polynomials whose
terms/factors we walk structurally (``_sorted_terms`` → ``(_DimTerm, coeff)``;
``_DimTerm._factors`` → ``(_DimFactor, exp)``; a factor is either a plain
variable or an operation (floordiv/mod/max/min) over sub-_DimExprs).

This module is the bridge between the tracing frontend (jaxprs with
polymorphic avals) and the paper's symbolic machinery.  It is written
against the JAX release pinned in ``pyproject.toml``.
"""
from __future__ import annotations

from typing import Any, Mapping, Optional, Tuple

from jax._src.export.shape_poly import _DimExpr

from .expr import SymbolicExpr


def is_symbolic_dim(d: Any) -> bool:
    return isinstance(d, _DimExpr)


def dim_to_expr(d: Any) -> SymbolicExpr:
    """Convert an int or jax _DimExpr into a SymbolicExpr."""
    if isinstance(d, (int,)):
        return SymbolicExpr.constant(d)
    if not is_symbolic_dim(d):
        raise TypeError(f"not a dimension: {type(d)}")
    out = SymbolicExpr.constant(0)
    for term, coeff in d._sorted_terms:
        t = SymbolicExpr.constant(int(coeff))
        for factor, exp in term._factors:
            base = _factor_to_expr(factor)
            for _ in range(int(exp)):
                t = t * base
        out = out + t
    return out


def _factor_to_expr(factor: Any) -> SymbolicExpr:
    if factor.var is not None:
        return SymbolicExpr.var(str(factor.var))
    op = str(factor.operation)
    operands = tuple(dim_to_expr(o) if is_symbolic_dim(o) else SymbolicExpr.constant(int(o))
                     for o in factor.operands)
    if op == "floordiv":
        return operands[0].floordiv(operands[1])
    if op == "mod":
        return operands[0].mod(operands[1])
    if op == "max":
        return SymbolicExpr.max_of(*operands)
    if op == "min":
        return SymbolicExpr.min_of(*operands)
    # Unknown operation: opaque but evaluable only via jax itself -> treat as
    # a fresh named atom keyed by its repr (sound, loses comparability).
    return SymbolicExpr.var(f"opaque<{factor}>")


def shape_to_exprs(shape: Tuple[Any, ...]) -> Tuple[SymbolicExpr, ...]:
    return tuple(dim_to_expr(d) for d in shape)


def refine_dim(d: Any, env: Mapping[str, int]) -> int:
    """Evaluate a (possibly symbolic) dim to a concrete int given an env."""
    if isinstance(d, int):
        return d
    return dim_to_expr(d).evaluate(env)


# -- declared dim ranges (bounded dynamic shapes) -----------------------------


def parse_range_spec(spec: Any) -> Tuple[Any, Any]:
    """Parse a user-facing dim-range spec into ``(lo, hi)``.

    Accepted forms (``None`` = unbounded on that side):

    - ``(lo, hi)`` tuple/list — either entry may be ``None``;
    - a bare ``int`` N — torch_xla-style ``<=N`` upper bound, lo defaults 1;
    - strings ``"lo..hi"``, ``"..hi"``, ``"lo.."``, ``"<=hi"``, ``">=lo"``.
    """
    if isinstance(spec, int):
        return 1, int(spec)
    if isinstance(spec, (tuple, list)):
        if len(spec) != 2:
            raise ValueError(f"range spec must be (lo, hi), got {spec!r}")
        lo, hi = spec
        return (None if lo is None else int(lo),
                None if hi is None else int(hi))
    if isinstance(spec, str):
        s = spec.replace(" ", "")
        if s.startswith("<="):
            return 1, int(s[2:])
        if s.startswith(">="):
            return int(s[2:]), None
        if ".." in s:
            lo_s, hi_s = s.split("..", 1)
            return (int(lo_s) if lo_s else None), (int(hi_s) if hi_s else None)
        raise ValueError(f"unrecognized range spec {spec!r}")
    raise TypeError(f"unrecognized range spec {spec!r}")


def declare_dim_ranges(shape_graph: Any, specs: Optional[Mapping[str, Any]]) -> None:
    """Record ``optimize(..., dynamic_dims=...)`` range specs on a ShapeGraph.

    ``specs`` maps symbolic dim names (as written in ``symbolic_dims``) to
    :func:`parse_range_spec`-accepted values.  Dims traced but absent from
    ``specs`` keep the default ``[1, +inf)`` assumption.
    """
    if not specs:
        return
    for name, spec in specs.items():
        lo, hi = parse_range_spec(spec)
        shape_graph.declare_range(name, lo, hi)
