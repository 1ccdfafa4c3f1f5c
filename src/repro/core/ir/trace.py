"""jaxpr -> Graph tracing (the paper's "dynamic shape computation graph").

We trace the target function once with ``jax.make_jaxpr`` over
ShapeDtypeStructs whose dynamic dims are ``jax.export.symbolic_shape``
variables, then convert to our IR.  Call-like primitives (jit, remat,
custom_jvp/vjp) are inlined so the analyses see a flat op graph, matching
the paper's post-fusion HLO-level view.  Control-flow primitives are kept
opaque with one exception: a top-level ``scan`` with a *symbolic* trip
count becomes a rolled loop node (see ``ir.loop``) — its body traced once
as a sub-graph so the downstream plan is O(body), not O(t·body).
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import numpy as np
from jax import tree_util
from jax._src import core as jcore

from ..symbolic import dim_to_expr
from ..symbolic.expr import SymbolicExpr
from .dynamism import BoundIntro, introduces_dim
from .graph import Graph, Node, Value
from .loop import LOOP_PARAM, LoopBody, rollable_body

# primitive name -> params key holding the sub-jaxpr to inline
_INLINE_CLOSED = {"pjit": "jaxpr", "jit": "jaxpr", "closed_call": "call_jaxpr",
                  "custom_jvp_call": "call_jaxpr", "custom_vjp_call": "call_jaxpr"}
_INLINE_OPEN = {"remat2": "jaxpr", "checkpoint": "jaxpr", "remat": "jaxpr"}


def _dims_of_aval(aval) -> Tuple[SymbolicExpr, ...]:
    return tuple(dim_to_expr(d) for d in aval.shape)


_TRANSFORM = re.compile(r"\w+\(")
# scopes kept in a label: the model's own (``layer0/attn``); deeper ones,
# such as the spec jnp.einsum scopes its ops with, fold into them
_LABEL_DEPTH = 2


def scope_label(name_stack: str, parent: str = "") -> str:
    """An eqn's op-group label from its ``jax.named_scope`` name stack.

    Transforms are dropped and name the pass instead: ``jvp(layer0)/attn``
    becomes ``fwd/layer0/attn``, ``transpose(jvp(layer0))/attn``
    ``bwd/layer0/attn``; a stack outside any differentiation keeps its
    scopes (``adamw``).  At most the outer two scopes are kept.  An eqn
    inlined from a ``pjit`` / ``custom_jvp`` body extends its parent eqn's
    label (``parent``), whose pass wins."""
    kind, scopes = "", []
    if parent:
        scopes = parent.split("/")
        if scopes[0] in ("fwd", "bwd"):
            kind, scopes = scopes[0], scopes[1:]
    elif name_stack.startswith("transpose("):
        kind = "bwd"
    elif name_stack.startswith("jvp("):
        kind = "fwd"
    scopes += [p for p in _TRANSFORM.sub("", name_stack).replace(")", "")
               .split("/") if p]
    return "/".join(([kind] if kind else []) + scopes[:_LABEL_DEPTH])


def _try_roll_scan(eqn, *, name: str) -> "LoopBody | None":
    """Convert a scan eqn to a :class:`LoopBody` when it is rollable.

    Rolled form requires a *symbolic* trip count (a static length gains
    nothing and some analyses — flops scaling, grad accumulation — rely
    on the opaque primitive), forward iteration order, no manual
    unrolling, and a body whose carry outputs satisfy
    :func:`rollable_body`.  Nested scans stay opaque: the body is traced
    with ``roll_loops=False``.
    """
    from ..symbolic import is_symbolic_dim

    params = eqn.params
    length = params.get("length")
    if not is_symbolic_dim(length):
        return None
    if params.get("reverse") or params.get("unroll", 1) not in (1, False):
        return None
    nc, nk = params["num_consts"], params["num_carry"]
    nx = len(eqn.invars) - nc - nk
    bg = graph_from_closed_jaxpr(params["jaxpr"], name=f"{name}.body",
                                 roll_loops=False)
    if bg.bound_dims:
        # a value-dependent op inside the body would need a BindDim per
        # iteration; rolled accounting has no per-step env, so the scan
        # stays opaque (the padded-to-cap semantics remain correct)
        return None
    if not rollable_body(bg, nc, nk):
        return None
    return LoopBody(graph=bg, num_consts=nc, num_carry=nk, num_xs=nx,
                    length_expr=dim_to_expr(length))


def graph_from_closed_jaxpr(closed, *, name: str = "",
                            roll_loops: bool = True) -> Graph:
    g = Graph()
    env: Dict[Any, Value] = {}
    # bound symbol -> the cap-shaped dim expr it replaced at introduction.
    # Consumers propagate the refinement forward per dim position (the
    # SoD² propagate half) so downstream allocations are accounted at the
    # bound symbol, not the cap.
    orig_expr_of: Dict[str, SymbolicExpr] = {}

    def _introduce(node: Node) -> None:
        spec = introduces_dim(node.prim_name)
        if spec is None:
            _propagate(node)
            return
        pv = node.outvals[spec.padded_out]
        cap_val = node.invals[spec.cap_arg]
        if spec.axis >= len(pv.dims) or spec.cap_axis >= len(cap_val.dims):
            return
        cap = cap_val.dims[spec.cap_axis]
        bname = f"__b{len(g.bound_dims)}"
        orig = tuple(pv.dims)
        dims = list(orig)
        dims[spec.axis] = SymbolicExpr.var(bname)
        pv.dims = tuple(dims)
        pv._nbytes_expr = None
        g.bound_dims[bname] = cap
        g.bound_intros[node.id] = BoundIntro(
            name=bname, cap=cap, node_id=node.id,
            padded_out=spec.padded_out, count_out=spec.count_out,
            axis=spec.axis)
        orig_expr_of[bname] = orig[spec.axis]

    def _propagate(node: Node) -> None:
        """Per-dim dataflow refinement of a consumer's cap-shaped output.

        An output dim expression ``e`` rewrites to a bound symbol ``b``
        iff exactly one refined operand *carries* ``b`` in its dims with
        ``e`` as the expression it replaced (so the extent provably flows
        from the bounded operand — elementwise chains, gathers, matmuls
        whose result dim is the bounded one), and no operand still holds
        ``e`` unrefined (a full-extent operand — e.g. the rhs of a padded
        add — forces the output back to the cap, which is sound).
        Anything ambiguous or synthesized from params stays at the cap.
        """
        if not orig_expr_of:
            return
        bset = frozenset(g.bound_dims)
        carried: Dict[SymbolicExpr, set] = {}
        blocked: set = set()
        for iv in node.invals:
            for d in iv.dims:
                fv = d.free_vars() & bset
                if fv:
                    for bname in fv:
                        carried.setdefault(orig_expr_of[bname],
                                           set()).add(bname)
                else:
                    blocked.add(d)
        if not carried:
            return
        for ov in node.outvals:
            if not ov.dims:
                continue
            dims = list(ov.dims)
            changed = False
            for a, e in enumerate(dims):
                if e.free_vars() & bset or e in blocked:
                    continue
                cands = carried.get(e, ())
                if len(cands) == 1:
                    dims[a] = SymbolicExpr.var(next(iter(cands)))
                    changed = True
            if changed:
                ov.dims = tuple(dims)
                ov._nbytes_expr = None

    def read(var) -> Value:
        if isinstance(var, jcore.Literal):
            aval = var.aval
            v = g.new_value(_dims_of_aval(aval), aval.dtype, aval.shape,
                            kind="const", const_val=np.asarray(var.val))
            g.consts.append(v)
            return v
        return env[var]

    def write(var, value: Value) -> None:
        env[var] = value

    jaxpr = closed.jaxpr
    # graph inputs
    for i, var in enumerate(jaxpr.invars):
        aval = var.aval
        v = g.new_value(_dims_of_aval(aval), aval.dtype, aval.shape, kind="input",
                        name=f"in{i}")
        g.inputs.append(v)
        write(var, v)
    # top-level consts
    for var, cval in zip(jaxpr.constvars, closed.consts):
        aval = var.aval
        v = g.new_value(_dims_of_aval(aval), aval.dtype, aval.shape, kind="const",
                        const_val=cval)
        g.consts.append(v)
        write(var, v)

    def process(jaxpr, read_local, write_local, parent=""):
        for eqn in jaxpr.eqns:
            pname = eqn.primitive.name
            label = scope_label(str(eqn.source_info.name_stack), parent)
            if pname in _INLINE_CLOSED or pname in _INLINE_OPEN:
                _inline(eqn, read_local, write_local, label)
                continue
            if pname == "scan" and roll_loops:
                body = _try_roll_scan(eqn, name=name)
                if body is not None:
                    invals = [read_local(v) for v in eqn.invars]
                    outvals = []
                    for ov in eqn.outvars:
                        aval = ov.aval
                        val = g.new_value(_dims_of_aval(aval), aval.dtype,
                                          aval.shape)
                        outvals.append(val)
                        if not isinstance(ov, jcore.DropVar):
                            write_local(ov, val)
                    g.add_node(eqn.primitive, invals, outvals,
                               {LOOP_PARAM: body}, label)
                    continue
            invals = [read_local(v) for v in eqn.invars]
            outvals = []
            for ov in eqn.outvars:
                aval = ov.aval
                val = g.new_value(_dims_of_aval(aval), aval.dtype, aval.shape)
                outvals.append(val)
                if not isinstance(ov, jcore.DropVar):
                    write_local(ov, val)
            _introduce(g.add_node(eqn.primitive, invals, outvals, eqn.params,
                                  label))

    def _inline(eqn, read_outer, write_outer, label):
        pname = eqn.primitive.name
        local_env: Dict[Any, Value] = {}

        def read_inner(var):
            if isinstance(var, jcore.Literal):
                return read(var)
            return local_env[var]

        def write_inner(var, value):
            local_env[var] = value

        if pname in _INLINE_CLOSED:
            sub = eqn.params[_INLINE_CLOSED[pname]]
            inner, consts = sub.jaxpr, sub.consts
            n_skip = eqn.params.get("num_consts", 0)
            # custom_jvp_call passes jvp consts first in some versions; the
            # closed call_jaxpr invars match eqn invars[n_skip:] if lengths differ
            outer_invals = [read_outer(v) for v in eqn.invars]
            if len(inner.invars) != len(outer_invals):
                outer_invals = outer_invals[len(outer_invals) - len(inner.invars):]
            for var, cval in zip(inner.constvars, consts):
                aval = var.aval
                cv = g.new_value(_dims_of_aval(aval), aval.dtype, aval.shape,
                                 kind="const", const_val=cval)
                g.consts.append(cv)
                write_inner(var, cv)
        else:  # open jaxpr (remat): constvars empty, invars match eqn invars
            inner = eqn.params[_INLINE_OPEN[pname]]
            outer_invals = [read_outer(v) for v in eqn.invars]
            assert not inner.constvars, f"{pname} with constvars unsupported"
        for var, val in zip(inner.invars, outer_invals):
            write_inner(var, val)
        process(inner, read_inner, write_inner, label)
        for outer_var, inner_var in zip(eqn.outvars, inner.outvars):
            if isinstance(outer_var, jcore.DropVar):
                continue
            write_outer(outer_var, read_inner(inner_var))

    process(jaxpr, read, write)

    for var in jaxpr.outvars:
        g.outputs.append(read(var))
    return g


def trace_to_graph(fn: Callable, *args, **kwargs) -> Tuple[Graph, Any]:
    """Trace ``fn`` over (possibly symbolic) ShapeDtypeStruct args.

    Returns (graph, out_shape_pytree).  The graph's ``in_tree``/``out_tree``
    record the pytree structure so the interpreter can offer the original
    calling convention.
    """
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    flat_args, in_tree = tree_util.tree_flatten((args, kwargs))
    g = graph_from_closed_jaxpr(closed)
    g.in_tree = in_tree
    out_shapes = jax.eval_shape(fn, *args, **kwargs)
    _, out_tree = tree_util.tree_flatten(out_shapes)
    g.out_tree = out_tree
    return g, out_shapes


# ---------------------------------------------------------------------------
# Runtime param refinement: evaluate symbolic dims inside eqn params
# ---------------------------------------------------------------------------


def _contains_symbolic(x) -> bool:
    from ..symbolic import is_symbolic_dim
    if is_symbolic_dim(x):
        return True
    if isinstance(x, (tuple, list)):
        return any(_contains_symbolic(e) for e in x)
    if isinstance(x, dict):
        return any(_contains_symbolic(v) for v in x.values())
    return False


def refine_params(params: Dict[str, Any], env: Dict[str, int]) -> Dict[str, Any]:
    """Replace jax symbolic dims inside eqn params with concrete ints."""
    from ..symbolic import is_symbolic_dim

    def go(x):
        if is_symbolic_dim(x):
            return dim_to_expr(x).evaluate(env)
        if isinstance(x, tuple):
            rebuilt = tuple(go(e) for e in x)
            if hasattr(x, "_fields"):  # namedtuple (e.g. GatherDimensionNumbers)
                return type(x)(*rebuilt)
            return rebuilt
        if isinstance(x, list):
            return [go(e) for e in x]
        if isinstance(x, dict):
            return {k: go(v) for k, v in x.items()}
        return x

    return {k: go(v) for k, v in params.items()}


def solve_env(graph: Graph, concrete_args: Sequence[Any]) -> Dict[str, int]:
    """Bind symbolic dim variables from the concrete shapes of flat inputs."""
    env: Dict[str, int] = {}
    deferred: List[Tuple[SymbolicExpr, int]] = []
    assert len(concrete_args) == len(graph.inputs), (
        f"expected {len(graph.inputs)} flat inputs, got {len(concrete_args)}")
    for val, arr in zip(graph.inputs, concrete_args):
        shape = np.shape(arr)
        assert len(shape) == len(val.dims), f"rank mismatch for {val}: {shape}"
        for dim_expr, concrete in zip(val.dims, shape):
            fv = dim_expr.free_vars()
            if not fv:
                expected = dim_expr.evaluate({})
                assert expected == concrete, (
                    f"static dim mismatch: expected {expected}, got {concrete}")
            elif len(fv) == 1 and dim_expr == SymbolicExpr.var(next(iter(fv))):
                name = next(iter(fv))
                if name in env:
                    assert env[name] == concrete, (
                        f"inconsistent binding for {name}: {env[name]} vs {concrete}")
                env[name] = int(concrete)
            else:
                deferred.append((dim_expr, int(concrete)))
    for expr, concrete in deferred:
        got = expr.evaluate(env)
        assert got == concrete, f"composite dim mismatch: {expr}={got} vs {concrete}"
    return env


def check_declared_ranges(shape_graph, env: Dict[str, int]) -> None:
    """Enforce the declared-range contract on a solved env.

    Compile-time decisions (schedule, static regen methods, guaranteed
    peak/arena bounds, bucket partitions) assume every dim stays inside
    its declared range; a dim outside it must raise before execution.
    Shared by both executors and the bucketed dispatch path — a single
    message, a single check.
    """
    for name, iv in shape_graph.declared_ranges.items():
        v = env.get(name)
        if v is not None and not iv.contains(v):
            raise ValueError(
                f"dim {name!r}={v} outside its declared range {iv}; "
                f"re-optimize with wider dynamic_dims to run this shape")


def solve_checked_env(graph: Graph, shape_graph,
                      concrete_args: Sequence[Any]) -> Dict[str, int]:
    """``solve_env`` + declared-range validation in one step.

    Callers that pass a pre-solved env to an executor (the bucketed
    dispatch hot path) have already been through this and skip both."""
    env = solve_env(graph, concrete_args)
    check_declared_ranges(shape_graph, env)
    return env
