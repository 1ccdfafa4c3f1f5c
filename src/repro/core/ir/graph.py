"""Computation-graph IR over which the paper's analyses run.

A ``Graph`` is a flat list of ``Node``s (one per primitive application)
connected by ``Value``s (tensors).  Every Value carries its shape as a tuple
of ``SymbolicExpr`` dims and its byte count as a ``SymbolicExpr`` — this is
the "dynamic shape graph" of the paper, with the symbolic shape information
attached (§2.1).
"""
from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..symbolic import SymbolicExpr, size_of


class Value:
    """A tensor edge in the graph."""

    __slots__ = (
        "id", "dims", "dtype", "aval_shape", "producer", "out_index",
        "consumers", "kind", "const_val", "name", "_nbytes_expr",
    )

    def __init__(
        self,
        vid: int,
        dims: Tuple[SymbolicExpr, ...],
        dtype: Any,
        aval_shape: Tuple[Any, ...],
        kind: str = "intermediate",  # 'input' | 'const' | 'intermediate'
        const_val: Any = None,
        name: str = "",
    ):
        self.id = vid
        self.dims = dims
        self.dtype = np.dtype(dtype)
        self.aval_shape = aval_shape  # raw dims (ints / jax _DimExpr), for refinement
        self.producer: Optional["Node"] = None
        self.out_index: int = -1
        self.consumers: List["Node"] = []
        self.kind = kind
        self.const_val = const_val
        self.name = name
        self._nbytes_expr = None

    @property
    def size_expr(self) -> SymbolicExpr:
        return size_of(self.dims)

    @property
    def nbytes_expr(self) -> SymbolicExpr:
        if self._nbytes_expr is None:
            self._nbytes_expr = self.size_expr * int(self.dtype.itemsize)
        return self._nbytes_expr

    def nbytes_concrete(self, env: Dict[str, int]) -> int:
        return self.nbytes_expr.evaluate(env)

    def is_materialized_input(self) -> bool:
        return self.kind in ("input", "const")

    def __repr__(self) -> str:  # pragma: no cover
        dims = "x".join(str(d) for d in self.dims) or "scalar"
        return f"%{self.id}:{self.dtype.name}[{dims}]"


class Node:
    """One primitive application."""

    __slots__ = ("id", "prim", "prim_name", "invals", "outvals", "params", "label")

    def __init__(self, nid: int, prim: Any, invals: List[Value], outvals: List[Value],
                 params: Dict[str, Any], label: str = ""):
        self.id = nid
        self.prim = prim
        self.prim_name = prim.name if prim is not None else "<none>"
        self.invals = invals
        self.outvals = outvals
        self.params = params
        # op-group label from the traced eqn's name stack (ir.trace):
        # ``fwd/layer0/attn``, ``bwd/layer0/mlp``, ``adamw``; "" unscoped
        self.label = label

    def __repr__(self) -> str:  # pragma: no cover
        return f"({self.id}:{self.prim_name} {self.invals} -> {self.outvals})"


# process-wide graph identity counter: interpreter/VM per-env caches are
# namespaced by it, so a size/params cache shared across executors can
# never alias two different graphs' entries for the same node/value id
# (ids restart at 0 per graph)
_GRAPH_UIDS = itertools.count()


@dataclass
class Graph:
    nodes: List[Node] = field(default_factory=list)
    inputs: List[Value] = field(default_factory=list)
    consts: List[Value] = field(default_factory=list)
    outputs: List[Value] = field(default_factory=list)
    # flat list of all values, id-indexed
    values: List[Value] = field(default_factory=list)
    in_tree: Any = None
    out_tree: Any = None
    uid: int = field(default_factory=lambda: next(_GRAPH_UIDS))
    # value-dependent bounded dims (ir.dynamism): insertion-ordered
    # bound-symbol name -> symbolic cap, and introducing node id ->
    # BoundIntro record.  Empty for purely range-dynamic graphs.
    bound_dims: Dict[str, SymbolicExpr] = field(default_factory=dict)
    bound_intros: Dict[int, Any] = field(default_factory=dict)

    _vid: itertools.count = field(default_factory=lambda: itertools.count())
    _nid: itertools.count = field(default_factory=lambda: itertools.count())

    # -- construction helpers -------------------------------------------------
    def new_value(self, dims, dtype, aval_shape, kind="intermediate", const_val=None, name="") -> Value:
        v = Value(next(self._vid), tuple(dims), dtype, tuple(aval_shape), kind, const_val, name)
        self.values.append(v)
        return v

    def add_node(self, prim, invals: Sequence[Value], outvals: Sequence[Value], params,
                 label: str = "") -> Node:
        n = Node(next(self._nid), prim, list(invals), list(outvals), dict(params), label)
        for i, ov in enumerate(outvals):
            ov.producer = n
            ov.out_index = i
        for iv in invals:
            iv.consumers.append(n)
        self.nodes.append(n)
        return n

    # -- queries ---------------------------------------------------------------
    def last_consumer_map(self, order: Optional[Sequence[Node]] = None) -> Dict[int, Node]:
        """value id -> the node (in `order`) that consumes it last."""
        order = order if order is not None else self.nodes
        pos = {n.id: i for i, n in enumerate(order)}
        out: Dict[int, Node] = {}
        for v in self.values:
            cons = [c for c in v.consumers if c.id in pos]
            if cons:
                out[v.id] = max(cons, key=lambda n: pos[n.id])
        return out

    def validate_order(self, order: Sequence[Node]) -> None:
        """Assert `order` is a valid topological order of the graph."""
        seen = set()
        ids = [n.id for n in order]
        assert len(ids) == len(self.nodes) and set(ids) == {n.id for n in self.nodes}, \
            "order must be a permutation of graph nodes"
        for n in order:
            for iv in n.invals:
                if iv.producer is not None:
                    assert iv.producer.id in seen, (
                        f"node {n.id}({n.prim_name}) scheduled before producer "
                        f"{iv.producer.id}({iv.producer.prim_name})"
                    )
            seen.add(n.id)

    def free_symbols(self) -> frozenset:
        out: frozenset = frozenset()
        for v in self.values:
            out |= v.nbytes_expr.free_vars()
        return out

    def stats(self) -> Dict[str, int]:
        return {
            "nodes": len(self.nodes),
            "values": len(self.values),
            "inputs": len(self.inputs),
            "consts": len(self.consts),
            "outputs": len(self.outputs),
            "bound_dims": len(self.bound_dims),
        }
