"""CachedOp — a traced subgraph as a single fused operator.

Parity target: src/imperative/cached_op.{h,cc} (the Gluon hybridize
backend). TPU-native design: the whole traced Symbol becomes ONE
synthetic OpDef whose forward replays the graph as a pure JAX function.
- eager call        → one jitted XLA executable (static_alloc/bulking
  equivalents come free from XLA buffer assignment + fusion); the
  compile rides the per-op jit cache, so with the compile watch on
  (``mxnet_tpu.compile_watch``) every CachedOp compile is captured
  under site ``op:_cachedopN.<head>`` with per-argument recompile
  diffs and storm tracking
- under autograd    → one tape node; backward compiles forward+vjp of
  the entire subgraph (CachedOp::Backward's cached grad graph role)
- train/eval        → two jit specializations via the __train__ attr
- BatchNorm moving stats → aux vars become mutable inputs (writeback)
"""
from __future__ import annotations

import itertools
from typing import Dict, List

from .base import MXNetError
from . import ops as _ops
from .ops.registry import OpDef

__all__ = ["CachedOp"]

_counter = itertools.count()


def build_graph_callable(symbol):
    """Compile-ready plan over a Symbol: returns (fn, arg_names,
    aux_names, n_rng, n_out) where fn(attrs, *vals, rng=None) replays the
    graph. ``vals`` are ordered args + aux; returns outputs + new_aux."""
    nodes = symbol._topo_nodes()
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    arg_pos = {n: i for i, n in enumerate(arg_names)}
    aux_pos = {n: len(arg_names) + i for i, n in enumerate(aux_names)}

    plan = []
    node_slot = {}
    slot = 0
    n_rng = 0
    for nd_ in nodes:
        if nd_.is_variable():
            pos = aux_pos.get(nd_.name, arg_pos.get(nd_.name))
            if pos is None:
                raise MXNetError("unbound variable %s" % nd_.name)
            node_slot[id(nd_)] = ("var", pos)
        else:
            nattrs = _ops.normalize_attrs(nd_.op, nd_.attrs)
            bindings = []
            for (s, i) in nd_.inputs:
                kind, ref = node_slot[id(s)]
                bindings.append((kind, ref, i))
            rs = None
            if nd_.op.needs_rng:
                rs = n_rng
                n_rng += 1
            aux_wb = []
            for mi in nd_.op.mutable_inputs:
                if mi < len(nd_.inputs):
                    src, _ = nd_.inputs[mi]
                    if src.is_variable() and src.name in aux_pos:
                        aux_wb.append(aux_pos[src.name])
                    else:
                        aux_wb.append(None)
            plan.append((nd_.op, nattrs, tuple(bindings), rs, aux_wb, slot))
            node_slot[id(nd_)] = ("res", slot)
            slot += 1

    head_refs = []
    for (n, i) in symbol._outputs:
        kind, ref = node_slot[id(n)]
        head_refs.append((kind, ref, i) if kind == "res" else (kind, ref, 0))

    n_out = len(head_refs)
    n_aux = len(aux_names)
    n_args = len(arg_names)

    def fn(attrs, *vals, rng=None):
        import jax
        is_train = bool(attrs.get("__train__", False))
        if n_rng and rng is not None:
            keys = jax.random.split(rng, n_rng)
        else:
            keys = None
        cur = list(vals)  # args + aux (aux mutated in place as we go)
        results: List[tuple] = []
        for (op, nattrs, bindings, rs, aux_wb, s) in plan:
            ivals = []
            for (kind, ref, i) in bindings:
                if kind == "var":
                    ivals.append(cur[ref])
                else:
                    ivals.append(results[ref][i])
            a = nattrs
            if "__train__" in op.defaults:
                a = dict(nattrs, __train__=is_train)
            if rs is not None:
                out = op.forward(a, *ivals, rng=keys[rs])
            else:
                out = op.forward(a, *ivals)
            if not isinstance(out, (tuple, list)):
                out = (out,)
            k = op.resolve_num_outputs(a)
            results.append(tuple(out[:k]))
            for wb, val in zip(aux_wb, out[k:]):
                if wb is not None:
                    cur[wb] = val
        outs = []
        for (kind, ref, i) in head_refs:
            outs.append(cur[ref] if kind == "var" else results[ref][i])
        # outputs followed by updated aux values (mutable-input contract)
        return tuple(outs) + tuple(cur[n_args + j] for j in range(n_aux))

    return fn, arg_names, aux_names, n_rng, n_out


class CachedOp:
    """Callable fused subgraph (reference: ndarray.CachedOp /
    MXCreateCachedOpEx)."""

    def __init__(self, sym, flags=()):
        self.symbol = sym
        fn, arg_names, aux_names, n_rng, n_out = build_graph_callable(sym)
        self.arg_names = arg_names
        self.aux_names = aux_names
        self.num_inputs = len(arg_names) + len(aux_names)
        mutable = tuple(range(len(arg_names), self.num_inputs))
        # name the synthetic op after the graph's head so compile-watch
        # records and debug strings identify WHICH hybridized block
        # recompiled, not just "_cachedop3"
        outs = sym.list_outputs()
        head = "".join(c if c.isalnum() or c == "_" else "_"
                       for c in (outs[0] if outs else "graph"))[:40]
        self._op = OpDef(
            "_cachedop%d.%s" % (next(_counter), head), fn,
            arg_names=arg_names + aux_names,
            defaults={"__train__": False},
            num_outputs=n_out,
            needs_rng=bool(n_rng),
            mutable_inputs=mutable,
            description="CachedOp(%s)" % sym.list_outputs())

    def __call__(self, *inputs):
        from .ndarray.ndarray import invoke_nd
        if len(inputs) != self.num_inputs:
            raise MXNetError(
                "CachedOp expects %d inputs (%d args + %d aux), got %d"
                % (self.num_inputs, len(self.arg_names),
                   len(self.aux_names), len(inputs)))
        out = invoke_nd(self._op, list(inputs), {})
        return out
