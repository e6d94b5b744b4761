"""Imperative autograd (parity: python/mxnet/autograd.py + src/imperative/).

TPU-native design: recording builds a lightweight tape DAG over NDArray
handles (the role of ``Imperative::RecordOp`` + per-node ``AGInfo``,
reference include/mxnet/imperative.h:42). ``backward`` does NOT
interpret the graph node-by-node like the reference's ``RunGraph``
(imperative.cc:508); it linearizes the tape into a *program*, compiles
forward+vjp into ONE XLA computation via ``jax.vjp`` under ``jax.jit``,
and caches the compiled executable keyed on program structure — so a
training loop pays tracing cost once, like CachedOp's per-signature
cache (cached_op.cc SetForwardGraph).

Recorded input buffers are stashed on the tape (jax arrays are
immutable, so this is free) matching the reference's saved-input
semantics when handles are mutated later.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .base import MXNetError

__all__ = ["record", "pause", "train_mode", "predict_mode", "is_recording",
           "is_training", "mark_variables", "backward", "grad", "get_symbol",
           "set_recording", "set_training", "Function"]

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.training = False
    return _state


def is_recording():
    return _st().recording


def is_training():
    return _st().training


def set_recording(is_record):
    prev = _st().recording
    _st().recording = bool(is_record)
    return prev


def set_training(train_mode_):
    prev = _st().training
    _st().training = bool(train_mode_)
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode_):
        self._enter_is_record = is_record
        self._enter_train_mode = train_mode_
        self._prev_is_record = None
        self._prev_train_mode = None

    def __enter__(self):
        if self._enter_is_record is not None:
            self._prev_is_record = set_recording(self._enter_is_record)
        if self._enter_train_mode is not None:
            self._prev_train_mode = set_training(self._enter_train_mode)
        return self

    def __exit__(self, ptype, value, trace):
        if self._enter_is_record is not None:
            set_recording(self._prev_is_record)
        if self._enter_train_mode is not None:
            set_training(self._prev_train_mode)


def record(train_mode=True):
    """Scope for recording ops for autograd (reference: autograd.py:122)."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


def mark_variables(variables, gradients, grad_reqs="write"):
    """Mark NDArrays as variables to compute gradient for
    (reference: autograd.py:197)."""
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for var, g, req in zip(variables, gradients, grad_reqs):
        var.grad = g
        var._grad_req = req


# ---------------------------------------------------------------------------
# Tape
# ---------------------------------------------------------------------------

class _TapeNode:
    __slots__ = ("op", "attrs", "inputs", "input_values", "rng", "n_outputs")

    def __init__(self, op, attrs, inputs, input_values, rng, n_outputs):
        self.op = op
        self.attrs = attrs
        self.inputs = inputs            # list[NDArray] handles
        self.input_values = input_values  # recorded raw jax buffers
        self.rng = rng
        self.n_outputs = n_outputs


def _record_op(op, nattrs, inputs, outputs, rng):
    node = _TapeNode(op, nattrs, list(inputs),
                     [i._data for i in inputs], rng, len(outputs))
    for i, o in enumerate(outputs):
        o._tape_node = node
        o._tape_index = i


# ---------------------------------------------------------------------------
# Program extraction + compiled backward
# ---------------------------------------------------------------------------

def _collect_graph(heads):
    """Topo-order tape nodes reachable from heads; gather leaves/consts."""
    nodes: List[_TapeNode] = []
    visited = set()

    def dfs(node):
        if node is None or id(node) in visited:
            return
        visited.add(id(node))
        for h in node.inputs:
            dfs(h._tape_node)
        nodes.append(node)

    for h in heads:
        dfs(h._tape_node)
    return nodes


def _build_program(heads, nodes):
    """Linearize into (instructions, leaf_handles, const_values, rng_keys).

    Instruction: (op, attr_key_repr, tuple of bindings); binding is
    ('l', i) leaf, ('n', node_pos, out_idx), or ('c', i) constant.
    """
    from .ops.registry import attr_key
    node_pos = {id(n): i for i, n in enumerate(nodes)}
    leaf_ids: Dict[int, int] = {}
    leaves: List[Any] = []
    consts: List[Any] = []
    rngs: List[Any] = []
    instrs = []
    struct = []

    def leaf_slot(h):
        if id(h) not in leaf_ids:
            leaf_ids[id(h)] = len(leaves)
            leaves.append(h)
        return leaf_ids[id(h)]

    for n in nodes:
        bindings = []
        for h, rec_val in zip(n.inputs, n.input_values):
            src = h._tape_node
            if src is not None and id(src) in node_pos:
                bindings.append(("n", node_pos[id(src)], h._tape_index))
            elif h._grad_req != "null":
                bindings.append(("l", leaf_slot(h)))
            else:
                bindings.append(("c", len(consts)))
                consts.append(rec_val)
        rng_slot = None
        if n.op.needs_rng:
            rng_slot = len(rngs)
            rngs.append(n.rng)
        instrs.append((n.op, dict(n.attrs), tuple(bindings), rng_slot,
                       n.n_outputs))
        struct.append((n.op.name, attr_key(n.attrs), tuple(bindings),
                       rng_slot, n.n_outputs))

    head_refs = []
    for h in heads:
        if h._tape_node is not None and id(h._tape_node) in node_pos:
            head_refs.append(("n", node_pos[id(h._tape_node)], h._tape_index))
        elif h._grad_req != "null":
            head_refs.append(("l", leaf_slot(h)))
        else:
            raise MXNetError("cannot differentiate a head that was not "
                             "computed under autograd.record()")
    return (instrs, tuple(struct), tuple(head_refs), leaves, consts, rngs)


def _run_program(instrs, head_refs, leaf_vals, const_vals, rng_keys):
    results: List[Tuple] = []
    for op, attrs, bindings, rng_slot, n_out in instrs:
        vals = []
        for b in bindings:
            if b[0] == "l":
                vals.append(leaf_vals[b[1]])
            elif b[0] == "n":
                vals.append(results[b[1]][b[2]])
            else:
                vals.append(const_vals[b[1]])
        if rng_slot is not None:
            out = op.forward(attrs, *vals, rng=rng_keys[rng_slot])
        else:
            out = op.forward(attrs, *vals)
        if not isinstance(out, (tuple, list)):
            out = (out,)
        results.append(tuple(out[:n_out]))
    heads = []
    for b in head_refs:
        heads.append(leaf_vals[b[1]] if b[0] == "l" else results[b[1]][b[2]])
    return tuple(heads)


_bwd_cache: Dict[Tuple, Any] = {}
_bwd_cache_lock = threading.Lock()


def _get_backward_fn(struct, instrs, head_refs):
    import hashlib

    import jax

    from . import compile_watch
    key = (struct, head_refs)
    fn = _bwd_cache.get(key)
    if fn is None:
        def fwd_bwd(leaf_vals, const_vals, rng_keys, cotangents):
            def f(lv):
                return _run_program(instrs, head_refs, lv, const_vals,
                                    rng_keys)
            outs, vjp_fn = jax.vjp(f, list(leaf_vals))
            grads, = vjp_fn(tuple(cotangents))
            return outs, grads
        # ``struct`` (op names + attr keys + bindings) IS the program
        # content this closure bakes in; its digest is the watch's
        # program identity. storm=False — each distinct tape is a new
        # program by design (specialization, not churn).
        token = hashlib.sha256(
            repr((struct, head_refs)).encode()).hexdigest()
        fn = compile_watch.jit(fwd_bwd, "autograd:backward",
                               statics=token[:16], storm=False)
        with _bwd_cache_lock:
            _bwd_cache[key] = fn
    return fn


def _prepare_program(heads):
    """Collect + linearize the tape under ``heads`` (shared by the
    first-order and create_graph backward paths)."""
    nodes = _collect_graph(heads)
    if not nodes and all(h._tape_node is None for h in heads):
        raise MXNetError("cannot call backward: no ops were recorded "
                         "(use autograd.record())")
    return _build_program(heads, nodes)


def _cotangents(heads, head_grads):
    """Raw jax cotangent buffers, defaulting to ones per head."""
    import jax.numpy as jnp
    if head_grads is None:
        return [jnp.ones(h.shape, h._data.dtype) for h in heads]
    return [jnp.ones(h.shape, h._data.dtype) if g is None else g._data
            for h, g in zip(heads, head_grads)]


def _do_backward(heads, head_grads):
    heads = list(heads)
    instrs, struct, head_refs, leaves, consts, rngs = \
        _prepare_program(heads)
    if not leaves:
        return [], []
    cots = _cotangents(heads, head_grads)
    fn = _get_backward_fn(struct, instrs, head_refs)
    _, grads = fn(tuple(l._data for l in leaves), tuple(consts),
                  tuple(rngs), tuple(cots))
    return leaves, grads


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Compute gradients of heads w.r.t. marked variables and accumulate
    into their ``.grad`` (reference: autograd.py:243)."""
    leaves, grads = _do_backward(heads, head_grads)
    for leaf, g in zip(leaves, grads):
        if leaf.grad is None:
            continue
        if leaf._grad_req == "add":
            leaf.grad._set_data(leaf.grad._data + g)
        else:  # write
            leaf.grad._set_data(g)
        leaf._fresh_grad = True


_hgrad_cache: Dict[Tuple, Any] = {}
_hgrad_counter = [0]


def _backward_as_op(heads, head_grads):
    """Differentiate ``heads`` w.r.t. the tape leaves by invoking the
    whole vjp program as ONE recorded op — so the returned gradients
    are themselves on the tape and a second ``backward``/``grad`` runs
    ``jax.vjp`` over this op's forward, i.e. true higher-order autograd
    (reference: create_graph=True, python/mxnet/autograd.py:270).
    Returns (leaves, grad_NDArrays)."""
    import jax
    from .ndarray.ndarray import NDArray, invoke_nd
    from .ops.registry import OpDef

    heads = list(heads)
    instrs, struct, head_refs, leaves, consts, rngs = \
        _prepare_program(heads)
    if not leaves:
        return [], []
    n_l, n_c, n_r = len(leaves), len(consts), len(rngs)
    key = (struct, head_refs)
    with _bwd_cache_lock:
        opdef = _hgrad_cache.get(key)
    if opdef is None:
        def grad_fwd(attrs, *vals):
            lv = vals[:n_l]
            cv = list(vals[n_l:n_l + n_c])
            rv = list(vals[n_l + n_c:n_l + n_c + n_r])
            cots = vals[n_l + n_c + n_r:]

            def f(lv_):
                return _run_program(instrs, head_refs, list(lv_), cv, rv)

            _, vjp_fn = jax.vjp(f, tuple(lv))
            grads, = vjp_fn(tuple(cots))
            return tuple(grads)

        with _bwd_cache_lock:
            opdef = _hgrad_cache.get(key)       # double-checked: the
            if opdef is None:                   # name must stay unique
                _hgrad_counter[0] += 1
                opdef = OpDef(
                    "_backward_program%d" % _hgrad_counter[0], grad_fwd,
                    arg_names=tuple("in%d" % i
                                    for i in range(n_l + n_c + n_r
                                                   + len(heads))),
                    num_outputs=n_l)
                _hgrad_cache[key] = opdef

    cots = [NDArray(c, ctx=heads[0]._ctx)
            for c in _cotangents(heads, head_grads)]
    const_nds = [NDArray(c, ctx=heads[0]._ctx) for c in consts]
    rng_nds = [NDArray(r, ctx=heads[0]._ctx) for r in rngs]
    out = invoke_nd(opdef, list(leaves) + const_nds + rng_nds + cots, {})
    grads = out if isinstance(out, (list, tuple)) else [out]
    return leaves, list(grads)


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Return gradients of heads w.r.t. variables; with
    ``create_graph=True`` the results stay on the tape for higher-order
    differentiation (reference: autograd.py:270)."""
    from .ndarray.ndarray import NDArray
    if isinstance(heads, NDArray):
        heads = [heads]
    if isinstance(variables, NDArray):
        variables = [variables]
        single = True
    else:
        single = False
    # temporarily mark
    prev = [(v._grad_req,) for v in variables]
    for v in variables:
        if v._grad_req == "null":
            v._grad_req = "write"
    hg = [head_grads] if isinstance(head_grads, NDArray) else head_grads
    if create_graph:
        leaves, grad_nds = _backward_as_op(heads, hg)
        gmap = {id(l): g for l, g in zip(leaves, grad_nds)}
    else:
        leaves, grads = _do_backward(heads, hg)
        gmap = {id(l): NDArray(g, ctx=l._ctx)
                for l, g in zip(leaves, grads)}
    out = []
    for v, pr in zip(variables, prev):
        if id(v) not in gmap:
            raise MXNetError("one of the variables does not participate in "
                             "the computation of heads")
        out.append(gmap[id(v)])
        v._grad_req = pr[0]
    return out[0] if single else out


def get_symbol(x):
    """Recover the Symbol tracing the computation of ``x``
    (reference: autograd.py:304)."""
    from .symbol.symbol import _symbol_from_tape
    return _symbol_from_tape(x)


class Function:
    """Custom differentiable function (reference: autograd.py:365).

    Round-1 scope: forward runs eagerly; backward is invoked on the host
    during tape replay via jax.pure_callback.
    """

    def __init__(self):
        self._used = False

    def forward(self, *inputs):
        raise NotImplementedError()

    def backward(self, *output_grads):
        raise NotImplementedError()

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray
        from .ops.registry import OpDef
        import jax

        outs = self.forward(*[i for i in inputs])
        single = not isinstance(outs, (list, tuple))
        out_list = [outs] if single else list(outs)

        if is_recording():
            func = self
            in_shapes = [(i.shape, i.dtype) for i in inputs]

            def fwd_raw(attrs, *vals):
                import jax.numpy as jnp

                @jax.custom_vjp
                def f(*v):
                    return tuple(o._data for o in out_list) if len(out_list) > 1 \
                        else out_list[0]._data

                def f_fwd(*v):
                    return f(*v), v

                def f_bwd(res, g):
                    gs = g if isinstance(g, tuple) else (g,)

                    def host_bwd(*host_gs):
                        import numpy as np
                        nd_gs = [NDArray(jnp.asarray(x)) for x in host_gs]
                        igrads = func.backward(*nd_gs)
                        if not isinstance(igrads, (list, tuple)):
                            igrads = [igrads]
                        return tuple(np.asarray(ig.asnumpy())
                                     for ig in igrads)

                    import jax.numpy as jnp
                    shapes = tuple(jax.ShapeDtypeStruct(s, d)
                                   for s, d in in_shapes)
                    out = jax.pure_callback(host_bwd, shapes, *gs)
                    return tuple(out)

                f.defvjp(f_fwd, f_bwd)
                return f(*vals)

            op = OpDef("_custom_function", fwd_raw,
                       arg_names=["in%d" % i for i in range(len(inputs))],
                       num_outputs=len(out_list))
            _record_op(op, {}, list(inputs), out_list, None)
        return outs
