"""Operator registry — the TPU-native equivalent of the NNVM op registry.

Reference model (include/mxnet/op_attr_types.h, src/operator/*): each op
registers FCompute kernels per device plus attribute functors
(FInferShape/FInferType/FGradient/FMutateInputs...). On TPU the design
collapses dramatically:

- An op's body is ONE pure JAX function ``forward(attrs, *inputs)`` —
  XLA compiles it for any backend, so there is no per-device kernel pair
  (``X.cc``/``X.cu``) and no mshadow expression layer.
- Gradients come from ``jax.vjp`` over the traced graph — no per-op
  FGradient registration.
- Shape/type inference comes from ``jax.eval_shape`` over the same
  function — no per-op FInferShape/FInferType.

What remains per-op, and is registered here: the forward body, input arg
names (for Symbol ``list_arguments``), number of outputs, RNG needs
(counter-based like the reference's parallel-random resource), mutable
input indices (BatchNorm aux-state writeback, optimizer update ops), and
attribute parsing (the dmlc ``Parameter`` struct role).

Eager dispatch mirrors ``Imperative::Invoke``
(src/imperative/imperative.cc:87): op + static attrs → a cached
``jax.jit`` callable (the analogue of the per-signature CachedOp cache).
"""
from __future__ import annotations

import ast
import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

from ..base import MXNetError, Registry

__all__ = ["OpDef", "register", "get_op", "find_op", "list_ops", "invoke",
           "normalize_attrs", "attr_key"]

_OP_REGISTRY: Registry = Registry("operator")


class OpDef:
    """A registered operator.

    Parameters
    ----------
    name : canonical op name (e.g. ``FullyConnected``, ``_plus_scalar``).
    forward : ``forward(attrs: dict, *inputs, rng=None) -> array | tuple``.
        Pure JAX function. If ``mutable_inputs`` is set, the returned tuple
        carries ``num_outputs`` real outputs followed by one updated value
        per mutable input (in order).
    arg_names : names of tensor inputs (Symbol ``list_arguments`` order).
    defaults : attribute name → default value (dmlc Parameter struct role).
    num_outputs : int, or callable ``attrs -> int`` for variadic outputs.
    key_var_num_args : attr holding the variadic input count (Concat's
        ``num_args``), mirroring nnvm's ``key_var_num_args``.
    needs_rng : op consumes a PRNG key (samplers, Dropout).
    mutable_inputs : indices of inputs updated in place (FMutateInputs).
    """

    def __init__(self, name: str, forward: Callable,
                 arg_names: Sequence[str] = ("data",),
                 defaults: Optional[Dict[str, Any]] = None,
                 num_outputs: Union[int, Callable] = 1,
                 key_var_num_args: Optional[str] = None,
                 needs_rng: bool = False,
                 mutable_inputs: Sequence[int] = (),
                 arg_names_fn: Optional[Callable] = None,
                 description: str = "",
                 attr_docs: Optional[Dict[str, str]] = None,
                 attr_ranges: Optional[Dict[str, tuple]] = None,
                 no_jit: bool = False):
        self.name = name
        self.forward = forward
        self.arg_names = list(arg_names)
        self.defaults = dict(defaults or {})
        self.num_outputs = num_outputs
        self.key_var_num_args = key_var_num_args
        self.needs_rng = needs_rng
        self.mutable_inputs = tuple(mutable_inputs)
        self.arg_names_fn = arg_names_fn  # attrs -> effective input names
        # no_jit: forward manages its own compilation/placement (e.g.
        # shard_map over a multi-device mesh, which a single-device
        # eager jit wrapper would reject)
        self.no_jit = bool(no_jit)
        self.description = description or (forward.__doc__ or "")
        # the dmlc Parameter-struct tier (SURVEY §5.6 tier 2): per-attr
        # documentation and (lo, hi) ranges; both feed the generated
        # frontend stubs' docstrings, ranges also validate at invoke
        self.attr_docs = dict(attr_docs or {})
        self.attr_ranges = dict(attr_ranges or {})

    def doc_signature(self) -> str:
        """Human signature + parameter table for generated stubs (the
        role of the reference's codegen from DMLC_DECLARE_FIELD docs,
        python/mxnet/ndarray/register.py:30)."""
        lines = ["%s(%s, **attrs)" % (self.name,
                                      ", ".join(self.arg_names)), ""]
        if self.description:
            lines += [self.description.strip(), ""]
        if self.defaults:
            lines.append("Parameters")
            lines.append("----------")
            for key, default in self.defaults.items():
                if key.startswith("__"):
                    continue
                entry = "%s : default %r" % (key, default)
                if key in self.attr_ranges:
                    entry += ", range %s" % (self.attr_ranges[key],)
                lines.append(entry)
                if key in self.attr_docs:
                    lines.append("    " + self.attr_docs[key])
        return "\n".join(lines)

    def validate_attrs(self, nattrs: Dict[str, Any]) -> None:
        """Range checks from the param tier (dmlc set_range role)."""
        for key, (lo, hi) in self.attr_ranges.items():
            val = nattrs.get(key)
            if val is None or not isinstance(val, (int, float)):
                continue
            if (lo is not None and val < lo) or \
                    (hi is not None and val > hi):
                raise MXNetError(
                    "%s: attribute %s=%r outside valid range [%s, %s]"
                    % (self.name, key, val, lo, hi))

    # -- helpers ---------------------------------------------------------
    def resolve_num_outputs(self, attrs: Dict[str, Any]) -> int:
        if callable(self.num_outputs):
            return self.num_outputs(attrs)
        return self.num_outputs

    def resolve_arg_names(self, attrs: Dict[str, Any], num_inputs=None) -> List[str]:
        if self.key_var_num_args:
            n = int(attrs.get(self.key_var_num_args,
                              num_inputs if num_inputs is not None else 1))
            base = self.arg_names[0] if self.arg_names else "arg"
            return ["%s%d" % (base, i) for i in range(n)]
        if self.arg_names_fn is not None:
            return list(self.arg_names_fn(normalize_attrs(self, attrs)))
        return list(self.arg_names)

    def __repr__(self):
        return "OpDef(%s)" % self.name


def register(name: str, forward: Optional[Callable] = None, *,
             aliases: Sequence[str] = (), **kwargs) -> Union[OpDef, Callable]:
    """Register an operator; usable as function or decorator."""
    def _do(fwd):
        op = OpDef(name, fwd, **kwargs)
        _OP_REGISTRY.register(name)(op)
        for a in aliases:
            _OP_REGISTRY.register(a)(op)
        return op
    if forward is not None:
        return _do(forward)
    return _do


def get_op(name: str) -> OpDef:
    try:
        return _OP_REGISTRY.get(name)
    except KeyError:
        raise MXNetError("Operator '%s' is not registered" % name)


def find_op(name: str) -> Optional[OpDef]:
    return _OP_REGISTRY.find(name)


def list_ops() -> List[str]:
    return sorted(_OP_REGISTRY.keys())


# ---------------------------------------------------------------------------
# Attribute normalization (dmlc Parameter parsing role)
# ---------------------------------------------------------------------------

_BOOL_STR = {"true": True, "True": True, "1": True,
             "false": False, "False": False, "0": False}


def _parse_attr_value(v):
    if not isinstance(v, str):
        return v
    if v in _BOOL_STR:
        return _BOOL_STR[v]
    if v == "None":
        return None
    if v.startswith("__subgraph__:"):
        from .control_flow import Subgraph
        return Subgraph.from_json_attr(v)
    try:
        return ast.literal_eval(v)
    except (ValueError, SyntaxError):
        return v


def normalize_attrs(op: OpDef, attrs: Dict[str, Any]) -> Dict[str, Any]:
    """Merge with defaults, parse stringly-typed values (from Symbol
    JSON or frontend kwargs), and range-check — mirroring dmlc
    Parameter::Init + set_range."""
    out = dict(op.defaults)
    for k, v in attrs.items():
        if v is None and k in out:
            continue
        out[k] = _parse_attr_value(v)
    if op.attr_ranges:
        op.validate_attrs(out)
    return out


def _hashable(v):
    if isinstance(v, (list, tuple)):
        return tuple(_hashable(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _hashable(x)) for k, x in v.items()))
    return v


def attr_key(attrs: Dict[str, Any]):
    return tuple(sorted((k, _hashable(v)) for k, v in attrs.items()))


# ---------------------------------------------------------------------------
# Eager dispatch with jit cache (Imperative::Invoke analogue)
# ---------------------------------------------------------------------------

_jit_cache: Dict[Tuple, Callable] = {}
_jit_lock = threading.Lock()


def _get_jitted(op: OpDef, nattrs: Dict[str, Any], n_inputs: int):
    key = (op.name, attr_key(nattrs), n_inputs, op.needs_rng)
    fn = _jit_cache.get(key)
    if fn is None:
        from .. import compile_watch
        arg_names = list(op.arg_names) if op.arg_names else None
        if op.needs_rng:
            def raw(rng, *arrays):
                return op.forward(nattrs, *arrays, rng=rng)
            names = ["rng"] + (arg_names or [])
        else:
            def raw(*arrays):
                return op.forward(nattrs, *arrays)
            names = arg_names

        def describe(*arrays):
            return compile_watch.describe_arrays(names, arrays)

        # program identity includes the op's static attrs (a _zeros
        # per param shape is specialization, not churn). Plain eager
        # micro-ops are polymorphic by design, so only CachedOp graphs
        # — one hybridized program, site "op:_cachedopN.<head>" —
        # participate in recompile-storm detection.
        is_cached = op.name.startswith("_cachedop")
        fn = compile_watch.jit(raw, "op:%s" % op.name,
                               describe=describe, statics=key[1:],
                               storm=is_cached)
        with _jit_lock:
            _jit_cache[key] = fn
    return fn


def _align_device_sets(input_arrays):
    """MXNet semantics let one op mix arrays the user placed on
    different devices; jax refuses eager math across device sets. When
    inputs disagree, re-place the minority onto the widest device set
    (replicated if it is a mesh) — the analogue of the implicit copies
    the reference's cross-device-copy op inserted."""
    if len(input_arrays) < 2:
        return input_arrays
    shardings = [getattr(a, "sharding", None) for a in input_arrays]
    first = next((s for s in shardings if s is not None), None)
    if first is None or all(s is None or s == first for s in shardings):
        return input_arrays  # common case: everything already agrees
    import jax
    sets = {}
    for s in shardings:
        if s is not None:
            sets.setdefault(tuple(sorted(d.id for d in s.device_set)), s)
    if len(sets) <= 1:
        return input_arrays
    widest = max(sets.values(), key=lambda s: len(s.device_set))
    try:
        from jax.sharding import NamedSharding, PartitionSpec as P
        target = NamedSharding(widest.mesh, P()) \
            if isinstance(widest, NamedSharding) else widest
    except Exception:
        target = widest
    out = []
    for a in input_arrays:
        s = getattr(a, "sharding", None)
        if s is not None and s.device_set != widest.device_set:
            a = jax.device_put(a, target)
        out.append(a)
    return out


def invoke(op: OpDef, input_arrays: Sequence[Any], attrs: Dict[str, Any],
           rng=None):
    """Eagerly execute ``op`` on raw jax arrays; returns tuple
    ``(outputs, aux_updates)`` where aux_updates is a list of (input_index,
    new_value) for mutable inputs."""
    input_arrays = _align_device_sets(list(input_arrays))
    nattrs = normalize_attrs(op, attrs)
    if op.no_jit:
        fn = (lambda *a: op.forward(nattrs, *a)) if not op.needs_rng \
            else (lambda rng_, *a: op.forward(nattrs, *a, rng=rng_))
    else:
        fn = _get_jitted(op, nattrs, len(input_arrays))
    if op.needs_rng:
        if rng is None:
            from .. import random as _random
            rng = _random.new_key()
        result = fn(rng, *input_arrays)
    else:
        result = fn(*input_arrays)
    if not isinstance(result, (tuple, list)):
        result = (result,)
    n_out = op.resolve_num_outputs(nattrs)
    outputs = tuple(result[:n_out])
    aux_updates = []
    if op.mutable_inputs:
        extras = result[n_out:]
        for idx, val in zip(op.mutable_inputs, extras):
            aux_updates.append((idx, val))
    return outputs, aux_updates
