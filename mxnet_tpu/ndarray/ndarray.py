"""NDArray — the user-visible array type.

Parity target: python/mxnet/ndarray/ndarray.py + src/ndarray/ndarray.cc.

TPU-native design: an :class:`NDArray` is a *mutable handle* over an
immutable ``jax.Array`` buffer. The reference's in-place semantics
(``x[:] = v``, ``kvstore.pull(out=w)``, optimizer updates) become buffer
swaps on the handle; aliasing views are not shared (documented
divergence — XLA owns memory layout). Asynchrony comes from JAX's async
dispatch: every op returns immediately with a future-backed array, and
``wait_to_read`` is ``block_until_ready`` — this replaces the reference's
dependency-engine Var scheduling (SURVEY §7: ThreadedEngine row).
"""
from __future__ import annotations

import numpy as _np

from ..base import MXNetError, numeric_types, integer_types
from ..context import Context, current_context, current_placement
from .. import ops as _ops

__all__ = ["NDArray", "invoke_nd", "array", "zeros", "ones", "full", "empty",
           "arange", "linspace", "eye", "moveaxis", "concatenate", "save",
           "load", "waitall", "imperative_mixed_precision"]


def _dtype_np(dt):
    return _np.dtype(dt) if dt is not None else None


class NDArray:
    """Multi-dimensional array on a device, with async semantics."""

    __array_priority__ = 1000.0

    def __init__(self, data, ctx=None):
        self._data = data          # jax.Array
        self._ctx = ctx if ctx is not None else current_context()
        self.grad = None           # NDArray or None
        self._grad_req = "null"
        self._tape_node = None     # autograd record entry
        self._tape_index = 0
        self._fresh_grad = False

    # -- basic properties ------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def size(self):
        n = 1
        for s in self.shape:
            n *= s
        return n

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def context(self):
        return self._ctx

    ctx = context

    @property
    def stype(self):
        return "default"

    @property
    def T(self):
        return self.transpose()

    @property
    def handle(self):
        # parity shim: reference exposes the C handle; we expose jax.Array
        return self._data

    # -- sync / host transfer -------------------------------------------
    def wait_to_read(self):
        self._data.block_until_ready()

    def wait_to_write(self):
        self._data.block_until_ready()

    def asnumpy(self):
        return _np.asarray(self._data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.asnumpy().reshape(()).item()

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.asscalar())
        raise ValueError("The truth value of an NDArray with multiple "
                         "elements is ambiguous.")

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __repr__(self):
        return '\n%s\n<NDArray %s @%s>' % (
            str(self.asnumpy()), 'x'.join(str(s) for s in self.shape),
            self._ctx)

    def __iter__(self):
        for i in range(self.shape[0]):
            yield self[i]

    # -- conversion ------------------------------------------------------
    def astype(self, dtype, copy=True):
        dt = _np.dtype(dtype)
        if not copy and self.dtype == dt:
            return self
        return invoke_nd("Cast", [self], {"dtype": dt.name})

    def copy(self):
        return invoke_nd("_copy", [self], {})

    def copyto(self, other):
        if isinstance(other, NDArray):
            if other is self:
                return other
            other._set_data(_device_put(self._data, other._ctx))
            return other
        if isinstance(other, Context):
            out = NDArray(_device_put(self._data, other), ctx=other)
            return out
        raise TypeError("copyto does not support type " + str(type(other)))

    def as_in_context(self, context):
        if self._ctx == context:
            return self
        return self.copyto(context)

    as_in_ctx = as_in_context

    def as_nd_ndarray(self):
        return self

    def tostype(self, stype):
        if stype == "default":
            return self
        from . import sparse as _sp
        return _sp.cast_storage(self, stype)

    def to_dlpack_for_read(self):
        from jax import dlpack as _dl
        return _dl.to_dlpack(self._data)

    # -- mutation (handle swap) -----------------------------------------
    def _set_data(self, new_data):
        self._data = new_data

    def __setitem__(self, key, value):
        import jax.numpy as jnp
        key = _clean_index(key)
        if isinstance(value, NDArray):
            v = value._data
        elif isinstance(value, numeric_types):
            v = value
        else:
            v = jnp.asarray(_np.asarray(value), dtype=self._data.dtype)
        if key == slice(None) and not isinstance(v, (int, float)) \
                and getattr(v, "shape", None) == self.shape:
            self._set_data(jnp.asarray(v, dtype=self._data.dtype))
        else:
            self._set_data(self._data.at[key].set(v))

    def __getitem__(self, key):
        # Routed through the registered `_getitem` op so the lookup is
        # recorded on the autograd tape (gradients flow through any
        # slice/int/fancy index, as in the reference which lowers
        # indexing to op.slice/op.take/op.gather_nd).
        spec, arrays = _index_spec(key, self._ctx)
        return invoke_nd("_getitem", [self] + arrays,
                         {"spec": spec, "num_arrays": len(arrays)})

    # -- autograd --------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):
        from .. import autograd  # noqa: F401
        self.grad = zeros(self.shape, ctx=self._ctx, dtype=self.dtype)
        self._grad_req = grad_req
        self._fresh_grad = False

    def detach(self):
        out = NDArray(self._data, ctx=self._ctx)
        return out

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd
        autograd.backward([self],
                          None if out_grad is None else [out_grad],
                          retain_graph=retain_graph, train_mode=train_mode)

    # -- generic op access ----------------------------------------------
    def _op1(self, opname, **kwargs):
        return invoke_nd(opname, [self], kwargs)

    # named math methods (subset of the reference's generated methods)
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (list, tuple)):
            shape = tuple(shape[0])
        if not shape:
            shape = kwargs.get("shape", None)
        reverse = kwargs.get("reverse", False)
        return invoke_nd("Reshape", [self],
                         {"shape": tuple(shape), "reverse": reverse})

    def reshape_like(self, other):
        return invoke_nd("reshape_like", [self, other], {})

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (list, tuple)):
            axes = tuple(axes[0])
        return invoke_nd("transpose", [self], {"axes": axes or None})

    def swapaxes(self, dim1, dim2):
        return invoke_nd("SwapAxis", [self], {"dim1": dim1, "dim2": dim2})

    def flatten(self):
        return invoke_nd("Flatten", [self], {})

    def expand_dims(self, axis):
        return invoke_nd("expand_dims", [self], {"axis": axis})

    def squeeze(self, axis=None):
        return invoke_nd("squeeze", [self], {"axis": axis})

    def broadcast_to(self, shape):
        return invoke_nd("broadcast_to", [self], {"shape": tuple(shape)})

    def broadcast_like(self, other):
        return invoke_nd("broadcast_like", [self, other], {})

    def tile(self, reps):
        return invoke_nd("tile", [self], {"reps": tuple(reps)})

    def repeat(self, repeats, axis=None):
        return invoke_nd("repeat", [self], {"repeats": repeats, "axis": axis})

    def pad(self, mode, pad_width, constant_value=0.0):
        return invoke_nd("Pad", [self], {"mode": mode, "pad_width": pad_width,
                                         "constant_value": constant_value})

    def flip(self, axis):
        return invoke_nd("reverse", [self], {"axis": axis})

    def clip(self, a_min, a_max):
        return invoke_nd("clip", [self], {"a_min": a_min, "a_max": a_max})

    def slice(self, begin, end, step=None):
        return invoke_nd("slice", [self],
                         {"begin": begin, "end": end, "step": step})

    def slice_axis(self, axis, begin, end):
        return invoke_nd("slice_axis", [self],
                         {"axis": axis, "begin": begin, "end": end})

    def take(self, indices, axis=0, mode="clip"):
        return invoke_nd("take", [self, _as_nd(indices, self._ctx)],
                         {"axis": axis, "mode": mode})

    def one_hot(self, depth, **kwargs):
        return invoke_nd("one_hot", [self], dict(kwargs, depth=depth))

    def pick(self, index, axis=-1, keepdims=False):
        return invoke_nd("pick", [self, _as_nd(index, self._ctx)],
                         {"axis": axis, "keepdims": keepdims})

    def sort(self, axis=-1, is_ascend=True):
        return invoke_nd("sort", [self], {"axis": axis, "is_ascend": is_ascend})

    def argsort(self, axis=-1, is_ascend=True):
        return invoke_nd("argsort", [self],
                         {"axis": axis, "is_ascend": is_ascend})

    def topk(self, axis=-1, k=1, ret_typ="indices", is_ascend=False):
        return invoke_nd("topk", [self], {"axis": axis, "k": k,
                                          "ret_typ": ret_typ,
                                          "is_ascend": is_ascend})

    def dot(self, other, transpose_a=False, transpose_b=False):
        return invoke_nd("dot", [self, other],
                         {"transpose_a": transpose_a,
                          "transpose_b": transpose_b})

    # reductions
    def sum(self, axis=None, keepdims=False, **kw):
        return invoke_nd("sum", [self], {"axis": axis, "keepdims": keepdims})

    def nansum(self, axis=None, keepdims=False, **kw):
        return invoke_nd("nansum", [self], {"axis": axis, "keepdims": keepdims})

    def mean(self, axis=None, keepdims=False, **kw):
        return invoke_nd("mean", [self], {"axis": axis, "keepdims": keepdims})

    def prod(self, axis=None, keepdims=False, **kw):
        return invoke_nd("prod", [self], {"axis": axis, "keepdims": keepdims})

    def max(self, axis=None, keepdims=False, **kw):
        return invoke_nd("max", [self], {"axis": axis, "keepdims": keepdims})

    def min(self, axis=None, keepdims=False, **kw):
        return invoke_nd("min", [self], {"axis": axis, "keepdims": keepdims})

    def norm(self, ord=2, axis=None, keepdims=False):
        return invoke_nd("norm", [self],
                         {"ord": ord, "axis": axis, "keepdims": keepdims})

    def argmax(self, axis=None, keepdims=False):
        return invoke_nd("argmax", [self], {"axis": axis, "keepdims": keepdims})

    def argmin(self, axis=None, keepdims=False):
        return invoke_nd("argmin", [self], {"axis": axis, "keepdims": keepdims})

    # unary math (generated-method parity via explicit list)
    def abs(self):
        return self._op1("abs")

    def sign(self):
        return self._op1("sign")

    def sqrt(self):
        return self._op1("sqrt")

    def square(self):
        return self._op1("square")

    def exp(self):
        return self._op1("exp")

    def log(self):
        return self._op1("log")

    def sigmoid(self):
        return self._op1("sigmoid")

    def tanh(self):
        return self._op1("tanh")

    def relu(self):
        return self._op1("relu")

    def softmax(self, axis=-1):
        return invoke_nd("softmax", [self], {"axis": axis})

    def log_softmax(self, axis=-1):
        return invoke_nd("log_softmax", [self], {"axis": axis})

    def round(self):
        return self._op1("round")

    def floor(self):
        return self._op1("floor")

    def ceil(self):
        return self._op1("ceil")

    def zeros_like(self):
        return self._op1("zeros_like")

    def ones_like(self):
        return self._op1("ones_like")

    def split(self, num_outputs, axis=1, squeeze_axis=False):
        return invoke_nd("SliceChannel", [self],
                         {"num_outputs": num_outputs, "axis": axis,
                          "squeeze_axis": squeeze_axis})

    # -- arithmetic operators -------------------------------------------
    def _binary(self, other, op, scalar_op, reverse=False):
        if isinstance(other, NDArray):
            args = [other, self] if reverse else [self, other]
            return invoke_nd(op, args, {})
        if isinstance(other, numeric_types):
            sname = scalar_op if not reverse else _RSCALAR.get(
                scalar_op, scalar_op)
            return invoke_nd(sname, [self], {"scalar": other})
        if isinstance(other, _np.ndarray):
            return self._binary(array(other, ctx=self._ctx), op, scalar_op,
                                reverse)
        raise TypeError("type %s not supported" % str(type(other)))

    def __add__(self, other):
        return self._binary(other, "broadcast_add", "_plus_scalar")

    def __radd__(self, other):
        return self.__add__(other)

    def __iadd__(self, other):
        out = self.__add__(other)
        self._set_data(out._data)
        return self

    def __sub__(self, other):
        return self._binary(other, "broadcast_sub", "_minus_scalar")

    def __rsub__(self, other):
        return self._binary(other, "broadcast_sub", "_minus_scalar",
                            reverse=True)

    def __isub__(self, other):
        out = self.__sub__(other)
        self._set_data(out._data)
        return self

    def __mul__(self, other):
        return self._binary(other, "broadcast_mul", "_mul_scalar")

    def __rmul__(self, other):
        return self.__mul__(other)

    def __imul__(self, other):
        out = self.__mul__(other)
        self._set_data(out._data)
        return self

    def __truediv__(self, other):
        return self._binary(other, "broadcast_div", "_div_scalar")

    def __rtruediv__(self, other):
        return self._binary(other, "broadcast_div", "_div_scalar",
                            reverse=True)

    def __itruediv__(self, other):
        out = self.__truediv__(other)
        self._set_data(out._data)
        return self

    __div__ = __truediv__
    __rdiv__ = __rtruediv__

    def __mod__(self, other):
        return self._binary(other, "broadcast_mod", "_mod_scalar")

    def __rmod__(self, other):
        return self._binary(other, "broadcast_mod", "_mod_scalar",
                            reverse=True)

    def __pow__(self, other):
        return self._binary(other, "broadcast_power", "_power_scalar")

    def __rpow__(self, other):
        return self._binary(other, "broadcast_power", "_power_scalar",
                            reverse=True)

    def __matmul__(self, other):
        return self.dot(other)

    def __neg__(self):
        return invoke_nd("negative", [self], {})

    def __abs__(self):
        return invoke_nd("abs", [self], {})

    def __eq__(self, other):
        if other is None:
            return False
        return self._binary(other, "broadcast_equal", "_equal_scalar")

    def __ne__(self, other):
        if other is None:
            return True
        return self._binary(other, "broadcast_not_equal", "_not_equal_scalar")

    def __gt__(self, other):
        return self._binary(other, "broadcast_greater", "_greater_scalar")

    def __ge__(self, other):
        return self._binary(other, "broadcast_greater_equal",
                            "_greater_equal_scalar")

    def __lt__(self, other):
        return self._binary(other, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, other):
        return self._binary(other, "broadcast_lesser_equal",
                            "_lesser_equal_scalar")

    def __hash__(self):
        return id(self)

    def __getstate__(self):
        return {"data": self.asnumpy(), "ctx": str(self._ctx)}

    def __setstate__(self, state):
        import jax.numpy as jnp
        self._data = jnp.asarray(state["data"])
        self._ctx = current_context()
        self.grad = None
        self._grad_req = "null"
        self._tape_node = None
        self._tape_index = 0
        self._fresh_grad = False


_RSCALAR = {"_minus_scalar": "_rminus_scalar", "_div_scalar": "_rdiv_scalar",
            "_mod_scalar": "_rmod_scalar", "_power_scalar": "_rpower_scalar"}


def _clean_index(key):
    """Convert NDArray indices inside a key to numpy/int."""
    if isinstance(key, NDArray):
        return key.asnumpy().astype(_np.int32)
    if isinstance(key, tuple):
        return tuple(_clean_index(k) for k in key)
    return key


def _index_spec(key, ctx):
    """Normalize an indexing key into (hashable spec, array inputs).

    Spec item kinds: ("s", start, stop, step) slice, ("b", v) bool
    scalar, ("n",) newaxis, ("e",) ellipsis, ("a",) array placeholder
    consumed in order from the extra op inputs (integers become 0-d
    array inputs so distinct values share one compiled program).
    Boolean masks are converted to integer coordinate arrays host-side
    (they are concrete values in the eager path, so this costs one sync
    at most).
    """
    items = key if isinstance(key, tuple) else (key,)
    spec = []
    arrays = []

    def push_array(a):
        np_a = a.asnumpy() if isinstance(a, NDArray) else _np.asarray(a)
        if np_a.dtype == _np.bool_:
            for coord in _np.nonzero(np_a):
                spec.append(("a",))
                arrays.append(array(coord.astype(_np.int32), ctx=ctx))
        else:
            spec.append(("a",))
            if isinstance(a, NDArray) and np_a.dtype != _np.bool_:
                arrays.append(a)
            else:
                arrays.append(array(np_a.astype(_np.int32), ctx=ctx))

    for it in items:
        if isinstance(it, slice):
            spec.append(("s", it.start, it.stop, it.step))
        elif it is None:
            spec.append(("n",))
        elif it is Ellipsis:
            spec.append(("e",))
        elif isinstance(it, (bool, _np.bool_)):
            # bool scalars are 0-d masks (numpy semantics: insert an
            # axis of size int(v)), NOT integers — and bool is an int
            # subclass, so this must be checked first.
            spec.append(("b", bool(it)))
        elif isinstance(it, integer_types) or isinstance(it, _np.integer):
            # pass the value as a 0-d array input, not a baked attr, so
            # x[0], x[1], ... share ONE compiled program (ints among
            # advanced indices are 0-d advanced indices in numpy, so
            # semantics are unchanged; jnp wraps negative values).
            spec.append(("a",))
            arrays.append(array(_np.int32(int(it)), ctx=ctx))
        elif isinstance(it, (NDArray, _np.ndarray, list)):
            push_array(it)
        else:
            raise MXNetError("NDArray indexing does not support key "
                             "component of type %s" % type(it))
    return tuple(spec), arrays


def _as_nd(x, ctx=None):
    if isinstance(x, NDArray):
        return x
    return array(x, ctx=ctx)


def _device_put(data, ctx: Context):
    """``data`` (host memory or a jax.Array) committed to ``ctx``'s
    device. Where the context resolves to no device this process can
    write to (none at all, or another process's: ``jax.devices()`` is
    global under ``jax.distributed``), on the process's default device,
    uncommitted."""
    import jax
    try:
        device = ctx.jax_device()
        if device.process_index == jax.process_index():
            return jax.device_put(data, device)
    except Exception:
        pass
    return jax.device_put(data)


class HostStagedNDArray(NDArray):
    """What :func:`array` returns inside a ``context.placement_scope``:
    the source's own host memory (``host``, dtype settled), on no device
    yet. The scope's owner — the input pipeline's placer — takes
    ``host`` and sends it to its target, once (``io/pipeline.py``).
    Whoever reads ``_data`` before that gets what :func:`array` gives
    outside a scope: the array committed to ``ctx``'s device."""

    def __init__(self, host, ctx):
        self.host = host
        super().__init__(None, ctx)

    @property
    def _data(self):
        if self.host is not None:
            self._placed, self.host = _device_put(self.host, self._ctx), None
        return self._placed

    @_data.setter
    def _data(self, value):
        self._placed = value
        if value is not None:
            self.host = None

    # a source may ask what it made without sending it anywhere
    def _held(self):
        return self._placed if self.host is None else self.host

    @property
    def shape(self):
        return tuple(self._held().shape)

    @property
    def dtype(self):
        return _np.dtype(self._held().dtype)


# ---------------------------------------------------------------------------
# The imperative entry point (Imperative::Invoke analogue)
# ---------------------------------------------------------------------------

def invoke_nd(op_name, inputs, attrs, out=None, ctx=None):
    """Eagerly invoke a registered op on NDArrays.

    Mirrors MXImperativeInvokeEx → Imperative::Invoke
    (reference: src/c_api/c_api_ndarray.cc:132, imperative.cc:87).
    """
    from .. import autograd
    from .. import random as _random

    op = _ops.get_op(op_name) if isinstance(op_name, str) else op_name
    attrs = {k: v for k, v in attrs.items() if v is not None or k in ("axis",)}
    if "__train__" in op.defaults:
        attrs["__train__"] = autograd.is_training()

    rng = None
    if op.needs_rng:
        rng = _random.new_key()

    raw = [i._data for i in inputs]
    outputs, aux_updates = _ops.invoke(op, raw, attrs, rng=rng)

    octx = ctx or (inputs[0]._ctx if inputs else current_context())
    if not inputs:
        # nullary op: honor ctx placement
        if isinstance(octx, str):
            octx = Context(octx.split("(")[0], 0)
        outputs = tuple(_device_put(o, octx) for o in outputs)

    out_nds = [NDArray(o, ctx=octx) for o in outputs]

    # aux writeback (BatchNorm moving stats, optimizer states)
    for idx, val in aux_updates:
        inputs[idx]._set_data(val)

    if autograd.is_recording():
        autograd._record_op(op, _ops.normalize_attrs(op, attrs), inputs,
                            out_nds, rng)

    if out is not None:
        outs = out if isinstance(out, (list, tuple)) else [out]
        for o, nd in zip(outs, out_nds):
            o._set_data(nd._data)
            o._tape_node = nd._tape_node
            o._tape_index = nd._tape_index
        return out

    if len(out_nds) == 1:
        return out_nds[0]
    return out_nds


# ---------------------------------------------------------------------------
# Creation functions
# ---------------------------------------------------------------------------

def array(source_array, ctx=None, dtype=None):
    was_np = isinstance(source_array, (_np.ndarray, _np.generic, NDArray)) \
        or hasattr(source_array, "__jax_array__") \
        or type(source_array).__module__.startswith("jax")
    if isinstance(source_array, NDArray):
        src = source_array.asnumpy()
    else:
        src = _np.asarray(source_array)
    from ..util import canonical_dtype
    if dtype is None:
        # MXNet: python lists default to float32; numpy keeps its dtype.
        # float64 always demotes to float32 (TPU-native math width);
        # int64 demotes unless MXNET_INT64_TENSOR_SIZE enables x64
        # (large-tensor index support, ref USE_INT64_TENSOR_SIZE).
        if not was_np or src.dtype == _np.float64:
            dtype = _np.float32
        else:
            dtype = canonical_dtype(src.dtype)
    # the dtype is settled on the HOST (canonical_dtype demotes
    # explicitly, so jax never emits its implicit-truncation warning,
    # VERDICT r4 item 5; a no-op view when it already matches), and the
    # host array goes straight to where the context says: one transfer,
    # no stop on the process's default device on the way
    host = src.astype(canonical_dtype(dtype), copy=False)
    if ctx is None and current_placement() is not None:
        return HostStagedNDArray(host, current_context())
    ctx = ctx or current_context()
    return NDArray(_device_put(host, ctx), ctx=ctx)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, integer_types):
        shape = (shape,)
    return invoke_nd("_zeros", [], {"shape": tuple(shape),
                                    "dtype": _np.dtype(dtype or "float32").name},
                     ctx=ctx or current_context())


def ones(shape, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, integer_types):
        shape = (shape,)
    return invoke_nd("_ones", [], {"shape": tuple(shape),
                                   "dtype": _np.dtype(dtype or "float32").name},
                     ctx=ctx or current_context())


def full(shape, val, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, integer_types):
        shape = (shape,)
    return invoke_nd("_full", [], {"shape": tuple(shape), "value": val,
                                   "dtype": _np.dtype(dtype or "float32").name},
                     ctx=ctx or current_context())


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype="float32"):
    return invoke_nd("_arange", [],
                     {"start": start, "stop": stop, "step": step,
                      "repeat": repeat, "dtype": _np.dtype(dtype).name},
                     ctx=ctx or current_context())


def linspace(start, stop, num, endpoint=True, ctx=None, dtype="float32"):
    return invoke_nd("_linspace", [],
                     {"start": start, "stop": stop, "num": num,
                      "endpoint": endpoint, "dtype": _np.dtype(dtype).name},
                     ctx=ctx or current_context())


def eye(N, M=0, k=0, ctx=None, dtype="float32"):
    return invoke_nd("_eye", [], {"N": N, "M": M, "k": k,
                                  "dtype": _np.dtype(dtype).name},
                     ctx=ctx or current_context())


def moveaxis(tensor, source, destination):
    axes = list(range(tensor.ndim))
    try:
        source = [source] if isinstance(source, int) else list(source)
        destination = [destination] if isinstance(destination, int) \
            else list(destination)
    except TypeError:
        raise MXNetError("bad source/destination")
    for s in source:
        axes.remove(s % tensor.ndim)
    for d, s in sorted(zip(destination, source)):
        axes.insert(d % tensor.ndim, s % tensor.ndim)
    return tensor.transpose(axes)


def concatenate(arrays, axis=0, always_copy=True):
    return invoke_nd("Concat", list(arrays),
                     {"dim": axis, "num_args": len(arrays)})


# module-level binary helpers (parity: ndarray.py maximum/minimum/...)
def _ufunc(lhs, rhs, op, scalar_op, rscalar_op=None):
    if isinstance(lhs, NDArray) and isinstance(rhs, NDArray):
        return invoke_nd(op, [lhs, rhs], {})
    if isinstance(lhs, NDArray):
        return invoke_nd(scalar_op, [lhs], {"scalar": rhs})
    if isinstance(rhs, NDArray):
        return invoke_nd(rscalar_op or scalar_op, [rhs], {"scalar": lhs})
    raise TypeError("at least one argument must be an NDArray")


def add(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_add", "_plus_scalar")


def subtract(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_sub", "_minus_scalar",
                  "_rminus_scalar")


def multiply(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_mul", "_mul_scalar")


def divide(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_div", "_div_scalar", "_rdiv_scalar")


def modulo(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_mod", "_mod_scalar", "_rmod_scalar")


def power(base, exp):
    return _ufunc(base, exp, "broadcast_power", "_power_scalar",
                  "_rpower_scalar")


def maximum(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_maximum", "_maximum_scalar")


def minimum(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_minimum", "_minimum_scalar")


def hypot(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_hypot", "_hypot_scalar")


def equal(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_equal", "_equal_scalar")


def not_equal(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_not_equal", "_not_equal_scalar")


def greater(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_greater", "_greater_scalar")


def greater_equal(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_greater_equal",
                  "_greater_equal_scalar")


def lesser(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_lesser", "_lesser_scalar")


def lesser_equal(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_lesser_equal", "_lesser_equal_scalar")


def logical_and(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_logical_and", "_logical_and_scalar")


def logical_or(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_logical_or", "_logical_or_scalar")


def logical_xor(lhs, rhs):
    return _ufunc(lhs, rhs, "broadcast_logical_xor", "_logical_xor_scalar")


def true_divide(lhs, rhs):
    return divide(lhs, rhs)


def waitall():
    import jax
    try:
        jax.effects_barrier()
    except Exception:
        pass


def imperative_mixed_precision(enable=True):
    """Placeholder for AMP hooks (contrib/amp in later reference versions)."""


# ---------------------------------------------------------------------------
# Serialization (reference: src/ndarray/ndarray.cc Save/Load, magic
# 0xF993fac9; here an npz container with the same list/dict surface)
# ---------------------------------------------------------------------------

_SAVE_LIST_KEY = "__mxnet_tpu_list__"


# sparse-aware serialization (the reference NDArray::Save is magic-
# tagged and sparse-aware, ndarray.cc:1576): sparse entries spill their
# components under reserved key prefixes inside the same npz payload
_SP_CSR_KEY = "__sparse_csr__::"
_SP_RSP_KEY = "__sparse_rsp__::"


def _flatten_entry(key, val, arrays):
    from .sparse import CSRNDArray, RowSparseNDArray
    if isinstance(val, CSRNDArray):
        p = _SP_CSR_KEY + key + "::"
        arrays[p + "data"] = val.data.asnumpy()
        arrays[p + "indices"] = val.indices.asnumpy()
        arrays[p + "indptr"] = val.indptr.asnumpy()
        arrays[p + "shape"] = _np.asarray(val.shape, _np.int64)
    elif isinstance(val, RowSparseNDArray):
        p = _SP_RSP_KEY + key + "::"
        arrays[p + "data"] = val.data.asnumpy()
        arrays[p + "indices"] = val.indices.asnumpy()
        arrays[p + "shape"] = _np.asarray(val.shape, _np.int64)
    else:
        arrays[key] = val.asnumpy()


def save(fname, data):
    if isinstance(data, NDArray) or (
            hasattr(data, "stype") and hasattr(data, "asnumpy")):
        data = [data]
    arrays = {}
    if isinstance(data, dict):
        for k, v in data.items():
            _flatten_entry(k, v, arrays)
    elif isinstance(data, (list, tuple)):
        for i, v in enumerate(data):
            _flatten_entry("%s%d" % (_SAVE_LIST_KEY, i), v, arrays)
    else:
        raise ValueError("data needs to either be a NDArray, dict of (str, "
                         "NDArray) pairs or a list of NDarrays.")
    # write-then-rename: a preempted save can never leave a truncated
    # file at fname (the file object keeps numpy from appending .npz)
    import os
    tmp = fname + ".tmp"
    with open(tmp, "wb") as sink:
        _np.savez(sink, **arrays)
    os.replace(tmp, fname)


def _unflatten(loaded):
    from .sparse import CSRNDArray, RowSparseNDArray
    out = {}
    sparse_parts = {}
    for k in loaded.keys():
        for prefix, stype in ((_SP_CSR_KEY, "csr"),
                              (_SP_RSP_KEY, "row_sparse")):
            if k.startswith(prefix):
                name, part = k[len(prefix):].rsplit("::", 1)
                sparse_parts.setdefault((name, stype), {})[part] = \
                    loaded[k]
                break
        else:
            out[k] = array(loaded[k])
    for (name, stype), parts in sparse_parts.items():
        shape = tuple(int(s) for s in parts["shape"])
        if stype == "csr":
            out[name] = CSRNDArray(
                array(parts["data"]), array(parts["indices"]),
                array(parts["indptr"]), shape)
        else:
            out[name] = RowSparseNDArray(
                array(parts["data"]), array(parts["indices"]), shape)
    return out


def load(fname):
    with open(fname, "rb") as f:
        loaded = _np.load(f, allow_pickle=False)
        out = _unflatten(loaded)
        keys = list(out.keys())
        if keys and all(k.startswith(_SAVE_LIST_KEY) for k in keys):
            return [out["%s%d" % (_SAVE_LIST_KEY, i)]
                    for i in range(len(keys))]
        return out
