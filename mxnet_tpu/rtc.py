"""Runtime kernel compilation — the TPU-native ``mx.rtc``.

Reference surface: ``mx.rtc.CudaModule`` compiles CUDA C source at
runtime via NVRTC and launches kernels on GPU NDArrays
(python/mxnet/rtc.py:42, include/mxnet/rtc.h:39). The TPU-native
translation (SURVEY §7: "RTC ≙ Pallas-from-source") keeps the same
object model — module(source).get_kernel(name, signature).launch(args,
ctx, grid, block) — but the source is PYTHON text defining Pallas
kernel bodies, compiled at runtime with exec + pallas_call:

    source = '''
    def axpy(alpha, x_ref, y_ref):
        y_ref[...] = y_ref[...] + alpha * x_ref[...]
    '''
    mod = PallasModule(source)
    k = mod.get_kernel("axpy", "float alpha, const float *x, float *y")
    k.launch((2.0, x, y), mx.cpu(), (1, 1, 1), (1, 1, 1))

Signature grammar matches the reference exactly: ``const`` marks an
input array, ``*`` marks an array, bare types are scalars. Non-const
arrays are in-out (the kernel reads and writes their ref, backed by
``input_output_aliases``), and launch writes results back into the
passed NDArrays — the reference's mutation contract. ``grid_dims``
maps onto the Pallas grid; ``block_dims`` has no TPU counterpart
(blocking comes from BlockSpecs / ref indexing) and must be (1, 1, 1).
On non-TPU platforms kernels run in Pallas interpret mode.
"""
from __future__ import annotations

import re

import numpy as _np

from .base import MXNetError

__all__ = ["PallasModule", "PallasKernel", "CudaModule"]

# reference rtc.py _DTYPE_CPP_TO_NP, plus numpy-style spellings
_DTYPE_TO_NP = {
    "float": _np.float32, "double": _np.float64, "__half": _np.float16,
    "uint8_t": _np.uint8, "int": _np.int32, "int32_t": _np.int32,
    "int8_t": _np.int8, "char": _np.int8, "int64_t": _np.int64,
    "float32": _np.float32, "float64": _np.float64,
    "float16": _np.float16, "bfloat16": "bfloat16",
    "int32": _np.int32, "int64": _np.int64, "int8": _np.int8,
    "uint8": _np.uint8, "bool": _np.bool_,
}

_SIG_RE = re.compile(
    r"""^\s*(const)?\s*([\w_]+)\s*(\*)?\s*([\w_]+)?\s*$""")


class PallasModule:
    """Compile Python/Pallas source text at runtime."""

    def __init__(self, source, options=(), exports=()):
        del options                      # nvrtc flags: no analogue
        self._source = source
        ns = {}
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl
        ns.update({"jax": jax, "jnp": jnp, "pl": pl})
        exec(compile(source, "<mx.rtc>", "exec"), ns)
        self._ns = ns
        for name in exports:
            if name not in ns:
                raise MXNetError(
                    "rtc source does not define exported name %r"
                    % name)

    def get_kernel(self, name, signature):
        fn = self._ns.get(name)
        if not callable(fn):
            raise MXNetError(
                "rtc module has no kernel function %r" % name)
        is_ndarray, is_const, dtypes = [], [], []
        for arg in re.sub(r"\s+", " ", signature).split(","):
            m = _SIG_RE.match(arg)
            if not m or m.groups()[1] == "const":
                raise ValueError(
                    'Invalid function prototype "%s". Must be in the '
                    'form of "(const) type (*) (name)"' % arg)
            is_const.append(bool(m.groups()[0]))
            dtype = m.groups()[1]
            is_ndarray.append(bool(m.groups()[2]))
            if dtype not in _DTYPE_TO_NP:
                raise TypeError(
                    "Unsupported kernel argument type %s. Supported: %s"
                    % (arg, ", ".join(sorted(_DTYPE_TO_NP))))
            dtypes.append(_np.dtype(_DTYPE_TO_NP[dtype]))
        return PallasKernel(fn, name, is_ndarray, is_const, dtypes)


class PallasKernel:
    """Launchable kernel; create via ``PallasModule.get_kernel``."""

    def __init__(self, fn, name, is_ndarray, is_const, dtypes):
        self._fn = fn
        self._name = name
        self._is_ndarray = is_ndarray
        self._is_const = is_const
        self._dtypes = dtypes

    def launch(self, args, ctx, grid_dims=(1, 1, 1),
               block_dims=(1, 1, 1), shared_mem=0):
        """Run the kernel. Arrays marked const are inputs; other
        arrays are in-out and receive the results in place (the
        reference CudaKernel.launch contract)."""
        from .ndarray import NDArray
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        if len(grid_dims) != 3 or len(block_dims) != 3:
            raise ValueError(
                "grid_dims/block_dims must be tuples of 3 integers")
        if tuple(block_dims) != (1, 1, 1):
            raise MXNetError(
                "block_dims have no TPU counterpart (blocking comes "
                "from Pallas BlockSpecs); pass (1, 1, 1)")
        if shared_mem:
            raise MXNetError("shared_mem has no TPU counterpart")
        if len(args) != len(self._dtypes):
            raise MXNetError(
                "PallasKernel(%s) expects %d arguments but got %d"
                % (self._name, len(self._dtypes), len(args)))

        grid = tuple(int(g) for g in grid_dims if int(g) > 1)
        in_vals = []          # const array values, in signature order
        out_specs = []        # (signature position, NDArray)
        scalars = {}
        for i, (arg, is_nd, const, dt) in enumerate(
                zip(args, self._is_ndarray, self._is_const,
                    self._dtypes)):
            if is_nd:
                if not isinstance(arg, NDArray):
                    raise MXNetError(
                        "argument %d of %s must be an NDArray"
                        % (i, self._name))
                if const:
                    in_vals.append(arg._data.astype(jnp.dtype(dt)))
                else:
                    out_specs.append((i, arg))
            else:
                # numpy scalar, baked as a compile-time literal (Pallas
                # rejects closure-captured traced values; the reference
                # also passes scalars by value per launch)
                scalars[i] = _np.dtype(dt).type(arg)
        if not out_specs:
            raise MXNetError(
                "kernel %s has no writable (non-const) array argument"
                % self._name)

        n_in = len(in_vals)
        const_pos = [i for i, (nd, c) in enumerate(
            zip(self._is_ndarray, self._is_const)) if nd and c]
        out_pos = [i for i, _ in out_specs]

        def body(*refs):
            # refs: const inputs, aliased in-out inputs, then outputs;
            # rebuild the kernel's signature-ordered argument list,
            # handing the OUTPUT ref for in-out positions
            ins = refs[:n_in]
            outs = refs[n_in + len(out_specs):]
            call_args = []
            for i in range(len(self._dtypes)):
                if i in scalars:
                    call_args.append(scalars[i])
                elif i in out_pos:
                    call_args.append(outs[out_pos.index(i)])
                else:
                    call_args.append(ins[const_pos.index(i)])
            self._fn(*call_args)

        # interpret mode is for devices Mosaic cannot target; decided
        # from the RESOLVED device — mx.gpu(0) is the TPU on this stack
        device = jax.devices()[0] if ctx is None else ctx.jax_device()
        interpret = device.platform != "tpu"
        out_shapes = [jax.ShapeDtypeStruct(a._data.shape,
                                           jnp.dtype(self._dtypes[i]))
                      for i, a in out_specs]
        io_alias = {n_in + j: j for j in range(len(out_specs))}
        kwargs = {"grid": grid} if grid else {}
        call = pl.pallas_call(
            body, out_shape=out_shapes,
            input_output_aliases=io_alias, interpret=interpret,
            **kwargs)
        results = call(*in_vals,
                       *[a._data.astype(jnp.dtype(self._dtypes[i]))
                         for i, a in out_specs])
        if not isinstance(results, (tuple, list)):
            results = (results,)
        for (i, arr), val in zip(out_specs, results):
            arr._set_data(val.astype(arr._data.dtype))


# the reference's class name kept as an alias so ported scripts run
CudaModule = PallasModule
