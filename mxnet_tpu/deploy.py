"""Standalone deploy artifacts — the TPU-native ``c_predict_api``.

Reference deploy story: ``HybridBlock.export`` emits symbol.json +
params, which the standalone C predict ABI (src/c_api/c_predict_api.cc)
or the single-file amalgamation build loads without the Python
framework. The TPU-native equivalent is a serialized StableHLO
program: ``export_compiled`` lowers the model's forward (params baked
in as constants) through ``jax.export`` into ONE portable file that
any JAX runtime can execute via ``load_compiled`` — no framework, no
model code, no param files.

    mx.deploy.export_compiled(net, "model.mxp",
                              input_shapes={"data": (1, 3, 224, 224)})
    pred = mx.deploy.load_compiled("model.mxp")
    probs = pred(x)                      # numpy/jax array in, out

Artifact format 2 (written by default; format-1 files still load):

- the meta block records the **output** shapes/dtypes next to the
  inputs, and :class:`Predictor` validates every call against the
  recorded signature (argument count, non-batch dims, dtype) so a
  mismatched call raises a descriptive :class:`MXNetError` instead of
  an opaque XLA shape error;
- ``export_compiled(..., batch_sizes=[1, 2, 4, 8])`` emits a
  **multi-signature** artifact: one exported program per bucket batch
  size in the same single file. :class:`Predictor` dispatches a call
  of batch ``b`` to the smallest bucket ``>= b`` (zero-pad rows in,
  slice rows back out — exact, a row's result never depends on its
  batch-mates), and ``mxnet_tpu.serving.InferenceServer`` uses the
  same ladder to coalesce concurrent requests with a fixed program
  cache (no recompile storms under arbitrary request mixes).

The on-disk layout stays backward compatible: MAGIC + meta length +
meta JSON + the program blobs back to back (format 1 readers of a
single-program format-2 file see exactly the old layout).

Artifact format 3 (``export_compiled(..., quantize=True)``): the
exported programs run the INT8 graph — ``contrib.quantization``
calibrates per-node ranges on ``calib_data`` (naive min/max), rewrites
eligible FullyConnected/Convolution nodes into
quantize→quantized_op→requantize→dequantize chains over
``ops.quantization`` (int8×int8→int32 on the MXU), and the meta's
``quantization`` block records the calibration ranges plus the
measured accuracy delta: export replays the calibration batches
through BOTH graphs and stores ``max_abs_delta`` — pass
``max_output_delta`` to make export FAIL when quantization moved any
output element further than tolerated (the accuracy-delta oracle).
Format 1/2 artifacts load unchanged; format-3 files read as format 2
plus the extra meta block.
"""
from __future__ import annotations

import json
import struct

import numpy as _np

from .base import MXNetError, atomic_write_bytes

__all__ = ["export_compiled", "load_compiled", "Predictor",
           "check_cast_dtype"]

_MAGIC = b"MXTPUDEPLOY1"


def _graph_fn(symbol, arg_params, aux_params, input_shapes, dtype):
    import jax.numpy as jnp
    from .cached_op import build_graph_callable

    fn, arg_names, aux_names, _n_rng, n_out = \
        build_graph_callable(symbol)
    data_names = [n for n in arg_names if n not in arg_params]
    missing = [n for n in data_names if n not in input_shapes]
    if missing:
        raise MXNetError(
            "export_compiled: provide input_shapes for %s" % missing)
    baked = {n: jnp.asarray(arg_params[n]._data
                            if hasattr(arg_params[n], "_data")
                            else arg_params[n])
             for n in arg_names if n in arg_params}
    baked_aux = {n: jnp.asarray(aux_params[n]._data
                                if hasattr(aux_params[n], "_data")
                                else aux_params[n])
                 for n in aux_names}

    def forward(*data):
        feed = dict(zip(data_names, data))
        vals = [feed[n] if n in feed else baked[n] for n in arg_names]
        vals.extend(baked_aux[n] for n in aux_names)
        outs = fn({"__train__": False}, *vals)[:n_out]
        return outs[0] if n_out == 1 else tuple(outs)

    return forward, data_names


def _specs(input_shapes, data_names, dtype, batch=None):
    """ShapeDtypeStructs for the data inputs; ``batch`` (a bucket
    size) replaces the leading dim of every input — by convention all
    data inputs share the batch dimension."""
    import jax
    import jax.numpy as jnp
    specs = []
    for n in data_names:
        shape = tuple(input_shapes[n])
        if batch is not None:
            if not shape:
                raise MXNetError(
                    "export_compiled: input %r is a scalar — "
                    "batch_sizes needs a leading batch dim" % n)
            shape = (int(batch),) + shape[1:]
        specs.append(jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)))
    return specs


def _out_meta(exported):
    return [{"shape": [int(s) for s in a.shape], "dtype": str(a.dtype)}
            for a in exported.out_avals]


def check_cast_dtype(name, arr, dtype_str, who="Predictor"):
    """The one dtype gate for artifact-described inputs (shared by
    :class:`Predictor` and ``serving.InferenceServer``): a
    ``same_kind`` cast is applied silently, anything else raises a
    descriptive error naming the input."""
    if dtype_str and str(arr.dtype) != dtype_str:
        if not _np.can_cast(arr.dtype, _np.dtype(dtype_str),
                            casting="same_kind"):
            raise MXNetError(
                "%s: input %r dtype %s cannot safely cast to the "
                "artifact's recorded %s"
                % (who, name, arr.dtype, dtype_str))
        arr = arr.astype(_np.dtype(dtype_str), copy=False)
    return arr


def _batch_arrays(batch):
    """Numpy data arrays of one calibration batch (DataBatch-style
    ``.data`` list, or a bare array)."""
    datas = batch.data if hasattr(batch, "data") else [batch]
    return [_np.asarray(d.asnumpy() if hasattr(d, "asnumpy") else d)
            for d in datas]


def _max_output_delta(fp32_fn, q_fn, calib_data, num_calib_batches,
                      n_inputs):
    """Replay calibration batches through both graphs; the largest
    absolute elementwise output difference is the artifact's recorded
    quantization accuracy delta."""
    delta, batches = 0.0, 0
    for batch in calib_data:
        xs = _batch_arrays(batch)[:n_inputs]
        ref = fp32_fn(*xs)
        got = q_fn(*xs)
        ref = ref if isinstance(ref, tuple) else (ref,)
        got = got if isinstance(got, tuple) else (got,)
        for r, g in zip(ref, got):
            d = _np.max(_np.abs(_np.asarray(g, _np.float32)
                                - _np.asarray(r, _np.float32)))
            delta = max(delta, float(d))
        batches += 1
        if num_calib_batches and batches >= num_calib_batches:
            break
    if hasattr(calib_data, "reset"):
        calib_data.reset()
    return delta, batches


def export_compiled(model, path, input_shapes, params=None,
                    aux_params=None, dtype="float32", batch_sizes=None,
                    quantize=False, calib_data=None,
                    num_calib_batches=None, excluded_sym_names=(),
                    max_output_delta=None):
    """Serialize ``model`` (a hybridized Gluon block, or a Symbol plus
    ``params``/``aux_params`` dicts) into one portable StableHLO file.
    Parameters are baked in as constants — the artifact is fully
    self-contained, like the reference's amalgamation build.

    ``batch_sizes`` (optional) exports one program per bucket batch
    size — a multi-signature artifact whose leading input dim is each
    bucket in turn (the serving bucket ladder). Without it, one
    program with exactly ``input_shapes`` is exported.

    ``quantize=True`` writes a **format-3 int8 artifact**: the graph
    is calibrated on ``calib_data`` (required; naive min/max over
    ``num_calib_batches``), rewritten through
    ``contrib.quantization.quantize_symbol`` (int8 MXU compute with
    per-node calibrated requantize ranges; ``excluded_sym_names``
    opts nodes out), and the exported programs ARE the quantized
    graph. The meta's ``quantization`` block records the ranges and
    the measured ``max_abs_delta`` between fp32 and int8 outputs over
    the calibration batches; with ``max_output_delta`` set, export
    raises :class:`MXNetError` instead of silently shipping an
    artifact whose quantization error exceeds the tolerance."""
    import jax
    from jax import export as jexport
    from . import symbol as sym_mod

    if isinstance(model, sym_mod.Symbol):
        symbol = model
        arg_params = dict(params or {})
        aux = dict(aux_params or {})
    else:                                  # Gluon HybridBlock
        if not getattr(model, "_cached_graph", None):
            raise MXNetError(
                "export_compiled: hybridize() the block and run one "
                "forward before exporting")
        symbol = model._cached_graph[1]
        arg_names = set(symbol.list_arguments())
        aux_names = set(symbol.list_auxiliary_states())
        arg_params, aux = {}, {}
        for name, p in model.collect_params().items():
            if name in arg_names:
                arg_params[name] = p.data()
            elif name in aux_names:
                aux[name] = p.data()

    forward, data_names = _graph_fn(symbol, arg_params, aux,
                                    input_shapes, dtype)
    quant_meta = None
    if quantize:
        from .contrib import quantization as _quant
        if calib_data is None:
            raise MXNetError(
                "export_compiled: quantize=True requires calib_data "
                "(a re-iterable batch source) for range calibration "
                "and the accuracy-delta oracle")
        ranges = _quant.calibrate_ranges(
            symbol, arg_params, aux, calib_data,
            num_calib_batches=num_calib_batches,
            data_name=data_names[0])
        qsym = _quant.quantize_symbol(
            symbol, excluded_symbols=set(excluded_sym_names),
            calib_ranges=ranges)
        q_forward, q_names = _graph_fn(qsym, arg_params, aux,
                                       input_shapes, dtype)
        if q_names != data_names:
            raise MXNetError(
                "export_compiled: quantized graph changed the data "
                "inputs %s -> %s" % (data_names, q_names))
        delta, batches = _max_output_delta(
            jax.jit(forward), jax.jit(q_forward), calib_data,
            num_calib_batches, len(data_names))
        if max_output_delta is not None and delta > max_output_delta:
            raise MXNetError(
                "export_compiled: int8 quantization moved an output "
                "element by %.6g — beyond the max_output_delta %.6g "
                "tolerance; widen the tolerance, exclude the worst "
                "layers (excluded_sym_names), or calibrate on more "
                "representative data" % (delta, max_output_delta))
        quant_meta = {
            "dtype": "int8",
            "calib_mode": "naive",
            "calib_batches": batches,
            "ranges": {n: [float(lo), float(hi)]
                       for n, (lo, hi) in sorted(ranges.items())},
            "excluded": sorted(excluded_sym_names),
            "max_abs_delta": delta,
            "tolerance": max_output_delta,
        }
        forward = q_forward
    jitted = jax.jit(forward)
    if batch_sizes is not None:
        buckets = sorted({int(b) for b in batch_sizes})
        if not buckets or buckets[0] < 1:
            raise MXNetError(
                "export_compiled: batch_sizes must be positive ints, "
                "got %r" % (batch_sizes,))
    else:
        buckets = [None]
    programs = []
    for b in buckets:
        exported = jexport.export(jitted)(
            *_specs(input_shapes, data_names, dtype, batch=b))
        if b is None:
            shape0 = tuple(input_shapes[data_names[0]])
            b = int(shape0[0]) if shape0 else 1
        programs.append((int(b), exported))
    blobs = [e.serialize() for _, e in programs]
    meta = {
        "format": 3 if quant_meta else 2,
        "inputs": [{"name": n, "shape": list(input_shapes[n]),
                    "dtype": str(dtype)} for n in data_names],
        "outputs": _out_meta(programs[0][1]),
        "programs": [{"batch": b, "length": len(blob),
                      "outputs": _out_meta(e)}
                     for (b, e), blob in zip(programs, blobs)],
        "framework": "mxnet_tpu",
    }
    if quant_meta:
        meta["quantization"] = quant_meta
    meta_bytes = json.dumps(meta).encode()
    # atomic_write_bytes (tmp + os.replace): a preempted export must
    # leave any previous artifact intact, never a truncated one a
    # serving replica could load
    atomic_write_bytes(path, b"".join(
        [_MAGIC, struct.pack("<I", len(meta_bytes)), meta_bytes]
        + blobs))
    return path


class Predictor:
    """Callable wrapper over a deserialized deploy artifact (the
    c_predict_api MXPredCreate/MXPredForward role).

    Calls are validated against the artifact meta — argument count,
    per-input non-batch dims, dtype — and a batch of ``b`` rows is
    dispatched to the smallest exported bucket ``>= b`` (rows
    zero-padded in, sliced back out; exact). A call that cannot match
    any recorded signature raises a descriptive :class:`MXNetError`
    instead of surfacing an opaque XLA error."""

    def __init__(self, programs, meta):
        if hasattr(programs, "call"):      # legacy (exported, meta)
            shape0 = (meta.get("inputs") or [{}])[0].get("shape") or []
            batch = int(shape0[0]) if shape0 else 1
            programs = [(batch, programs)]
        self._programs = sorted(programs, key=lambda p: p[0])
        self.meta = meta

    @property
    def input_names(self):
        return [i["name"] for i in self.meta["inputs"]]

    @property
    def batch_sizes(self):
        """The exported bucket ladder (ascending)."""
        return [b for b, _ in self._programs]

    @property
    def output_info(self):
        """Recorded output shapes/dtypes (format 2; None on format-1
        artifacts that predate the field)."""
        return self.meta.get("outputs")

    @property
    def quantization(self):
        """The format-3 quantization block — calibration ranges,
        measured ``max_abs_delta``, exclusions — or None on an fp32
        artifact."""
        return self.meta.get("quantization")

    # -- validation --------------------------------------------------------
    def _validate(self, arrays):
        """Check ``arrays`` against the artifact meta; returns the
        shared batch size (None when the meta records no shapes)."""
        inputs = self.meta.get("inputs") or []
        if inputs and len(arrays) != len(inputs):
            raise MXNetError(
                "Predictor: artifact takes %d input(s) %s, got %d "
                "argument(s)" % (len(inputs),
                                 [i.get("name") for i in inputs],
                                 len(arrays)))
        batch = None
        for spec, arr in zip(inputs, arrays):
            name = spec.get("name", "?")
            want = [int(s) for s in (spec.get("shape") or [])]
            if want:
                got = list(arr.shape)
                if len(got) != len(want):
                    raise MXNetError(
                        "Predictor: input %r has rank %d, artifact "
                        "recorded shape %s (rank %d)"
                        % (name, len(got), want, len(want)))
                if got[1:] != want[1:]:
                    raise MXNetError(
                        "Predictor: input %r non-batch dims %s do not "
                        "match the artifact's recorded %s"
                        % (name, got[1:], want[1:]))
                if batch is None:
                    batch = got[0]
                elif got[0] != batch:
                    raise MXNetError(
                        "Predictor: inconsistent batch dims — input "
                        "%r has %d rows where earlier inputs had %d"
                        % (name, got[0], batch))
            check_cast_dtype(name, arr, spec.get("dtype"))
        return batch

    def _cast(self, arrays):
        inputs = self.meta.get("inputs") or []
        return [check_cast_dtype(inputs[i].get("name", "?"), arr,
                                 inputs[i].get("dtype"))
                if i < len(inputs) else arr
                for i, arr in enumerate(arrays)]

    def bucket_for(self, batch):
        """The smallest exported bucket ``>= batch``; raises a
        descriptive error past the ladder's top."""
        from .serving.batcher import BucketLadder
        b = BucketLadder(self.batch_sizes).bucket_for(batch)
        if b is None:
            raise MXNetError(
                "Predictor: batch %d exceeds the largest exported "
                "bucket %d (ladder %s) — re-export with a bigger "
                "bucket or split the call"
                % (batch, self._programs[-1][0], self.batch_sizes))
        return b

    def program(self, bucket):
        """The exported program for an exact bucket size."""
        for b, e in self._programs:
            if b == bucket:
                return e
        raise MXNetError("Predictor: no program for bucket %d "
                         "(ladder %s)" % (bucket, self.batch_sizes))

    # -- prediction --------------------------------------------------------
    def __call__(self, *args):
        arrays = [a.asnumpy() if hasattr(a, "asnumpy")
                  else _np.asarray(a) for a in args]
        batch = self._validate(arrays)
        arrays = self._cast(arrays)
        if batch is None:                  # shape-less legacy meta
            return self._programs[0][1].call(*arrays)
        bucket = self.bucket_for(batch)
        exported = self.program(bucket)
        if bucket != batch:
            arrays = [_np.concatenate(
                [a, _np.zeros((bucket - batch,) + a.shape[1:],
                              dtype=a.dtype)]) for a in arrays]
        out = exported.call(*arrays)
        if bucket != batch:
            if isinstance(out, tuple):
                out = tuple(o[:batch] for o in out)
            else:
                out = out[:batch]
        return out

    predict = __call__


def load_compiled(path):
    """Load an ``export_compiled`` artifact (format 1, 2, or 3 — a
    format-3 file reads as format 2 whose programs happen to run the
    int8 graph). Needs only jax — not the framework's model code or
    parameter files."""
    from jax import export as jexport
    with open(path, "rb") as f:
        magic = f.read(len(_MAGIC))
        if magic != _MAGIC:
            raise MXNetError("%s is not a mxnet_tpu deploy artifact"
                             % path)
        (mlen,) = struct.unpack("<I", f.read(4))
        meta_bytes = f.read(mlen)
        meta = json.loads(meta_bytes.decode())
        if meta.get("format", 1) >= 2 and meta.get("programs"):
            programs = []
            for p in meta["programs"]:
                blob = f.read(int(p["length"]))
                if len(blob) != int(p["length"]):
                    raise MXNetError(
                        "%s is truncated: program for bucket %s is "
                        "short" % (path, p.get("batch")))
                programs.append((int(p["batch"]),
                                 jexport.deserialize(blob)))
        else:                              # format 1: one trailing blob
            blob = f.read()
            shape0 = (meta.get("inputs") or [{}])[0].get("shape") or []
            batch = int(shape0[0]) if shape0 else 1
            programs = [(batch, jexport.deserialize(blob))]
    return Predictor(programs, meta)
