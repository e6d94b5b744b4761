"""KVStore — parameter synchronization (parity: python/mxnet/kvstore.py
+ src/kvstore/).

Types (factory semantics mirror kvstore.cc:40 substring matching):

- ``local`` / ``device`` — single-process aggregation. The reference
  reduces across GPU copies (CommCPU/CommDevice, comm.h); here values
  live as single (possibly mesh-sharded) arrays, so Reduce is a tree-sum
  of the pushed list compiled by XLA.
- ``tpu_sync`` (also matches ``dist_sync`` / ``dist_device_sync``) — the
  SURVEY §5.8 north star: push/pull lower to psum collectives over the
  ICI mesh via jax.distributed rank/size when launched multi-process,
  replacing the ps-lite ZPush/ZPull path wholesale.
- ``dist_async`` — accepted; degrades to sync (documented divergence,
  SURVEY §2.2 Async SGD row), announced by a one-time warning.

``update_on_kvstore`` semantics, optimizer/updater hosting, row_sparse
pull, and gradient-compression API parity are kept.

Fault tolerance (see README "Fault tolerance" + ``mxnet_tpu.fault``):
dist-type push/pull run under ``fault.with_retries`` — transient
transport errors and planned faults (``MXNET_FAULT_PLAN`` sites
``push``/``pull``/``allreduce``/``init``) are retried with exponential
backoff, and a persistently failing op raises
``CollectiveTimeoutError`` after ``MXNET_KVSTORE_TIMEOUT`` instead of
erroring out on the first attempt. Caveat: retrying a CROSS-PROCESS
collective is only coordinated when the fault is symmetric (a planned
fault fires on every worker running the same plan; real one-sided
transport errors need the symmetric retry barrier a later elastic PR
adds) — the proven lanes are the single-process degenerate case and
planned-fault chaos runs.

Observability: with a telemetry run active (``mxnet_tpu.telemetry``),
every push/pull is accounted per key — bytes moved and caller-observed
latency (retry backoff included) — under comm kinds ``push``/``pull``.
"""
from __future__ import annotations

import functools
import logging
import pickle

from . import fault
from . import telemetry
from .base import MXNetError
from . import optimizer as opt
from .ndarray import NDArray

__all__ = ["KVStore", "create"]


def _to_jnp(np_arr):
    import jax.numpy as jnp
    return jnp.asarray(np_arr)


def _canonical_index_dtype():
    from .util import canonical_dtype
    import numpy as _np
    return canonical_dtype(_np.int64)


def _ctype_key_value(key, vals):
    if isinstance(key, (tuple, list)):
        return list(key), list(vals)
    return [key], [vals]


class _TwoBitCompressor:
    """Threshold quantizer with per-key error feedback (the worker side
    of ref gradient_compression.h: Quantize2Bit + residual kept local).
    Values land in {-t, 0, +t}; the dropped mass feeds the next push."""

    def __init__(self, threshold):
        if threshold <= 0:
            raise ValueError("2bit compression threshold must be > 0")
        self.threshold = threshold
        self._residual = {}

    def compress(self, key, arr):
        import jax.numpy as jnp
        t = self.threshold
        x = arr._data
        res = self._residual.get(key)
        if res is not None:
            x = x + res
        q = jnp.where(x >= t, jnp.asarray(t, x.dtype),
                      jnp.where(x <= -t, jnp.asarray(-t, x.dtype),
                                jnp.zeros((), x.dtype)))
        self._residual[key] = x - q
        return NDArray(q, ctx=arr._ctx)


def _ensure_process_group():
    """A dist kvstore created in a worker spawned by ``python -m
    mxnet_tpu.tools.launch -n N ...`` joins the DMLC_* process group
    (fault.join_process_group — retrying, shared with package import);
    a process already in a group (manual initialize, TPU pod runtime)
    or with no contract in the env is left untouched."""
    import jax
    try:
        if jax.process_count() > 1:
            return
    except Exception:
        pass
    fault.join_process_group()


_DIST_ASYNC_WARNED = False


def _warn_dist_async_once():
    """dist_async degrades to synchronous updates on this backend (the
    documented divergence, SURVEY §2.2 Async SGD row) — say so once
    instead of silently changing semantics."""
    global _DIST_ASYNC_WARNED
    if not _DIST_ASYNC_WARNED:
        _DIST_ASYNC_WARNED = True
        logging.warning(
            "kvstore 'dist_async' degrades to synchronous updates on "
            "this backend (documented divergence, SURVEY §2.2 Async SGD "
            "row): pushes are psum-reduced across workers like "
            "'tpu_sync', with the same retry/timeout guarding.")


class KVStore:
    """Key-value store for parameter synchronization
    (reference: kvstore.py:61)."""

    def __init__(self, kv_type="local"):
        self._type = kv_type
        self._data = {}
        self._updater = None
        self._optimizer = None
        self._compression_params = None
        self._is_dist = ("dist" in kv_type) or ("tpu" in kv_type)
        if self._is_dist:
            if "async" in kv_type:
                _warn_dist_async_once()
            _ensure_process_group()

    # -- identity --------------------------------------------------------
    @property
    def type(self):
        return self._type

    @property
    def rank(self):
        import jax
        try:
            return jax.process_index()
        except Exception:
            return 0

    @property
    def num_workers(self):
        import jax
        try:
            return jax.process_count()
        except Exception:
            return 1

    # -- core ops --------------------------------------------------------
    def init(self, key, value):
        keys, vals = _ctype_key_value(key, value)
        for k, v in zip(keys, vals):
            if isinstance(v, (list, tuple)):
                v = v[0]
            self._data[k] = v.copy()

    def _guarded(self, fn, site):
        """Run one sync phase under fault.with_retries on dist stores
        (and whenever a fault plan is active); the local fast path
        stays a direct call. Callers keep state mutation OUT of the
        retried region — the injection point fires at the top of each
        attempt, and only communication re-runs on failure."""
        if self._is_dist:
            return fault.with_retries(fn, site=site)
        return fault.guard(fn, site)

    def push(self, key, value, priority=0):
        """Aggregate value(s) into the store.

        Single-device-list push: tree-sum (the CommDevice Reduce role).
        On multi-process tpu_sync, the sum additionally runs a psum
        across processes via jax collectives.
        """
        keys, vals = _ctype_key_value(key, value)
        for k, v in zip(keys, vals):
            self._push_one(k, v)

    def _push_one(self, k, v):
        # local phase — aggregation and compression mutate worker-local
        # state (compression residual), so they run exactly once
        if isinstance(v, (list, tuple)):
            # CommDevice semantics (comm.h:451): gather the
            # per-device copies onto the first device's placement,
            # then tree-sum there (XLA fuses the adds).
            vs = [v[0]] + [self._like(x, v[0]) for x in v[1:]]
            agg = self._tree_sum(vs)
        else:
            agg = v
        comp = getattr(self, "_compression", None)
        if comp is not None:
            from .ndarray.sparse import BaseSparseNDArray
            if not isinstance(agg, BaseSparseNDArray):
                agg = comp.compress(k, agg)
        # communication phase — the only retried region; re-running the
        # reduce is free of side effects on this worker. The telemetry
        # latency is caller-observed: retry backoff counts.
        with telemetry.comm_span("push", k, agg):
            agg = self._guarded(
                functools.partial(self._global_reduce, agg), site="push")
        # apply phase — runs at most once per push, so a retried
        # transport failure can never double-apply an optimizer update
        if self._optimizer is not None:
            self._ensure_updater()
        if self._updater is not None:
            self._align_placement(agg, self._data[k])
            self._updater(self._key_index(k), agg, self._data[k])
        else:
            # KVStoreLocal without updater: merged value replaces the
            # stored one (kvstore_local.h PushImpl assign semantics)
            self._data[k] = agg.copy()

    @staticmethod
    def _tree_sum(vals):
        """The Reduce kernel of a list-push (CommDevice Reduce role,
        comm.h:451): sum the per-worker copies. Works on NDArrays or raw
        device arrays and is jit-traceable."""
        agg = vals[0]
        for other in vals[1:]:
            agg = agg + other
        return agg

    @staticmethod
    def _like(arr, ref):
        """arr re-placed onto ref's sharding (no-op when it matches)."""
        from .ndarray.sparse import BaseSparseNDArray
        if isinstance(arr, BaseSparseNDArray) \
                or isinstance(ref, BaseSparseNDArray):
            return arr  # sparse values carry their own placement
        if getattr(arr._data, "sharding", None) == \
                getattr(ref._data, "sharding", None):
            return arr
        import jax
        return NDArray(jax.device_put(arr._data, ref._data.sharding),
                       ctx=ref._ctx)

    def _align_placement(self, pushed, stored):
        """Move the stored value onto the pushed gradient's sharding when
        they differ — a dp-mesh executor pushes replicated global arrays
        while kvstore copies were made pre-mesh on one device, and jax
        refuses eager math across device sets."""
        from .ndarray.sparse import BaseSparseNDArray
        if isinstance(pushed, BaseSparseNDArray) \
                or isinstance(stored, BaseSparseNDArray):
            return
        p, s = pushed._data, stored._data
        ps = getattr(p, "sharding", None)
        ss = getattr(s, "sharding", None)
        if ps is not None and ss is not None and ps != ss:
            import jax
            stored._set_data(jax.device_put(s, ps))

    def _global_reduce(self, arr):
        """Cross-process allreduce for tpu_sync (SURVEY §5.8 north star).

        On backends with cross-process SPMD (TPU pods) the reduce runs
        IN-PROGRAM: each worker's value becomes one shard of a global
        array over a 'worker' mesh axis and a single jitted psum (XLA
        collective over ICI/DCN) produces the sum — replacing the
        reference's ps-lite ZPush/ZPull round trip
        (kvstore_dist.h:211). Backends without it (jaxlib's CPU
        backend refuses multiprocess computations) exchange through
        the process group's coordination service
        (``parallel.multihost.cross_host_sum``): rank-keyed gathers +
        a deterministic rank-order fold — the same channel the ps-lite
        server pool occupied, minus the server processes. Either way
        the bytes land in the per-link (ici/dcn) telemetry split.
        """
        if not self._is_dist or self.num_workers == 1:
            return arr
        from .ndarray.sparse import BaseSparseNDArray, RowSparseNDArray
        if isinstance(arr, RowSparseNDArray):
            return self._global_reduce_rsp(arr)
        if isinstance(arr, BaseSparseNDArray):
            # CSR is not a reference dist-push format (the server merge
            # at kvstore_dist_server.h:499 is rsp-only); dense roundtrip
            stype = arr.stype
            return self._global_reduce(arr.tostype("default")) \
                .tostype(stype)
        import jax
        import numpy as _np
        from .parallel import multihost
        if getattr(self, "_inprogram_reduce", None) is None:
            self._inprogram_reduce = multihost.supports_global_spmd()
        if self._inprogram_reduce:
            try:
                from jax.sharding import Mesh, PartitionSpec as P
                from jax.experimental import multihost_utils
                from .parallel import collectives

                # one device per process carries that worker's shard
                per_proc = {}
                for d in jax.devices():
                    per_proc.setdefault(d.process_index, d)
                workers = [per_proc[i] for i in sorted(per_proc)]
                mesh = Mesh(_np.asarray(workers), ("worker",))
                local = arr._data[None]  # (1, ...) local shard
                glob = multihost_utils.host_local_array_to_global_array(
                    local, mesh, P("worker"))
                summed = collectives.all_reduce(glob, mesh, axis="worker")
                # back to a process-local array before any eager math
                local_sum = multihost_utils.global_array_to_host_local_array(
                    summed, mesh, P())
                return NDArray(local_sum[0], ctx=arr._ctx)
            except Exception as exc:
                # disable for the rest of the run so every push doesn't
                # re-raise; the host roundtrip is correct but slow, and
                # silence would hide that the fast path is dead
                import warnings
                warnings.warn(
                    "kvstore %s: in-program collective reduce failed "
                    "(%s: %s); falling back to the coordination-"
                    "service exchange for all subsequent pushes"
                    % (self._type, type(exc).__name__, exc))
                self._inprogram_reduce = False
        local = _np.asarray(arr._data)[None]      # (1, ...) local row
        total = multihost.cross_host_sum("kv_push", [local])[0]
        telemetry.comm_links("kvstore_push", 0,
                             int(local.nbytes) * (self.num_workers - 1))
        return NDArray(_to_jnp(total), ctx=arr._ctx)

    def _global_reduce_rsp(self, arr):
        """Row-union cross-worker reduce for row_sparse values — the
        TPU-native form of the reference server's rsp merge
        (kvstore_dist_server.h:499 ApplyUpdates row union).

        Workers exchange ONE bool presence mask per row (N bools, not
        N*D values), deterministically agree on the sorted union of
        touched rows, scatter their local rows onto union slots, and
        allreduce only the (U, D) union block — the embedding-gradient
        value never densifies to (N, D)."""
        import numpy as _np
        import jax.numpy as jnp
        from .ndarray.sparse import RowSparseNDArray
        from .parallel import multihost

        N = int(arr.shape[0])
        row_shape = tuple(arr.shape[1:])
        idx = arr._sp_indices._data
        mask = jnp.zeros((N,), jnp.bool_).at[idx].set(True)
        # presence masks ride the coordination service (N bools per
        # worker — control-plane-sized on every backend)
        masks = _np.stack([m[0] for m in multihost.exchange_arrays(
            "kv_rsp_mask", [_np.asarray(mask)])])           # (W, N)
        union = _np.nonzero(masks.any(axis=0))[0] \
            .astype(_np.int64)                              # sorted
        dtype = arr._sp_data._data.dtype
        if union.size == 0:
            return RowSparseNDArray(
                NDArray(jnp.zeros((0,) + row_shape, dtype),
                        ctx=arr._ctx),
                NDArray(jnp.zeros(
                    (0,), _canonical_index_dtype()), ctx=arr._ctx),
                arr.shape, ctx=arr._ctx)
        pos = jnp.searchsorted(jnp.asarray(union), idx)
        local = jnp.zeros((union.shape[0],) + row_shape, dtype) \
            .at[pos].add(arr._sp_data._data)
        summed = self._global_reduce(NDArray(local, ctx=arr._ctx))
        return RowSparseNDArray(
            summed, NDArray(jnp.asarray(union), ctx=arr._ctx),
            arr.shape, ctx=arr._ctx)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        keys, outs = _ctype_key_value(key, out)
        for k, o in zip(keys, outs):
            with telemetry.comm_span("pull", k, self._data.get(k)):
                self._guarded(
                    functools.partial(self._pull_one, k, o,
                                      ignore_sparse),
                    site="pull")

    def _pull_one(self, k, o, ignore_sparse):
        from .ndarray.sparse import BaseSparseNDArray
        if k not in self._data:
            raise MXNetError("kvstore: key %s not initialized" % str(k))
        v = self._data[k]
        if isinstance(v, BaseSparseNDArray):
            if ignore_sparse:
                return  # reference pull skips sparse values
            tgts = o if isinstance(o, (list, tuple)) else [o]
            for tgt in tgts:
                v.copyto(tgt)
            return
        if isinstance(o, (list, tuple)):
            # Broadcast: each destination keeps its own placement
            # (comm.h Broadcast copies back out to every device).
            for oo in o:
                oo._set_data(self._like(v, oo)._data)
        else:
            o._set_data(self._like(v, o)._data)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows of a value (reference:
        kvstore.py row_sparse_pull → kvstore_dist.h EncodeRowSparseKey).

        The stored value's selected rows are gathered on-device; the
        returned row set is deduplicated and sorted, as the reference
        guarantees. ``out`` must be row_sparse (the reference asserts
        the same); a dense ``out`` raises MXNetError.
        """
        import numpy as _host_np
        from .ndarray.sparse import RowSparseNDArray, BaseSparseNDArray
        assert out is not None and row_ids is not None
        keys, outs = _ctype_key_value(key, out)
        if isinstance(row_ids, NDArray):
            row_ids = [row_ids] * len(keys)
        for k, o, rid in zip(keys, outs, row_ids):
            v = self._data[k]
            if isinstance(v, BaseSparseNDArray):
                v = v.tostype("default")
            rid_np = _host_np.unique(
                rid.asnumpy().astype(_host_np.int64)
                if isinstance(rid, NDArray)
                else _host_np.asarray(rid, dtype=_host_np.int64))
            rid_nd = NDArray(_to_jnp(rid_np), ctx=v._ctx)
            rows = v.take(rid_nd)
            tgts = o if isinstance(o, (list, tuple)) else [o]
            for tgt in tgts:
                if isinstance(tgt, RowSparseNDArray):
                    tgt._sp_data = rows.copy()
                    tgt._sp_indices = NDArray(_to_jnp(rid_np),
                                              ctx=v._ctx)
                    tgt._shape = v.shape
                else:
                    # reference asserts the out stype is row_sparse
                    # (kvstore.py row_sparse_pull); a dense out would
                    # silently get a (len(row_ids), D) buffer in place
                    # of its declared full shape.
                    raise MXNetError(
                        "row_sparse_pull requires 'out' arrays with "
                        "stype='row_sparse', got dense NDArray for key "
                        "%s" % (k,))

    # -- updater/optimizer ----------------------------------------------
    def set_updater(self, updater):
        self._updater = updater

    _updater_func = property(lambda self: self._updater)

    def set_optimizer(self, optimizer):
        """Host the optimizer kvstore-side (update_on_kvstore=True path;
        reference runs it server-side, kvstore_dist_server.h:346)."""
        self._optimizer = optimizer
        self._ensure_updater()

    def _ensure_updater(self):
        if self._updater is None and self._optimizer is not None:
            self._updater = opt.get_updater(self._optimizer)

    def _key_index(self, key):
        if not hasattr(self, "_key_order"):
            self._key_order = {}
        if key not in self._key_order:
            self._key_order[key] = len(self._key_order)
        return self._key_order[key]

    # -- gradient compression -------------------------------------------
    def set_gradient_compression(self, compression_params):
        """2-bit gradient compression with worker-side error feedback
        (reference: src/kvstore/gradient_compression.h:52). Each push
        quantizes grad+residual to {-threshold, 0, +threshold} before
        the cross-worker reduce — 2 bits of information per element on
        the wire — and keeps the quantization error as the residual
        added to the next push, the reference's feedback loop."""
        if "type" not in compression_params:
            raise ValueError("compression_params requires 'type'")
        ctype = compression_params["type"]
        if ctype not in ("2bit", "none"):
            raise ValueError(
                "unsupported gradient compression type %r (2bit|none)"
                % (ctype,))
        self._compression_params = dict(compression_params)
        if ctype == "2bit":
            self._compression = _TwoBitCompressor(
                float(compression_params.get("threshold", 0.5)))
        else:
            self._compression = None

    # -- distributed control --------------------------------------------
    def barrier(self):
        if self.num_workers > 1:
            # device sync where the backend can span processes,
            # coordination-service barrier where it cannot (CPU)
            from .parallel import distributed
            distributed.barrier("kvstore_barrier")

    def _barrier(self):
        self.barrier()

    def _send_command_to_servers(self, head, body):
        pass

    def save_optimizer_states(self, fname, dump_optimizer=False):
        assert self._updater is not None, "Cannot save states for " \
            "distributed training without updater"
        from .base import atomic_write_bytes
        atomic_write_bytes(fname, self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        assert self._updater is not None, "Cannot load states for " \
            "distributed training without updater"
        self._updater.set_states(open(fname, 'rb').read())


def create(name='local'):
    """Factory (reference: kvstore.py:649; type matching kvstore.cc:40)."""
    if not isinstance(name, str):
        raise TypeError('name must be a string')
    if name not in ('local', 'device', 'nccl', 'tpu_sync', 'dist_sync',
                    'dist_device_sync', 'dist_async', 'dist'):
        # substring semantics like the reference factory
        if not any(t in name for t in ('local', 'device', 'dist', 'tpu')):
            raise MXNetError("unknown KVStore type %s" % name)
    return KVStore(name)
