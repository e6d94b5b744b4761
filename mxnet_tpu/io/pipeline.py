"""Staged asynchronous input pipeline: multi-worker decode + device
prefetch, so ``data_wait`` disappears from the step critical path.

The reference framework hides input cost behind compute with a whole
C++ iterator stack — PrefetcherIter → ThreadedIter → BatchLoader
(SURVEY §3.5) — whose Python port here had shrunk to one daemon thread
handing back *host* batches: decode was serial and the host→device
transfer still ran inside the consumer's step. Following the staged-
parallelism design of tf.data (Murray et al., VLDB 2021) and the
compute/transfer-overlap argument of PyTorch DDP (Li et al., VLDB
2020), this module splits the input path into three explicit stages:

1. **Decode/augment pool** — ``MXNET_DATA_WORKERS`` threads (numpy /
   cv2 / PIL release the GIL, the reference's OMP parser role). A
   single scheduler thread pulls work items from the source *in
   order* and fans the expensive decode out to the pool; because the
   resulting futures enter the hand-off queue in submission order,
   delivery order is always the source order — no reorder buffer,
   no nondeterminism. Sources that implement the split protocol
   (:meth:`DataIter.next_raw` + :meth:`DataIter.decode_raw`, see
   ``NDArrayIter``/``ImageRecordIter``) get true multi-worker decode;
   any other iterator degrades to serialized ``next()`` calls — still
   fully asynchronous with the consumer, like the old prefetcher.
2. **Device prefetch** — the pipeline owns where a batch goes. While a
   placement is set, the source decodes inside a
   ``context.placement_scope``: ``mx.nd.array(host)`` without a ``ctx``
   sends the bytes nowhere and hands over the source's own host memory
   (a ``HostStagedNDArray``). The placer thread then resolves the
   placement for each array — a device, a ``Sharding``, or the
   per-array callable, where name and shape are known — and calls
   ``jax.device_put(host, target)``: a view of the host array for each
   target chip and one host-to-chip transfer each, no stop on the
   default device on the way. It *blocks until the transfer lands*, so
   H2D overlaps the current step's compute and the consumer receives
   device-resident arrays. An array that arrives already committed to
   devices (built outside the decode threads, or with an explicit
   ``ctx``) is resharded device to device as before, and counted:
   ``profiler.counters()`` has ``pipeline_placed_from_host`` and
   ``pipeline_resharded``, the ``pipeline.h2d`` span carries the
   ``route``. Bytes and latency are accounted per array name under the
   telemetry ``h2d`` kind (``tools.diagnose`` renders an H2D table
   showing how much transfer ran off the critical path). A source must
   not write to host memory it has handed to ``mx.nd.array`` in its
   decode before the batch is delivered: until the placer has sent it,
   the array IS that memory.
3. **Backpressure-bounded buffering** — every queue is bounded
   (decode: workers+depth futures; ready: ``prefetch_depth``), every
   put is stop-aware (timeout loop checking the stop event), and
   shutdown drains queues before joining, so ``reset()``/``close()``/
   GC never leak a blocked thread.

Donation safety: the fused train step (``fused_step.py``) donates only
weights and optimizer state — batch inputs ride in the non-donated
argument block — and each emitted batch is a fresh ``device_put``
result, never an alias of a buffer a previous step handed to XLA, so
pipeline batches feed ``fused_step``'s traced inputs directly.

Telemetry: the consumer-side ``data_wait`` span opens ONLY when the
ready queue runs dry (a non-blocking get is tried first), so the phase
now measures true input stalls instead of every fetch; all pipeline
threads are off the accounting thread, so their decode/transfer time
never pollutes the step timeline.
"""
from __future__ import annotations

import contextlib
import queue
import threading
from concurrent.futures import ThreadPoolExecutor

from .. import envs, tracing
from ..context import placement_scope
from .io import DataBatch, DataIter

__all__ = ["AsyncInputPipeline", "data_workers", "pipeline_enabled",
           "placement_for_module", "make_sharded_pipeline",
           "place_batch", "stop_aware_put"]

_SENTINEL = object()      # end-of-epoch marker
_PUT_TICK = 0.05          # stop-aware put poll interval (seconds)


def stop_aware_put(q, item, stop, tick=_PUT_TICK):
    """Bounded put that gives up when ``stop`` fires, so a full queue
    can never wedge a producer thread past shutdown. Returns False
    when the put was abandoned. The one copy of the discipline every
    off-critical-path background stage uses (this pipeline's decode/
    placer threads; ``checkpoint.py``'s writer keeps the plain
    blocking put because its queue-full state IS the intended
    backpressure on the training thread)."""
    while not stop.is_set():
        try:
            q.put(item, timeout=tick)
            return True
        except queue.Full:
            continue
    return False


def data_workers(default=2):
    """The configured decode-pool width (``MXNET_DATA_WORKERS``)."""
    return max(1, envs.get_int("MXNET_DATA_WORKERS", default))


def pipeline_enabled():
    """The ``MXNET_DATA_PIPELINE`` gate for the fit-loop wiring —
    default ON; ``0``/``false``/``off`` fall back to the plain
    iterator (re-read each fit so benchmarks can toggle it)."""
    return envs.get_bool("MXNET_DATA_PIPELINE")


# ---------------------------------------------------------------------------
# device placement
# ---------------------------------------------------------------------------

def _placement_target(placement, name, data):
    """Resolve a placement spec to the device/sharding for one array.
    ``placement`` is a jax.Device, a Sharding, or a callable
    ``(name, array) -> device/sharding/None``."""
    if callable(placement) and not hasattr(placement, "device_kind") \
            and not hasattr(placement, "addressable_devices"):
        return placement(name, data)
    return placement


# what profiler.counters() counts, by the route an array took
_ROUTE_COUNTER = {"host": "pipeline_placed_from_host",
                  "reshard": "pipeline_resharded"}


def _put_one(nd_arr, placement, name):
    """Send one NDArray where ``placement`` says and block until it is
    resident — on the placer thread, off the step critical path. Three
    routes, told apart by what the array in hand is:

    - ``host``: still the source's host memory (made inside the
      pipeline's placement scope). ``jax.device_put(host, target)``
      takes a view per target chip and makes one host-to-chip transfer
      each; with no target it goes to its own context's device.
    - ``reshard``: already committed to devices, elsewhere. Today's
      device-to-device ``device_put``, unchanged.
    - ``resident``: already where asked. The block is still the
      transfer-completion barrier the consumer would otherwise pay
      inside its first op.

    Either way the array's bytes and the wait are accounted under h2d.
    """
    import jax

    from .. import profiler, telemetry
    from ..ndarray import NDArray
    if getattr(nd_arr, "stype", "default") != "default":
        return nd_arr            # sparse batches stay host-side
    host = getattr(nd_arr, "host", None)
    data = host if host is not None else nd_arr._data
    if data is None:
        return nd_arr
    target = _placement_target(placement, name, data)
    if host is not None:
        route = "host"
        if target is None:
            target = nd_arr._ctx.jax_device()
    elif target is None:
        return nd_arr
    elif getattr(data, "sharding", None) == target or (
            getattr(target, "device_kind", None) is not None
            and getattr(data, "devices", None) is not None
            and data.devices() == {target}):
        route = "resident"
    else:
        route = "reshard"
    nbytes = int(getattr(data, "nbytes", 0) or 0)
    out = nd_arr
    # the placer runs AHEAD of consumption by design; while the ring is
    # on, the context token parents the transfer to the step that was
    # open while it ran — explicit args, not thread identity (this
    # thread is off the accounting thread on purpose)
    with tracing.span("pipeline.h2d", "io", tid=tracing.track("io:h2d"),
                      bytes=nbytes, name=name, route=route,
                      **(tracing.context() or {})) as sp:
        if route != "resident":
            data = jax.device_put(data, target)
            out = NDArray(data, ctx=nd_arr._ctx)
        data.block_until_ready()
    if route in _ROUTE_COUNTER:
        profiler.increment_counter(_ROUTE_COUNTER[route])
    telemetry.h2d(name, nbytes, sp.t1 - sp.t0)
    return out


def place_batch(batch, placement, data_names=None, label_names=None):
    """Place one batch's arrays on the target device/sharding.
    Handles :class:`DataBatch`, bare NDArrays, and (nested)
    lists/tuples of them — the gluon DataLoader's ``(data, label)``
    pairs included. Non-array leaves pass through untouched;
    ``data_names``/``label_names`` label the h2d accounting (the
    batch's own ``provide_data`` wins when set)."""
    from ..ndarray import NDArray
    if placement is None or batch is None:
        return batch
    if isinstance(batch, NDArray):
        return _put_one(batch, placement,
                        data_names[0] if data_names else "data")
    if isinstance(batch, DataBatch):
        names_d = [d.name for d in batch.provide_data] \
            if batch.provide_data else list(data_names or [])
        names_l = [l.name for l in batch.provide_label] \
            if batch.provide_label else list(label_names or [])

        def put_roster(arrays, names, fallback):
            if arrays is None:
                return None
            out = []
            for i, a in enumerate(arrays):
                if not isinstance(a, NDArray):
                    out.append(a)    # numpy leaves stay host-side
                    continue
                name = names[i] if i < len(names) else \
                    "%s%d" % (fallback, i)
                out.append(_put_one(a, placement, name))
            return out

        placed = DataBatch(put_roster(batch.data, names_d, "data"),
                           put_roster(batch.label, names_l, "label"),
                           pad=batch.pad, index=batch.index,
                           bucket_key=batch.bucket_key,
                           provide_data=batch.provide_data,
                           provide_label=batch.provide_label)
        # bucketed batches (bucketing.BucketedPipeline) ride validity
        # info as attributes — the mask contract must survive placement
        for extra in ("valid_lengths", "valid_rows"):
            if hasattr(batch, extra):
                setattr(placed, extra, getattr(batch, extra))
        return placed
    if isinstance(batch, (list, tuple)):
        # a 2-element batch is the (data, label) convention — label the
        # second element's h2d accounting accordingly
        names_per = [data_names] * len(batch)
        if len(batch) == 2:
            names_per[1] = label_names or ["label"]
        placed = [place_batch(b, placement, names_per[i], label_names)
                  for i, b in enumerate(batch)]
        if hasattr(batch, "_fields"):    # namedtuple: positional fields
            return type(batch)(*placed)
        return type(batch)(placed)
    return batch


def _dp_placement(mesh, rep, shard, batch_args=None):
    """The one copy of ``Executor._dp_place``'s sharding rule as a
    placement callable: batch args whose leading dim splits over the
    mesh's device count go on ``shard``, everything else on ``rep`` —
    so batches the pipeline pre-places make the executor's own
    placement pass a no-op."""
    n_dp = mesh.devices.size

    def place(name, arr):
        if (batch_args is None or name in batch_args) \
                and getattr(arr, "ndim", 0) >= 1 \
                and arr.shape[0] % n_dp == 0:
            return shard
        return rep
    return place


def placement_for_module(module):
    """The placement spec matching a bound Module's executor: the
    mesh's dp/replicated shardings when the bind spans devices, else
    the single bound device. None when the module has no executor to
    consult."""
    ex = getattr(module, "_exec", None)
    if ex is None:
        return None
    mesh = getattr(ex, "_mesh", None)
    if mesh is not None:
        rep, shard = ex._dp_shardings()
        batch_args = set(getattr(ex, "_batch_args", ()) or ())
        return _dp_placement(mesh, rep, shard, batch_args)
    try:
        return ex._ctx.jax_device()
    except Exception:
        return None


def make_sharded_pipeline(source, mesh, prefetch_depth=2,
                         num_workers=None):
    """A pipeline whose batches land pre-sharded for a data-parallel
    mesh step: batch-dim-divisible arrays over ``dp``, the rest
    replicated (``parallel/data_parallel.py`` consumes these without a
    second ``device_put``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    place = _dp_placement(mesh, NamedSharding(mesh, P()),
                          NamedSharding(mesh, P("dp")))
    return AsyncInputPipeline(source, num_workers=num_workers,
                              prefetch_depth=prefetch_depth,
                              placement=place)


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------

class AsyncInputPipeline(DataIter):
    """Three-stage asynchronous wrapper around a :class:`DataIter`
    (or anything with ``next()``/``reset()``).

    Stage 1 parallelizes decode across ``num_workers`` threads when the
    source implements the split protocol (``next_raw``/``decode_raw``),
    preserving source order; stage 2 moves each decoded batch onto
    ``placement`` (device / Sharding / per-array callable) ahead of
    consumption; stage 3 is the bounded, stop-aware buffering between
    them. Epoch semantics match ``PrefetchingIter``: the source's
    ``StopIteration`` ends the epoch, ``reset()`` restarts cleanly.
    """

    def __init__(self, source, num_workers=None, prefetch_depth=2,
                 placement=None):
        super().__init__(getattr(source, "batch_size", 0) or 0)
        self._source = source
        self._workers = num_workers if num_workers is not None \
            else data_workers()
        self._workers = max(1, int(self._workers))
        self.prefetch_depth = max(1, int(prefetch_depth))
        self._placement = placement
        self._split = hasattr(source, "next_raw") and \
            hasattr(source, "decode_raw")
        try:
            self._data_names = [d.name if hasattr(d, "name") else d[0]
                                for d in source.provide_data]
        except Exception:
            self._data_names = []
        try:
            self._label_names = [l.name if hasattr(l, "name") else l[0]
                                 for l in source.provide_label]
        except Exception:
            self._label_names = []
        self._stop = None
        self._threads = []
        self._pool = None
        self._decode_q = None
        self._ready_q = None
        self._exhausted = False
        self._start()

    # -- DataIter surface --------------------------------------------------
    @property
    def provide_data(self):
        return self._source.provide_data

    @property
    def provide_label(self):
        return self._source.provide_label

    def set_placement(self, placement):
        """Adopt a new device/sharding target. Takes effect on the next
        batch the placer touches (attribute reads are atomic); batches
        already in the ready queue keep their old placement — consumers
        transfer those themselves, exactly as before placement existed.
        A batch decoded before the first placement was set is already on
        the default device: the placer reshards it, and counts it."""
        self._placement = placement

    # -- lifecycle ---------------------------------------------------------
    def _start(self):
        self._stop = threading.Event()
        self._exhausted = False
        # decode_q holds futures (split mode) or whole batches; its
        # bound is the in-flight decode window — workers + a margin so
        # the pool never idles waiting on the placer
        self._decode_q = queue.Queue(
            maxsize=self._workers + self.prefetch_depth)
        self._ready_q = queue.Queue(maxsize=self.prefetch_depth)
        if self._split and self._workers > 1:
            self._pool = ThreadPoolExecutor(
                max_workers=self._workers,
                thread_name_prefix="mxio-decode")
        else:
            self._pool = None
        sched = threading.Thread(target=self._scheduler, daemon=True,
                                 name="mxio-sched")
        placer = threading.Thread(target=self._placer, daemon=True,
                                  name="mxio-place")
        self._threads = [sched, placer]
        sched.start()
        placer.start()

    def _stop_aware_put(self, q, item):
        return stop_aware_put(q, item, self._stop)

    def _scheduler(self):
        """Stage-1 driver: pull work from the source IN ORDER (the
        source itself is never touched concurrently), fan decode out to
        the pool, and emit futures/batches in submission order."""
        stop = self._stop
        src = self._source
        try:
            while not stop.is_set():
                try:
                    # the context is captured HERE (the scheduling
                    # thread) and handed to the pool worker as an
                    # explicit token: the decode span is parented to the
                    # step that triggered the fetch, never to the worker
                    if self._pool is not None:
                        item = self._pool.submit(
                            self._decode, src.next_raw(),
                            tracing.context())
                    elif self._split:
                        # one worker: still use the split so randomness
                        # is drawn serially (bit-identical to eager)
                        item = self._decode(src.next_raw(),
                                            tracing.context())
                    else:
                        with self._decoding(tracing.context()):
                            item = src.next()
                except StopIteration:
                    break
                except Exception as exc:        # surface in consumer
                    self._stop_aware_put(self._decode_q, exc)
                    return
                if not self._stop_aware_put(self._decode_q, item):
                    return
        finally:
            self._stop_aware_put(self._decode_q, _SENTINEL)

    @contextlib.contextmanager
    def _decoding(self, ctx):
        """What surrounds a source's decode on whichever thread runs it:
        the ``pipeline.decode`` span, parented to the triggering step by
        the ``ctx`` token, and — while the pipeline has a placement —
        the thread's placement scope, so that the arrays the source
        makes stay host memory for the placer to send (stage 2)."""
        with tracing.span("pipeline.decode", "io",
                          tid=tracing.track("io:decode"), **(ctx or {})), \
                placement_scope(self._placement):
            yield

    def _decode(self, raw, ctx):
        """Decode one work item (pool or scheduler thread)."""
        with self._decoding(ctx):
            return self._source.decode_raw(raw)

    def _placer(self):
        """Stage-2 driver: resolve decode results in order, commit them
        to the target device/sharding (blocking HERE, off the critical
        path, so the consumer receives transfer-complete batches), and
        fill the bounded ready queue."""
        stop = self._stop
        while not stop.is_set():
            try:
                item = self._decode_q.get(timeout=_PUT_TICK)
            except queue.Empty:
                continue
            if item is _SENTINEL:
                self._stop_aware_put(self._ready_q, _SENTINEL)
                return
            if isinstance(item, Exception):
                self._stop_aware_put(self._ready_q, item)
                stop.set()       # the scheduler must not keep decoding
                return
            try:
                batch = item.result() if hasattr(item, "result") \
                    else item
                batch = place_batch(batch, self._placement,
                                    self._data_names,
                                    self._label_names)
            except Exception as exc:            # noqa: BLE001
                self._stop_aware_put(self._ready_q, exc)
                stop.set()       # the scheduler must not keep decoding
                return
            if not self._stop_aware_put(self._ready_q, batch):
                return

    def _shutdown_threads(self):
        """Stop, drain, then join — in that order. Draining both
        queues unblocks any producer mid-put; the stop-aware puts
        guarantee a bounded exit even if the consumer never drains.
        Returns the threads (if any) still alive after the join
        timeout — wedged inside a stalled source read/decode."""
        stop = self._stop
        if stop is None:
            return []
        stop.set()
        for q in (self._decode_q, self._ready_q):
            if q is None:
                continue
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
        for t in self._threads:
            t.join(timeout=5)
        wedged = [t for t in self._threads if t.is_alive()]
        self._threads = []
        if self._pool is not None:
            # a wedged producer may be stalled inside a pool decode:
            # don't let shutdown() block on it too
            self._pool.shutdown(wait=not wedged)
            self._pool = None
        return wedged

    def reset(self):
        """Stop the pipeline, reset the source, and restart with the
        SAME configured ``prefetch_depth`` and worker pool. Refuses to
        reset the source while a producer is wedged inside it (a
        stalled read) — resetting under a live reader would corrupt
        its cursor/record state."""
        wedged = self._shutdown_threads()
        if wedged:
            from ..base import MXNetError
            raise MXNetError(
                "input pipeline reset: producer thread(s) %s did not "
                "exit within the join timeout (source read stalled?); "
                "refusing to reset the source under a live reader"
                % [t.name for t in wedged])
        self._source.reset()
        self._start()

    def close(self):
        """Tear the pipeline down for good (also runs at GC). The
        source is the caller's — its own close()/GC handles it."""
        self._shutdown_threads()

    def __del__(self):
        try:
            self._shutdown_threads()
        except Exception:       # interpreter teardown
            pass

    # -- consumption -------------------------------------------------------
    def next(self):
        if self._exhausted:
            raise StopIteration
        try:
            # fast path: a ready batch means NO data stall — data_wait
            # must measure only true queue-dry time
            item = self._ready_q.get_nowait()
        except queue.Empty:
            with tracing.span("pipeline.wait", phase="data_wait"):
                item = self._blocking_get()
        if item is _SENTINEL:
            self._exhausted = True
            raise StopIteration
        if isinstance(item, Exception):
            self._exhausted = True
            raise item
        return item

    def _blocking_get(self):
        stop = self._stop
        while True:
            try:
                return self._ready_q.get(timeout=_PUT_TICK)
            except queue.Empty:
                if stop.is_set():
                    return _SENTINEL
                if not any(t.is_alive() for t in self._threads):
                    # producers died without a sentinel (should not
                    # happen; defensive against a hard thread kill)
                    return _SENTINEL

    def iter_next(self):
        try:
            self._cached = self.next()
            return True
        except StopIteration:
            self._cached = None
            return False

    # the base-class protocol (iter_next + accessors) serves the batch
    # iter_next fetched
    def getdata(self):
        return self._cached.data

    def getlabel(self):
        return self._cached.label

    def getpad(self):
        return self._cached.pad

    def getindex(self):
        return self._cached.index
