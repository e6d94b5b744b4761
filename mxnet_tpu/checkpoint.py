"""Async sharded checkpointing + elastic resume.

PR 1 made ``Module.fit`` checkpoints atomic (tmp + ``os.replace``) but
they stayed synchronous single-file host writes: every epoch that lands
a save stalls the step for the full device→host copy + serialize +
write + fsync, and the format cannot express state that is sharded
across a mesh. This module completes that half of the fault-tolerance
story (ROADMAP item 3):

- **Copy-on-snapshot, off the critical path** —
  :meth:`CheckpointManager.save` captures each param/aux buffer as a
  device-side copy: an async dispatch costing no host sync and no D2H
  on the training thread, yet immune to the fused train step later
  DONATING the source buffer to XLA (a bare reference would be read
  after deletion by the writer). The snapshot is enqueued; a background
  writer thread performs the D2H transfer, serialization, checksum,
  write and fsync — the same off-critical-path pattern as
  ``io/pipeline.py``'s placer stage. The in-flight queue is bounded
  (``MXNET_CHECKPOINT_INFLIGHT``, default 2): a slow disk applies
  backpressure to the training loop instead of growing host memory
  without bound. Optimizer state is the one pre-serialized piece (its
  buffers ARE replaced in place per step, so the pickle happens at
  enqueue time, accounted as the blocking snapshot cost).

- **One manifest + per-shard artifacts** — each save writes the
  parameters as per-mesh-position shard files plus a JSON manifest
  (``<prefix>-<epoch>.ckpt.json``) holding every shard's sha256 and
  every parameter's piece layout (shard file, key, global index).
  Shard 0 is named ``<prefix>-<epoch>.params`` and carries every
  whole/replicated entry in the PR 1 single-file key format, so a
  checkpoint saved on one device is **byte-compatible with the legacy
  loader**, and legacy epoch listing/scan keep working unchanged.
  Every file is written tmp + fsync + ``os.replace`` and the manifest
  is written LAST — a SIGKILL mid-save strands at most unreferenced
  tmp/shard files, never a manifest pointing at a torn shard; the
  resume scan (``model.load_latest_valid_checkpoint``) verifies the
  checksums and falls back to the previous epoch on any mismatch.

- **Elastic resume** — :func:`load_arrays` re-assembles each
  parameter's global value from its pieces on the host, so
  :func:`restore_params` can ``jax.device_put`` the result against the
  *current* mesh with ``NamedSharding`` (via
  ``parallel.data_parallel.shard_params``): a run preempted on N
  devices resumes on M devices, sharded or replicated, with the same
  values. ``Module.fit(resume_from_checkpoint=True)`` gets this for
  free — params re-enter through the bound executor's own placement.

- **Observability** — the training thread's blocking share (snapshot +
  enqueue wait, or the whole save in sync mode) runs under the
  existing telemetry ``checkpoint`` phase; the writer thread reports a
  ``checkpoint`` JSONL record per save (bytes, snapshot/serialize/
  write/fsync sub-spans, async vs blocking split, last good epoch)
  rendered by ``tools.diagnose``'s Checkpoint table.

- **Deterministic failure testing** — the writer visits the fault
  sites ``ckpt_write`` (before each file write) and ``ckpt_fsync``
  (before each fsync), so ``MXNET_FAULT_PLAN`` can kill or stall a
  save at an exact file boundary. A failed save — injected or real —
  warns and leaves the previous good checkpoint as the resume point;
  it never kills the training loop it protects.

``MXNET_ASYNC_CHECKPOINT=1`` (default) selects the background writer in
``Module.fit``; ``0`` runs the same subsystem synchronously on the
training thread (identical files, identical trajectory — only the
step-time tail differs; not measured on the chip).
"""
from __future__ import annotations

import hashlib
import io as _io
import json
import logging
import os
import queue
import threading
import time

import numpy as _np

from . import envs
from .base import MXNetError

__all__ = ["CheckpointManager", "async_checkpoint_enabled",
           "manifest_path", "load_manifest", "validate_manifest",
           "latest_manifest_epoch", "load_arrays", "load_param_arrays",
           "restore_params", "save_arrays", "saved_dtype_policy",
           "atomic_write_file", "write_bytes_async", "flush_async_writes"]

_PIECE_SEP = "::piece"       # shard-file key suffix for partial pieces
MANIFEST_FORMAT = 1


def async_checkpoint_enabled():
    """The ``MXNET_ASYNC_CHECKPOINT`` gate (default ON) — re-read per
    fit so benchmarks and tests can toggle it."""
    return envs.get_bool("MXNET_ASYNC_CHECKPOINT")


def _tag(prefix, epoch):
    return "%s-%04d" % (prefix, int(epoch))


def manifest_path(prefix, epoch):
    return _tag(prefix, epoch) + ".ckpt.json"


def _shard_file(prefix, epoch, shard, n_shards):
    """Shard 0 keeps the legacy single-file name so PR 1-era loaders
    (and the epoch scan's ``-NNNN.params`` pattern) read new
    checkpoints; higher mesh positions get their own artifact."""
    if shard == 0:
        return _tag(prefix, epoch) + ".params"
    return "%s.shard%02d-of-%02d.params" % (_tag(prefix, epoch), shard,
                                            n_shards)


# ---------------------------------------------------------------------------
# durable file writes (tmp + fsync + os.replace, fault-injectable)
# ---------------------------------------------------------------------------

def atomic_write_file(fname, payload):
    """The checkpoint write discipline: ``<fname>.tmp`` + fsync +
    ``os.replace``, visiting the ``ckpt_write``/``ckpt_fsync`` fault
    sites so MXNET_FAULT_PLAN can abort or stall a save at an exact
    file boundary. A raised fault leaves at most a ``.tmp`` behind —
    never a live, torn ``fname``."""
    from . import fault
    fault.inject("ckpt_write")
    tmp = fname + ".tmp"
    with open(tmp, "wb") as sink:
        sink.write(payload)
        sink.flush()
        fault.inject("ckpt_fsync")
        os.fsync(sink.fileno())
    os.replace(tmp, fname)


def _sha256(payload):
    return hashlib.sha256(payload).hexdigest()


# ---------------------------------------------------------------------------
# shared single-file background writer (gluon Trainer.save_states)
# ---------------------------------------------------------------------------

_bytes_q = None
_bytes_thread = None
_bytes_lock = threading.Lock()
_bytes_errors = []       # (fname, "Type: msg") since the last flush


def _bytes_writer_loop():
    while True:
        fname, payload = _bytes_q.get()
        try:
            atomic_write_file(fname, payload)
        except Exception as exc:               # noqa: BLE001
            with _bytes_lock:
                _bytes_errors.append(
                    (fname, "%s: %s" % (type(exc).__name__,
                                        str(exc)[:200])))
            logging.getLogger(__name__).warning(
                "checkpoint: background write of %s failed (%s: %s)",
                fname, type(exc).__name__, exc)
        finally:
            _bytes_q.task_done()


def write_bytes_async(fname, payload):
    """Durably write ``payload`` to ``fname`` from the shared
    background writer (bounded queue — same backpressure discipline as
    :class:`CheckpointManager`). The caller already holds a consistent
    byte snapshot, so this is safe for pre-serialized state blobs."""
    global _bytes_q, _bytes_thread
    with _bytes_lock:
        if _bytes_thread is None or not _bytes_thread.is_alive():
            _bytes_q = queue.Queue(
                maxsize=max(1, envs.get_int("MXNET_CHECKPOINT_INFLIGHT")))
            _bytes_thread = threading.Thread(
                target=_bytes_writer_loop, daemon=True,
                name="mxckpt-bytes")
            _bytes_thread.start()
    _bytes_q.put((fname, payload))


def flush_async_writes():
    """Block until every :func:`write_bytes_async` payload landed,
    then raise :class:`MXNetError` naming any writes that failed since
    the last flush — a deferred durable write must not fail silently
    (the synchronous path raises, so the async path surfaces the same
    error here)."""
    q = _bytes_q
    if q is not None:
        q.join()
    with _bytes_lock:
        errors, _bytes_errors[:] = list(_bytes_errors), []
    if errors:
        raise MXNetError(
            "background checkpoint write(s) failed: "
            + "; ".join("%s (%s)" % e for e in errors))


# ---------------------------------------------------------------------------
# snapshot: consistent zero-copy capture of a param roster
# ---------------------------------------------------------------------------

def _snapshot_entry(key, value, flat):
    """Capture one roster entry into ``flat`` without blocking: dense
    NDArrays (and raw jax arrays — e.g. the flat dp-sharded optimizer
    state of ``parallel.grad_sync``) contribute a device-side COPY of
    their buffer — an async dispatch preserving the source's sharding,
    not a host sync. The copy (not a bare reference) matters: the fit
    loop re-points the executor's buffers at these same arrays
    (same-device ``device_put`` aliases), and the fused train step
    then DONATES them to XLA — a reference snapshot would be reading a
    deleted buffer by the time the writer thread serializes it. Sparse
    NDArrays and numpy fall back to a host copy now (their buffers can
    be replaced component-wise)."""
    data = getattr(value, "_data", None)
    if data is not None and getattr(value, "stype", "default") \
            == "default":
        flat[key] = data.copy()       # donation-proof device-side copy
    elif hasattr(value, "addressable_shards"):
        flat[key] = value.copy()      # raw jax array, sharding kept
    elif hasattr(value, "asnumpy"):
        # sparse: reuse the nd.save component layout inside shard 0
        from .ndarray.ndarray import _flatten_entry
        _flatten_entry(key, value, flat)
    else:
        flat[key] = _np.asarray(value)


def snapshot_params(arg_params, aux_params=None, extra=None):
    """A consistent point-in-time capture of ``{'arg:name': buffer}``
    (plus ``aux:``) suitable for handing to the background writer —
    O(#params) reference grabs, no device sync, no host copy for dense
    entries. ``extra`` entries carry their full key verbatim (the
    ``opt:bucketBB.slotS`` sharded-optimizer-state roster rides here;
    its per-device pieces land in the manifest's shard files exactly
    like a sharded parameter's)."""
    flat = {}
    for k, v in (arg_params or {}).items():
        _snapshot_entry("arg:%s" % k, v, flat)
    for k, v in (aux_params or {}).items():
        _snapshot_entry("aux:%s" % k, v, flat)
    for k, v in (extra or {}).items():
        _snapshot_entry(k, v, flat)
    return flat


# ---------------------------------------------------------------------------
# sharded serialization
# ---------------------------------------------------------------------------

def _device_order(mesh_devices):
    """Stable shard numbering: position in the flattened device list."""
    return {d: i for i, d in enumerate(mesh_devices)}


def _spans_processes(sharding):
    """True when a sharding's device set covers more than one process
    (a genuinely global array — only possible on backends with
    cross-process SPMD)."""
    try:
        procs = {getattr(d, "process_index", 0)
                 for d in sharding.device_set}
        return len(procs) > 1
    except Exception:
        return False


def _split_shards(flat, process_index=None):
    """Partition a snapshot into per-mesh-position piece rosters.

    Returns ``(shards, layout, n_shards)`` where ``shards[s]`` maps
    shard-file keys to host numpy arrays and ``layout[key]`` is the
    manifest entry (shape, dtype, pieces). Whole/replicated entries go
    to shard 0 under their plain key (legacy format); an entry sharded
    across devices contributes one piece per distinct index, placed in
    the shard of the device that owns it. The D2H transfer happens
    here — on the caller (writer) thread.

    Multi-process mode (``process_index`` given): the LAYOUT covers
    every piece — for process-spanning arrays it is derived from the
    sharding's global ``devices_indices_map``, identical on all ranks
    — but ``shards`` materializes only the pieces THIS process's
    devices own; whole/replicated/host entries are owned by rank 0.
    Each rank writes its own shard files and rank 0 writes the
    manifest after the all-shards barrier (:func:`save_arrays`)."""
    shards = {0: {}} if process_index in (None, 0) else {}
    layout = {}
    for key, data in flat.items():
        sharding = getattr(data, "sharding", None)
        addressable = getattr(data, "addressable_shards", None)
        pieces = []
        if sharding is not None and addressable is not None \
                and process_index is not None \
                and _spans_processes(sharding) \
                and not getattr(data, "is_fully_replicated", True):
            # global (cross-process) array: layout from the global
            # index map — every rank computes the same table; only
            # locally-owned pieces materialize bytes
            order = _device_order(list(sharding.mesh.devices.flat)) \
                if hasattr(sharding, "mesh") else {}
            local = {p.device: p for p in addressable}
            imap = sharding.devices_indices_map(tuple(data.shape))
            devs = sorted(imap, key=lambda d: order.get(d, 1 << 30))
            seen = {}
            for dev in devs:
                index = tuple(
                    (0 if sl.start is None else int(sl.start),
                     int(dim) if sl.stop is None else int(sl.stop))
                    for sl, dim in zip(imap[dev], data.shape))
                if index in seen:
                    continue          # replicated copy of this piece
                seen[index] = dev
                s = order.get(dev, len(seen) - 1)
                pkey = "%s%s%d" % (key, _PIECE_SEP, len(pieces))
                if dev in local:
                    shards.setdefault(s, {})[pkey] = \
                        _np.asarray(local[dev].data)
                pieces.append({"shard": s, "key": pkey,
                               "index": [list(ix) for ix in index]})
        elif sharding is not None and addressable is not None \
                and len(addressable) > 1 \
                and process_index in (None, 0) \
                and not getattr(data, "is_fully_replicated", True):
            order = _device_order(list(sharding.mesh.devices.flat)) \
                if hasattr(sharding, "mesh") else {}
            seen = set()
            for piece in addressable:
                index = tuple(
                    (0 if sl.start is None else int(sl.start),
                     int(dim) if sl.stop is None else int(sl.stop))
                    for sl, dim in zip(piece.index, data.shape))
                if index in seen:
                    continue          # replicated copy of this piece
                seen.add(index)
                s = order.get(piece.device, len(seen) - 1)
                pkey = "%s%s%d" % (key, _PIECE_SEP, len(pieces))
                shards.setdefault(s, {})[pkey] = _np.asarray(piece.data)
                pieces.append({"shard": s, "key": pkey,
                               "index": [list(ix) for ix in index]})
        if not pieces:
            if process_index in (None, 0):
                shards[0][key] = _np.asarray(data)
            pieces = [{"shard": 0, "key": key, "index": None}]
        if hasattr(data, "shape"):
            layout[key] = {"shape": [int(s) for s in data.shape],
                           "dtype": str(_np.dtype(data.dtype)),
                           "pieces": pieces}
        else:                          # flattened sparse component
            layout[key] = {"pieces": pieces}
    # renumber shard ids densely (sorted device order -> 0..k-1): on a
    # multi-axis mesh the distinct-piece owners need not sit at flat
    # positions 0..k-1, and the manifest shard list, piece references
    # and file names must agree on one contiguous numbering. The map
    # derives from the LAYOUT's piece union (not the locally-
    # materialized shards) so every rank of a multi-process save
    # numbers — and names — its files identically.
    used = sorted({p["shard"] for entry in layout.values()
                   for p in entry["pieces"]} | set(shards))
    pos = {s: i for i, s in enumerate(used)}
    if any(s != i for s, i in pos.items()):
        shards = {pos[s]: roster for s, roster in shards.items()}
        for entry in layout.values():
            for piece in entry["pieces"]:
                piece["shard"] = pos[piece["shard"]]
    return shards, layout, len(used)


def _npz_bytes(arrays):
    buf = _io.BytesIO()
    _np.savez(buf, **arrays)
    return buf.getvalue()


def _process_topology():
    """(process_index, process_count) of the running job — (0, 1) for
    a plain single-process run."""
    try:
        import jax
        return int(jax.process_index()), int(jax.process_count())
    except Exception:
        return 0, 1


def save_arrays(prefix, epoch, flat, states_bytes=None, symbol=None,
                meta=None):
    """Write one sharded checkpoint: shard files first, manifest last.

    ``flat`` is a :func:`snapshot_params` roster. Returns the stats
    dict the telemetry record is built from. Raises on failure (incl.
    planned ``ckpt_write``/``ckpt_fsync`` faults) — the caller decides
    whether that is fatal; the manifest is only ever written after
    every shard it references landed and fsynced.

    ``meta`` is an optional JSON-safe dict recorded verbatim under the
    manifest's ``meta`` key — the AMP dtype policy rides here as
    ``{"dtype_policy": policy.describe()}`` so a checkpoint knows what
    precision it was trained under (loaders that predate the key
    ignore it; the manifest format is unchanged).

    **Multi-process jobs** (a jax.distributed group; every rank calls
    this — SPMD discipline): each rank durably writes the shard files
    its own devices own (rank 0 also owns every whole/replicated
    entry, the symbol and the optimizer states), every rank then meets
    an all-shards coordination barrier, and ONLY rank 0 writes the
    manifest — last, after checksumming every referenced shard file
    (its own from memory, its peers' from the shared filesystem). A
    rank that died mid-epoch fails the barrier on the survivors, so
    the save fails cleanly and the previous manifest stays the resume
    point; a torn shard can never be referenced because the manifest
    postdates every shard fsync."""
    t0 = time.perf_counter()
    me, world = _process_topology()
    shards, layout, n_shards = _split_shards(
        flat, me if world > 1 else None)
    t_snap = time.perf_counter()
    dirname = os.path.dirname(prefix)
    if dirname:
        os.makedirs(dirname, exist_ok=True)

    local_entries = {}
    payloads = []
    total_bytes = 0
    for s in sorted(shards):
        payload = _npz_bytes(shards[s])
        fname = _shard_file(prefix, epoch, s, n_shards)
        local_entries[s] = {"file": os.path.basename(fname),
                            "sha256": _sha256(payload),
                            "bytes": len(payload)}
        payloads.append((fname, payload))
        total_bytes += len(payload)
    t_ser = time.perf_counter()

    if symbol is not None and me == 0:
        symbol.save("%s-symbol.json" % prefix)
    # states BEFORE shards: a kill between the two strands only a
    # .states file (an epoch with no .params is never listed), whereas
    # the reverse order would leave a durable legacy-loadable .params
    # whose missing states the scan accepts — a resume with silently
    # fresh optimizer state
    states_entry = None
    if states_bytes is not None and me == 0:
        states_file = _tag(prefix, epoch) + ".states"
        atomic_write_file(states_file, states_bytes)
        states_entry = {"file": os.path.basename(states_file),
                        "sha256": _sha256(states_bytes),
                        "bytes": len(states_bytes)}
        total_bytes += len(states_bytes)
    for fname, payload in payloads:
        atomic_write_file(fname, payload)
    t_write = time.perf_counter()

    if world > 1:
        # every rank's shards are durable before anyone proceeds; a
        # dead rank fails this barrier (bounded) on the survivors and
        # the save fails cleanly — the old manifest stays good
        from .parallel import multihost
        multihost.barrier("ckpt/%s" % _tag(prefix, epoch))
        if me != 0:
            t_end = time.perf_counter()
            return {"epoch": int(epoch), "bytes": total_bytes,
                    "shards": len(payloads), "manifest": False,
                    "snapshot_ms": round((t_snap - t0) * 1e3, 3),
                    "serialize_ms": round((t_ser - t_snap) * 1e3, 3),
                    "write_ms": round((t_write - t_ser) * 1e3, 3),
                    "manifest_ms": 0.0,
                    "total_ms": round((t_end - t0) * 1e3, 3)}

    shard_entries = []
    for s in range(n_shards):
        entry = local_entries.get(s)
        if entry is None:
            # a peer's shard (shared filesystem): checksum the bytes
            # it fsynced — the manifest must vouch for every file it
            # references, whoever wrote it
            fname = _shard_file(prefix, epoch, s, n_shards)
            if not os.path.isfile(fname):
                raise MXNetError(
                    "checkpoint %s: peer shard %d (%s) missing after "
                    "the all-shards barrier" % (_tag(prefix, epoch),
                                                s, fname))
            with open(fname, "rb") as f:
                payload = f.read()
            entry = {"file": os.path.basename(fname),
                     "sha256": _sha256(payload),
                     "bytes": len(payload)}
        shard_entries.append(entry)

    manifest = {"format": MANIFEST_FORMAT, "epoch": int(epoch),
                "time": time.time(),
                "shards": [dict(e, shard=i)
                           for i, e in enumerate(shard_entries)],
                "params": layout}
    if world > 1:
        manifest["processes"] = world
    if states_entry is not None:
        manifest["optimizer_states"] = states_entry
    if meta:
        manifest["meta"] = dict(meta)
    atomic_write_file(manifest_path(prefix, epoch),
                      json.dumps(manifest, sort_keys=True).encode())
    t_end = time.perf_counter()
    return {"epoch": int(epoch), "bytes": total_bytes,
            "shards": len(shard_entries),
            "snapshot_ms": round((t_snap - t0) * 1e3, 3),
            "serialize_ms": round((t_ser - t_snap) * 1e3, 3),
            "write_ms": round((t_write - t_ser) * 1e3, 3),
            "manifest_ms": round((t_end - t_write) * 1e3, 3),
            "total_ms": round((t_end - t0) * 1e3, 3)}


# ---------------------------------------------------------------------------
# load / validate / elastic restore
# ---------------------------------------------------------------------------

def latest_manifest_epoch(prefix, validate=True):
    """The newest epoch under ``prefix`` whose manifest (and, with
    ``validate``, every artifact it references) checks out — the
    supervised launcher's resume scan (``tools/launch.py --supervise
    --resume-prefix``) and the workers' own restart hook. Torn or
    corrupt epochs are skipped with a warning, exactly like the
    training-side resume scan; returns None when nothing usable
    exists."""
    import glob
    import re
    base = os.path.basename(prefix)
    dirname = os.path.dirname(prefix) or "."
    # \d{4,}, not \d{4}: '%04d' grows past four digits at epoch 10000
    # (the model.py epoch-scan precedent)
    pat = re.compile(re.escape(base) + r"-(\d{4,})\.ckpt\.json$")
    epochs = []
    for path in glob.glob(os.path.join(dirname, base + "-*.ckpt.json")):
        m = pat.match(os.path.basename(path))
        if m:
            epochs.append(int(m.group(1)))
    for epoch in sorted(epochs, reverse=True):
        try:
            if validate:
                validate_manifest(prefix, epoch)
            elif load_manifest(prefix, epoch) is None:
                continue
            return epoch
        except (MXNetError, ValueError, OSError) as exc:
            logging.getLogger(__name__).warning(
                "checkpoint scan: epoch %04d under %s is torn/corrupt "
                "(%s) — skipping", epoch, prefix, exc)
    return None


def load_manifest(prefix, epoch):
    """The parsed manifest for ``(prefix, epoch)``, or None when this
    epoch predates the manifest format (a PR 1-era single file)."""
    path = manifest_path(prefix, epoch)
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        return json.load(f)


def _read_entry(prefix, epoch, entry, validate=True):
    """Read one manifest artifact's bytes, verifying existence and
    (when ``validate``) its recorded sha256 — raising MXNetError that
    names the missing/torn file. One read serves both the checksum and
    the deserialization."""
    base = os.path.dirname(_tag(prefix, epoch))
    path = os.path.join(base, entry["file"]) if base else entry["file"]
    if not os.path.isfile(path):
        raise MXNetError(
            "checkpoint %s: missing artifact %s"
            % (_tag(prefix, epoch), entry["file"]))
    with open(path, "rb") as f:
        payload = f.read()
    if validate and _sha256(payload) != entry["sha256"]:
        raise MXNetError(
            "checkpoint %s: artifact %s is torn/corrupt "
            "(checksum mismatch)" % (_tag(prefix, epoch),
                                     entry["file"]))
    return payload


def validate_manifest(prefix, epoch, manifest=None):
    """Verify every artifact the manifest references: shard files and
    the optimizer-state sibling must exist and match their recorded
    sha256. Raises MXNetError naming the torn file; returns the
    manifest on success."""
    manifest = manifest if manifest is not None \
        else load_manifest(prefix, epoch)
    if manifest is None:
        raise MXNetError("no manifest for %s" % _tag(prefix, epoch))
    entries = list(manifest["shards"])
    if manifest.get("optimizer_states") is not None:
        entries.append(manifest["optimizer_states"])
    for entry in entries:
        _read_entry(prefix, epoch, entry)
    return manifest


def _restore_dtype(arr, entry):
    """Give a shard-file array back its manifest dtype: npz preserves
    extension dtypes (bf16/fp16 low-precision params) only as raw void
    bytes, so a loaded ``|V2`` buffer is re-viewed as the dtype the
    layout recorded — a zero-copy reinterpretation, bit-exact."""
    want = entry.get("dtype")
    if not want or str(arr.dtype) == want:
        return arr
    dt = _np.dtype(want)
    return arr.view(dt) if arr.dtype.itemsize == dt.itemsize \
        else arr.astype(dt)


def load_arrays(prefix, epoch, validate=True):
    """Load a manifest checkpoint back into a flat ``{'arg:name':
    NDArray}`` host dict, re-assembling sharded entries from their
    pieces. ``validate=True`` (default) checksums every referenced
    artifact (shards AND the optimizer-state sibling) against the same
    bytes it deserializes — one read per file — so torn writes surface
    as MXNetError, exactly what the resume scan catches to fall back
    an epoch."""
    from .ndarray.ndarray import _unflatten
    from . import ndarray as nd
    manifest = load_manifest(prefix, epoch)
    if manifest is None:
        raise MXNetError("no manifest for %s" % _tag(prefix, epoch))
    shard_data = []
    for entry in manifest["shards"]:
        payload = _read_entry(prefix, epoch, entry, validate=validate)
        shard_data.append(dict(_np.load(_io.BytesIO(payload),
                                        allow_pickle=False)))
    if validate and manifest.get("optimizer_states") is not None:
        _read_entry(prefix, epoch, manifest["optimizer_states"])
    whole, out = {}, {}
    for key, entry in manifest["params"].items():
        pieces = entry["pieces"]
        if len(pieces) == 1 and pieces[0]["index"] is None:
            whole[key] = _restore_dtype(
                shard_data[pieces[0]["shard"]][pieces[0]["key"]], entry)
            continue
        full = _np.empty(tuple(entry["shape"]),
                         _np.dtype(entry["dtype"]))
        for p in pieces:
            ix = tuple(slice(a, b) for a, b in p["index"])
            full[ix] = _restore_dtype(shard_data[p["shard"]][p["key"]],
                                      entry)
        out[key] = nd.array(full)
    out.update(_unflatten(whole))
    return out


def load_param_arrays(prefix, epoch, validate=True):
    """Flat ``{name: numpy array}`` of a manifest checkpoint's ``arg``
    parameters (``aux`` entries ride along under their plain names) —
    the decode server's weight hot-swap source
    (``serving.DecodeServer.swap_weights(prefix=..., epoch=...)``).
    Values come back as plain host arrays: placement is the caller's
    (the topology-neutral manifest makes the swap a pure placement
    problem — save on any mesh, serve on any device)."""
    flat = load_arrays(prefix, epoch, validate=validate)
    out = {}
    for key, val in flat.items():
        name = key.split(":", 1)[1] if ":" in key else key
        out[name] = val.asnumpy() if hasattr(val, "asnumpy") \
            else _np.asarray(val)
    return out


def saved_dtype_policy(prefix, epoch):
    """The :class:`~mxnet_tpu.amp.DtypePolicy` a manifest checkpoint
    was saved under (the ``meta.dtype_policy`` record), or None for a
    checkpoint saved without one — pre-AMP manifests and plain fp32
    runs look identical here."""
    from .amp import DtypePolicy
    manifest = load_manifest(prefix, epoch)
    meta = (manifest or {}).get("meta") or {}
    return DtypePolicy.from_describe(meta.get("dtype_policy"))


def restore_params(prefix, epoch, mesh=None, rules=None, validate=True,
                   policy=None):
    """Elastic resume: load ``(arg_params, aux_params)`` from a
    manifest checkpoint and, when ``mesh`` is given, re-place every
    parameter against the *current* mesh via ``jax.device_put`` with
    ``NamedSharding`` (``parallel.data_parallel.shard_params``;
    ``rules`` maps name substrings to PartitionSpecs, default
    replicated). The save-time topology is irrelevant — values are
    re-assembled on the host first, so a 1-device save resumes sharded
    on N devices and vice versa.

    ``policy`` casts every parameter to its per-name resolved dtype on
    the host, BEFORE placement: pass an ``amp.DtypePolicy`` to resume
    under that policy (an AMP checkpoint stores fp32 masters, so any
    resume precision is a cast of the exact master — bit-identical
    wherever dtypes agree), or the string ``"manifest"`` to re-adopt
    whatever policy the checkpoint was saved under (a no-op when none
    was recorded). The save-time and resume-time policies are fully
    decoupled: bf16-trained checkpoints resume fp32 and vice versa."""
    flat = load_arrays(prefix, epoch, validate=validate)
    arg_params, aux_params = {}, {}
    for k, v in flat.items():
        tp, name = k.split(":", 1)
        (arg_params if tp == "arg" else aux_params)[name] = v
    if policy == "manifest":
        policy = saved_dtype_policy(prefix, epoch)
    if policy is not None:
        arg_params = policy.cast_params(arg_params)
        aux_params = policy.cast_params(aux_params)
    if mesh is not None:
        from .parallel.data_parallel import shard_params
        arg_params = shard_params(arg_params, mesh, rules=rules)
        aux_params = shard_params(aux_params, mesh, rules=rules)
    return arg_params, aux_params


# ---------------------------------------------------------------------------
# the manager: bounded-queue background writer
# ---------------------------------------------------------------------------

_CLOSE = object()


class CheckpointManager:
    """Owns one checkpoint prefix's save pipeline for a training loop.

    Async mode (default): ``save()`` snapshots (reference grabs +
    optimizer-state pickle), opens the telemetry ``checkpoint`` span
    only for that blocking part plus any enqueue backpressure wait,
    and returns; a daemon writer thread does D2H + serialize + durable
    writes. Sync mode runs the identical writer code on the calling
    thread. Failed saves warn and leave :attr:`last_good_epoch`
    untouched — checkpointing never kills the run it protects."""

    def __init__(self, prefix, symbol=None, async_=None, inflight=None,
                 logger=None, meta=None):
        self.prefix = prefix
        self._symbol = symbol
        self._symbol_saved = False
        self.meta = dict(meta) if meta else None
        self.async_ = async_checkpoint_enabled() if async_ is None \
            else bool(async_)
        depth = inflight if inflight is not None \
            else envs.get_int("MXNET_CHECKPOINT_INFLIGHT")
        self._q = queue.Queue(maxsize=max(1, int(depth)))
        self._thread = None
        self._lock = threading.Lock()
        self.logger = logger or logging.getLogger(__name__)
        self.last_good_epoch = None
        self.saves = 0
        self.failures = 0
        self.bytes_written = 0
        self._idle = threading.Event()
        self._idle.set()

    # -- public surface ---------------------------------------------------
    def save(self, epoch, arg_params, aux_params=None, states_bytes=None,
             extra=None):
        """Checkpoint ``epoch``. Blocking cost in async mode is the
        snapshot + (only under backpressure) the bounded-queue wait;
        sync mode blocks for the whole durable write. Both run under
        the telemetry ``checkpoint`` phase. ``extra`` rides verbatim
        keys into the shard roster (sharded optimizer state)."""
        from . import telemetry, tracing
        with telemetry.span("checkpoint"):
            t0 = time.perf_counter()
            # causal context captured HERE, on the training thread
            # that triggered the save — the writer thread's trace
            # span parents to this step via the explicit token
            ctx = tracing.context()
            flat = snapshot_params(arg_params, aux_params, extra=extra)
            if not self.async_:
                self._write(epoch, flat, states_bytes, t0,
                            blocking=True, ctx=ctx)
                return
            self._ensure_thread()
            self._idle.clear()
            # bounded put IS the backpressure: a slow disk stalls the
            # trainer here instead of queueing unbounded snapshots.
            # The enqueue time is stamped AFTER put() returns so that
            # stall lands in blocking_ms (the trainer paid it), not
            # async_ms — the writer reads it through the shared dict
            timing = {"t0": t0, "ctx": ctx}
            self._q.put((epoch, flat, states_bytes, timing))
            timing["t_enq"] = time.perf_counter()

    def wait(self):
        """Block until every enqueued save has been written (or
        failed). The post-loop resume scan and tests call this."""
        if self._thread is None:
            return
        self._q.join()
        self._idle.wait()

    def close(self):
        """Drain in-flight saves and stop the writer thread. Safe to
        call twice; the manager can be reused after (a new thread
        starts lazily)."""
        if self._thread is None:
            return
        self._q.join()
        self._idle.wait()
        self._q.put(_CLOSE)
        self._thread.join(timeout=30)
        self._thread = None

    def stats(self):
        with self._lock:
            return {"saves": self.saves, "failures": self.failures,
                    "bytes_written": self.bytes_written,
                    "last_good_epoch": self.last_good_epoch,
                    "async": self.async_}

    # -- writer -----------------------------------------------------------
    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._writer_loop, daemon=True,
                name="mxckpt-write")
            self._thread.start()

    def _writer_loop(self):
        while True:
            item = self._q.get()
            if item is _CLOSE:
                self._q.task_done()
                return
            epoch, flat, states_bytes, timing = item
            try:
                self._write(epoch, flat, states_bytes, timing["t0"],
                            blocking=False,
                            t_enq=timing.get("t_enq"),
                            ctx=timing.get("ctx"))
            finally:
                self._q.task_done()
                if self._q.unfinished_tasks == 0:
                    self._idle.set()

    def _symbol_once(self):
        if self._symbol is not None and not self._symbol_saved:
            self._symbol.save("%s-symbol.json" % self.prefix)
            self._symbol_saved = True

    def _write(self, epoch, flat, states_bytes, t0, blocking,
               t_enq=None, ctx=None):
        """One durable save + its accounting; never raises. ``ctx`` is
        the trace-context token save() captured on the training thread
        — the writer's trace span parents to that step explicitly."""
        from . import telemetry, tracing
        t_work0 = time.perf_counter()
        if t_enq is None and not blocking:
            # writer won the handoff race before save() stamped the
            # enqueue time — the put cannot have blocked, so now is
            # the enqueue time to within the race window
            t_enq = time.perf_counter()
        rec = {"epoch": int(epoch), "async": not blocking}
        try:
            self._symbol_once()
            stats = save_arrays(self.prefix, epoch, flat,
                                states_bytes=states_bytes,
                                meta=self.meta)
            rec.update(stats, ok=True)
            with self._lock:
                self.saves += 1
                self.bytes_written += stats["bytes"]
                if self.last_good_epoch is None \
                        or epoch > self.last_good_epoch:
                    self.last_good_epoch = epoch
        except Exception as exc:               # noqa: BLE001
            with self._lock:
                self.failures += 1
            rec.update(ok=False, error="%s: %s"
                       % (type(exc).__name__, str(exc)[:200]))
            self.logger.warning(
                "checkpoint: save of epoch %d failed (%s: %s) — "
                "last good epoch is %s", epoch, type(exc).__name__,
                exc, self.last_good_epoch)
        now = time.perf_counter()
        if blocking:
            rec["blocking_ms"] = round((now - t0) * 1e3, 3)
            rec["async_ms"] = 0.0
        else:
            rec["blocking_ms"] = round((t_enq - t0) * 1e3, 3)
            rec["async_ms"] = round((now - t_enq) * 1e3, 3)
        rec["last_good_epoch"] = self.last_good_epoch
        if tracing._tracer is not None:
            args = dict(ctx or {})
            args.update(epoch=int(epoch), ok=bool(rec.get("ok")),
                        bytes=rec.get("bytes", 0))
            tracing.add("ckpt:epoch%04d" % int(epoch), "checkpoint",
                        t_work0, now - t_work0,
                        tid=tracing.track("checkpoint"), args=args)
        telemetry.checkpoint_event(rec)
