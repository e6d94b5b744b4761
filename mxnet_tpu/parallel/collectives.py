"""Collective primitives over the mesh.

The TPU-native replacement for the reference's communication backends
(SURVEY §5.8): CommCPU/CommDevice tree reduce, NCCL ring collectives and
ps-lite push/pull all collapse into XLA collectives over ICI/DCN. These
wrappers exist for the eager KVStore path and for shard_map kernels;
inside pjit programs, sharding annotations let XLA insert them.

Observability: with a telemetry run active (``mxnet_tpu.telemetry``),
each eager collective is accounted — input bytes and caller-observed
latency — under comm kind ``collective`` keyed by the primitive name;
with the compile watch active (``mxnet_tpu.compile_watch``) each
primitive's compiles are captured under site ``collective:<name>``.
The shard_map callable is built once per (primitive, mesh, statics)
and cached — the old per-call closure forced a re-trace on every
eager call.
"""
from __future__ import annotations

import functools

__all__ = ["all_reduce", "all_gather", "reduce_scatter", "broadcast",
           "ppermute", "barrier", "psum_eager",
           "bucket_reduce_scatter", "bucket_all_gather"]

# (primitive, mesh, statics) -> compile_watch-wrapped jitted shard_map
_prim_cache = {}


def _account_links(name, mesh, axis, value=None, nbytes=None):
    """Ledger one collective's intra-host (ici) vs cross-host (dcn)
    byte split under its primitive name (mesh.link_split's hop model);
    cheap no-op without a telemetry run."""
    from .. import telemetry
    if not telemetry.enabled():
        return
    if nbytes is None:
        nbytes = int(getattr(value, "nbytes", 0) or 0)
    from .mesh import link_split
    try:
        ici, dcn = link_split(mesh, axis, nbytes)
    except ValueError:
        return
    telemetry.comm_links(name, ici, dcn)


def _watched(prim, mesh, statics, build):
    """The cached, compile-watched form of one collective primitive.
    ``build()`` returns the shard_map-wrapped pure function; the
    wrapper jits it (jit(shard_map(f)) is the canonical spelling) so
    repeated eager calls stop re-tracing and every XLA compile is
    observable."""
    key = (prim, mesh, statics)
    fn = _prim_cache.get(key)
    if fn is None:
        from .. import compile_watch

        def describe(*arrays):
            return compile_watch.describe_arrays(["x"], arrays)

        fn = compile_watch.jit(build(), "collective:%s" % prim,
                               describe=describe,
                               statics=(str(mesh), statics))
        _prim_cache[key] = fn
    return fn


def _shard_map():
    import jax

    def wrapped(f, **kwargs):
        # psum outputs are replicated but the static checker can't
        # always infer it; disable the check
        return jax.shard_map(f, check_vma=False, **kwargs)
    return wrapped


def all_reduce(x, mesh, axis="dp", op="sum"):
    """Sum the shards of ``x`` along a mesh axis; result is the reduced
    (replicated) value — CommDevice::Reduce / ncclReduce role.

    When a fault plan is active (site ``allreduce``) the eager call runs
    under ``fault.with_retries``: planned/transient failures back off
    and retry, an unrecoverable hang raises CollectiveTimeoutError."""
    import jax
    from jax.sharding import PartitionSpec as P
    from .. import fault

    def f(v):
        if op == "sum":
            return jax.lax.psum(v, axis)
        if op == "max":
            return jax.lax.pmax(v, axis)
        if op == "mean":
            return jax.lax.pmean(v, axis)
        raise ValueError(op)

    def run():
        return _watched(
            "all_reduce", mesh, (axis, op),
            lambda: _shard_map()(f, mesh=mesh, in_specs=(P(axis),),
                                 out_specs=P()))(x)

    from .. import telemetry
    _account_links("all_reduce", mesh, axis, x)
    with telemetry.comm_span("collective", "all_reduce", x):
        return fault.guard(run, "allreduce")


def all_gather(x, mesh, axis="dp", tiled=True):
    import jax
    from jax.sharding import PartitionSpec as P

    def f(v):
        return jax.lax.all_gather(v, axis, tiled=tiled)

    from .. import telemetry
    _account_links("all_gather", mesh, axis, x)
    with telemetry.comm_span("collective", "all_gather", x):
        return _watched(
            "all_gather", mesh, (axis, bool(tiled)),
            lambda: _shard_map()(f, mesh=mesh, in_specs=(P(axis),),
                                 out_specs=P()))(x)


def reduce_scatter(x, mesh, axis="dp"):
    """Reduce the per-device contributions of ``x`` and scatter the
    sum along the mesh axis. A leading dim that does not divide the
    axis size (formerly a hard XLA shape error inside psum_scatter) is
    zero-padded up to the next multiple before the collective and the
    padding rows are sliced back off the (sharded) result — the sum is
    unaffected because the pad contributes exact zeros."""
    import jax
    from jax.sharding import PartitionSpec as P

    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    d0 = int(x.shape[0]) if getattr(x, "ndim", 0) else 1
    rem = d0 % n

    def f(v):
        if rem:
            pad = [(0, n - rem)] + [(0, 0)] * (v.ndim - 1)
            v = jax.numpy.pad(v, pad)
        return jax.lax.psum_scatter(v, axis, tiled=True)

    from .. import telemetry
    _account_links("reduce_scatter", mesh, axis, x)
    with telemetry.comm_span("collective", "reduce_scatter", x):
        out = _watched(
            "reduce_scatter", mesh, (axis, rem),
            lambda: _shard_map()(f, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(axis)))(x)
    return out[:d0] if rem else out


def bucket_reduce_scatter(stacked, mesh, axis="dp", key="bucket"):
    """One collective for a whole gradient bucket: ``stacked`` is a
    list of same-dtype ``(axis_size, *shape)`` arrays sharded over
    ``axis`` on dim 0 — each row one device's local contribution. The
    bucket is flattened+concatenated per device, zero-padded so the
    total divides the axis size, and reduce-scattered: the return is
    the summed flat bucket of length ``padded_total`` sharded over
    ``axis``, ready for a shard-local (ZeRO) optimizer update. The
    eager counterpart of ``grad_sync.make_bucketed_apply``'s
    in-program constraint, accounted as one ``grad_sync`` comm span
    under ``key``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    total = sum(int(_prod(v.shape[1:])) for v in stacked)
    pad = (-(-total // n) * n) - total
    sizes = tuple(int(_prod(v.shape[1:])) for v in stacked)
    dt = stacked[0].dtype

    def f(*vs):
        segs = [v.reshape(-1) for v in vs]
        if pad:
            segs.append(jnp.zeros((pad,), dt))
        return jax.lax.psum_scatter(jnp.concatenate(segs), axis,
                                    tiled=True)

    from .. import telemetry
    _account_links("bucket_reduce_scatter", mesh, axis,
                   nbytes=(total + pad) * dt.itemsize)
    # ledger the LOGICAL payload — the reduced padded bucket, one
    # direction — not the (n_dev, ...) stacked operands, so the bytes
    # column is comparable with the in-program and kvstore grad_sync
    # rows (each of which counts bucket bytes once per direction)
    with telemetry.comm_span("grad_sync", key,
                             nbytes=(total + pad) * dt.itemsize):
        return _watched(
            "bucket_reduce_scatter", mesh,
            (axis, sizes, str(dt), pad),
            lambda: _shard_map()(f, mesh=mesh,
                                 in_specs=tuple(P(axis)
                                                for _ in stacked),
                                 out_specs=P(axis)))(*stacked)


def bucket_all_gather(flat, mesh, axis="dp", key="bucket"):
    """Gather a reduce-scattered flat bucket back to a replicated
    vector (the updated-params all-gather of the eager bucketed path).
    Accounted as one ``grad_sync`` comm span under ``key``."""
    import jax
    from jax.sharding import PartitionSpec as P

    def f(v):
        return jax.lax.all_gather(v, axis, tiled=True)

    from .. import telemetry
    _account_links("bucket_all_gather", mesh, axis, flat)
    with telemetry.comm_span("grad_sync", key, flat):
        return _watched(
            "bucket_all_gather", mesh, (axis,),
            lambda: _shard_map()(f, mesh=mesh, in_specs=(P(axis),),
                                 out_specs=P()))(flat)


def _prod(shape):
    out = 1
    for s in shape:
        out *= int(s)
    return out


def ppermute(x, mesh, axis, perm):
    import jax
    from jax.sharding import PartitionSpec as P

    def f(v):
        return jax.lax.ppermute(v, axis, perm)

    from .. import telemetry
    _account_links("ppermute", mesh, axis, x)
    with telemetry.comm_span("collective", "ppermute", x):
        return _watched(
            "ppermute", mesh, (axis, tuple(map(tuple, perm))),
            lambda: _shard_map()(f, mesh=mesh, in_specs=(P(axis),),
                                 out_specs=P(axis)))(x)


def broadcast(x, mesh, axis="dp", root=0):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def f(v):
        idx = jax.lax.axis_index(axis)
        v = jnp.where(idx == root, v, jnp.zeros_like(v))
        return jax.lax.psum(v, axis)

    from .. import telemetry
    _account_links("broadcast", mesh, axis, x)
    with telemetry.comm_span("collective", "broadcast", x):
        return _watched(
            "broadcast", mesh, (axis, int(root)),
            lambda: _shard_map()(f, mesh=mesh, in_specs=(P(axis),),
                                 out_specs=P(axis)))(x)


def psum_eager(arrays):
    """Sum a python list of same-shape arrays in one fused XLA op (the
    single-process CommDevice Reduce role)."""
    import jax.numpy as jnp
    out = arrays[0]
    for a in arrays[1:]:
        out = out + a
    return out


def barrier(name="barrier"):
    import jax
    from .. import telemetry
    try:
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            with telemetry.comm_span("collective", "barrier"):
                multihost_utils.sync_global_devices(name)
    except Exception:
        pass
