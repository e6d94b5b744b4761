"""Device mesh management.

The TPU-native replacement for the reference's device-group machinery
(kvstore device lists, `group2ctx` placement, ps-lite rank/size). A
:func:`create_mesh` builds a ``jax.sharding.Mesh`` whose axes name the
parallelism dimensions:

- ``dp`` — data parallel (batch sharding; allreduce ≙ psum over dp)
- ``tp`` — tensor parallel (weight sharding inside layers)
- ``sp`` — sequence/context parallel (ring attention / Ulysses)
- ``ep`` — expert parallel (MoE expert sharding)
- ``pp`` — pipeline stages

Collectives ride ICI within a slice; across slices XLA routes over DCN
automatically when the mesh spans hosts (jax.distributed).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import numpy as np

__all__ = ["create_mesh", "auto_mesh", "make_mesh", "mesh_axes",
           "local_mesh", "PartitionSpec", "NamedSharding", "replicated",
           "shard_batch", "dp_mesh", "distinct_devices", "use_mesh",
           "current_mesh", "set_current_mesh", "axis_hosts",
           "link_split"]

_DP_MESH_CACHE = {}
_CURRENT_MESH = [None]


def set_current_mesh(mesh):
    """Install ``mesh`` as the process-wide active parallelism mesh.
    Ops that can exploit mesh axes (``_contrib_flash_attention``'s
    ring/ulysses impls, gluon.contrib MeshAttention) consult it — the
    registry's op surface has no mesh argument, same as the reference's
    ops have no device-group argument (placement is ambient context
    there too). Returns the previous mesh."""
    prev = _CURRENT_MESH[0]
    _CURRENT_MESH[0] = mesh
    return prev


def current_mesh():
    return _CURRENT_MESH[0]


class use_mesh:
    """``with use_mesh(mesh): ...`` scoped set_current_mesh."""

    def __init__(self, mesh):
        self._mesh = mesh
        self._prev = None

    def __enter__(self):
        self._prev = set_current_mesh(self._mesh)
        return self._mesh

    def __exit__(self, *exc):
        set_current_mesh(self._prev)


def dp_mesh(devices):
    """The shared 1-axis 'dp' mesh over an ordered device tuple. Cached
    so Parameter replication, split_and_load batch sharding, and
    executors binding the same context list all agree on one Mesh."""
    key = tuple(devices)
    mesh = _DP_MESH_CACHE.get(key)
    if mesh is None:
        mesh = create_mesh({"dp": len(devices)}, devices=list(devices))
        _DP_MESH_CACHE[key] = mesh
    return mesh


def distinct_devices(ctx_list):
    """Contexts resolved to unique jax devices, order kept. Reference
    scripts pass repeated contexts (e.g. ``[gpu(0), gpu(0)]``) and
    CPU-only hosts resolve every accelerator id to the same device —
    both degrade to fewer distinct devices rather than erroring."""
    devices = []
    for c in ctx_list:
        d = c.jax_device()
        if d not in devices:
            devices.append(d)
    return devices


def PartitionSpec(*axes):
    from jax.sharding import PartitionSpec as P
    return P(*axes)


def NamedSharding(mesh, spec):
    from jax.sharding import NamedSharding as NS
    return NS(mesh, spec)


def create_mesh(axis_sizes: Dict[str, int], devices=None):
    """Build a Mesh from {'dp': 2, 'tp': 4, ...}; axis order is the dict
    order. Product must equal the device count used."""
    import jax
    from jax.sharding import Mesh
    names = list(axis_sizes.keys())
    sizes = [int(axis_sizes[n]) for n in names]
    total = int(np.prod(sizes))
    n_have = len(devices) if devices is not None else len(jax.devices())
    if total != n_have:
        raise ValueError(
            "mesh axes %s product %d != device count %d"
            % (axis_sizes, total, n_have))
    if devices is None:
        # topology-aware on TPU: jax.devices() is id order, which on a
        # 2x2 puts a diagonal (two-hop) step in a 1-D axis (ids 0,1,2,3
        # sit at (0,0),(1,0),(0,1),(1,1)); mesh_utils orders the axis
        # along physical neighbours. Off-TPU it is the plain reshape.
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(sizes)
    else:
        dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, names)


def auto_mesh(n_devices: Optional[int] = None,
              prefer: Sequence[str] = ("dp", "tp", "sp")):
    """Factor the device count into a sensible default mesh: largest
    power-of-2 split across the preferred axes (dp gets the remainder)."""
    import jax
    n = n_devices if n_devices is not None else len(jax.devices())
    sizes = {k: 1 for k in prefer}
    axes = list(prefer)
    i = len(axes) - 1
    rem = n
    # give trailing axes factors of 2 first, rest to dp
    while i > 0 and rem % 2 == 0 and rem > 2:
        sizes[axes[i]] *= 2
        rem //= 2
        i -= 1
    sizes[axes[0]] = rem
    return create_mesh(sizes, devices=jax.devices()[:n])


def make_mesh(data=None, fsdp=None, tp=None, devices=None, hosts=None):
    """The multi-axis mesh entry point for the sharding-rules layer
    (``parallel.sharding_rules``): axes are named with the rules
    layer's own vocabulary — ``data`` carries the batch, ``fsdp`` the
    parameter row shards, ``tp`` the tensor-parallel column shards —
    so ``SpecLayout.for_mesh`` resolves them literally instead of
    folding everything onto a 1-axis ``dp`` mesh.

    Sizes left ``None`` default to 1, except ``data`` which absorbs
    whatever devices remain: ``make_mesh(fsdp=4, tp=2)`` on 8 devices
    is a ``data=1 × fsdp=4 × tp=2`` mesh; on 16 it is ``data=2``.
    Axis order is data-outermost (``data``, ``fsdp``, ``tp``), the
    GSPMD convention that keeps fsdp/tp collectives on the
    fastest-varying (densest-ICI) device neighbors.

    **Process-aware (multi-host) mode** — when the job runs as a
    jax.distributed group with more than one process (or ``hosts=`` is
    passed explicitly), the mesh is built over EVERY process's devices
    (``jax.devices()``), ordered rank-major with each host's local
    devices contiguous: the data axis (outermost) then splits on host
    boundaries first, so the inner fsdp/tp collectives stay on the
    intra-host fast link (ICI) and only the data-axis gradient
    exchange crosses hosts (DCN) — :func:`link_split` is the per-link
    accounting of exactly that layout. ``hosts=`` additionally
    validates the topology: it must equal the process count spanned by
    the chosen devices, and the inner ``fsdp*tp`` block must divide
    each host's local device count (an inner axis straddling two hosts
    would silently put every weight collective on the slow link)."""
    import jax
    if devices is not None:
        devices = list(devices)
        if hosts is not None:
            # the host-contiguity contract holds for explicit device
            # lists too: rank-major, local ids ascending
            devices = sorted(
                devices,
                key=lambda d: (getattr(d, "process_index", 0), d.id))
    else:
        devices = list(jax.devices())
        try:
            multi = jax.process_count() > 1
        except Exception:
            multi = False
        if multi or hosts is not None:
            # rank-major, local ids ascending: each host contiguous
            devices = sorted(devices,
                             key=lambda d: (d.process_index, d.id))
    n = len(devices)
    if hosts is not None:
        hosts = int(hosts)
        actual = len({getattr(d, "process_index", 0) for d in devices})
        if hosts != actual:
            raise ValueError(
                "make_mesh(hosts=%d): the %d available devices span "
                "%d process(es) — launch contract and topology "
                "disagree" % (hosts, n, actual))
        if n % hosts:
            raise ValueError(
                "make_mesh(hosts=%d): %d devices do not split evenly "
                "across hosts" % (hosts, n))
        inner_block = (int(fsdp) if fsdp else 1) * (int(tp) if tp
                                                    else 1)
        if (n // hosts) % inner_block:
            raise ValueError(
                "make_mesh(hosts=%d): fsdp*tp = %d does not divide "
                "the %d devices local to each host — an inner axis "
                "straddling hosts would put every weight collective "
                "on the cross-host (DCN) link" % (hosts, inner_block,
                                                  n // hosts))
    fsdp = int(fsdp) if fsdp is not None else 1
    tp = int(tp) if tp is not None else 1
    if fsdp < 1 or tp < 1:
        raise ValueError("make_mesh: axis sizes must be >= 1, got "
                         "fsdp=%s tp=%s" % (fsdp, tp))
    inner = fsdp * tp
    if data is None:
        if n % inner:
            raise ValueError(
                "make_mesh: fsdp*tp = %d does not divide the %d "
                "available devices" % (inner, n))
        data = n // inner
    data = int(data)
    if data < 1:
        raise ValueError("make_mesh: axis sizes must be >= 1, got "
                         "data=%s" % data)
    total = data * inner
    if total > n:
        raise ValueError(
            "make_mesh: data=%d x fsdp=%d x tp=%d needs %d devices, "
            "only %d available" % (data, fsdp, tp, total, n))
    return create_mesh({"data": data, "fsdp": fsdp, "tp": tp},
                       devices=devices[:total])


def local_mesh(axis_name="dp"):
    import jax
    return create_mesh({axis_name: len(jax.devices())})


def mesh_axes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P())


def shard_batch(mesh, batch_axes=("dp",)):
    """Sharding for a batch tensor: dim 0 split over given mesh axes."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(tuple(batch_axes)))


def axis_hosts(mesh, axis):
    """(group_size, hosts_per_group) for one mesh axis: how many
    devices a collective over ``axis`` spans, and how many distinct
    hosts (process indices) each of its device groups touches. Groups
    are the sub-axes holding every OTHER axis fixed; on the layouts
    :func:`make_mesh` builds they all touch the same host count."""
    import numpy as _np2
    names = list(mesh.axis_names)
    if axis not in names:
        raise ValueError("mesh has no axis %r (axes: %s)"
                         % (axis, names))
    arr = mesh.devices
    k = names.index(axis)
    moved = _np2.moveaxis(arr, k, -1)
    groups = moved.reshape(-1, arr.shape[k])
    hosts = max(len({getattr(d, "process_index", 0) for d in row})
                for row in groups)
    return int(arr.shape[k]), int(hosts)


def link_split(mesh, axis, nbytes):
    """Split one collective's logical payload into (ici_bytes,
    dcn_bytes): of the ``n-1`` pairwise combine hops a ring/fold
    reduction over an ``n``-device axis performs, the ones joining two
    devices on the SAME host ride the intra-host fast link (ICI) and
    the ``h-1`` host-boundary hops ride the cross-host link (DCN),
    where ``h`` is the axis's host span. Hop shares weight the payload:
    an axis entirely inside one host is pure ICI; a 2-host x 4-local
    axis puts 1/7 of its combine traffic on DCN. This is the
    accounting model telemetry's per-link table renders — a layout
    audit (is my fsdp axis really intra-host?), not a wire-byte
    meter."""
    n, h = axis_hosts(mesh, axis)
    if n <= 1:
        return 0, 0
    hops = n - 1
    dcn_hops = max(h - 1, 0)
    dcn = int(round(nbytes * dcn_hops / hops))
    return int(nbytes) - dcn, dcn
