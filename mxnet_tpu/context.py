"""Device context management.

Parity with ``python/mxnet/context.py`` in the reference, re-targeted at
JAX's device model. A :class:`Context` names a (device_type, device_id)
pair; it resolves lazily to a concrete ``jax.Device``:

- ``mx.cpu(i)``  → the JAX CPU backend device *i* (always available).
- ``mx.tpu(i)``  → TPU device *i* (the native target of this framework).
- ``mx.gpu(i)``  → accepted for API compatibility; resolves to the default
  accelerator if one exists (so reference scripts that say ``mx.gpu()``
  run unmodified on TPU), else raises at resolution time.

Unlike the reference there is no per-context memory pool to manage —
XLA owns HBM — so the context is purely a placement annotation.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

from .base import MXNetError, classproperty

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "gpu_memory_info", "placement_scope",
           "current_placement"]


class Context:
    """Device context (reference: python/mxnet/context.py:29)."""

    # Parity with reference devtype mapping (context.py:58-66) + tpu.
    devtype2str = {1: 'cpu', 2: 'gpu', 3: 'cpu_pinned', 5: 'cpu_shared', 6: 'tpu'}
    devstr2type = {v: k for k, v in devtype2str.items()}

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in Context.devstr2type:
                raise MXNetError("unknown device type %r" % (device_type,))
            self.device_typeid = Context.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self) -> str:
        return Context.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return '%s(%d)' % (self.device_type, self.device_id)

    def __repr__(self):
        return self.__str__()

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = _initial_default_context()
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, ptype, value, trace):
        Context._default_ctx.value = self._old_ctx

    # ---- JAX resolution ------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device."""
        import jax
        dt = self.device_type
        if dt in ('cpu', 'cpu_pinned', 'cpu_shared'):
            cpus = jax.devices('cpu')   # raises where JAX_PLATFORMS
            if self.device_id >= len(cpus):     # excludes the host
                raise MXNetError(
                    "context %s: only %d cpu device(s) available"
                    % (self, len(cpus)))
            return cpus[self.device_id]
        # gpu/tpu: use the default backend's devices (on this stack that is
        # the TPU / accelerator backend; 'gpu' accepted for compat).
        devs = jax.devices()
        if devs[0].platform == 'cpu':
            # No accelerator in this process (the CPU test mesh): an
            # accelerator context lands on a host device. Counted, so
            # a run that must be on the chip can assert it never
            # happened (chip_smoke.py does) instead of trusting it.
            from . import profiler
            profiler.increment_counter("context_accelerator_on_cpu")
            return devs[self.device_id % len(devs)]
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %s: only %d device(s) available" % (self, len(devs)))
        return devs[self.device_id]

    def empty_cache(self):
        """No-op: XLA owns the memory pool (reference frees GPU pool here)."""

    @classproperty
    def default_ctx(cls):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = _initial_default_context()
        return Context._default_ctx.value


def _initial_default_context() -> "Context":
    """First-use default: the accelerator when one is present, else cpu.

    This framework is TPU-native — a bare ``mx.nd.array(...)`` must land
    on the TPU, exactly as the reference lands on the build's native
    device. ``MXNET_DEFAULT_CONTEXT=cpu`` (or ``tpu``/``gpu``) overrides.
    Unit tests pin ``JAX_PLATFORMS=cpu`` and therefore still get cpu.
    """
    from . import envs
    override = envs.get_str("MXNET_DEFAULT_CONTEXT").lower()
    if override:
        return Context(override, 0)
    import jax
    # a backend that fails to initialise raises here: the default
    # context never quietly becomes the host
    if jax.devices()[0].platform != 'cpu':
        return Context('tpu', 0)
    return Context('cpu', 0)


def cpu(device_id=0):
    """Return a CPU context (reference: context.py:201)."""
    return Context('cpu', device_id)


def cpu_pinned(device_id=0):
    return Context('cpu_pinned', device_id)


def gpu(device_id=0):
    """Accelerator context; on this framework it aliases the TPU backend."""
    return Context('gpu', device_id)


def tpu(device_id=0):
    """TPU context — the native device of this framework."""
    return Context('tpu', device_id)


def num_gpus():
    """Number of accelerator devices visible (reference: context.py:242)."""
    import jax
    devs = jax.devices()
    if devs and devs[0].platform != 'cpu':
        return len(devs)
    return 0


def num_tpus():
    import jax
    return len([d for d in jax.devices() if d.platform != 'cpu'])


def gpu_memory_info(device_id=0):
    """(free, total) memory on accelerator ``device_id``."""
    import jax
    devs = [d for d in jax.devices() if d.platform != 'cpu']
    if not devs:
        raise MXNetError("no accelerator device present")
    stats = devs[device_id].memory_stats() or {}
    total = stats.get('bytes_limit', 0)
    used = stats.get('bytes_in_use', 0)
    return (total - used, total)


def current_context() -> Context:
    """The thread-local default context (reference: context.py:257)."""
    return Context.default_ctx


@contextlib.contextmanager
def placement_scope(placement):
    """The thread-local default *placement*, beside the default context:
    inside the scope, arrays this thread makes from host memory without
    an explicit ``ctx`` (``mx.nd.array(host)``) are ``placement``'s
    owner's to place. ``placement`` is whatever the owner resolves
    later — a ``jax.Device``, a ``Sharding``, a per-array callable — so
    the constructor sends the bytes nowhere and keeps them host-side.
    The input pipeline opens it around a source's decode on its own
    threads (``io/pipeline.py``); it never reaches another thread and
    ends with the ``with`` block. ``None`` opens no scope."""
    local = Context._default_ctx
    outer = getattr(local, "placement", None)
    local.placement = placement
    try:
        yield
    finally:
        local.placement = outer


def current_placement():
    """The placement scope open on this thread, or None."""
    return getattr(Context._default_ctx, "placement", None)
