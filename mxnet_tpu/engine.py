"""Execution engine shim.

Reference: src/engine/ (ThreadedEnginePerDevice and friends) +
python/mxnet/engine.py. On TPU, op ordering and async dispatch are
provided by JAX/XLA: every dispatched computation returns a
future-backed array and XLA serializes device work per stream, which is
exactly the ordering guarantee the reference's Var read/write dependency
tracking provides for single-stream programs. What remains host-side:

- ``NaiveEngine`` ≙ ``jax.disable_jit()`` (synchronous debug mode,
  selected with MXNET_ENGINE_TYPE like the reference, engine.cc:33).
- bulking context managers (engine.h set_bulk_size) are accepted and
  no-op: whole-graph jit already executes fused programs.
- ``wait_for_all`` / per-array ``wait_to_read`` are the sync points.
"""
from __future__ import annotations

import contextlib

from . import envs

__all__ = ["bulk", "set_bulk_size", "wait_for_all", "engine_type",
           "naive_engine", "compiler_options"]

_bulk_size = 15
_compiler_options = None


def compiler_options(ctx=None):
    """Default XLA compile options for the framework's jitted programs.

    On TPU the latency-hiding scheduler overlaps the while-loop's
    cross-memory-space prefetches with compute (a measured ~3% on the
    ResNet-50 train step); other backends get no extra options — the
    options are TPU-only compile options, so callers that may compile
    for CPU (mixed-device processes, the op-level eager jits) must pass
    their target ``ctx`` or skip the options. Override with
    MXNET_XLA_COMPILER_OPTIONS="k=v,k2=v2" or disable with
    MXNET_XLA_COMPILER_OPTIONS=none (the reference's engine knobs are
    env-driven the same way, docs/faq/env_var.md).
    """
    global _compiler_options
    if _compiler_options is None:
        env = envs.get_str("MXNET_XLA_COMPILER_OPTIONS")
        if env == "none":
            _compiler_options = {}
        elif env:
            # explicit user options: applied verbatim on every backend
            _compiler_options = dict(kv.split("=", 1)
                                     for kv in env.split(",") if "=" in kv)
            _compiler_options["__from_env__"] = True
        else:
            _compiler_options = {
                "xla_tpu_enable_latency_hiding_scheduler": "true"}
    if not _compiler_options:
        return None
    if _compiler_options.get("__from_env__"):
        return {k: v for k, v in _compiler_options.items()
                if k != "__from_env__"}
    # the built-in default is a TPU-only option: gate on the device the
    # target ctx resolves to (the whole process's default without one)
    import jax
    device = ctx.jax_device() if ctx is not None else jax.devices()[0]
    if device.platform != "tpu":
        return None
    return _compiler_options


def engine_type():
    return envs.get_str("MXNET_ENGINE_TYPE")


def set_bulk_size(size):
    global _bulk_size
    prev = _bulk_size
    _bulk_size = size
    return prev


@contextlib.contextmanager
def bulk(size):
    prev = set_bulk_size(size)
    try:
        yield
    finally:
        set_bulk_size(prev)


def wait_for_all():
    from .ndarray import waitall
    from . import fault
    # faultable sync point: a planned hang here surfaces as a typed
    # CollectiveTimeoutError after MXNET_KVSTORE_TIMEOUT instead of
    # wedging the host thread (site "wait" in MXNET_FAULT_PLAN)
    return fault.guard(waitall, "wait")


@contextlib.contextmanager
def naive_engine():
    """Synchronous, uncompiled execution for debugging (NaiveEngine)."""
    import jax
    with jax.disable_jit():
        yield
