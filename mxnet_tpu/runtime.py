"""Runtime feature detection (parity: python/mxnet/runtime.py +
src/libinfo.cc). Features reflect the TPU-native build. Also the one
place the process's compile cache is chosen
(:func:`enable_compile_cache`)."""
from __future__ import annotations

import os

__all__ = ["Features", "feature_list", "enable_compile_cache"]


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache for this process and
    return its directory. Call before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
    and nothing is set in code. Otherwise the cache is
    ``<checkout>/.jax_cache`` — an absolute path derived from where
    this package sits, the same from any working directory and in any
    process, because the path is part of how a cache is found again: a
    temp name, pid or timestamp never hits. The minimum compile time
    to store drops to zero so the short step programs are kept too.
    This is the only compile cache in the tree.

    Hits and misses (JAX's own monitoring events) are mirrored into
    ``profiler.counters()`` as ``jax_cache_hits``/``jax_cache_misses``,
    so a warm run can show that it was warm."""
    import jax
    global _cache_events
    if not _cache_events:
        _cache_events = True
        jax.monitoring.register_event_listener(_count_cache_event)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


_cache_events = False


def _count_cache_event(event, **_):
    if event.startswith("/jax/compilation_cache/cache_"):
        from . import profiler
        profiler.increment_counter("jax_" + event.rsplit("/", 1)[1])


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = enabled

    def __repr__(self):
        return "%s %s" % ("✔" if self.enabled else "✖", self.name)


def _detect():
    import jax
    feats = {
        "TPU": any(d.platform != "cpu" for d in jax.devices()),
        "XLA": True,
        "PALLAS": True,
        "CUDA": False, "CUDNN": False, "NCCL": False, "TENSORRT": False,
        "MKLDNN": False,
        "OPENCV": _has("cv2"),
        "DIST_KVSTORE": True,
        "INT64_TENSOR_SIZE": True,
        "SIGNAL_HANDLER": True,
        "F16C": True,
        "JAX_DISTRIBUTED": True,
    }
    return {k: Feature(k, v) for k, v in feats.items()}


def _has(mod):
    try:
        __import__(mod)
        return True
    except ImportError:
        return False


class Features(dict):
    def __init__(self):
        super().__init__(_detect())

    def is_enabled(self, name):
        return self[name.upper()].enabled


def feature_list():
    return list(Features().values())
