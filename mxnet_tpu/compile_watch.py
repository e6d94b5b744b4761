"""Compile & hardware-utilization observability (SURVEY §5.1 gap #2).

The telemetry layer answers *where did this step's wall-clock go*; this
module answers the two questions a TPU-native stack lives or dies by:

1. **How much did XLA compilation cost this run — and why did it
   recompile?** Every framework ``jax.jit`` site (the executor's
   forward / forward+backward programs, the fused train step, the
   per-op eager jit cache that backs ``CachedOp``, the eager
   collectives, and the inference server's bucket-ladder programs —
   ``serving:bN``, one per bucket, staged through :func:`jit` so the
   "fixed program cache under arbitrary request mixes" claim is a
   checkable :func:`site_stats` oracle) routes through :func:`jit`,
   which stages compilation explicitly (``lower()`` + ``compile()``)
   so each compile is:

   - timed (per-compile duration + cumulative compile seconds),
   - keyed (the argument-signature cache key that triggered it),
   - diffed against the previous key of the same *logical program*
     (same site name, across executor rebinds), naming the argument
     whose shape/dtype/weak-type/sharding changed — the
     **recompile cause**,
   - mined for XLA's own ``cost_analysis()`` (flops, bytes accessed)
     and ``memory_analysis()`` where the backend provides them —
     consulted ONCE per compile, never per step.

   A **recompile storm** — ``MXNET_COMPILE_STORM_K`` (default 3)
   compiles of one program within ``MXNET_COMPILE_STORM_STEPS``
   (default 50) steps — fires a one-time warning naming the churning
   argument, the classic symptom of an unpadded/unbucketed input loop.

2. **What fraction of the hardware's peak did each step achieve?**
   Every watched dispatch accrues its executable's flops/bytes into the
   current step; at each telemetry step boundary the accumulators
   combine with the step's wall time into **MFU** (model-flops
   utilization) and memory-bandwidth utilization against a per-device
   peak table (built-in numbers for TPU generations, a placeholder for
   CPU, both overridable via ``MXNET_DEVICE_PEAK_FLOPS`` /
   ``MXNET_DEVICE_PEAK_BW`` — per-device values in FLOP/s and bytes/s).
   The peak is **dtype-aware**: each compiled program's flops are
   normalized by its compute dtype's ``PEAK_DTYPE_FACTOR`` (narrowest
   float in the argument signature — fp32 at half the bf16 MXU rate,
   int8 at double), so AMP, fp32, and int8 programs all report MFU
   against the peak they could actually reach.

Everything flows into the active telemetry run: ``compile`` and
``utilization`` JSONL record kinds, plus ``compile``/``utilization``
blocks in the ``summary`` record; ``python -m mxnet_tpu.tools.diagnose
run.jsonl`` renders the compile log and the utilization table.
Compiles at the fused-step sites additionally bridge into
``profiler.counters()`` as ``fused_step_compile_ms`` so the fused
cache's hit/miss counters and its compile seconds reconcile in one
place.

Off by default, always cheap when off: a watched function's call path
is one module-global ``None`` check before delegating to the plain
``jax.jit`` callable, and the telemetry step hook is the same check —
with the watch disabled the JSONL sink is byte-identical to a run
without this module. Enable with ``MXNET_COMPILE_WATCH=1`` (picked up
at wrapper creation and at ``telemetry.start()``) or explicitly via
:func:`enable`.

Safety valve: the staged ``Compiled`` executable is stricter than
``jax.jit`` (it will not re-specialize). The signature key covers
shape/dtype/weak-type/sharding, so a mismatch should never happen —
but if a staged call ever fails where the plain path would not, the
wrapper permanently falls back to its ``jax.jit`` twin for that
function and counts the degradation, instead of killing the job it
observes.
"""
from __future__ import annotations

import threading
import time
import warnings
from collections import deque

from . import envs

__all__ = ["enabled", "enable", "disable", "reset", "maybe_enable",
           "jit", "stats", "site_stats", "recent_mfu", "peak_table",
           "dtype_peak_factor", "describe_arrays", "step_reset",
           "run_reset", "WatchedFunction"]

_lock = threading.Lock()
_watch = None          # the active _Watch; module-global None check


# ---------------------------------------------------------------------------
# peak-performance tables
# ---------------------------------------------------------------------------

# Peak FLOP/s per chip, keyed by ``jax.Device.device_kind`` (bf16 MXU
# peak — Google Cloud TPU documentation, the per-generation "system
# architecture" pages; "TPU v5 lite" is what a v5e chip reports). The
# ONE peak table in the package, read through peak_table() (the
# benchmark keeps its own with its source, ``benchmark/peaks.py``).
# The "cpu" row is a placeholder that keeps the MFU math defined on
# the CPU test mesh; no CPU figure is ever reported as a device metric.
PEAK_FLOPS = {
    "TPU v2": 45e12, "TPU v3": 123e12, "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
    "cpu": 1e11,
}

# Peak HBM (or DRAM) bandwidth, bytes/s per chip.
PEAK_BW = {
    "TPU v2": 700e9, "TPU v3": 900e9, "TPU v4": 1228e9,
    "TPU v5 lite": 819e9, "TPU v5e": 819e9, "TPU v5p": 2765e9,
    "TPU v6 lite": 1638e9, "TPU v6e": 1638e9,
    "cpu": 50e9,
}

# Relative achievable peak by COMPUTE dtype, against the tables' bf16
# MXU numbers: fp32 matmuls run as multi-pass bf16 on the MXU (half
# rate as the documented convention here), fp64 is emulated, and int8
# rides the double-rate path newer generations expose. A program's
# compute dtype is the NARROWEST float in its argument signature —
# a mixed-precision program's matmuls run in its low dtype while the
# fp32 master weights ride along element-wise (int8 only when no
# float argument exists: a quantized graph's range scalars ride fp32
# and must not mask wider compute). MFU is normalized per program by
# this factor, so one bf16 AMP step and one fp32 step of the same
# model report comparable utilization instead of the fp32 run
# appearing to waste half the hardware it never had.
PEAK_DTYPE_FACTOR = {
    "float64": 0.25, "float32": 0.5,
    "float16": 1.0, "bfloat16": 1.0,
    "int8": 2.0,
}


def dtype_peak_factor(dtype):
    """The per-dtype peak factor the MFU math uses (1.0 for unknown
    dtypes). Importable by benchmarks — one dtype convention tree-wide."""
    return PEAK_DTYPE_FACTOR.get(str(dtype), 1.0)


_DTYPE_WIDTH = {"float64": 3, "float32": 2, "bfloat16": 1,
                "float16": 1}


def _key_compute_dtype(key):
    """The compute dtype of one argument-signature key: the narrowest
    float among array leaves, else int8 when only int8 arrays flow,
    else None (integer-only programs run no MXU math worth scaling)."""
    narrowest = None
    saw_int8 = False
    for sig in key:
        if len(sig) != 4 or not isinstance(sig[1], str):
            continue                   # python-scalar leaf
        dt = sig[1]
        if dt == "int8":
            saw_int8 = True
        elif dt in _DTYPE_WIDTH and (
                narrowest is None
                or _DTYPE_WIDTH[dt] < _DTYPE_WIDTH[narrowest]):
            narrowest = dt
    if narrowest is not None:
        return narrowest
    return "int8" if saw_int8 else None


def _lookup_peak(table, kind):
    """The table row for ``kind`` — a device that is not in the table
    is an error, never a default: a utilization figure against a
    guessed peak is worse than none."""
    try:
        return table[kind]
    except KeyError:
        raise KeyError(
            "compile_watch: device kind %r is not in the peak table "
            "(known: %s) — add its published peak with the source, or "
            "set MXNET_DEVICE_PEAK_FLOPS / MXNET_DEVICE_PEAK_BW"
            % (kind, ", ".join(sorted(table)))) from None


def peak_table():
    """The (per-device peak FLOP/s, peak bytes/s, device kind, device
    count) the MFU math uses — env overrides applied. Importable by
    benchmarks so there is exactly one peak table in the tree."""
    import jax
    devices = jax.local_devices()
    kind = devices[0].device_kind
    flops = envs.get_float("MXNET_DEVICE_PEAK_FLOPS") or \
        _lookup_peak(PEAK_FLOPS, kind)
    bw = envs.get_float("MXNET_DEVICE_PEAK_BW") or \
        _lookup_peak(PEAK_BW, kind)
    return float(flops), float(bw), kind, max(1, len(devices))


# ---------------------------------------------------------------------------
# watch state
# ---------------------------------------------------------------------------

class _Watch:
    """All compile/utilization accumulators. Mutation under the module
    lock; the telemetry callbacks never run while this lock is held
    (lock order: telemetry._lock → compile_watch._lock, never the
    reverse)."""

    def __init__(self):
        self.t0 = time.time()
        self.compile_count = 0
        self.compile_total_s = 0.0
        self.programs = {}      # site -> per-program dict
        self.storms = []        # [{"program","arg","compiles","steps"}]
        self.degraded = 0       # staged calls that fell back to jit
        self.dispatches = 0     # watched compiled-call executions
        self.site_last = {}     # site -> (flops, bytes) of the most
                                # recent dispatch (metering attribution)
        # current-step accumulators, drained by the telemetry step hook
        self.step_flops = 0.0
        self.step_flops_norm = 0.0   # dtype-factor-normalized flops
        self.step_bytes = 0.0
        self.step_dispatches = 0
        self.step_compiles = 0
        self.step_compile_s = 0.0
        # whole-run utilization accumulators
        self.total_flops = 0.0
        self.total_bytes = 0.0
        self.mfu_ring = deque(maxlen=max(
            1, envs.get_int("MXNET_TELEMETRY_RING")))
        self.bw_ring = deque(maxlen=self.mfu_ring.maxlen)
        self.storm_k = max(2, envs.get_int("MXNET_COMPILE_STORM_K"))
        self.storm_steps = max(
            1, envs.get_int("MXNET_COMPILE_STORM_STEPS"))
        self.peak_flops, self.peak_bw, self.device_kind, self.n_devices \
            = peak_table()

    def program(self, site, statics):
        """Per-program state. Identity is (site, statics): two watched
        functions with different STATIC configuration (an op's attrs,
        a fused step's guard/optimizer key) are different programs by
        design — a compile of each is specialization, not churn —
        while the same site+statics recompiling on argument signature
        IS churn. stats() re-aggregates per site for reporting."""
        key = (site, statics)
        p = self.programs.get(key)
        if p is None:
            p = self.programs[key] = {
                "site": site, "count": 0, "total_s": 0.0,
                "last_desc": None, "causes": {}, "recent": deque(),
                "warned": False, "churn": {}}
        return p


def enabled():
    """True while the compile watch is active."""
    return _watch is not None


def enable():
    """Turn the watch on (idempotent). Reads the storm/peak env knobs
    and registers the per-step utilization probe with telemetry."""
    global _watch
    with _lock:
        if _watch is None:
            _watch = _Watch()
    from . import telemetry
    telemetry._util_probe = _step_probe
    telemetry._util_reset = step_reset
    return _watch


def disable():
    """Turn the watch off; watched functions fall back to their plain
    ``jax.jit`` twins (already-compiled signatures are kept)."""
    global _watch
    from . import telemetry
    telemetry._util_probe = None
    telemetry._util_reset = None
    with _lock:
        _watch = None


def reset():
    """disable() + forget nothing else (wrappers keep their compiled
    caches — recompiling identical programs would distort the very
    compile accounting this module exists for)."""
    disable()


def maybe_enable():
    """Enable when MXNET_COMPILE_WATCH asks for it (called at wrapper
    creation and from ``telemetry.start``). Returns True when active
    after the call."""
    if _watch is not None:
        return True
    if envs.get_bool("MXNET_COMPILE_WATCH"):
        enable()
        return True
    return False


# ---------------------------------------------------------------------------
# argument signatures
# ---------------------------------------------------------------------------

def _leaf_sig(leaf):
    """Hashable compile-relevant signature of one argument leaf: shape,
    dtype, weak-type, and sharding (device placement re-specializes a
    compile exactly like a shape change does)."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        # python scalar: jit weak-types it by python type
        return ("py", type(leaf).__name__)
    aval = getattr(leaf, "aval", None)
    weak = bool(getattr(aval, "weak_type", False))
    sharding = getattr(leaf, "sharding", None)
    try:
        hash(sharding)
    except TypeError:
        sharding = str(sharding)
    return (tuple(shape), str(getattr(leaf, "dtype", "?")), weak,
            sharding)


def _short_sig(leaf):
    """Human form of a leaf signature: ``f32[32,784]``."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        return type(leaf).__name__
    dt = str(getattr(leaf, "dtype", "?"))
    dt = {"float32": "f32", "float64": "f64", "float16": "f16",
          "bfloat16": "bf16", "int32": "i32", "int64": "i64",
          "uint32": "u32", "uint8": "u8", "bool": "pred"}.get(dt, dt)
    return "%s[%s]" % (dt, ",".join(str(d) for d in shape))


def describe_arrays(names, arrays):
    """name -> short signature dict for a flat array list (call-site
    helper for the ``describe`` hook)."""
    out = {}
    for i, a in enumerate(arrays):
        n = names[i] if names is not None and i < len(names) \
            else "arg%d" % i
        out[str(n)] = _short_sig(a)
    return out


def _default_describe(args):
    """Generic description when the call site gave none: tree-flatten
    the args and label leaves by positional path."""
    import jax
    leaves = jax.tree_util.tree_leaves(args)
    return {"arg%d" % i: _short_sig(v) for i, v in enumerate(leaves)}


def _diff_desc(old, new):
    """(cause, churning-arg names) between two description dicts.
    Names are kept whole — "aux:moving_mean" must not collapse to
    "aux" — so churn attribution points at the actual tensor. Only
    arguments present on BOTH sides with a different signature count
    as churn; a different argument SET means a different model was
    bound at this site (ensemble/sweep), which is setup, not churn."""
    if old is None:
        return "first_compile", []
    modified = []                    # (full name, human detail)
    reshaped = []
    for name in new:
        if name not in old:
            reshaped.append("%s: new %s" % (name, new[name]))
        elif old[name] != new[name]:
            modified.append((name, "%s: %s -> %s"
                             % (name, old[name], new[name])))
    for name in old:
        if name not in new:
            reshaped.append("%s: removed" % name)
    if modified:
        names = [n for n, _ in modified]
        shown = [d for _, d in modified[:3]]
        if len(modified) > 3:
            shown.append("+%d more" % (len(modified) - 3))
        return "changed " + "; ".join(shown), names
    if reshaped:
        return "rebound " + "; ".join(reshaped[:3]), []
    # identical description but a different full key (sharding or
    # weak-type nuance the short form hides) or a fresh wrapper for
    # the same logical program (an executor rebind)
    return "rebind_or_placement", []


# ---------------------------------------------------------------------------
# cost / memory analysis
# ---------------------------------------------------------------------------

def _cost_of(compiled):
    """(flops, bytes_accessed) from the executable's own cost model;
    zeros when the backend offers none."""
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return (float(ca.get("flops", 0.0) or 0.0),
                float(ca.get("bytes accessed", 0.0) or 0.0))
    except Exception:
        return 0.0, 0.0


def _memory_of(compiled):
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        out = {}
        for k in ("generated_code_size_in_bytes",
                  "argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v:
                out[k.replace("_in_bytes", "")] = int(v)
        return out or None
    except Exception:
        return None


# ---------------------------------------------------------------------------
# the watched jit wrapper
# ---------------------------------------------------------------------------

class WatchedFunction:
    """A ``jax.jit`` twin that stages compilation explicitly when the
    watch is on. Callable exactly like the jitted function (positional
    args only — every framework site is positional)."""

    __slots__ = ("_jitted", "_site", "_describe", "_cache", "_mu",
                 "_broken", "_counter", "_statics", "_storm")

    def __init__(self, fn, site, describe=None, counter=None,
                 statics=None, storm=True, **jit_kwargs):
        import jax
        self._jitted = jax.jit(fn, **jit_kwargs)
        self._site = site
        self._describe = describe
        self._counter = counter      # extra profiler counter for
        self._cache = {}             # compile ms at this site
        self._statics = statics      # program identity = (site, statics)
        self._storm = bool(storm)    # storm-track this program?
        self._mu = threading.Lock()
        self._broken = False

    @property
    def site(self):
        return self._site

    def __call__(self, *args, **kwargs):
        w = _watch
        if w is None or self._broken or kwargs:
            return self._jitted(*args, **kwargs)
        return self._watched_call(w, args)

    # -- watched path ------------------------------------------------------
    def _watched_call(self, w, args):
        import jax
        try:
            leaves = jax.tree_util.tree_leaves(args)
            if any(isinstance(a, jax.core.Tracer) for a in leaves):
                # called under an outer trace (a caller composing this
                # program into its own jit): staging is meaningless
                # there — the outer program owns the compile
                return self._jitted(*args)
            key = tuple(_leaf_sig(a) for a in leaves)
        except Exception:
            return self._jitted(*args)
        entry = self._cache.get(key)
        if entry is None:
            entry = self._compile(w, key, args)
            if entry is None:        # staging failed: degraded fallback
                return self._jitted(*args)
        out = entry["fn"](*args)
        _accrue(w, entry["flops"], entry["flops_norm"], entry["bytes"],
                self._site)
        return out

    def _compile(self, w, key, args):
        # the whole staging runs under the wrapper's own lock: two
        # threads racing on the same signature (decode-pool workers
        # hitting a shared eager-op wrapper) must produce ONE compile,
        # one record, one storm-clock entry — not N duplicates
        with self._mu:
            entry = self._cache.get(key)
            if entry is not None:
                return entry
            t0 = time.perf_counter()
            try:
                compiled = self._jitted.lower(*args).compile()
            except Exception:
                # never let the observability layer change what the
                # program raises: re-run through the plain jit twin (a
                # genuinely bad call re-raises identically; a
                # staging-only failure permanently degrades this
                # wrapper instead of the job)
                self._broken = True
                with _lock:
                    w.degraded += 1
                warnings.warn(
                    "compile_watch: staged compile failed for %r; "
                    "falling back to plain jax.jit for this "
                    "program (compile accounting degraded)"
                    % self._site)
                return None
            dur = time.perf_counter() - t0
            flops, nbytes = _cost_of(compiled)
            mem = _memory_of(compiled)
            try:
                desc = self._describe(*args) \
                    if self._describe is not None \
                    else _default_describe(args)
            except Exception:
                desc = _default_describe(args)
            cdtype = _key_compute_dtype(key)
            factor = dtype_peak_factor(cdtype) if cdtype else 1.0
            entry = {"fn": compiled, "flops": flops, "bytes": nbytes,
                     "flops_norm": flops / factor, "dtype": cdtype}
            self._cache[key] = entry
        event = _record_compile(w, self._site, self._statics,
                                self._storm, dur, desc, flops, nbytes,
                                mem)
        if cdtype is not None:
            event["compute_dtype"] = cdtype
        if self._counter:
            from . import profiler
            profiler.increment_counter(self._counter, dur * 1e3)
        _emit_compile_record(event)
        return entry


def jit(fn, site, describe=None, counter=None, statics=None,
        storm=True, **jit_kwargs):
    """Wrap ``fn`` exactly like ``jax.jit(fn, **jit_kwargs)`` but
    observable: ``site`` names the logical program (recompiles of the
    same (site, statics) identity are diffed/storm-tracked across
    wrapper instances — executor rebinds included), ``describe(*args)
    -> {arg_name: short_sig}`` names arguments for the recompile-cause
    diff, ``counter`` optionally mirrors compile milliseconds into a
    ``profiler.counters()`` entry, and ``storm=False`` opts a
    polymorphic-by-design program (the eager micro-op jits: ``_copy``
    over every param shape is specialization, not churn) out of the
    storm warning while keeping its compiles in the log.

    The staged ``lower().compile()`` goes through JAX's own persistent
    compilation cache (``runtime.enable_compile_cache``) like any
    ``jax.jit`` call: a warm process still records each program here
    once, with the time it took to load instead of to compile."""
    maybe_enable()
    return WatchedFunction(fn, site, describe=describe, counter=counter,
                           statics=statics, storm=storm, **jit_kwargs)


# ---------------------------------------------------------------------------
# accounting
# ---------------------------------------------------------------------------

def _accrue(w, flops, flops_norm, nbytes, site=None):
    # run totals accrue at the step boundary (the probe), not here, so
    # they mean "work attributed to this run's steps" — backlog dropped
    # by step_reset() never counts
    with _lock:
        w.dispatches += 1
        w.step_dispatches += 1
        w.step_flops += flops
        w.step_flops_norm += flops_norm
        w.step_bytes += nbytes
        if site is not None:
            w.site_last[site] = (flops, nbytes)


def _step_clock(w):
    """The storm window's clock: telemetry steps when a run is active,
    watched dispatches otherwise (a bare churn loop with no telemetry
    still storms)."""
    from . import telemetry
    run = telemetry._run
    if run is not None:
        return run.steps
    return w.dispatches


def _record_compile(w, site, statics, storm_track, dur, desc, flops,
                    nbytes, mem):
    """Fold one compile into the program's stats (under the lock) and
    return the JSONL-ready event dict. The storm check runs here; the
    warning itself fires outside the lock."""
    storm = None
    clock = _step_clock(w)
    with _lock:
        w.compile_count += 1
        w.compile_total_s += dur
        w.step_compiles += 1
        w.step_compile_s += dur
        p = w.program(site, statics)
        p["count"] += 1
        p["total_s"] += dur
        cause, changed = _diff_desc(p["last_desc"], desc)
        p["last_desc"] = desc
        ckey = cause.split(" ", 1)[0]
        p["causes"][ckey] = p["causes"].get(ckey, 0) + 1
        for n in changed:
            p["churn"][n] = p["churn"].get(n, 0) + 1
        # only argument-churn compiles count toward the storm window:
        # first compiles and rebinds (an ensemble binding N models, an
        # eval clone) are setup cost, not an unpadded input loop
        if changed:
            p["recent"].append(clock)
        while p["recent"] and clock - p["recent"][0] > w.storm_steps:
            p["recent"].popleft()
        if storm_track and changed and len(p["recent"]) >= w.storm_k \
                and not p["warned"]:
            p["warned"] = True
            arg = max(p["churn"], key=p["churn"].get)
            storm = {"program": site, "arg": arg,
                     "compiles": len(p["recent"]),
                     "window_steps": w.storm_steps}
            w.storms.append(storm)
        seq = p["count"]
    if storm is not None:
        warnings.warn(
            "compile_watch: recompile storm — program '%s' compiled "
            "%d times within %d steps; argument '%s' keeps changing "
            "shape/dtype. Pad or bucket it (each distinct signature "
            "is a full XLA compile)."
            % (storm["program"], storm["compiles"],
               storm["window_steps"], storm["arg"]), stacklevel=3)
        from . import telemetry
        telemetry.note("compile_storms")
    event = {"type": "compile", "program": site, "n": seq,
             "dur_ms": round(dur * 1e3, 3), "cause": cause}
    if changed:
        event["changed"] = list(changed)
    if flops:
        event["flops"] = flops
    if nbytes:
        event["bytes"] = nbytes
    if mem:
        event["memory"] = mem
    return event


def _emit_compile_record(event):
    """Append the compile event to the active telemetry run (no-op
    without one) and, when tracing is on, render it as a duration
    event on the trace's ``compile`` track (ts backdated by the
    compile's own duration). Called with NO compile_watch lock held."""
    from . import telemetry, tracing
    telemetry.external_record(event)
    if tracing._tracer is not None:
        dur_s = event.get("dur_ms", 0.0) / 1e3
        args = {"program": event.get("program"),
                "cause": event.get("cause")}
        if event.get("changed"):
            args["changed"] = event["changed"]
        tracing.add("compile:%s" % event.get("program"), "compile",
                    tracing.now() - dur_s, dur_s,
                    tid=tracing.track("compile"), args=args)


def step_reset():
    """Drop anything accrued OUTSIDE an open telemetry step (warmup
    dispatches, init work between runs) — telemetry calls this at
    ``step_begin`` so a step's utilization reflects only its own
    dispatches, never a pre-step backlog that would push MFU past
    100%. No-op when the watch is off."""
    w = _watch
    if w is None:
        return
    with _lock:
        w.step_flops = 0.0
        w.step_flops_norm = 0.0
        w.step_bytes = 0.0
        w.step_dispatches = 0
        w.step_compiles = 0
        w.step_compile_s = 0.0


def run_reset():
    """Re-scope the utilization accumulators to a fresh telemetry run
    (called from ``telemetry.start``): the MFU/BW rings and the
    flops/bytes totals describe THIS run in its summary, not the
    process's lifetime — compile counts/seconds stay lifetime (program
    identity outlives runs) and are run-scoped via the start()
    baseline instead."""
    w = _watch
    if w is None:
        return
    with _lock:
        w.mfu_ring.clear()
        w.bw_ring.clear()
        w.total_flops = 0.0
        w.total_bytes = 0.0
        w.step_flops = 0.0
        w.step_flops_norm = 0.0
        w.step_bytes = 0.0
        w.step_dispatches = 0
        w.step_compiles = 0
        w.step_compile_s = 0.0


def _step_probe(step_seq, dur_s):
    """telemetry's per-step hook (installed by :func:`enable`): drain
    the step accumulators into a ``utilization`` record dict, or None
    when this step dispatched nothing watched. Runs under telemetry's
    lock — must not call back into telemetry."""
    w = _watch
    if w is None:
        return None
    with _lock:
        flops = w.step_flops
        flops_norm = w.step_flops_norm
        nbytes = w.step_bytes
        dispatches = w.step_dispatches
        compiles = w.step_compiles
        compile_s = w.step_compile_s
        w.step_flops = 0.0
        w.step_flops_norm = 0.0
        w.step_bytes = 0.0
        w.step_dispatches = 0
        w.step_compiles = 0
        w.step_compile_s = 0.0
        if dispatches == 0 and compiles == 0:
            return None
        w.total_flops += flops
        w.total_bytes += nbytes
        rec = {"dispatches": dispatches}
        if dur_s > 0 and flops:
            # normalized flops measure each program against ITS
            # dtype's achievable peak (PEAK_DTYPE_FACTOR): a pure-bf16
            # step divides by the full table peak, a pure-fp32 step by
            # half of it, a mixed step by the flop-weighted blend
            mfu = flops_norm / (dur_s * w.peak_flops * w.n_devices)
            rec["flops"] = flops
            if flops_norm != flops:
                rec["flops_norm"] = flops_norm
            # 6 SIGNIFICANT digits: CPU-scale MFUs live around 1e-5,
            # where fixed decimal rounding would destroy the value
            rec["mfu"] = float("%.6g" % mfu)
            w.mfu_ring.append(mfu)
        if dur_s > 0 and nbytes:
            bwu = nbytes / (dur_s * w.peak_bw * w.n_devices)
            rec["bytes"] = nbytes
            rec["bw_util"] = float("%.6g" % bwu)
            w.bw_ring.append(bwu)
        if compiles:
            rec["compiles"] = compiles
            rec["compile_ms"] = round(compile_s * 1e3, 3)
        return rec


# ---------------------------------------------------------------------------
# queries
# ---------------------------------------------------------------------------

def recent_mfu(n=None):
    """Mean MFU over the last ``n`` utilization-carrying steps (None
    when the watch is off or nothing has been measured) — the
    Speedometer's extra column."""
    w = _watch
    if w is None:
        return None
    with _lock:
        vals = list(w.mfu_ring)
    if n:
        vals = vals[-int(n):]
    if not vals:
        return None
    return sum(vals) / len(vals)


def stats():
    """Snapshot of everything: compile counts/seconds per program,
    causes, storms, utilization aggregates, the peak table in use.
    None when the watch is off."""
    w = _watch
    if w is None:
        return None
    from .telemetry import percentile
    with _lock:
        programs = {}
        for p in w.programs.values():
            # aggregate the (site, statics) identities back to the
            # site for reporting: one table row per logical call site
            agg = programs.get(p["site"])
            if agg is None:
                agg = programs[p["site"]] = {
                    "count": 0, "total_s": 0.0, "causes": {},
                    "specializations": 0}
            agg["count"] += p["count"]
            agg["total_s"] = round(agg["total_s"] + p["total_s"], 6)
            agg["specializations"] += 1
            for k, v in p["causes"].items():
                agg["causes"][k] = agg["causes"].get(k, 0) + v
            if p["churn"]:
                churn = agg.setdefault("churn", {})
                for k, v in p["churn"].items():
                    churn[k] = churn.get(k, 0) + v
        mfu = list(w.mfu_ring)
        bwu = list(w.bw_ring)
        out = {
            "compiles": w.compile_count,
            "compile_total_s": round(w.compile_total_s, 6),
            "programs": programs,
            "storms": [dict(s) for s in w.storms],
            "dispatches": w.dispatches,
            "degraded": w.degraded,
            "total_flops": w.total_flops,
            "total_bytes": w.total_bytes,
            "device_kind": w.device_kind,
            "n_devices": w.n_devices,
            "peak_flops": w.peak_flops,
            "peak_bw": w.peak_bw,
        }
    if mfu:
        out["mfu"] = {"p50": percentile(mfu, 50),
                      "p90": percentile(mfu, 90),
                      "last": mfu[-1], "samples": len(mfu)}
    if bwu:
        out["bw_util"] = {"p50": percentile(bwu, 50),
                          "p90": percentile(bwu, 90),
                          "samples": len(bwu)}
    return out


def site_stats(prefix=None):
    """Per-site compile counts — ``{site: {"count", "total_s"}}``,
    optionally filtered to sites starting with ``prefix``. The serving
    tests use this as the bounded-program-cache oracle: under any request mix, ``site_stats("serving")``
    must hold exactly the bucket-ladder sites, each compiled once per
    replica device. None when the watch is off."""
    w = _watch
    if w is None:
        return None
    out = {}
    with _lock:
        for p in w.programs.values():
            site = p["site"]
            if prefix is not None and not site.startswith(prefix):
                continue
            agg = out.setdefault(site, {"count": 0, "total_s": 0.0})
            agg["count"] += p["count"]
            agg["total_s"] = round(agg["total_s"] + p["total_s"], 6)
    return out


def last_dispatch(site):
    """Cost of the most recent watched dispatch at ``site`` —
    ``{"flops", "bytes"}`` straight from the compiled program's
    ``cost_analysis()`` — or None when the watch is off or the site
    has not dispatched. This is the metering layer's per-program cost
    source: a caller that just ran a program under ``site`` reads the
    dispatch's cost here and attributes each batch row its share.
    With the watch off, metering's FLOP fields stay 0 (token and
    page*second conservation are unaffected)."""
    w = _watch
    if w is None:
        return None
    with _lock:
        c = w.site_last.get(site)
    if c is None:
        return None
    return {"flops": c[0], "bytes": c[1]}


def summary_blocks():
    """The ``compile`` / ``utilization`` blocks telemetry.report()
    embeds in the summary record; (None, None) when the watch is off —
    which is what keeps an off-run's sink byte-identical."""
    s = stats()
    if s is None:
        return None, None
    compile_block = {
        "count": s["compiles"],
        "total_s": s["compile_total_s"],
        "programs": s["programs"],
    }
    if s["storms"]:
        compile_block["storms"] = s["storms"]
    if s["degraded"]:
        compile_block["degraded"] = s["degraded"]
    util_block = {
        "device_kind": s["device_kind"],
        "n_devices": s["n_devices"],
        "peak_flops": s["peak_flops"],
        "peak_bw": s["peak_bw"],
        "total_flops": s["total_flops"],
        "total_bytes": s["total_bytes"],
    }
    if "mfu" in s:
        util_block["mfu"] = s["mfu"]
    if "bw_util" in s:
        util_block["bw_util"] = s["bw_util"]
    return compile_block, util_block
