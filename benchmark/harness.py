"""What every driver and metric reader shares: the run's context, the
clock, the count of compilations, the profiler slice and the device
record. No cell, model or metric is named here.
"""
from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(ROOT, "benchmark", "out")

now = time.perf_counter


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_object(path):
    """``"package.module:attribute"`` -> the object."""
    module, _, attr = path.partition(":")
    obj = importlib.import_module(module)
    for part in attr.split("."):
        obj = getattr(obj, part)
    return obj


def by_name(entries, name, what):
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit("benchmark: no %s named %r in BENCHMARK.json"
                     % (what, name))


class Context:
    """One run of one cell: what was asked (``spec``, ``cell``,
    ``config``, ``traffic``, ``args``) and, filled in by the driver,
    what was measured (``raw``: a dict of plain numbers and lists),
    ``trace`` (a ``trace_reduce.Trace`` or None) and ``peak``."""

    def __init__(self, spec, cell, config, traffic, args, t_start):
        self.spec, self.cell, self.args = spec, cell, args
        self.rehearse = args.rehearse
        self.config, self.traffic = self.sized(config), self.sized(traffic)
        self.t_start = t_start
        self.seed, self.seconds = args.seed, args.seconds
        self.tracing = bool(args.trace)
        self.chips = cell["chips"]
        self.raw, self.trace, self.peak = {}, None, None
        self.compiles = CompileCounter()

    def sized(self, block):
        """A file's parameters; in a rehearsal, with its ``tiny`` block
        laid over them."""
        if self.rehearse and "tiny" in block:
            return {**block, **block["tiny"]}
        return block

    def end_to_end(self):
        """Names of the end-to-end metrics this cell reports."""
        return [m["name"] for m in self.spec["end_to_end"]
                if self.cell["name"] in m.get("workloads",
                                              [self.cell["name"]])]


class CompileCounter:
    """Programs JAX built or loaded from its cache, counted from JAX's
    own monitoring event (one per ``compile_or_get_cached``), so that a
    compile outside the program's own watched sites counts too."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


def over_limit(compared):
    """The problems of a run from ``{name: {value, limit}}``, the numbers
    that decide ``correct``: a number is sound when it is there and not
    over its limit (a NaN is over every limit)."""
    return ["%s %s over its limit %s" % (name, pair["value"], pair["limit"])
            for name, pair in compared.items()
            if pair["value"] is None or not pair["value"] <= pair["limit"]]


def _trace_dir(ctx):
    return os.path.join(OUT, "trace", ctx.cell["name"])


def span(name):
    """A host span in the profiler's own trace, on the device's clock;
    costs next to nothing while no trace is being taken."""
    import jax
    return jax.profiler.TraceAnnotation("bench:" + name)


@contextlib.contextmanager
def profiler_slice(ctx):
    """Trace what runs inside into ``benchmark/out/trace/<cell>``.
    ``reduce_trace`` reads it back once the driver has returned: that is
    seconds of Python, which inside the window held the loop or starved
    the threads that offer load (the open-loop generator ran 19 and
    103 ms late behind it, my chip run, PR 26)."""
    import jax
    trace_dir = _trace_dir(ctx)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # 280k Python frames in 10 steps
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with span("slice"):
            yield
    finally:
        jax.profiler.stop_trace()


def reduce_trace(ctx):
    """The slice ``profiler_slice`` wrote, reduced into ``ctx.trace``;
    nothing where the run took no trace."""
    from . import trace_reduce
    trace_dir = _trace_dir(ctx)
    path = trace_reduce.find_xplane(trace_dir)
    if path is not None:
        ctx.trace = trace_reduce.Trace(trace_reduce.load(path))
        with open(os.path.join(trace_dir, "planes.json"), "w") as f:
            json.dump(trace_reduce.describe(ctx.trace.planes), f, indent=1)


def memory_peak(ctx):
    """Peak bytes on the fullest chip, and its two parts. On this
    runtime ``memory_stats()['peak_bytes_in_use']`` counts the buffers a
    program is handed and returns but not the temporaries it plans for
    itself (PERF.md section 7: a decode step that plans 4.6 GB of them
    left the counter where it was). So the peak is the counter plus the
    largest ``temp_size_in_bytes`` among the programs loaded, which the
    runtime reports per executable and per device: an upper bound when
    the largest program does not run at the moment most buffers live."""
    import jax
    devices = jax.devices()[:ctx.chips]
    live = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    temp = 0
    for exe in devices[0].client.live_executables():
        try:
            temp = max(temp, int(
                exe.get_compiled_memory_stats().temp_size_in_bytes))
        except Exception:       # a runtime that keeps no such record
            continue
    return {"buffers_peak_bytes": live, "largest_program_temp_bytes": temp,
            "memory_peak_bytes": live + temp}


def device_record(ctx):
    import jax
    devices = jax.devices()
    # a driver reads the memory while its programs are still loaded
    memory = ctx.raw.get("memory") or memory_peak(ctx)
    record = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": memory["memory_peak_bytes"]}
    if ctx.trace is not None:
        record["busy_s"] = ctx.trace.busy_s()
        record["window_s"] = ctx.trace.window_s
    return record


def percentile(values, q):
    """The ``q``-th percentile by linear interpolation; None when empty."""
    import numpy as np
    return float(np.percentile(values, q)) if len(values) else None
