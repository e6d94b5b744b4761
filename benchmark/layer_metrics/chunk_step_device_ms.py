"""Median device duration of the MIXED step program in the traced slice
(device 0): a decode step that carries a chunk of a prompt. The mixed
program is the configuration's step program with ``_chunk`` behind its
name (``jit__decode_fn`` -> ``jit__decode_fn_chunk``), so
``decode_step_device_ms`` counts both and this one the second alone.
Nothing where no such program ran in the slice."""
import statistics

NAME, UNIT, LAYER = "chunk_step_device_ms", "ms", "Decode scheduler"

# behind the step program's own name, in front of the fingerprint
SUFFIX = r"_chunk\b"


def durations_s(ctx):
    step = ctx.config.get("trace_names", {}).get("step_module")
    if ctx.trace is None or not ctx.trace.devices or step is None:
        return []
    return ctx.trace.module_durations_s(step + SUFFIX)


def compute(ctx):
    d = durations_s(ctx)
    return 1e3 * statistics.median(d) if d else None
