"""Operations and bytes of the latent-attention / routed-expert step and
of its kernels, from shapes and the program's own counters, and what the
readers of those kernels share (``benchmark/kernel_costs.py`` does the
same for the per-head attention kernels).

Everything is what the ALGORITHM needs, never what an implementation
happens to move: the experts' bytes are those of the experts some token
of the step CHOSE (the program's own count, ``experts_touched``), never
all that are held; a cached token is its 576 values (the pool stores a
row 640 wide, which is the implementation's). So a share of a roofline
read from these cannot pass 100%.

Names of kernels and programs, and the patterns of operations that
are no kernel, come from the configuration's
``trace_names``; the model's sizes from ``ctx.raw["model"]``, which the
driver fills from the model it built.
"""
from __future__ import annotations

import re

from . import program_spans, trace_reduce

def kernel_pattern(kernel):
    """Operation events of one named Pallas kernel (any prefix of word
    characters that a transformation puts in front)."""
    return r"^%%\w*?mx_%s\." % re.escape(kernel)


def expert_bytes(model, bytes_per_value=2):
    """One routed expert's three matrices."""
    return 3 * model["d_model"] * model["d_expert"] * bytes_per_value


def latent_token_bytes(model, bytes_per_value=2):
    """One cached token of one layer: ``[c_kv, k_r]``."""
    return (model["kv_rank"] + model["rope"]) * bytes_per_value


def latent_token_flops(model):
    """Absorbed decode attention of one cached token in one layer: every
    head's score over the latent's width and its weighted sum over the
    compressed part; a multiply-accumulate is two operations."""
    return 2 * model["n_heads"] * (
        model["kv_rank"] + model["rope"] + model["kv_rank"])


def attention_params(model):
    d, h = model["d_model"], model["n_heads"]
    return (d * model["q_rank"]
            + model["q_rank"] * h * (model["nope"] + model["rope"])
            + d * (model["kv_rank"] + model["rope"])
            + model["kv_rank"] * h * (model["nope"] + model["v_dim"])
            + h * model["v_dim"] * d)


def step_bytes(model, touched_per_step, live_tokens, weights=2, kv=2):
    """Bytes one decode step has to read at the least: every matrix it
    multiplies by once — attention of every layer, the dense layers'
    MLP, each expert layer's shared expert and (float32) router, the
    experts the step's tokens chose (``touched_per_step``, summed over
    the expert layers), the output head — and the latent of the tokens
    that are live in the batch, in every layer. The embedding's rows
    and the activations are left out."""
    d = model["d_model"]
    moe = model["n_moe_layers"]
    matrices = (
        model["n_layers"] * attention_params(model)
        + model["n_dense_layers"] * 3 * d * model["d_ff"]
        + moe * model["n_shared"] * 3 * d * model["d_expert"]
        + d * model["vocab"]) * weights
    router = moe * d * model["n_routed_experts"] * 4
    return (matrices + router
            + touched_per_step * expert_bytes(model, weights)
            + model["n_layers"] * live_tokens
            * latent_token_bytes(model, kv))


def live_tokens_per_step(ctx):
    """Mean cached tokens a decode step attends to, over the window: a
    stream's i-th token (i >= 1) came from a step that attended to its
    prompt and the i tokens before it."""
    a, b = ctx.raw["stats0"], ctx.raw["stats1"]
    steps = b["decode_steps"] - a["decode_steps"]
    if not steps:
        return None
    w = ctx.raw["window_s"]
    live = sum(s["prompt_len"] + i for s in ctx.raw["streams"]
               for i, t in enumerate(s["times"]) if i and 0.0 <= t < w)
    return live / steps


def step_counts(ctx):
    """The model's own counters of the traced decode steps, one dict a
    step (``moe_slots``, ``experts_touched``, ``max_load``), from the
    arguments of the ``mx:decode.readback`` spans; nothing where the
    program has no such arguments."""
    spans = program_spans.of(ctx)
    out = []
    for sp in (spans.named("decode.readback") if spans else []):
        try:
            out.append({k: float(sp.stats[k]) for k in (
                "moe_slots", "experts_touched", "max_load")})
        except (KeyError, TypeError, ValueError):
            continue
    return out


def touched_per_step(ctx):
    """Experts touched a decode step, summed over the expert layers: the
    mean over the traced steps' own counts, or, where the trace holds
    none, over the window's (``stats()["moe"]``)."""
    counts = step_counts(ctx)
    if counts:
        return sum(c["experts_touched"] for c in counts) / len(counts)
    delta = ctx.raw.get("moe_delta") or {}
    if delta.get("steps"):
        return delta["experts_touched"] / delta["steps"]
    return None


def _modules(ctx, key):
    names = ctx.config.get("trace_names", {})
    if ctx.trace is None or not ctx.trace.devices or key not in names:
        return []
    return ctx.trace.events(ctx.trace.devices[0],
                            trace_reduce.MODULES_LINE, names[key])


def ops_in_modules(ctx, op_pattern, module_key):
    """``(seconds in the matching operations that ran inside the named
    program, executions of that program)`` on device 0 of the slice."""
    modules = _modules(ctx, module_key)
    if not modules:
        return 0.0, 0
    return ctx.trace.op_s(op_pattern, inside=[
        (s, e) for _, s, e in modules]), len(modules)


def kernel_s_per_step(ctx, kernel_key, module_key="step_module"):
    """Device seconds in one named kernel per execution of a program."""
    names = ctx.config.get("trace_names", {})
    if kernel_key not in names:
        return None
    seconds, n = ops_in_modules(
        ctx, kernel_pattern(names[kernel_key]), module_key)
    return seconds / n if n and seconds else None


def patterns_s_per_step(ctx, patterns_key, module_key="step_module"):
    """Device seconds per execution of a program in the operations whose
    event name (the HLO line) matches one of the configuration's
    ``trace_names[patterns_key]``. A profile's events carry no
    ``op_name``, so a ``jax.named_scope`` cannot be read back from them
    (my chip run, PR 27): operations that are no named kernel are found
    by the shapes they make, which the configuration lists."""
    patterns = ctx.config.get("trace_names", {}).get(patterns_key)
    if not patterns:
        return None
    seconds, n = ops_in_modules(
        ctx, "|".join("(?:%s)" % p for p in patterns), module_key)
    return seconds / n if n and seconds else None
