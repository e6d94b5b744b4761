"""Operations and bytes of ONE chip's part of a decode step that a mesh
shares — a chip's key/value head with its query heads, its experts, its
columns of the head — and what the step's all-reduces carry, from shapes
and the program's own counters. ``benchmark/window_moe_costs.py`` holds
the attention layers' pieces and ``latent_moe_costs.py`` the expert's
and the trace helpers: imported, not copied.

``ctx.raw["model"]`` holds ONE chip's sizes
(``drivers/serve_sharded_window_moe.chip_sizes``), the counters on
``mx:decode.readback`` are chip 0's, and every time is device 0's: one
chip's work against one chip's time. What the ALGORITHM needs, never
what an implementation moves: the experts a token of the step chose, the
keys and values the live rows' positions have reached.
"""
from __future__ import annotations

import re

from . import latent_moe_costs as base
from . import trace_reduce
from . import window_moe_costs as window


def attention_params(model):
    """A chip's W_q, W_o, W_k, W_v of every layer (and the per-head gate
    where the block has one)."""
    d, width = model["d_model"], model["head_dim"]
    gate = 1 if model.get("gated") else 0
    return sum(d * (2 * h * width + 2 * model["n_kv_heads"] * width
                    + gate * h) for h in model["heads"])


def matrix_params(model):
    """What a chip multiplies every lane by whatever the routing:
    attention, a dense layer's MLP and a shared expert (every chip
    computes those alike), its columns of the head."""
    d, moe = model["d_model"], model["n_moe_layers"]
    return (attention_params(model)
            + model["n_dense_layers"] * 3 * d * model["d_ff"]
            + moe * 3 * d * model["d_shared"] + d * model["vocab"])


def step_bytes(model, touched_per_step, live_tokens, ring_bytes, weights=2,
               kv=2):
    """Bytes ONE chip has to read in a plain step at the least: every
    matrix of its part once, the (float32) router whole, the experts IT
    holds that a token chose (``touched_per_step``: chip 0's count,
    summed over the layers), its key/value head's cached tokens of the
    full-attention layers and its rings' visible keys and values
    (``ring_bytes``: chip 0's count, already bytes)."""
    router = model["n_moe_layers"] * model["d_model"] \
        * model["n_routed_experts"] * 4
    return (matrix_params(model) * weights + router
            + touched_per_step * base.expert_bytes(model, weights)
            + window.global_attn_bytes(model, live_tokens, kv) + ring_bytes)


def step_flops(model, rows, slots_per_step, live_tokens, ring_bytes, kv=2):
    """Operations ONE chip has to run in a plain step of ``rows`` rows:
    every row through its part of the matrices, the router, the slots
    its experts were handed (``slots_per_step``: chip 0's count), and
    its query heads over the keys they may see."""
    d = model["d_model"]
    dense = 2 * rows * (matrix_params(model)
                        + model["n_moe_layers"] * d
                        * model["n_routed_experts"])
    experts = 2 * slots_per_step * 3 * d * model["d_expert"]
    return dense + experts \
        + window.global_attn_flops(model, live_tokens) \
        + window.ring_attn_flops(model, ring_bytes, kv)


def exchange_bytes(model, lanes):
    """What one chip hands the all-reduces of a step of ``lanes`` lanes:
    a float32 ``(lanes, hidden)`` array behind every attention layer and
    every expert layer (the program says the same on
    ``mx:decode.dispatch``: ``exchange_bytes``)."""
    return (model["n_layers"] + model["n_moe_layers"]) * lanes \
        * model["d_model"] * 4


# -- the trace: plain steps, collectives, the chips' busy time -------------

def plain_step(ctx):
    """The pattern of the PLAIN step program's events (``jit_<step>(<n>)``,
    never the mixed step's ``jit_<step>_chunk(<n>)``); None where the
    configuration names no step program."""
    step = ctx.config.get("trace_names", {}).get("step_module")
    return None if step is None else re.escape(step) + r"\("


def plain_steps(ctx, device=None):
    """``[(start, end), ...]`` of the plain step program on one device of
    the slice (device 0)."""
    pattern = plain_step(ctx)
    if ctx.trace is None or not ctx.trace.devices or pattern is None:
        return []
    return [(s, e) for _n, s, e in ctx.trace.events(
        device or ctx.trace.devices[0], trace_reduce.MODULES_LINE, pattern)]


# A collective by the KIND of its HLO line (``%name = shape kind(operands``):
# under ``shard_map`` a ``psum`` is the instruction ``%psum.392 = f32[64,
# 2304]{…} all-reduce(%fusion.488)`` — named for the primitive, so
# ``trace_reduce.COLLECTIVE``, which looks at the name, does not see it (my
# chip run, PR 48: it matched the arg-max's ``%all-gather`` alone)
COLLECTIVE_KIND = re.compile(
    r"^%\S+ = .*? (all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?\(")


def is_collective(event_name):
    return bool(trace_reduce.COLLECTIVE.search(event_name)
                or COLLECTIVE_KIND.search(event_name))


def collective_intervals(trace, device):
    """Disjoint intervals in which a collective operation was under way
    on ``device``: the synchronous ones and the ``-start`` / ``-done``
    ends on the operations line, the span between those ends on the
    asynchronous line."""
    return trace_reduce.union(
        (s, e) for n, s, e in trace.events(device, trace_reduce.OPS_LINE)
        + trace.events(device, trace_reduce.ASYNC_LINE)
        if is_collective(n))


def compute_intervals(trace, device):
    """Disjoint intervals in which an operation that is no collective
    ran on ``device``."""
    return trace_reduce.union(
        (s, e) for n, s, e in trace.events(device, trace_reduce.OPS_LINE)
        if not is_collective(n))


def exposed_collective_s(trace, device):
    """``Trace.exposed_collective_s`` with collectives found by kind:
    seconds in which a collective was under way on ``device`` and no
    other operation ran."""
    return trace_reduce.total(trace_reduce.subtract(
        collective_intervals(trace, device),
        compute_intervals(trace, device))) / 1e9


def inside(intervals, cover):
    """Nanoseconds of ``intervals`` (disjoint, sorted) that lie inside
    ``cover``."""
    cover = trace_reduce.union(cover)
    return trace_reduce.total(intervals) - trace_reduce.total(
        trace_reduce.subtract(intervals, cover))


def compute_per_step_s(ctx, device):
    """Seconds ``device`` ran an operation that is no collective inside
    its own executions of the step programs (plain and mixed), a step;
    None without any. (A chip that waits in an all-reduce is busy in it:
    whole busy time is the same on every chip by construction.)"""
    step = ctx.config.get("trace_names", {}).get("step_module")
    if ctx.trace is None or step is None:
        return None
    steps = [(s, e) for _n, s, e in ctx.trace.events(
        device, trace_reduce.MODULES_LINE, step)]
    if not steps:
        return None
    return inside(compute_intervals(ctx.trace, device), steps) / 1e9 \
        / len(steps)


def slots_per_step(ctx):
    """Slots chip 0's experts were handed a step, summed over the
    layers: the traced steps' own counts, else the window's."""
    counts = base.step_counts(ctx)
    if counts:
        return sum(c["moe_slots"] for c in counts) / len(counts)
    delta = ctx.raw.get("moe_delta") or {}
    if delta.get("steps"):
        return delta["moe_slots"] / delta["steps"]
    return None


def sharded(ctx):
    """Whether the run's model is one a mesh shares (the driver says how
    many chips in ``raw.model.chips``)."""
    return (ctx.raw.get("model") or {}).get("chips", 1) > 1
