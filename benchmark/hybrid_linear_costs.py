"""Operations and bytes of a decode step whose layers are mostly linear
attention (a gated delta rule, its state a fixed array a row) with a
latent-attention layer a group, over routed experts — from shapes and
the program's own counters, and what the readers of its kernels share
(``benchmark/latent_moe_costs.py`` holds the expert layer's, the latent
kernel's and the trace helpers).

Everything is what the ALGORITHM needs, never what an implementation
happens to move: a live row's state is read once and written once a
linear layer (``2 * H * d * d`` float32 values) with the step's vectors
beside it; a dead row of the window costs nothing here (the program
streams its state too: that is the implementation's). So a share of a
roofline read from these cannot pass 100%.

The model's sizes come from the configuration's ``model.kwargs`` (the
published keys) and ``ctx.raw["model"]``, which the driver fills.
"""
from __future__ import annotations

from . import latent_moe_costs as base
from . import program_spans


def sizes(ctx):
    """The shapes the costs are made of."""
    kw = ctx.config["model"]["kwargs"]
    group = kw["layer_group_size"]
    n = kw["num_hidden_layers"]
    return {"d_model": kw["hidden_size"], "heads": kw["num_attention_heads"],
            "d": kw["head_dim"], "latent_layers": n // group,
            "linear_layers": n - n // group,
            "conv_rows": kw.get("short_conv_kernel_size", 4) - 1}


def state_row_bytes(s, state=4):
    """One row's recurrent state in one linear layer: ``S``."""
    return s["heads"] * s["d"] * s["d"] * state


def kda_step_bytes(s, rows_live, state=4):
    """Bytes the step kernel has to move at the least, all linear
    layers: a live row's state read and written, and beside it the
    step's vectors of that row — ``alpha``, ``k``, ``beta k``, ``q`` and
    ``v`` in, ``o`` out, ``H d`` float32 values each."""
    small = 6 * s["heads"] * s["d"] * 4
    return rows_live * s["linear_layers"] * (
        2 * state_row_bytes(s, state) + small)


def kda_step_flops(s, rows_live):
    """Operations of the same: the decay (``d d``), ``k^T S``, the rank-1
    correction and ``S^T q`` (``2 d d`` each) a head."""
    return rows_live * s["linear_layers"] * s["heads"] \
        * 7 * s["d"] * s["d"]


def linear_attention_params(s):
    """One linear layer's matrices: ``wqkv``, ``wf``, ``wo`` and the two
    head-wise projections."""
    width = s["heads"] * s["d"]
    return s["d_model"] * (3 * width + width + 2 * s["heads"]) \
        + width * s["d_model"]


def latent_attention_params(model, s):
    """One latent layer's: no query rank, and the head-wise gate."""
    d, h = s["d_model"], s["heads"]
    return (d * h * (model["nope"] + model["rope"])
            + d * (model["kv_rank"] + model["rope"])
            + model["kv_rank"] * h * (model["nope"] + model["v_dim"])
            + h * model["v_dim"] * d + d * h)


def rows_live_per_step(ctx):
    """Live rows a decode step: the mean over the traced steps' own
    ``state_rows_live`` (the ``mx:decode.readback`` spans), or, where the
    trace holds none, the window's tokens from decode steps over its
    steps (a live row takes one token a step)."""
    spans = program_spans.of(ctx)
    rows = []
    for sp in (spans.named("decode.readback") if spans else []):
        try:
            rows.append(float(sp.stats["state_rows_live"]))
        except (KeyError, TypeError, ValueError):
            continue
    if rows:
        return sum(rows) / len(rows)
    delta = ctx.raw.get("stats_delta") or {}
    if delta.get("decode_steps"):
        return (delta["tokens_out"] - delta["prefill_steps"]) \
            / delta["decode_steps"]
    return None


def step_bytes(ctx, touched_per_step, live_tokens, rows_live):
    """Bytes one decode step has to move at the least: every matrix it
    multiplies by once — the linear and the latent layers' attention,
    the dense layers' MLP, each expert layer's shared expert and
    (float32) router, the experts the step's tokens chose, the head —
    the live rows' recurrent state TWICE (read and written) and their
    convolution rows twice, and the latent of the live tokens in the
    latent layers. The embedding's rows and the activations are left
    out."""
    model, s = ctx.raw["model"], sizes(ctx)
    per = ctx.config["bytes_per_value"]
    d, moe = s["d_model"], model["n_moe_layers"]
    matrices = (
        s["linear_layers"] * linear_attention_params(s)
        + s["latent_layers"] * latent_attention_params(model, s)
        + model["n_dense_layers"] * 3 * d * model["d_ff"]
        + moe * model["n_shared"] * 3 * d * model["d_expert"]
        + d * model["vocab"]) * per["weights"]
    router = moe * d * model["n_routed_experts"] * 4
    conv = 2 * rows_live * s["linear_layers"] * s["conv_rows"] \
        * 3 * s["heads"] * s["d"] * per["weights"]
    return (matrices + router
            + touched_per_step * base.expert_bytes(model, per["weights"])
            + 2 * rows_live * s["linear_layers"]
            * state_row_bytes(s, per["state"]) + conv
            + s["latent_layers"] * live_tokens
            * base.latent_token_bytes(model, per["kv"]))
