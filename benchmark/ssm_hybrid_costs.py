"""Operations and bytes of a decode step whose layers are mostly
state-space (a selective scan, its state a fixed ``(N, E)`` float32
array a row and the last rows of a short convolution) with a
full-attention layer a period, over a dense MLP and a head tied to the
embedding — from shapes and the program's own spans, and what the
readers of its kernels share (``benchmark/latent_moe_costs.py`` holds
the trace helpers: imported, not copied).

Everything is what the ALGORITHM needs, never what an implementation
happens to move: a live row's state is read once and written once a
state-space layer with the step's vectors beside it, ``A`` once a layer
whatever the rows; a dead row of the window costs nothing here (the
program streams its state too: that is the implementation's); a row's
convolution reads its ``K - 1`` rows and writes ONE (the program shifts
and writes all of them back); a cached token is one key and one value
of its key/value heads (the kernel fetches whole pages); a chunk's
lanes are its LIVE ones. So a share of a roofline read from these cannot
pass 100%.

The model's sizes come from the configuration's ``model.kwargs`` (the
published keys); what a step carried — its live rows, its chunk's live
lanes — from the program's own ``mx:decode.dispatch`` spans.
"""
from __future__ import annotations

import bisect

from . import latent_moe_costs as base
from . import program_spans

# multiply-adds and the exponential of one (state, channel) of one
# position: delta A, exp, the decay's product, (delta u) B, the sum, h C,
# the read-out's sum
OPS_PER_STATE = 7


def sizes(ctx):
    """The shapes the costs are made of."""
    kw = ctx.config["model"]["kwargs"]
    n, period = kw["num_hidden_layers"], kw["attn_layer_period"]
    attention = sum(i % period == kw["attn_layer_offset"] for i in range(n))
    heads = kw["num_attention_heads"]
    return {"d_model": kw["hidden_size"], "d_ff": kw["intermediate_size"],
            "vocab": kw["vocab_size"], "n_layers": n,
            "attention_layers": attention, "ssm_layers": n - attention,
            "heads": heads, "kv_heads": kw["num_key_value_heads"],
            "head_dim": kw.get("head_dim") or kw["hidden_size"] // heads,
            "d_inner": kw["mamba_expand"] * kw["hidden_size"],
            "d_state": kw["mamba_d_state"], "dt_rank": kw["mamba_dt_rank"],
            "d_conv": kw["mamba_d_conv"]}


def state_row_bytes(s, state=4):
    """One row's recurrent state in one state-space layer: ``h``."""
    return s["d_state"] * s["d_inner"] * state


def ssm_matrix_params(s):
    """One state-space layer's matrices: ``W_in``, ``W_x``, ``W_dt``,
    ``W_out``."""
    D, E, N, R = s["d_model"], s["d_inner"], s["d_state"], s["dt_rank"]
    return D * 2 * E + E * (R + 2 * N) + R * E + E * D


def ssm_vector_params(s):
    """The same layer's float32 arrays: the convolution's taps and bias,
    the three inner norms' gains, ``b_dt``, ``A_log`` and ``D_skip``."""
    E, N, R = s["d_inner"], s["d_state"], s["dt_rank"]
    return s["d_conv"] * E + E + R + 2 * N + E + N * E + E


def attention_params(s):
    width = s["head_dim"]
    return s["d_model"] * width * (2 * s["heads"] + 2 * s["kv_heads"])


def mlp_params(s):
    return 3 * s["d_model"] * s["d_ff"]


def params(s):
    """Every parameter of the model: the embedding counts once (it is
    the head), the norms' gains a layer and the final one."""
    return (s["ssm_layers"] * (ssm_matrix_params(s) + ssm_vector_params(s))
            + s["attention_layers"] * attention_params(s)
            + s["n_layers"] * (mlp_params(s) + 2 * s["d_model"])
            + s["vocab"] * s["d_model"] + s["d_model"])


def kv_token_bytes(s, kv=2):
    """One cached token of one attention layer: K and V."""
    return 2 * s["kv_heads"] * s["head_dim"] * kv


def matrix_bytes(s, weights=2):
    """Every matrix a step multiplies by, once (the head is the
    embedding's own matrix, read whole), and the state-space layers'
    float32 arrays."""
    return (s["ssm_layers"] * ssm_matrix_params(s)
            + s["attention_layers"] * attention_params(s)
            + s["n_layers"] * mlp_params(s)
            + s["vocab"] * s["d_model"]) * weights \
        + s["ssm_layers"] * ssm_vector_params(s) * 4


def ssm_step_bytes(s, rows_live, state=4):
    """Bytes the step kernel has to move at the least, all state-space
    layers: a live row's state read and written, beside it the step's
    vectors of that row — ``delta``, ``u`` in and ``y`` out, ``E``
    float32 values each, ``B`` and ``C``, ``N`` each — and ``A`` once a
    layer."""
    E, N = s["d_inner"], s["d_state"]
    small = (3 * E + 2 * N) * 4
    return s["ssm_layers"] * (
        rows_live * (2 * state_row_bytes(s, state) + small) + N * E * 4)


def ssm_step_ops(s, rows_live):
    """Elementwise operations of the same: :data:`OPS_PER_STATE` a
    (state, channel)."""
    return rows_live * s["ssm_layers"] * OPS_PER_STATE \
        * s["d_state"] * s["d_inner"]


def ssm_chunk_bytes(s, lanes, state=4):
    """Bytes the chunk's scan has to move at the least, all state-space
    layers: the live lanes' ``delta`` and ``u`` in and ``y`` out, their
    ``B`` and ``C``, the request's state read and written, ``A``."""
    E, N = s["d_inner"], s["d_state"]
    return s["ssm_layers"] * (
        lanes * (3 * E + 2 * N) * 4 + 2 * state_row_bytes(s, state)
        + N * E * 4)


def ssm_chunk_ops(s, lanes):
    return lanes * s["ssm_layers"] * OPS_PER_STATE \
        * s["d_state"] * s["d_inner"]


def step_bytes(s, rows_live, live_tokens, per):
    """Bytes one step has to move at the least: every matrix once, the
    live rows' recurrent state TWICE (read and written), their
    convolution rows (``K - 1`` read, one written) and the keys and
    values of the live tokens in the attention layers. The embedding's
    gathered rows and the activations are left out."""
    conv = rows_live * s["ssm_layers"] * s["d_conv"] * s["d_inner"] \
        * per["weights"]
    return (matrix_bytes(s, per["weights"])
            + 2 * rows_live * s["ssm_layers"]
            * state_row_bytes(s, per["state"]) + conv
            + s["attention_layers"] * live_tokens
            * kv_token_bytes(s, per["kv"]))


def step_flops(s, rows_live, chunk_lanes, live_tokens):
    """Matrix-product operations of one step: every lane through every
    layer's matrices (a multiply-accumulate is two), the rows and ONE
    lane of a chunk through the head, and the rows' scores and weighted
    values over their live keys."""
    body = (s["ssm_layers"] * ssm_matrix_params(s)
            + s["attention_layers"] * attention_params(s)
            + s["n_layers"] * mlp_params(s))
    head = s["vocab"] * s["d_model"]
    return 2 * ((rows_live + chunk_lanes) * body
                + (rows_live + (chunk_lanes > 0)) * head) \
        + s["attention_layers"] * live_tokens * 4 * s["heads"] \
        * s["head_dim"]


def least_step_s(s, rows_live, chunk_lanes, live_tokens, per, peak):
    """The least time of one step by the roofline: the larger of its
    bytes at the memory's peak and of its matrix products at the
    MXU's."""
    return max(
        step_bytes(s, rows_live, live_tokens, per)
        / peak["hbm_bytes_per_s"],
        step_flops(s, rows_live, chunk_lanes, live_tokens)
        / peak["flops_per_s"])


def dispatched(ctx):
    """``[(live rows, the chunk's live lanes), ...]``, one a step the
    traced slice dispatched, from the program's own
    ``mx:decode.dispatch`` spans (``state_rows_live``, ``chunk``: 0
    without one); nothing where the program has no such spans."""
    spans = program_spans.of(ctx)
    out = []
    for sp in (spans.named("decode.dispatch") if spans else []):
        try:
            out.append((float(sp.stats["state_rows_live"]),
                        float(sp.stats.get("chunk", 0))))
        except (KeyError, TypeError, ValueError):
            continue
    return out


def widest_rung(ctx):
    """The widest mixed program's lanes: the ladder's largest rung
    within twice its smallest (``DecodeServer``'s rule)."""
    ladder = sorted(ctx.config["server"]["kwargs"]["seq_ladder"])
    return max(r for r in ladder if r <= 2 * ladder[0])


def rung_of(ctx, lanes):
    """The mixed program that carries a chunk of ``lanes`` live lanes:
    the smallest rung that holds them."""
    ladder = sorted(ctx.config["server"]["kwargs"]["seq_ladder"])
    return min(r for r in ladder if r >= lanes)


def step_modules(ctx, rung=None):
    """Device intervals ``(start, end)`` of the step programs in the
    traced slice (device 0): all of them, or the mixed programs of one
    ``rung`` alone — told apart by the chunk kernel's name inside them
    (``mx_ssm_chunk.c<rung>.``), never by a shape."""
    names = ctx.config.get("trace_names", {})
    if ctx.trace is None or not ctx.trace.devices \
            or "step_module" not in names:
        return []
    from . import trace_reduce
    device = ctx.trace.devices[0]
    found = [(s, e) for _, s, e in ctx.trace.events(
        device, trace_reduce.MODULES_LINE, names["step_module"])]
    if rung is None or "ssm_chunk_kernel" not in names:
        return found
    # the rung's kernel calls, read once: a program is the rung's where
    # one of them starts inside it
    calls = sorted(s for _, s, _e in ctx.trace.events(
        device, trace_reduce.OPS_LINE,
        base.kernel_pattern(names["ssm_chunk_kernel"]) + r"c%d\." % rung))
    return [(s, e) for s, e in found
            if bisect.bisect_left(calls, s) < bisect.bisect_left(calls, e)]


def mfu(ctx, widest=False):
    """Least time by the roofline over device time, as a share: the mean
    over the slice's dispatched steps (with ``widest`` those of the
    widest mixed rung alone) of :func:`least_step_s`, over the mean
    device time of the matching step programs. None where the slice
    holds none, the program has no such spans, or the configuration is
    another model's."""
    if "mamba_d_state" not in ctx.config["model"].get("kwargs", {}):
        return None
    rung = widest_rung(ctx) if widest else None
    modules = step_modules(ctx, rung)
    steps = dispatched(ctx)
    if rung is not None:
        steps = [(r, n) for r, n in steps if n and rung_of(ctx, n) == rung]
    if not modules or not steps or ctx.peak is None:
        return None
    live = base.live_tokens_per_step(ctx)
    if live is None:
        return None
    s, per = sizes(ctx), ctx.config["bytes_per_value"]
    least = sum(least_step_s(s, r, n, live, per, ctx.peak)
                for r, n in steps) / len(steps)
    device = sum(e - s0 for s0, e in modules) / len(modules) / 1e9
    return 100.0 * least / device
