"""Operations and bytes of a decode step whose attention layers are
mostly SLIDING-WINDOW (a ring of the last ``W`` keys and values a row)
with a full-attention layer every few, grouped-query heads whose count
differs by the kind of layer, over routed experts and a shared one —
and of its kernels and of the banded prefill, from shapes and the
program's own counters (``benchmark/latent_moe_costs.py`` holds the
expert layer's and the trace helpers: imported, not copied).

Everything is what the ALGORITHM needs, never what an implementation
happens to move: a live row's ring weighs the keys and values its
position has reached, ``min(p + 1, W)`` of them (the program's own
count, ``ring_bytes`` on ``mx:decode.readback``; the kernel fetches all
``W`` slots, which is the implementation's); a full-attention layer's
cache the tokens that are live (the kernel fetches whole pages); the
experts those some token of the step CHOSE. So a share of a roofline
read from these cannot pass 100%.

The model's sizes come from ``ctx.raw["model"]``, which the driver fills
from the model it built: ``heads`` and ``kinds`` a layer.
"""
from __future__ import annotations

import re

from . import latent_moe_costs as base
from . import program_spans

SLIDING, FULL = "sliding_attention", "full_attention"
COUNTERS = ("ring_rows_wrapped", "global_pages_live", "ring_bytes")
WINDOW = re.compile(r"\.w(\d+)\.")


def layers(model, kind):
    return sum(k == kind for k in model["kinds"])


def heads(model, kind):
    """Query heads of the layers of one kind, summed over them."""
    return sum(h for h, k in zip(model["heads"], model["kinds"])
               if k == kind)


def kv_token_bytes(model, bytes_per_value=2):
    """One cached token of one layer: K and V of every key/value head."""
    return 2 * model["n_kv_heads"] * model["head_dim"] * bytes_per_value


def attention_flops_per_key(model, kind):
    """One query position against one visible key, all layers of a kind:
    every query head scores it over the head size and weights its value;
    a multiply-accumulate is two operations."""
    return 2 * 2 * heads(model, kind) * model["head_dim"]


def ring_keys(model, ring_bytes, bytes_per_value=2):
    """Visible ring keys a row-step, summed over the rows (one layer's
    count: every sliding layer sees the same), from the step's own
    ``ring_bytes``."""
    return ring_bytes / (layers(model, SLIDING)
                         * kv_token_bytes(model, bytes_per_value))


def ring_attn_flops(model, ring_bytes, bytes_per_value=2):
    return ring_keys(model, ring_bytes, bytes_per_value) \
        * attention_flops_per_key(model, SLIDING)


def global_attn_bytes(model, live_tokens, bytes_per_value=2):
    """The full-attention layers' cache of ``live_tokens`` tokens."""
    return layers(model, FULL) * live_tokens \
        * kv_token_bytes(model, bytes_per_value)


def global_attn_flops(model, live_tokens):
    return live_tokens * attention_flops_per_key(model, FULL)


def attention_params(model):
    """Every layer's W_q, W_o, W_k, W_v and per-head gate."""
    d, width = model["d_model"], model["head_dim"]
    return sum(d * (2 * h * width + 2 * model["n_kv_heads"] * width + h)
               for h in model["heads"])


def step_bytes(model, touched_per_step, live_tokens, ring_bytes, weights=2,
               kv=2):
    """Bytes one decode step has to read at the least: every matrix it
    multiplies by once — attention of every layer, the dense layers'
    MLP, each expert layer's shared expert and (float32) router, the
    experts the step's tokens chose (``touched_per_step``, summed over
    the expert layers), the output head — the full-attention layers'
    keys and values of the tokens that are live, and the rings' visible
    keys and values (``ring_bytes``: already bytes, all sliding layers).
    The embedding's rows and the activations are left out."""
    d, moe = model["d_model"], model["n_moe_layers"]
    matrices = (attention_params(model)
                + model["n_dense_layers"] * 3 * d * model["d_ff"]
                + moe * 3 * d * model["d_shared"]
                + d * model["vocab"]) * weights
    router = moe * d * model["n_routed_experts"] * 4
    return (matrices + router
            + touched_per_step * base.expert_bytes(model, weights)
            + global_attn_bytes(model, live_tokens, kv) + ring_bytes)


def banded_visible(q, window):
    """Keys the ``q`` queries of a causal prefill see in all, ``window``
    of them at the most with a query's own (None: no window)."""
    w = q if window is None else min(window, q)
    return w * (w + 1) // 2 + (q - w) * w


def banded_fwd_flops(bh, q, d, window):
    """The two products of the grouped forward at one call's shapes:
    scores and weighted values over the keys a query may see."""
    return 2 * bh * banded_visible(q, window) * 2 * d


def call_window(event_name):
    """The window in a ``mx_grouped_fwd`` call's name (``.w512.``), None
    for a call without one."""
    m = WINDOW.search(event_name)
    return int(m.group(1)) if m else None


def step_counts(ctx):
    """The model's own counters of the traced decode steps this
    configuration adds, one dict a step, from the arguments of the
    ``mx:decode.readback`` spans; nothing where the program has no such
    arguments."""
    spans = program_spans.of(ctx)
    out = []
    for sp in (spans.named("decode.readback") if spans else []):
        try:
            out.append({k: float(sp.stats[k]) for k in COUNTERS})
        except (KeyError, TypeError, ValueError):
            continue
    return out


def per_step(ctx, counter):
    """One of :data:`COUNTERS` a decode step: the mean over the traced
    steps' own counts, or, where the trace holds none, over the
    window's (``stats()["moe"]``); None where the program counts no such
    thing."""
    counts = step_counts(ctx)
    if counts:
        return sum(c[counter] for c in counts) / len(counts)
    delta = ctx.raw.get("moe_delta") or {}
    if delta.get("steps") and delta.get(counter):
        return delta[counter] / delta["steps"]
    return None


def sizes_known(ctx):
    """Whether the run's model is one these costs describe."""
    return "kinds" in (ctx.raw.get("model") or {})
