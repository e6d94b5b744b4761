"""Operations and bytes of the block-diffusion step and of its paged
block-decode kernel, from shapes and the program's own counters
(``benchmark/latent_moe_costs.py`` does the same for the latent model
and holds what the readers of a step's kernels share: it is imported,
not copied).

Everything is what the ALGORITHM needs, never what an implementation
happens to move: the experts' bytes are those of the experts some
position of the step CHOSE (the program's own count,
``experts_touched``), never all that are held; the cached keys and
values are those of the tokens COMMITTED in front of each row's block
(the step's own count, ``keys_live`` on ``mx:decode.dispatch``), never
the whole pages the kernel fetches. So a share of a roofline read from
these cannot pass 100%.

The model's sizes come from ``ctx.raw["model"]``, which the driver fills
from the model it built.
"""
from __future__ import annotations

from . import program_spans
from .latent_moe_costs import expert_bytes


def kv_token_bytes(model, bytes_per_value=2):
    """One cached token of one layer: K and V of every key/value head."""
    return 2 * model["n_kv_heads"] * model["head_dim"] * bytes_per_value


def kv_token_flops(model):
    """Block-decode attention of one cached token in one layer: every
    query position of the block and every query head scores it over the
    head size and weights its value; a multiply-accumulate is two
    operations."""
    return 2 * 2 * model["block_length"] * model["n_heads"] \
        * model["head_dim"]


def attention_params(model):
    d, width = model["d_model"], model["head_dim"]
    return d * width * (2 * model["n_heads"] + 2 * model["n_kv_heads"])


def step_bytes(model, touched_per_step, keys_live, weights=2, kv=2):
    """Bytes one block step has to read at the least: every matrix it
    multiplies by once — attention of every layer, each layer's
    (float32) router, the experts the step's positions chose
    (``touched_per_step``, summed over the layers), the output head —
    and the keys and values committed in front of the rows' blocks, in
    every layer. The embedding's rows and the activations are left
    out."""
    layers, d = model["n_layers"], model["d_model"]
    return ((layers * attention_params(model) + d * model["vocab"])
            * weights
            + layers * d * model["n_routed_experts"] * 4
            + touched_per_step * expert_bytes(model, weights)
            + layers * keys_live * kv_token_bytes(model, kv))


def keys_live_per_step(ctx):
    """Mean cached tokens a traced block step attends to, summed over
    its rows: the step's own count on ``mx:decode.dispatch``; nothing
    where the program's span has no such argument."""
    spans = program_spans.of(ctx)
    live = []
    for sp in (spans.named("decode.dispatch") if spans else []):
        try:
            live.append(float(sp.stats["keys_live"]))
        except (KeyError, TypeError, ValueError):
            continue
    return sum(live) / len(live) if live else None
