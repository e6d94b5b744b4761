"""Operations and bytes of the speculative step of a latent-attention,
routed-expert model with several residual streams and a next-token
module, and of its two-query paged latent kernel, from shapes and the
program's own counters (``benchmark/latent_moe_costs.py`` does the same
for the one-token step and holds what the readers of a step's kernels
share: it is imported, not copied).

Everything is what the ALGORITHM needs, never what an implementation
happens to move: the experts' bytes are those of the experts some
position of the step CHOSE (the program's own count,
``experts_touched``, the module's expert layer among them), never all
that are held; the head is counted ONCE though the step multiplies by
it twice (the main model's logits and the module's: one read could
serve both); the cached latent is that of the keys the host KNOWS to be
in front of each row (``keys_live`` on ``mx:decode.dispatch``, a lower
bound: a row whose position the program decides may be one further on),
576 values a key, never "a token a step" and never the 640 the pool
stores. So a share of a roofline read from these cannot pass 100%.

The model's sizes come from ``ctx.raw["model"]``, which the driver fills
from the model it built.
"""
from __future__ import annotations

from .block_diffusion_costs import keys_live_per_step       # noqa: F401
from .latent_moe_costs import (attention_params, expert_bytes,
                               latent_token_bytes)


def blocks(model):
    """Blocks a step runs: the main model's and the module's."""
    return model["n_layers"] + model["n_draft_layers"]


def mixing_params(model):
    """One sublayer's mixing matrix: ``n C`` rows, ``2 n + n n``
    columns (float32)."""
    n = model["streams"]
    return n * model["d_model"] * (2 * n + n * n) if n > 1 else 0


def verify_key_flops(model):
    """The verify kernel's operations for one cached key in one layer:
    every query position's every head scores it over the latent's width
    and weights its compressed part; a multiply-accumulate is two."""
    return model["verify_positions"] * 2 * model["n_heads"] * (
        model["kv_rank"] + model["rope"] + model["kv_rank"])


def verify_least_s(model, keys_live, peak, kv=2):
    """The least time the two-query latent kernel needs a step, all
    layers: the larger of reading the live keys' latent and computing
    on it (at 2 positions of 32 heads 1.41 ns of bytes against 0.71 ns
    of operations a key a layer on a v5e: memory binds)."""
    keys = keys_live * blocks(model)
    return max(keys * latent_token_bytes(model, kv) / peak["hbm_bytes_per_s"],
               keys * verify_key_flops(model) / peak["flops_per_s"])


def step_bytes(model, touched_per_step, keys_live, weights=2, kv=2):
    """Bytes one speculative step has to read at the least: every matrix
    it multiplies by, once — attention of every block, the dense layers'
    MLP, each expert layer's shared expert and (float32) router, both
    sublayers' (float32) mixing matrices of every block, the module's
    projection, the experts the step's positions chose
    (``touched_per_step``, summed over the expert layers), the output
    head ONCE — and the latent of the keys live in front of its rows, in
    every block's cache layer. The embedding's rows and the activations
    are left out."""
    d = model["d_model"]
    moe = model["n_moe_layers"]
    matrices = (
        blocks(model) * attention_params(model)
        + model["n_dense_layers"] * 3 * d * model["d_ff"]
        + moe * model["n_shared"] * 3 * d * model["d_expert"]
        + model["n_draft_layers"] * 2 * d * d
        + d * model["vocab"]) * weights
    float32 = (moe * d * model["n_routed_experts"]
               + 2 * blocks(model) * mixing_params(model)) * 4
    return (matrices + float32
            + touched_per_step * expert_bytes(model, weights)
            + blocks(model) * keys_live * latent_token_bytes(model, kv))
