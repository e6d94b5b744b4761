"""Driver for serving cells whose model keeps fixed state a row beside
its pages (linear-attention layers with a latent-attention layer a
group, over routed experts): ``serve_latent_moe``'s run — its load, its
clock, its records and its check, the same objects — with weights of its
own, because that driver fills every vector with 1 or 0 and the gate of
a linear-attention layer needs two vectors DRAWN: at ``A_log = 0`` and
``dt_bias = 0`` a step's decay would be ``exp(-5 sigmoid(N(0, 1)))``, a
state that forgets in two tokens and hides a wrong slot or a lost state
from ``correct``. The configuration's ``weights.vectors`` names them,
``{suffix: [low, high]}``, uniform from the seed; its reference is
``benchmark/reference/hybrid_linear_moe_lm.py``.
"""
from __future__ import annotations

from . import serve_latent_moe as base

_make_matrices = base.make_params


def make_params(model, spec, seed):
    """``serve_latent_moe.make_params``' weights, then every vector
    whose name ends in a key of ``spec["vectors"]`` drawn uniformly in
    that key's ``[low, high]``, on the device, from the seed."""
    import jax
    import jax.numpy as jnp
    params = _make_matrices(model, spec, seed)
    drawn = sorted(name for name in params
                   if any(name.endswith("." + s) for s in spec["vectors"]))

    def make(key):
        out = {}
        for i, name in enumerate(drawn):
            low, high = spec["vectors"][name.rsplit(".", 1)[1]]
            out[name] = jax.random.uniform(
                jax.random.fold_in(key, i), params[name].shape,
                jnp.float32, low, high)
        return out

    params.update(jax.jit(make)(
        jax.random.fold_in(jax.random.PRNGKey(seed), 1 << 20)))
    return params


def run(ctx):
    """``serve_latent_moe.run`` with this module's weights in its
    place."""
    base.make_params = make_params
    try:
        return base.run(ctx)
    finally:
        base.make_params = _make_matrices
