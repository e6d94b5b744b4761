"""Device time of the chunkwise delta rule inside one prefill (the
operations of the ``mx_kda_chunk`` scope, every linear-attention layer:
a profile's events carry no scope, so they are found by the shapes they
make, ``trace_names.kda_chunk_ops``) per prefill program of the traced
slice. What a Pallas chunk kernel would have to beat."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "kda_prefill_ms", "ms", "Kernels"


def compute(ctx):
    s = costs.patterns_s_per_step(ctx, "kda_chunk_ops", "prefill_module")
    return None if s is None else 1e3 * s
