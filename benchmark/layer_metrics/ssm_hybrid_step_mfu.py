"""Every step program of the traced slice, plain and mixed, against the
roofline of the WHOLE step: the least time of each dispatched step —
the larger of (every matrix once + the live rows' state twice + their
convolution rows + the live keys) over the memory's peak and of the
lanes' matrix products over the MXU's — summed, over the step programs'
device time summed (``benchmark/ssm_hybrid_costs.mfu``). A share of the
whole step's peak: what a ``perf_opt`` on this cell's
``serve_tok_per_s`` reports."""
from benchmark import ssm_hybrid_costs as costs

NAME, UNIT, LAYER = "ssm_hybrid_step_mfu", "%", "Model step"


def compute(ctx):
    return costs.mfu(ctx)
