"""``ssm_hybrid_step_mfu`` over the WIDEST mixed rung's steps alone —
the steps whose device time sets the tail of the gap between tokens:
the least time by the roofline of the dispatched steps whose chunk takes
the widest mixed program, over that program's device time (found by the
chunk kernel's name inside it). A share of the whole step's peak: what a
``perf_opt`` on this cell's ``itl_p99_ms`` reports."""
from benchmark import ssm_hybrid_costs as costs

NAME, UNIT, LAYER = "ssm_hybrid_mixed_step_mfu", "%", "Model step"


def compute(ctx):
    return costs.mfu(ctx, widest=True)
