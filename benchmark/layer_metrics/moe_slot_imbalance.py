"""The busiest held expert's tokens over the mean tokens an expert, per
decode step, mean over the traced steps: ``max_load`` over ``moe_slots /
(expert layers x experts held)``, the program's own counters on
``mx:decode.readback``. 1 is an even spread; the grouped matmul's row
tiles are filled by it."""
from benchmark import latent_moe_costs as costs

NAME, UNIT, LAYER = "moe_slot_imbalance", "ratio", "Expert layer"


def compute(ctx):
    model = ctx.raw.get("model")
    ratios = [c["max_load"] * model["n_moe_layers"] * model["experts_held"]
              / c["moe_slots"]
              for c in costs.step_counts(ctx) if c["moe_slots"]]
    return sum(ratios) / len(ratios) if ratios else None
