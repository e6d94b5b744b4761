"""Share of the held experts that a decode step's tokens chose, mean
over the window's steps and the expert layers: ``stats()["moe"]``'s
``experts_touched`` over steps x expert layers x experts held. What the
grouped matmul has to read scales with it."""
NAME, UNIT, LAYER = "moe_experts_touched_share", "%", "Expert layer"


def compute(ctx):
    delta, model = ctx.raw.get("moe_delta"), ctx.raw.get("model")
    if not delta or not delta.get("steps"):
        return None
    return 100.0 * delta["experts_touched"] / (
        delta["steps"] * model["n_moe_layers"] * model["experts_held"])
