"""Share of the window's decode steps that carried a chunk of a prompt
on the lanes of the mixed step program: the server's ``chunk_steps``
over ``decode_steps``, ``stats()`` after the window less before it. A
program that does not count it (every prompt a prefill program of its
own) leaves the metric out."""
NAME, UNIT, LAYER = "chunk_step_share", "%", "Decode scheduler"


def compute(ctx):
    a, b = ctx.raw.get("stats0", {}), ctx.raw.get("stats1", {})
    if "chunk_steps" not in a or "chunk_steps" not in b:
        return None
    steps = b["decode_steps"] - a["decode_steps"]
    if not steps:
        return None
    return 100.0 * (b["chunk_steps"] - a["chunk_steps"]) / steps
