"""Forward passes a row spent for each token it gave out, over the
window: the server's ``stats()["block"]`` ``denoise_passes`` +
``commit_passes`` (a pass of one row each) over ``tokens_out``, after
the window less before it. A block of 4 that unmasks one position a
pass and then commits reads 1.25; a rule that unmasks more a pass reads
less, down to 0.5. A program that counts no passes (every other model:
one pass a token) leaves the metric out."""
NAME, UNIT, LAYER = "block_passes_per_token", "ratio", "Decode scheduler"


def compute(ctx):
    delta = ctx.raw.get("block_delta")
    a, b = ctx.raw.get("stats0", {}), ctx.raw.get("stats1", {})
    if not delta or "tokens_out" not in a or "tokens_out" not in b:
        return None
    tokens = b["tokens_out"] - a["tokens_out"]
    if not tokens:
        return None
    return (delta["denoise_passes"] + delta["commit_passes"]) / tokens
