"""Forward passes a row spent for each token it gave out, over the
window: the server's ``stats()["block"]`` ``denoise_passes`` +
``commit_passes`` (a pass of one row each) over ``tokens_out``, after
the window less before it. A block of 4 that unmasks one position a
pass reads 1.0 since PR 36 (four passes for four tokens: the commit
rides with the next block's first denoising pass and is no pass of its
own; 1.0014 on the cell, ledger PR 40, with the pass a row spends before
it is seen to have ended; 1.25 until then); a rule that unmasks more a
pass reads less, down to 0.25. A program that counts no passes (every
other model: one pass a token) leaves the metric out."""
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
