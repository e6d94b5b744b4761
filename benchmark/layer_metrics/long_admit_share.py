"""Share of the window's admissions that are LONG (a prompt past the
shortest rung): of the streams sent inside the window, those whose
prompt needs the longest rung's prefill. A long admission stalls every
stream for its prefill, so a run that admits more of them has a wider
token-gap tail and fewer tokens: this explains a spread between runs.
Nothing where the ladder has one rung."""
NAME, UNIT, LAYER = "long_admit_share", "%", "Decode scheduler"


def compute(ctx):
    ladder = sorted(ctx.config["server"]["kwargs"].get("seq_ladder", []))
    if len(ladder) < 2:
        return None
    w = ctx.raw.get("window_s")
    sent = [s for s in ctx.raw.get("streams", [])
            if w is not None and 0.0 <= s["sent"] < w]
    if not sent:
        return None
    return 100.0 * sum(s["prompt_len"] > ladder[0] for s in sent) \
        / len(sent)
