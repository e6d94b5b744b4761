"""Share of the window's verified drafts that were accepted: the
server's ``stats()["spec"]`` ``drafts_accepted`` over
``drafts_verified``, after the window less before it. A step hands out
``1 + this`` tokens a row, so tokens a second scale with it at the same
step time. Seeded random weights read 0 (a draft is right about once in
a vocabulary); a program that verifies no drafts leaves the metric
out."""
NAME, UNIT, LAYER = "mtp_accept_share", "%", "Model step"


def compute(ctx):
    delta = ctx.raw.get("spec_delta")
    if not delta or not delta.get("drafts_verified"):
        return None
    return 100.0 * delta["drafts_accepted"] / delta["drafts_verified"]
