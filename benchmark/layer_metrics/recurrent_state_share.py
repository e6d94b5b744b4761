"""Share of the serving state's bytes that is fixed state a row: the
state arrays the server holds for its window (``stats()["state"]
["bytes"]``, whatever the contexts) over those and the bytes of the
pages in use when the window closed (``stats()["kv"]``). Nothing where
the program keeps no such state."""
NAME, UNIT, LAYER = "recurrent_state_share", "%", "KV pool"


def compute(ctx):
    stats = ctx.raw.get("stats1") or {}
    state, kv = stats.get("state"), stats.get("kv")
    if not state or not kv or not state.get("bytes"):
        return None
    pages = kv["used"] * kv["page_size"] * kv["token_bytes"]
    return 100.0 * state["bytes"] / (state["bytes"] + pages)
