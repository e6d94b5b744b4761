"""Most pages of the KV pool ever in use over the pages it has
(``stats()['kv']``; the peak counts from the server's start)."""
NAME, UNIT, LAYER = "kv_pages_peak_share", "%", "KV pool"


def compute(ctx):
    kv = ctx.raw.get("stats1", {}).get("kv")
    if not kv or not kv.get("pages"):
        return None
    return 100.0 * kv["peak_used"] / kv["pages"]
