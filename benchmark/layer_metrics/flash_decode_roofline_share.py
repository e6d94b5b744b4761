"""The paged decode-attention kernel against the memory roofline, over
the steps of the traced slice that it times and no others.

``benchmark/launch_join.py`` joins each step program of the slice to the
``mx:decode.dispatch`` span that launched it, and that span carries the
step's own ``pages_live``: the pages of the pool that hold the keys its
decoding rows attend, ``sum(position // page_size + 1)`` over them. For
every PLAIN step joined that ran whole inside the slice (a mixed step's
count also holds its chunk's pages, which the composed chunk attention
walks and this kernel does not: left out, kernel time and pages alike)
the bytes reckoned are

    pages_live x page_size x 2 (K, V) x n_layers x d_model x kv bytes

(``kernel_costs.paged_decode_bytes``: on ``opt-6.7b.json`` 128 x 2 x 8 x
4096 x 4 B = 33.55 MB a live page), at the chip's HBM bandwidth, over
the device time inside the ``mx_flash_decode`` calls that ran inside
those same programs. The kernel reads a live page whole, one query a
row, so it is bound by memory bandwidth and the share cannot pass 100%:
what is over the bytes is its grid step a table column, live or dead
(1.2-1.6 us each, PERF.md section 6, PR 24). A window-wide count over a
slice's kernel time can pass it (104.75% once on the open loop, whose
slice's rows are not the window's: ledger, PR 38). ``raw["flash_decode"]``
keeps the steps, pages, bytes a step and kernel seconds a step counted."""
from benchmark import kernel_costs, launch_join

NAME, UNIT, LAYER = "flash_decode_roofline_share", "%", "Kernels"


def compute(ctx):
    joined = launch_join.of(ctx)
    if joined is None or ctx.peak is None:
        return None
    lo, hi = ctx.trace.window
    steps = [p for p in joined.programs
             if p.kind == "step" and p.launch is not None
             and "pages_live" in p.launch.stats
             and "chunk" not in p.launch.stats
             and lo <= p.start + p.lead and p.end + p.lead <= hi]
    kernel_s = ctx.trace.op_s(kernel_costs.pattern("flash_decode"),
                              inside=[(p.start, p.end) for p in steps])
    if not kernel_s:
        return None
    pages = sum(int(p.launch.stats["pages_live"]) for p in steps)
    m = ctx.raw["model"]
    least = kernel_costs.paged_decode_bytes(
        m["n_layers"], m["d_model"], pages,
        ctx.config["server"]["kwargs"]["page_size"],
        ctx.config["bytes_per_value"]["kv"])
    ctx.raw["flash_decode"] = {
        "steps": len(steps), "pages_live": pages,
        "bytes_per_step": least / len(steps),
        "kernel_s_per_step": kernel_s / len(steps)}
    return 100.0 * least / ctx.peak["hbm_bytes_per_s"] / kernel_s
