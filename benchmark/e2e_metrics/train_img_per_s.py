"""Images a second through complete optimizer steps: the median, over the
stretches between two ``block_until_ready`` of the loop (every
``sync_every`` steps), of the stretch's images over its seconds. Global
images on several chips. A median of some tens of readings, because the
one-chip machine's host now and then stops the whole process for a second
or more (PERF.md, findings of PR 22)."""
import statistics

NAME, UNIT = "train_img_per_s", "img/s"


def compute(ctx):
    syncs = ctx.raw.get("syncs")
    if not syncs or len(syncs) < 2:
        return None
    per_step = ctx.raw["images"] / ctx.raw["steps"]
    return statistics.median(
        (s1 - s0) * per_step / (t1 - t0)
        for (s0, t0), (s1, t1) in zip(syncs, syncs[1:]) if s1 > s0)
