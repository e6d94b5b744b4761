"""Median device duration of the prefill programs of the LONGEST rung
(``raw["model"]["long_rung"]``: the programs joined to ``program=
"prefill"`` launches whose ``rung`` is that, ``benchmark/launch_join
.py``): what a token gap that holds a long admission is made of, beside
one step. Nothing where the slice holds no such prefill."""
import statistics

from benchmark import launch_join

NAME, UNIT, LAYER = "long_prefill_device_ms", "ms", "Model step"


def compute(ctx):
    rung = (ctx.raw.get("model") or {}).get("long_rung")
    joined = launch_join.of(ctx)
    if rung is None or joined is None:
        return None
    ms = [p.ns / 1e6 for p in joined.prefills()
          if p.launch.stats.get("rung") == rung]
    return statistics.median(ms) if ms else None
