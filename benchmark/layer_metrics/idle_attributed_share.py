"""Share of device 0's idle time in the slice that ``launch_join``
charges to a named span of the program that lies in another (not to a
root: ``decode.tick``, ``decode.wait``; not to ``"process stopped"``,
``"unresolved"`` or ``"unattributed"``): how much of what idle is left
the program's own spans explain. ``raw["idle_by_span"]`` lists the
seconds by name, the classes included."""
from benchmark import launch_join

NAME, UNIT, LAYER = "idle_attributed_share", "%", "Decode scheduler"


def compute(ctx):
    joined = launch_join.of(ctx)
    by_span = joined.idle() if joined else None
    if not by_span:
        return None
    apart = joined.roots() | {launch_join.STOPPED, launch_join.UNRESOLVED,
                              launch_join.UNATTRIBUTED}
    return 100.0 * sum(ns for name, ns in by_span.items()
                       if name not in apart) / sum(by_span.values())
