"""Share of device 0's idle time that lies under no span of the program,
or under a top-level span that has children (``mx:decode.tick``) and
none of them: a check on the instrumentation, not a lever."""
from benchmark import program_spans

NAME, UNIT, LAYER = "gap_unattributed_share", "%", "Decode scheduler"


def compute(ctx):
    by_name = program_spans.idle(ctx)
    if not by_name:
        return None
    roots = {sp.name for sp in program_spans.of(ctx).spans
             if sp.parent is None and sp.children}
    lost = sum(ns for name, ns in by_name.items()
               if name is None or name in roots)
    return 100.0 * lost / sum(by_name.values())
