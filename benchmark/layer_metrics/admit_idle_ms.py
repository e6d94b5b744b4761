"""Mean over the slice's joined prefills of device 0's idle time from
the end of the program before the prefill to the start of the first
step program after it, on the device's own clock: what one admission
leaves exposed (the prefill's token is read before the next step is
built and launched)."""
from benchmark import launch_join

NAME, UNIT, LAYER = "admit_idle_ms", "ms", "Decode scheduler"


def compute(ctx):
    joined = launch_join.of(ctx)
    if joined is None:
        return None
    programs, idle = joined.programs, []
    for p in joined.prefills():
        at = programs.index(p)
        after = next((k for k in range(at + 1, len(programs))
                      if programs[k].kind == "step"), None)
        if at and after is not None:
            idle.append(programs[after].start - programs[at - 1].end
                        - sum(q.ns for q in programs[at:after]))
    return sum(idle) / len(idle) / 1e6 if idle else None
