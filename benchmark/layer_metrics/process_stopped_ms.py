"""Device-0 idle in the slice that lies in a silence of the whole host
plane of 50 ms or more (``launch_join``'s ``"process stopped"``): the
machine's stops of the process, which no span of the program can
show."""
from benchmark import launch_join

NAME, UNIT, LAYER = "process_stopped_ms", "ms", "Decode scheduler"


def compute(ctx):
    joined = launch_join.of(ctx)
    if joined is None:
        return None
    return joined.idle().get(launch_join.STOPPED, 0.0) / 1e6
