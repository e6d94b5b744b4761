"""Milliseconds of the window in which the container's CPU quota
throttled the process: ``stats()["host"]["throttled_s"]`` after the
window less before it. Beside ``process_stopped_ms`` it says whether the
stops are the quota's. Left out where the program or the machine's
cgroup keeps no such count."""
NAME, UNIT, LAYER = "host_throttled_ms", "ms", "Decode scheduler"


def compute(ctx):
    a = ctx.raw.get("stats0", {}).get("host", {})
    b = ctx.raw.get("stats1", {}).get("host", {})
    if "throttled_s" not in a or "throttled_s" not in b:
        return None
    return 1e3 * (b["throttled_s"] - a["throttled_s"])
