"""From a ``jax.profiler`` trace to numbers.

``load(path)`` reads an ``.xplane.pb`` with ``ProfileData.from_file``
into a plain structure (``planes -> lines -> (name, start_ns, end_ns)``)
that the functions below reduce, and that a test can write by hand.

What the planes of a TPU v5e trace are called (looked at by hand, my
chip run, PR 22 — the list is in PERF.md section 3): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per
executed program (``jit_<function>(<fingerprint>)``) and whose line
``XLA Ops`` has one event per HLO operation; host threads sit in
``/host:CPU``, one line per thread, and hold the ``TraceAnnotation``
spans. All planes share one clock, in nanoseconds.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"     # start..done of asynchronous operations
SPAN_PREFIX = "bench:"          # the benchmark's own host spans
SLICE_SPAN = SPAN_PREFIX + "slice"
# An operation's event is named by its whole HLO line, ``%name = shape
# kind(operands), ...``: a pattern for a kind of operation has to look
# at the name or the kind, never at the operands.
COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)")
MOSAIC = re.compile(r" custom-call\(")    # a Pallas kernel on the TPU
NAME_CHARS = 120


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path):
    """The trace as ``{plane: {line: [(name, start_ns, end_ns), ...]}}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = float(ev.start_ns)
                events.append((ev.name[:4 * NAME_CHARS], start,
                               start + float(ev.duration_ns)))
    return planes


# --- interval arithmetic ---------------------------------------------------

def union(intervals):
    """Disjoint sorted intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals):
    return sum(e - s for s, e in intervals)


def subtract(intervals, cover):
    """The parts of ``intervals`` (disjoint, sorted) outside ``cover``
    (disjoint, sorted)."""
    out = []
    j = 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(cover) and cover[k][0] < e:
            if cover[k][0] > at:
                out.append((at, cover[k][0]))
            at = max(at, cover[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def gaps(busy, lo, hi):
    return subtract([(lo, hi)], busy)


# --- the reduction ---------------------------------------------------------

class Trace:
    """One traced slice. ``window`` is the benchmark's own ``bench:slice``
    span where the host recorded one, else the extent of the device
    events."""

    def __init__(self, planes):
        self.planes = planes
        self.devices = sorted(
            (p for p in planes if DEVICE_PLANE.match(p)),
            key=lambda p: int(DEVICE_PLANE.match(p).group(1)))
        self.spans = sorted(
            (s, e, n) for line in planes.get(HOST_PLANE, {}).values()
            for n, s, e in line if n.startswith(SPAN_PREFIX))
        slices = [(s, e) for s, e, n in self.spans if n == SLICE_SPAN]
        if slices:
            self.window = (min(s for s, _ in slices),
                           max(e for _, e in slices))
        else:
            every = [(s, e) for d in self.devices
                     for _, s, e in self.events(d, OPS_LINE)
                     + self.events(d, MODULES_LINE)]
            self.window = (min(s for s, _ in every),
                           max(e for _, e in every)) if every else (0., 0.)

    def events(self, device, line, pattern=None):
        """Events of one device line inside the window, clipped to it."""
        lo, hi = getattr(self, "window", (float("-inf"), float("inf")))
        out = []
        for n, s, e in self.planes.get(device, {}).get(line, []):
            if pattern is not None and not re.search(pattern, n):
                continue
            s, e = max(s, lo), min(e, hi)
            if e > s:
                out.append((n, s, e))
        return out

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9

    def busy(self, device):
        """Disjoint intervals in which an operation ran on ``device``."""
        ops = self.events(device, OPS_LINE) \
            or self.events(device, MODULES_LINE)
        return union((s, e) for _, s, e in ops)

    def busy_s(self):
        """Seconds an operation ran on the device, averaged over chips."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy(d)) for d in self.devices) \
            / len(self.devices) / 1e9

    def module_s(self, pattern=None, device=None):
        """Device seconds inside programs whose name matches."""
        device = device or self.devices[0]
        return total(union((s, e) for _, s, e in self.events(
            device, MODULES_LINE, pattern))) / 1e9

    def module_durations_s(self, pattern, device=None):
        device = device or self.devices[0]
        return [(e - s) / 1e9 for _, s, e in self.events(
            device, MODULES_LINE, pattern)]

    def op_s(self, pattern, device=None, inside=None):
        """Device seconds inside operations whose name matches; with
        ``inside`` (intervals, as of the programs they ran in) the part
        of them that lies there."""
        device = device or self.devices[0]
        ops = union((s, e) for _, s, e in self.events(
            device, OPS_LINE, pattern))
        if inside is not None:
            return (total(ops) - total(subtract(ops, union(inside)))) / 1e9
        return total(ops) / 1e9

    def exposed_collective_s(self, device=None):
        """Seconds in which a collective operation was under way on the
        device (synchronous ones and the ``-start``/``-done`` ends on the
        operations line, the span between those ends on the asynchronous
        line) and no other operation ran."""
        device = device or self.devices[0]
        ops = self.events(device, OPS_LINE)
        coll = union((s, e) for n, s, e in
                     ops + self.events(device, ASYNC_LINE)
                     if COLLECTIVE.search(n))
        rest = union((s, e) for n, s, e in ops
                     if not COLLECTIVE.search(n))
        return total(subtract(coll, rest)) / 1e9

    def top_ops(self, n=10, device=None):
        """``[[name, seconds], ...]``: the operations with most device
        time, executions of one operation added up. A name is the head
        of the operation's HLO line: its name and the shape it makes."""
        device = device or self.devices[0]
        by_name = {}
        for name, s, e in self.events(device, OPS_LINE) \
                or self.events(device, MODULES_LINE):
            name = name[:NAME_CHARS]
            by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
        return [[k, v] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n=10, device=None, unnamed="unattributed"):
        """``[[what, seconds], ...]``: idle time of the device inside the
        window, by the benchmark span that covers most of each gap."""
        device = device or self.devices[0]
        spans = [(s, e, name) for s, e, name in self.spans
                 if name != SLICE_SPAN]
        by_name = {}
        for lo, hi in gaps(self.busy(device), *self.window):
            best, best_cover = unnamed, 0.0
            for s, e, name in spans:
                if s >= hi:
                    break
                cover = min(e, hi) - max(s, lo)
                if cover > best_cover:
                    best, best_cover = name[len(SPAN_PREFIX):], cover
            by_name[best] = by_name.get(best, 0.0) + (hi - lo) / 1e9
        return [[k, v] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:n]]


def describe(planes, per_line=5):
    """Plane and line names with a few events each — what to look at by
    hand before trusting the constants above."""
    out = {}
    for pname, lines in planes.items():
        out[pname] = {
            lname: {"events": len(evs),
                    "sample": sorted({n for n, _, _ in evs})[:per_line]}
            for lname, evs in lines.items()}
    return out
