"""The program's own spans in a ``jax.profiler`` trace.

``mxnet_tpu.tracing.span`` opens every loop-level span of the program as
a ``TraceAnnotation`` named ``mx:<name>``, so a profile holds them on
``/host:CPU``, one line per thread, on the device's clock, with the
span's arguments as the event's stats. ``trace_reduce.load`` merges the
lines of one name and drops the stats; this module reads the same file
again and keeps both, because nesting is a property of one line and a
reader may need an argument.

A ``Spans`` is made from ``[[(name, start_ns, end_ns, stats), ...], ...]``
(one list per line), which a test writes by hand. A program without
such spans (an earlier commit) gives an empty ``Spans``: every reader
then finds nothing and leaves its metric out.

**The two clocks of a profile.** The device's events and the host's are
stamped by different clocks, and in every profile read so far (my chip
runs, PR 23) the device's lay 0.3-1.9 ms EARLY. ``benchmark/launch_join.py``
bounds that lead for every program from the program's own ``seq`` /
``waits`` spans, and charges the device's idle time to these spans.
"""
from __future__ import annotations

import os
import statistics

from . import trace_reduce

PREFIX = "mx:"


class Span:
    __slots__ = ("name", "start", "end", "line", "stats", "parent",
                 "children")

    def __init__(self, name, start, end, line, stats):
        self.name, self.start, self.end = name, start, end
        self.line, self.stats = line, stats
        self.parent, self.children = None, []

    @property
    def ns(self):
        return self.end - self.start

    @property
    def self_ns(self):
        """The span's time that no child span covers."""
        return self.ns - trace_reduce.total(trace_reduce.union(
            (c.start, c.end) for c in self.children))


class Spans:
    def __init__(self, lines):
        self.spans = []
        for at, events in enumerate(lines):
            open_ = []              # the spans around the one at hand
            for name, start, end, stats in sorted(
                    events, key=lambda ev: (ev[1], -ev[2])):
                if not name.startswith(PREFIX):
                    continue
                sp = Span(name[len(PREFIX):], start, end, at, stats)
                while open_ and open_[-1].end < sp.end:
                    open_.pop()
                if open_:
                    sp.parent = open_[-1]
                    open_[-1].children.append(sp)
                open_.append(sp)
                self.spans.append(sp)
        self.spans.sort(key=lambda sp: (sp.start, -sp.end))

    def named(self, *names):
        return [sp for sp in self.spans if sp.name in names]

    def charged(self, lo, hi):
        """The span an interval that no span boundary cuts is charged
        to: of the spans that cover it, on whatever line, the one that
        began last, which on one line is the innermost. None under no
        span."""
        best = None
        for sp in self.spans:       # by start, the longer first
            if sp.start > lo:
                break
            if sp.end >= hi:
                best = sp
        return best

    def attribute(self, intervals):
        """``{name or None: ns}``: each interval cut at every span
        boundary inside it, each piece charged to its innermost span."""
        edges = sorted({t for sp in self.spans for t in (sp.start, sp.end)})
        out = {}
        for lo, hi in intervals:
            cuts = [lo] + [t for t in edges if lo < t < hi] + [hi]
            for a, b in zip(cuts, cuts[1:]):
                sp = self.charged(a, b)
                name = None if sp is None else sp.name
                out[name] = out.get(name, 0.0) + (b - a)
        return out


def from_xplane(path):
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            lines.append([
                (ev.name, float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns),
                 dict(ev.stats))
                for ev in line.events if ev.name.startswith(PREFIX)])
    return Spans(lines)


def of(ctx):
    """The traced slice's spans, read once a run; None when the run
    took no trace."""
    if not hasattr(ctx, "program_spans"):
        from . import harness
        path = None
        if ctx.trace is not None:
            path = trace_reduce.find_xplane(os.path.join(
                harness.OUT, "trace", ctx.cell["name"]))
        ctx.program_spans = None if path is None else from_xplane(path)
    return ctx.program_spans


def median_ms(ctx, name):
    """Median duration of the spans called ``name``; None without any."""
    spans = of(ctx)
    found = spans.named(name) if spans else []
    return statistics.median(sp.ns for sp in found) / 1e6 if found else None
