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
runs, PR 23) the device's lay 0.3-1.9 ms EARLY: a decode program
"started" that long before libtpu's own host event enqueued it. The
lead differs between profiles and steps by 0.2-0.3 ms inside one. A gap
of 2.7 ms cannot be split among host spans across such a lead, so
``device_leads`` bounds it for every program from two things that
cannot be otherwise: a program starts after the host enqueued it
(``DoEnqueueProgram``) and ends before the host saw it end
(``ReadSyncFlag``). Where a profile has no such events the lead is
unknown, and ``idle`` gives nothing rather than a split that is off by
the lead.
"""
from __future__ import annotations

import bisect
import os
import statistics

from . import trace_reduce

PREFIX = "mx:"
# libtpu's own host events around one program: the runtime thread hands
# the program to the chip; the completion thread reads the chip's flag
LAUNCH, NOTICE = "DoEnqueueProgram", "ReadSyncFlag"
PAIRED_WITHIN_NS = 5e6      # a launch or notice this near is the program's


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


def device_leads(trace):
    """``[(start, lead, slack), ...]`` for the programs of device 0, by
    their start: the nanoseconds by which a program's stamps lie before
    the host's clock, as the middle of its own two bounds, and half the
    distance between them. From above, the first notice the host took
    after the program's end: whichever program that notice is of, it
    ended no earlier. From below, the last launch the host made before
    the program can have begun, which is its stamped start plus that
    upper bound: a loop that reads every step's result back launches
    nothing while the step runs. A program with no such notice or launch
    within ``PAIRED_WITHIN_NS`` has no bounds and is left out. None
    where under half of the programs have them: a host plane without
    these events, or a device so late that each notice precedes the
    stamped end it belongs to."""
    host = trace.planes.get(trace_reduce.HOST_PLANE, {})
    launches, notices = (
        sorted(s for line in host.values() for n, s, _ in line if n == name)
        for name in (LAUNCH, NOTICE))
    programs = sorted((s, e) for _, s, e in trace.planes[
        trace.devices[0]].get(trace_reduce.MODULES_LINE, ()))
    out = []
    for start, end in programs:
        at = bisect.bisect_left(notices, end)
        if at == len(notices) or notices[at] - end > PAIRED_WITHIN_NS:
            continue
        high = notices[at] - end
        at = bisect.bisect_right(launches, start + high)
        if not at or start + high - launches[at - 1] > PAIRED_WITHIN_NS:
            continue
        low = launches[at - 1] - start      # never above high: chosen so
        out.append((start, (low + high) / 2, (high - low) / 2))
    return out if out and 2 * len(out) >= len(programs) else None


def idle(ctx):
    """``{name or None: ns}`` of device 0's idle time inside the traced
    window, on the host's clock, by the program span each piece of it
    lies under. Every busy interval is moved by the lead of the program
    it belongs to (one without bounds of its own by its neighbour's);
    ``raw["device_lead_ms"]`` records the median lead, the smallest, the
    largest and the widest slack. None when there is no device trace,
    the program wrote no span, or the lead cannot be measured."""
    spans = of(ctx)
    if spans is None or not spans.spans or not ctx.trace.devices:
        return None
    if not hasattr(ctx, "program_idle"):
        trace = ctx.trace
        leads = device_leads(trace)
        ctx.program_idle = ctx.raw["device_lead_ms"] = None
        if leads is not None:
            by = [lead for _, lead, _ in leads]
            ctx.raw["device_lead_ms"] = [
                ns / 1e6 for ns in (statistics.median(by), min(by), max(by),
                                    max(slack for _, _, slack in leads))]
            starts = [start for start, _, _ in leads]
            device = trace.planes[trace.devices[0]]
            busy = []       # moved first, cut to the host's window after
            for _, s, e in (device.get(trace_reduce.OPS_LINE)
                            or device.get(trace_reduce.MODULES_LINE, ())):
                lead = by[max(0, bisect.bisect_right(starts, s) - 1)]
                busy.append((s + lead, e + lead))
            ctx.program_idle = spans.attribute(trace_reduce.gaps(
                trace_reduce.union(busy), *trace.window))
    return ctx.program_idle


def idle_gaps(ctx, n=10):
    """``[[span or "unattributed", seconds], ...]``, the ``n`` largest:
    ``idle`` as the result line's ``breakdown.idle_gaps`` wants it.
    None where ``idle`` gives nothing, and for a configuration that
    names no ``trace_names.step_module``: the leads are paired for a loop
    that launches nothing while its step runs, and a configuration whose
    loop is such names that step."""
    if not ctx.config.get("trace_names", {}).get("step_module"):
        return None
    by_name = idle(ctx)
    if not by_name:
        return None
    return [[name or "unattributed", ns / 1e9] for name, ns in sorted(
        by_name.items(), key=lambda kv: -kv[1])[:n]]


def idle_ms_per_step(ctx, names):
    """Device-idle milliseconds under the spans ``names`` for each
    decode program of the slice."""
    by_name = idle(ctx)
    step = ctx.config.get("trace_names", {}).get("step_module")
    if by_name is None or step is None:
        return None
    steps = len(ctx.trace.module_durations_s(step))
    if not steps:
        return None
    return sum(by_name.get(n, 0.0) for n in names) / 1e6 / steps
