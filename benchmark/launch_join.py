"""The device's programs joined to the launches that made them.

Since the serving loop dispatches step N+1 before it reads step N, a
program starts 11-17 ms after it was launched and nearness in time no
longer says which launch a program is of. The program says it itself:
every span that hands the device a program carries ``seq`` (and
``program``: ``step``, ``prefill``, ``cow``), every span that waits for
one carries ``waits`` (``mxnet_tpu/serving/decode.py``). This module
reads them from ``program_spans.of(ctx)`` beside device 0's
``XLA Modules`` line.

**Identity** (``join``). The device runs what one thread launched, in
order, so the programs of the slice are a run of consecutive numbers and
one offset names them all. A slice may open with programs whose launch
lies before it and close with launches whose programs lie after it. Of
the offsets under which every launch span's ``program`` agrees with the
kind of its device program (the configuration's
``trace_names.prefill_module`` / ``.step_module``; anything else is
``other``, a copy-on-write), the one is taken under which the
``waits=n`` spans end nearest to the end of program n: a read-back
returns a fraction of a millisecond after its program ends, one program
off is a whole step off. No such offset, or two that cannot be told
apart: None, never a guess. **Where the runtime numbers its runs** —
libtpu 0.0.34 does: a device program's event and the host's
``DoEnqueueProgram`` of it carry the same ``run_id`` stat (my chip run,
PR 35) — that says it outright and order is not needed: an enqueue is of
the launch span that began last before it (or of an earlier one, where
the runtime's thread ran late), which fixes ``run_id - seq`` for the
whole slice (``run_ids`` reads the stats, which ``trace_reduce.load``
drops). Order is the fallback, and on the five serving cells both gave
the same numbers.

**The clock** (``Join.__init__``). The device's stamps and the host's
differ by a lead L (host time = device stamp + L) that is another value
in every profile and drifts 0.2-0.3 ms inside one. With identity known L
is bounded by what cannot be otherwise: program n starts no earlier than
its launch span began (L >= launch.start - start_n) and ends no later
than its wait span ended (L <= wait.end - end_n). Every read-back gives
a tight upper bound, every launch onto an idle device a tight lower one.
libtpu's own ``DoEnqueueProgram`` events, by their ``run_id`` or else
taken in the launches' order, never by nearness, tighten the lower bound
where a profile has one a launch; where it has not, the spans alone do.
A program takes the bounds of its neighbourhood, and those of the whole
slice widened by the drift.

**Idle, attributed** (``Join.idle``). The time inside the slice in which
no program ran on device 0, moved onto the host's clock, from the first
program the device line holds to the last: the line opens with the first
program that STARTS inside the profile, so the 2-3 ms in front of it,
in which the program before it was still running, are not idle time (of
``sdar-blockdiff-batch``'s 3.6 ms of "idle" a slice, 3.2 were that; my
chip run, PR 35). First, the part
of a gap that lies in a silence of the whole ``/host:CPU`` plane of
50 ms or more (no event of any line begins or ends) is charged to
``"process stopped"``, not to the span that happened to be open — unless
that span is a loop's wait for work (``decode.wait``) or none: a server
with nothing to do is silent too (the steady cell between requests).
Then a
gap narrower than the lead's slack beside it is ``"unresolved"``, not
split. What is left is cut at span boundaries and charged to the
innermost ``mx:`` span (``Spans.attribute``), ``"unattributed"`` under
none.
"""
from __future__ import annotations

import bisect
import os
import re
import statistics

from . import program_spans, trace_reduce

STOPPED, UNRESOLVED, UNATTRIBUTED = \
    "process stopped", "unresolved", "unattributed"
NEAR_NS = 5e6           # a read-back returns this soon after its program
APART_NS = 1e6          # two offsets nearer than this cannot be told apart
SILENCE_NS = 50e6       # no host event for so long: the process stood
STOP_OVERLAP_NS = 5e6   # less idle than this inside a silence is no stop's
NEIGHBOURHOOD_NS = 0.25e9
DRIFT_NS = 0.3e6        # the lead's drift inside one profile
ENQUEUE = "DoEnqueueProgram"   # libtpu hands one program to the chip
INF = float("inf")


class Program:
    """One event of device 0's ``XLA Modules`` line, on the device's
    clock, with its number, its launch and wait spans where the profile
    holds them, and the lead and slack its stamps are moved by."""

    __slots__ = ("name", "start", "end", "kind", "seq", "launch", "wait",
                 "low", "high", "lead", "slack")

    def __init__(self, name, start, end, kind):
        self.name, self.start, self.end, self.kind = name, start, end, kind
        self.seq = self.launch = self.wait = None
        self.low, self.high = -INF, INF

    @property
    def ns(self):
        return self.end - self.start


def _kind(name, names):
    for kind in ("prefill", "step"):
        if re.search(names[kind + "_module"], name):
            return kind
    return "other"


def _launched(span):
    """The kind of device program a launch span's ``program`` makes."""
    program = span.stats["program"]
    return program if program in ("prefill", "step") else "other"


def run_ids(path):
    """``{"programs": {start_ns: run_id}, "enqueues": {run_id:
    start_ns}}`` of device 0's programs and the host's enqueues in the
    xplane at ``path``; empty where the runtime gives its events no
    ``run_id``."""
    from jax.profiler import ProfileData
    out = {"programs": {}, "enqueues": {}}
    devices = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ENQUEUE:
                        run = dict(ev.stats).get("run_id")
                        if run is not None:
                            out["enqueues"][int(run)] = float(ev.start_ns)
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
    devices.sort(key=lambda p: int(
        trace_reduce.DEVICE_PLANE.match(p.name).group(1)))
    for line in devices[0].lines if devices else ():
        if line.name == trace_reduce.MODULES_LINE:
            for ev in line.events:
                run = dict(ev.stats).get("run_id")
                if run is not None:
                    out["programs"][float(ev.start_ns)] = int(run)
    return out


def _first_by_run_id(programs, launches, ids):
    """The ``seq`` of the first program from the runtime's own run ids.
    An enqueue happens inside its launch span or after it, so it is of
    the launch span that began last before it, or (the runtime's thread
    ran late) of an earlier one: ``run_id - seq`` of that span is the
    slice's one number or less, and the largest seen is taken, if under
    it every enqueue comes at or after its launch began. None where the
    profile has no run ids or they do not fit."""
    runs = [ids["programs"].get(p.start) for p in programs]
    if None in runs or [b - a for a, b in zip(runs, runs[1:])] \
            != [1] * (len(runs) - 1):
        return None
    spans = sorted(launches.values(), key=lambda sp: sp.start)
    starts = [sp.start for sp in spans]
    ahead = []
    for run, at in ids["enqueues"].items():
        k = bisect.bisect_right(starts, at) - 1
        # behind the last launch span that ended inside the profile an
        # enqueue may be of a launch whose span did not
        if k >= 0 and (k + 1 < len(spans) or at <= spans[k].end):
            ahead.append(run - int(spans[k].stats["seq"]))
    if not ahead or any(
            run - max(ahead) in launches
            and at < launches[run - max(ahead)].start
            for run, at in ids["enqueues"].items()):
        return None
    return runs[0] - max(ahead)


def join(spans, modules, names, ids=None):
    """``([Program, ...], by)``: device 0's programs by start, each with
    its ``seq`` and its spans, and what named them (``"run_id"`` or
    ``"order"``); None where the spans carry no numbers or no one offset
    fits (the module's docstring). ``ids``: ``run_ids`` of the same
    profile, where there is one to read."""
    launches = {int(sp.stats["seq"]): sp for sp in spans.spans
                if "seq" in sp.stats and "program" in sp.stats}
    waits = {int(sp.stats["waits"]): sp for sp in spans.spans
             if "waits" in sp.stats}
    programs = [Program(n, s, e, _kind(n, names))
                for n, s, e in sorted(modules, key=lambda ev: ev[1])]
    if not launches or not waits or not programs:
        return None
    fits = []
    for first in range(min(waits) - len(programs) + 1, max(waits) + 1):
        late = []
        for at, prog in enumerate(programs):
            launch = launches.get(first + at)
            if launch is not None and _launched(launch) != prog.kind:
                break
            wait = waits.get(first + at)
            if wait is not None:
                late.append(wait.end - prog.end)
        else:
            if late:
                fits.append((abs(statistics.median(late)), first))
    fits.sort()
    first, by = _first_by_run_id(programs, launches, ids) if ids else None, \
        "run_id"
    if first is None:
        if not fits or fits[0][0] > NEAR_NS \
                or len(fits) > 1 and fits[1][0] - fits[0][0] < APART_NS:
            return None
        first, by = fits[0][1], "order"
    elif first not in [f for _, f in fits]:     # its kinds do not agree
        return None
    for at, prog in enumerate(programs):
        prog.seq = first + at
        prog.launch, prog.wait = launches.get(prog.seq), waits.get(prog.seq)
    return programs, by


def _enqueues(programs, host, ids):
    """``{seq: start}`` of libtpu's enqueue of each launch the profile
    holds: by run id where those named the programs, else taken in order
    from the first launch on; nothing where the profile has not exactly
    one such event a launch, each at or after its launch span began."""
    if ids:
        ahead = ids["programs"][programs[0].start] - programs[0].seq
        return {run - ahead: at for run, at in ids["enqueues"].items()}
    launched = [p.launch for p in programs if p.launch is not None]
    stamps = sorted(s for line in host.values() for n, s, _ in line
                    if n == ENQUEUE and launched
                    and s >= launched[0].start)[:len(launched)]
    if len(stamps) != len(launched) or any(
            s < sp.start for s, sp in zip(stamps, launched)):
        return {}
    return {int(sp.stats["seq"]): s for s, sp in zip(stamps, launched)}


class Join:
    """The joined programs of one slice with the lead of each, and the
    slice's idle time by what the host was doing."""

    def __init__(self, programs, spans, host, window, ids=None):
        """``ids``: the profile's ``run_ids`` where they named the
        programs, else None."""
        self.programs, self.spans, self.window = programs, spans, window
        self.host = host
        self.by = "run_id" if ids else "order"
        self.enqueued = _enqueues(programs, host, ids)
        for p in programs:
            if p.launch is not None:
                p.low = self.enqueued.get(p.seq, p.launch.start) - p.start
            if p.wait is not None:
                p.high = p.wait.end - p.end
        low = max(p.low for p in programs) - DRIFT_NS
        high = min(p.high for p in programs) + DRIFT_NS
        self.consistent = True
        for p in programs:
            near = [q for q in programs
                    if abs(q.start - p.start) <= NEIGHBOURHOOD_NS]
            lo = max(low, max(q.low for q in near))
            hi = min(high, min(q.high for q in near))
            # bounds that cross by more than the drift: a wrong offset
            self.consistent &= hi - lo >= -2 * DRIFT_NS
            p.lead, p.slack = (lo + hi) / 2, max(hi - lo, 0.0) / 2
        self.by_span = None

    def record(self):
        """``raw["launch_join"]``: how much of the slice was joined and
        how well its clock is known."""
        leads = [p.lead for p in self.programs]
        return {
            "by": self.by,
            "programs_seen": len(self.programs),
            "programs_joined": sum(
                p.launch is not None or p.wait is not None
                for p in self.programs),
            "first_seq": self.programs[0].seq,
            "lead_ms": [ns / 1e6 for ns in (
                statistics.median(leads), min(leads), max(leads))],
            "widest_slack_ms": max(p.slack for p in self.programs) / 1e6,
            "enqueues_paired": len(self.enqueued),
            "consistent": self.consistent,
        }

    def silences(self):
        """The stretches of the window, ``SILENCE_NS`` or longer, in
        which no event of any host line begins or ends while the program
        is inside a span that is no wait for work."""
        lo, hi = self.window
        edges = sorted({lo, hi} | {
            t for line in self.host.values() for _, s, e in line
            for t in (s, e) if lo < t < hi})
        out = []
        for a, b in zip(edges, edges[1:]):
            if b - a >= SILENCE_NS:
                sp = self.spans.charged(a, b)
                if sp is not None and not sp.name.endswith(".wait"):
                    out.append((a, b))
        return out

    def idle(self):
        """``{what: ns}`` of device 0's idle time inside the window, on
        the host's clock, between the first program the device line
        holds and the last: a span's name, or one of ``STOPPED``,
        ``UNRESOLVED``, ``UNATTRIBUTED``. Every nanosecond of it is
        charged once."""
        if self.by_span is not None:
            return self.by_span
        moved = [(p.start + p.lead, p.end + p.lead, p.slack)
                 for p in self.programs]
        busy = trace_reduce.union((s, e) for s, e, _ in moved)
        window = (max(self.window[0], busy[0][0]),
                  min(self.window[1], busy[-1][1]))
        silences = self.silences()
        out = {}

        def charge(name, ns):
            if ns > 0:
                out[name] = out.get(name, 0.0) + ns

        for lo, hi in trace_reduce.gaps(busy, *window):
            stood = [piece for piece in trace_reduce.subtract(
                [(lo, hi)], trace_reduce.gaps(silences, lo, hi))
                if piece[1] - piece[0] >= STOP_OVERLAP_NS]
            charge(STOPPED, trace_reduce.total(stood))
            # the programs on either side say how well the gap's ends
            # are known
            slack = max([k for s, e, k in moved
                         if abs(e - lo) < 1 or abs(s - hi) < 1] or [0.0])
            for a, b in trace_reduce.subtract([(lo, hi)], stood):
                if b - a < slack:
                    charge(UNRESOLVED, b - a)
                    continue
                for name, ns in self.spans.attribute([(a, b)]).items():
                    charge(name or UNATTRIBUTED, ns)
        self.by_span = out
        return out

    def roots(self):
        """Names of the spans that lie in no other: a loop's pass, its
        wait for work. (A slice that opens inside a pass holds children
        without their parent: a name that is ever a child is no root.)"""
        names = {sp.name for sp in self.spans.spans if sp.parent is None}
        return names - {sp.name for sp in self.spans.spans
                        if sp.parent is not None}

    def prefills(self):
        """The programs joined to a ``program="prefill"`` launch."""
        return [p for p in self.programs if p.launch is not None
                and p.launch.stats["program"] == "prefill"]


def of(ctx):
    """The traced slice's ``Join``, made once a run; None without a
    device trace, for a program whose spans carry no numbers (an earlier
    commit), and where no offset fits. ``raw["launch_join"]`` says how
    much was joined, ``raw["idle_by_span"]`` where the idle time went."""
    if hasattr(ctx, "launch_join"):
        return ctx.launch_join
    ctx.launch_join = None
    spans = program_spans.of(ctx)
    names = ctx.config.get("trace_names", {})
    if spans is None or ctx.trace is None or not ctx.trace.devices \
            or "prefill_module" not in names or "step_module" not in names \
            or not any("seq" in sp.stats for sp in spans.spans):
        return None
    ids = getattr(ctx, "run_ids", None)
    if ids is None and getattr(ctx, "cell", None) is not None:
        from . import harness
        path = trace_reduce.find_xplane(os.path.join(
            harness.OUT, "trace", ctx.cell["name"]))
        ids = run_ids(path) if path else None
    found = join(spans, ctx.trace.planes[ctx.trace.devices[0]].get(
        trace_reduce.MODULES_LINE, ()), names, ids)
    if found is None:
        return None
    joined = Join(found[0], spans,
                  ctx.trace.planes.get(trace_reduce.HOST_PLANE, {}),
                  ctx.trace.window, ids if found[1] == "run_id" else None)
    ctx.raw["launch_join"] = joined.record()
    if not joined.consistent:       # a bound broken: the offset is wrong
        return None
    ctx.raw["idle_by_span"] = [[name, ns / 1e9] for name, ns in sorted(
        joined.idle().items(), key=lambda kv: -kv[1])]
    ctx.launch_join = joined
    return joined


def idle_gaps(ctx, n=10):
    """``[[what, seconds], ...]``, the ``n`` largest of ``Join.idle``:
    the slice's idle time as the result line's ``breakdown.idle_gaps``
    wants it, under the program's own spans. None where ``of`` gives
    nothing (a training cell, a program that numbers no launch) or the
    device was never idle between two programs."""
    joined = of(ctx)
    return (ctx.raw["idle_by_span"][:n] or None) if joined else None
