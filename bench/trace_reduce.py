"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle
time, per-kernel device time, and the longest idle gaps named by what
the host was doing in them.

A TPU plane (``/device:TPU:<n>``) carries an ``XLA Ops`` line, one
event per operation run on the chip, and an ``XLA Modules`` line, one
event per compiled program run.  Host spans that the benchmark opens
with ``jax.profiler.TraceAnnotation`` (names starting ``bench:``) sit
on the host plane, on the same clock.  The functions below take any
objects with ``name``, ``start_ns``, ``duration_ns`` and ``stats``, so a
test can feed them a synthesised trace.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    duration_ns: float
    stats: Tuple = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


def _ev(e) -> Event:
    return Event(e.name, float(e.start_ns), float(e.duration_ns),
                 tuple(e.stats))


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals as a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of [lo, hi) between the disjoint ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def matches(ev: Event, pattern: str) -> bool:
    """An event is a call of ``pattern`` if its name or any string stat
    (the HLO op, its long name, the kernel name) contains it."""
    if pattern in ev.name:
        return True
    return any(isinstance(v, str) and pattern in v for _, v in ev.stats)


def within(ev: Event, spans: Sequence[Interval]) -> bool:
    mid = ev.start_ns + ev.duration_ns / 2
    return any(s <= mid < e for s, e in spans)


@dataclasses.dataclass
class DeviceTrace:
    """What one device did over the traced window."""
    window: Interval
    ops: List[Event]
    modules: List[Event]
    host_spans: List[Event]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy(self) -> List[Interval]:
        return clip(merge((e.start_ns, e.end_ns) for e in self.ops),
                    *self.window)

    @property
    def busy_s(self) -> float:
        return length(self.busy()) * 1e-9

    def module_spans(self, pattern: str) -> List[Interval]:
        return [(m.start_ns, m.end_ns) for m in self.modules
                if pattern in m.name]

    def module_runs(self, pattern: str) -> Tuple[int, float]:
        """(runs, device seconds) of the programs whose name holds
        ``pattern``, inside the window."""
        spans = clip(self.module_spans(pattern), *self.window)
        return len(spans), length(spans) * 1e-9

    def kernel_s(self, pattern: str,
                 inside: Optional[Sequence[Interval]] = None) -> float:
        """Device seconds of the operations that call ``pattern``,
        optionally only those inside the given spans."""
        evs = [e for e in self.ops if matches(e, pattern)]
        if inside is not None:
            evs = [e for e in evs if within(e, inside)]
        return length(clip([(e.start_ns, e.end_ns) for e in evs],
                           *self.window)) * 1e-9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` operation names with most device self time (an op
        that encloses others, as ``while`` encloses its body, keeps only
        the time none of them covers)."""
        lo, hi = self.window
        inside = [e for e in self.ops if e.end_ns > lo and e.start_ns < hi]
        tot: Dict[str, float] = defaultdict(float)
        for e, own in self_times(inside):
            tot[op_family(e.name)] += own * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]

    def idle_by_host(self, n: int = 10) -> List[Tuple[str, float]]:
        """Idle device time grouped by the host span that covers most of
        each gap (``host:none`` where no benchmark span covers it); the
        ``n`` largest groups."""
        spans = [s for s in self.host_spans if s.name != WINDOW_SPAN]
        tot: Dict[str, float] = defaultdict(float)
        for gs, ge in gaps(self.busy(), *self.window):
            best, cover = "host:none", 0.0
            for s in spans:
                ov = min(ge, s.end_ns) - max(gs, s.start_ns)
                if ov > cover:
                    best, cover = s.name[len(SPAN_PREFIX):], ov
            tot[best] += (ge - gs) * 1e-9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def self_times(events: Sequence[Event]) -> List[Tuple[Event, float]]:
    """Each event with its self time: its duration less the union of the
    events wholly inside it on the same line."""
    order = sorted(events, key=lambda e: (e.start_ns, -e.duration_ns))
    own = {id(e): e.duration_ns for e in order}
    kids: Dict[int, List[Interval]] = defaultdict(list)
    stack: List[Event] = []
    for e in order:
        while stack and (stack[-1].end_ns <= e.start_ns
                         or stack[-1].end_ns < e.end_ns):
            stack.pop()          # ended, or only overlaps: not a parent
        if stack:
            kids[id(stack[-1])].append((e.start_ns, e.end_ns))
        stack.append(e)
    for e in order:
        own[id(e)] -= length(merge(kids[id(e)]))
    return [(e, own[id(e)]) for e in order]


def op_family(name: str) -> str:
    """``%fusion.123 = f32[...] ...`` or ``fusion.123`` -> ``fusion``:
    the HLO instruction's name without its text or instance number, so
    that the same kind of op adds up."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.:]\d+$", "", head)


def from_planes(planes) -> List[DeviceTrace]:
    """One ``DeviceTrace`` per TPU plane of a parsed profile; the window
    is the benchmark's ``bench:window`` host span where there is one,
    else the extent of the device's operations."""
    host_spans: List[Event] = []
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    host_spans.append(_ev(e))
    win = [s for s in host_spans if s.name == WINDOW_SPAN]
    out = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name}: no {OPS_LINE!r} line; lines "
                             f"are {sorted(lines)}")
        ops = [_ev(e) for e in lines[OPS_LINE].events]
        mods = ([_ev(e) for e in lines[MODULES_LINE].events]
                if MODULES_LINE in lines else [])
        if win:
            window = (win[0].start_ns, win[0].end_ns)
        elif ops:
            window = (min(e.start_ns for e in ops),
                      max(e.end_ns for e in ops))
        else:
            window = (0.0, 0.0)
        out.append(DeviceTrace(window, ops, mods, host_spans))
    return out


def read(log_dir: str) -> List[DeviceTrace]:
    """Parse the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    path = max(paths, key=os.path.getmtime)
    return from_planes(ProfileData.from_file(path).planes)


def describe(planes, limit: int = 12) -> str:
    """A short text map of a trace: planes, lines, event counts and the
    first event names with their stats, to look at one trace by hand."""
    rows = []
    for plane in planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  LINE {line.name!r}: {len(evs)} events")
            seen = set()
            for e in evs:
                fam = op_family(e.name)
                if fam in seen:
                    continue
                seen.add(fam)
                rows.append(f"    {e.name} {e.duration_ns:.0f}ns "
                            f"{list(e.stats)[:8]}")
                if len(seen) >= limit:
                    break
    return "\n".join(rows)
