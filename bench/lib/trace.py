"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

A trace is read into plain ``Plane``/``Line``/``Event`` records, so the
reduction can be checked on a constructed trace with known answers.

* The window is the host span ``bench.window`` that the harness writes
  around it; every number below is clipped to it. The host line is the
  thread that holds that span.
* Device planes are those with an ``XLA Ops`` line: one event per device
  operation. Operations nest (a ``while`` holds its body's operations),
  so busy time is the union of their intervals and an operation's own
  time excludes the operations inside it. An operation is named by its
  HLO name and result type, ``fused_vq_matmul.26 f32[16,18432]``.
* The ``XLA Modules`` line holds one event per program execution. A
  program is named by the host span that dispatched it,
  ``PjitFunction(<name>)`` on the host line: each execution is given the
  latest such span that began before it, and every execution of one
  compiled module (one module event name) takes the name most of its
  executions were given.
* Kernel time: the operations inside a program's executions whose name
  starts with one of given kernel names.
* Idle gaps: the stretches of the window in which a device runs no
  operation, each labelled with the innermost host span on the host line
  at the gap's middle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DISPATCH = re.compile(r"^PjitFunction\((.*)\)$")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]

    def line(self, name: str) -> Optional[Line]:
        return next((ln for ln in self.lines if ln.name == name), None)


def start(trace_dir: str) -> None:
    """Start the JAX profiler with Python's function tracer off, so the
    host runs at its own speed and carries only the harness's and JAX's
    own spans."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]


def load(path: str) -> List[Plane]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [Plane(p.name, [Line(ln.name, [
        Event(ev.name, float(ev.start_ns), float(ev.duration_ns),
              dict(ev.stats)) for ev in ln.events]) for ln in p.lines])
        for p in pd.planes]


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.3 f32[8,128]``; a name without an HLO body is kept."""
    head, sep, rest = hlo.partition(" = ")
    head = head.lstrip("%")
    if not sep:
        return head
    m = re.match(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return f"{head} {m.group(0)}" if m else head


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def self_times(events: Sequence[Event], lo: float, hi: float
               ) -> List[Tuple[Event, float]]:
    """Each event's time inside [lo, hi] less that of the events nested
    inside it."""
    out: List[List[object]] = []
    stack: List[List[object]] = []
    for ev in sorted(events, key=lambda e: (e.start_ns, -e.dur_ns)):
        while stack and stack[-1][0].end_ns <= ev.start_ns:
            stack.pop()
        own = max(0.0, min(ev.end_ns, hi) - max(ev.start_ns, lo))
        rec = [ev, own]
        if stack:
            stack[-1][1] -= own
        stack.append(rec)
        out.append(rec)
    return [(ev, max(0.0, t)) for ev, t in out]


class Summary:
    def __init__(self, planes: List[Plane]):
        self.planes = planes
        host = [(ln, ev) for p in planes for ln in p.lines
                for ev in ln.events if ev.name == WINDOW_SPAN]
        if len(host) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN!r} span in the "
                             f"trace, found {len(host)}")
        self.host_line, win = host[0]
        self.t0, self.t1 = win.start_ns, win.end_ns
        self.devices = [p for p in planes if p.line(OPS_LINE) is not None]
        if not self.devices:
            raise ValueError("no device plane with an 'XLA Ops' line")
        self._names = self._program_names()

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _inside(self, e: Event) -> bool:
        return e.end_ns > self.t0 and e.start_ns < self.t1

    def _clip(self, e: Event) -> float:
        return max(0.0, min(e.end_ns, self.t1) - max(e.start_ns, self.t0))

    def _ops(self, plane: Plane) -> List[Event]:
        return [e for e in plane.line(OPS_LINE).events if self._inside(e)]

    def _modules(self, plane: Plane) -> List[Event]:
        ln = plane.line(MODULES_LINE)
        return [] if ln is None else [e for e in ln.events if self._inside(e)]

    def _program_names(self) -> Dict[str, str]:
        """Module event name -> the name of the function that dispatched
        most of its executions."""
        spans = sorted((e.start_ns, m.group(1)) for e in self.host_line.events
                       for m in [_DISPATCH.match(e.name)] if m)
        starts = [s for s, _ in spans]
        votes: Dict[str, collections.Counter] = collections.defaultdict(
            collections.Counter)
        for p in self.devices:
            for e in self._modules(p):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                votes[e.name][spans[i][1] if i >= 0 else ""] += 1
        return {mod: c.most_common(1)[0][0] for mod, c in votes.items()}

    def _executions(self, plane: Plane, programs: Sequence[str]
                    ) -> List[Event]:
        return [e for e in self._modules(plane)
                if self._names.get(e.name) in programs]

    def _busy(self, plane: Plane) -> List[Tuple[float, float]]:
        return union((max(e.start_ns, self.t0), min(e.end_ns, self.t1))
                     for e in self._ops(plane))

    def busy_s(self) -> float:
        per = [sum(b - a for a, b in self._busy(p)) for p in self.devices]
        return sum(per) / len(per) * 1e-9

    def program_s(self, programs: Sequence[str]) -> float:
        """Device seconds of the executions of the named programs, per
        device."""
        return sum(self._clip(e) for p in self.devices
                   for e in self._executions(p, programs)) \
            / len(self.devices) * 1e-9

    def program_count(self, programs: Sequence[str]) -> float:
        return sum(1 for p in self.devices
                   for e in self._executions(p, programs)
                   if self.t0 <= e.start_ns < self.t1) / len(self.devices)

    def kernel_s(self, kernels: Sequence[str], programs: Sequence[str]) -> float:
        """Own device seconds of the operations named after one of
        ``kernels`` inside the named programs' executions, per device."""
        tot = 0.0
        for p in self.devices:
            runs = sorted((e.start_ns, e.end_ns)
                          for e in self._executions(p, programs))
            if not runs:
                continue
            starts = [a for a, _ in runs]
            for ev, own in self_times(self._ops(p), self.t0, self.t1):
                if not op_name(ev.name).startswith(tuple(kernels)):
                    continue
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                if i >= 0 and ev.start_ns < runs[i][1]:
                    tot += own
        return tot / len(self.devices) * 1e-9

    def top_ops(self, n: int = 10) -> List[List[object]]:
        """The operations with the most own time, per device, with the
        program they ran in."""
        tot: Dict[str, float] = collections.Counter()
        for p in self.devices:
            runs = sorted((e.start_ns, e.end_ns, self._names.get(e.name, ""))
                          for e in self._modules(p))
            starts = [a for a, _, _ in runs]
            for ev, own in self_times(self._ops(p), self.t0, self.t1):
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                prog = runs[i][2] if i >= 0 and ev.start_ns < runs[i][1] \
                    else ""
                tot[f"{prog}/{op_name(ev.name)}" if prog
                    else op_name(ev.name)] += own
        k = len(self.devices)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, s / k * 1e-9] for name, s in top]

    def idle_gaps(self, n: int = 10) -> List[List[object]]:
        gaps = []
        for p in self.devices:
            t = self.t0
            for a, b in self._busy(p) + [(self.t1, self.t1)]:
                if a > t:
                    gaps.append((t, a))
                t = max(t, b)
        host = self.host_line.events
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (a + b) / 2
            covering = [e for e in host if e.start_ns <= mid < e.end_ns]
            label = max(covering, key=lambda e: (e.start_ns, -e.dur_ns)).name \
                if covering else "no host span"
            out.append([label, (b - a) * 1e-9])
        return out
