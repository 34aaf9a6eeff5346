"""Reduction of a JAX profiler trace to the numbers the metrics read.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into a
flat list of events ``(plane, line, name, start_s, dur_s)``.  ``Trace``
reduces them:

- device busy: the union of the intervals of the ops on each device
  (line ``XLA Ops`` of each ``/device:TPU:<n>`` plane) inside the traced
  window, averaged over the devices; the idle share is 1 minus busy over
  the window;
- device time of a jitted module: the events of line ``XLA Modules``
  whose name, less its ``(<id>)`` suffix, is the module's
  (``jit_decode_step``);
- top ops by device time, and the idle time summed by what the host's
  Python thread was doing in each gap (``Trace.gap_owner``).

The traced window is the host annotation ``chipbench.window``.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "chipbench.window"
OPS, MODULES = "XLA Ops", "XLA Modules"


@dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


def is_device(plane: str) -> bool:
    return plane.startswith("/device:TPU:")


def is_host(plane: str) -> bool:
    return plane.startswith("/host:")


def load(trace_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: device
    ops and modules, and every host event that has a duration."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(files[-1]).planes:
        dev, host = is_device(plane.name), is_host(plane.name)
        if not (dev or host):
            continue
        for line in plane.lines:
            if dev and line.name not in (OPS, MODULES):
                continue
            for ev in line.events:
                if ev.duration_ns > 0:
                    events.append(Event(plane.name, line.name, ev.name,
                                        ev.start_ns * 1e-9,
                                        ev.duration_ns * 1e-9))
    return events


def read_dump(path: str) -> List[Event]:
    """Events saved as gzipped JSON rows ``[plane, line, name, start,
    dur]`` (the recorded trace of the tests)."""
    with gzip.open(path, "rt") as f:
        return [Event(*r) for r in json.load(f)]


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """``a`` less ``b``; both unions (sorted, disjoint)."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def module_name(name: str) -> str:
    return name.split("(", 1)[0]


HLO = re.compile(r"^(%\S+) = .*?\s([a-z][\w-]*)\(")


def op_name(name: str) -> str:
    """``%fusion.12 fusion`` from the op's HLO text."""
    m = HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


class Trace:
    def __init__(self, events: Sequence[Event]):
        self.events = list(events)
        marks = [e for e in self.events
                 if is_host(e.plane) and e.name == WINDOW]
        if marks:
            self.lo, self.hi = marks[0].start, marks[0].end
        else:
            self.lo = min(e.start for e in self.events)
            self.hi = max(e.end for e in self.events)
        self.devices = sorted({e.plane for e in self.events
                               if is_device(e.plane) and e.line == OPS})
        self._ops = {d: [e for e in self.events
                         if e.plane == d and e.line == OPS
                         and e.end > self.lo and e.start < self.hi]
                     for d in self.devices}
        self._busy = {d: clip(union((e.start, e.end) for e in ops),
                              self.lo, self.hi)
                      for d, ops in self._ops.items()}
        host = [e for e in self.events
                if is_host(e.plane) and e.name != WINDOW]
        self._python = [e for e in host if e.line.startswith("python")] \
            or host

    @property
    def window_s(self) -> float:
        return self.hi - self.lo

    @property
    def busy_s(self) -> Optional[float]:
        if not self.devices:
            return None
        return sum(total(b) for b in self._busy.values()) / len(self.devices)

    def idle_share(self) -> Optional[float]:
        busy = self.busy_s
        return None if busy is None else 1.0 - busy / self.window_s

    def module_durations(self, name: str) -> List[float]:
        """Device seconds of each call of module ``name`` that ran wholly
        inside the window, on every device."""
        return [e.dur for e in self.events
                if is_device(e.plane) and e.line == MODULES
                and module_name(e.name) == name
                and e.start >= self.lo and e.end <= self.hi]

    def top_ops(self, k: int) -> List[List]:
        per: Dict[str, float] = defaultdict(float)
        for ops in self._ops.values():
            for e in ops:
                per[op_name(e.name)] += e.dur
        n = max(len(self.devices), 1)
        ranked = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[name, s / n] for name, s in ranked]

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the first device inside the window."""
        if not self.devices:
            return []
        return subtract([(self.lo, self.hi)], self._busy[self.devices[0]])

    def gap_owner(self, lo: float, hi: float) -> str:
        """What the host's Python thread was doing in ``[lo, hi]``: the
        shortest event of that thread that overlaps at least half of the
        gap, else the one that overlaps it most; ``"host idle"`` where
        none does.  Its annotations and, where the profiler traces
        Python, its calls are on that thread."""
        best, most, inner = "host idle", 0.0, None
        for e in self._python:
            ov = min(e.end, hi) - max(e.start, lo)
            if ov <= 0:
                continue
            if ov > most:
                best, most = e.name, ov
            if 2 * ov >= hi - lo and (inner is None or e.dur < inner.dur):
                inner = e
        return inner.name if inner is not None else best

    def idle_gaps(self, k: int, short: float = 50e-6) -> List[List]:
        """Idle seconds of the first device, summed by the host event
        that each gap is named by, largest first.  Gaps under ``short``
        seconds lie between the ops of one program and are summed as
        one entry."""
        per: Dict[str, float] = defaultdict(float)
        for lo, hi in self.gaps():
            name = (f"between ops (<{short * 1e6:g} us)" if hi - lo < short
                    else self.gap_owner(lo, hi))
            per[name] += hi - lo
        ranked = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[name, s] for name, s in ranked]
