"""Device idle time put on the program's own host spans.

The serving and training loops mark their phases with profiler spans
(``serve.sync``, ``serve.emit``, ``train.input``, ...), recorded on the
host thread whose clock the device events are aligned to.  Each idle
interval of a device can then be intersected with the spans the host was
in.  The span names are the program's; a program without them yields no
reading here.
"""

from __future__ import annotations

from typing import Collection, List, Optional, Tuple

from chipbench import trace as tr

Intervals = List[Tuple[float, float]]


def intersect(a: Intervals, b: Intervals) -> Intervals:
    """``a`` and ``b`` both; each a union (sorted, disjoint)."""
    return tr.subtract(a, tr.subtract(a, b))


def idle_in_spans(trace: Optional[tr.Trace],
                  names: Collection[str]) -> Optional[float]:
    """Seconds in which a device ran no operation while the host was
    inside a span named in ``names``, inside the traced window, averaged
    over the devices; None where the trace has no device or no such
    span."""
    if trace is None or not trace.devices:
        return None
    spans = tr.clip(tr.union((e.start, e.end) for e in trace.events
                             if tr.is_host(e.plane) and e.name in names),
                    trace.lo, trace.hi)
    if not spans:
        return None
    idle = []
    for d in trace.devices:
        busy = tr.clip(tr.union((e.start, e.end) for e in trace.events
                                if e.plane == d and e.line == tr.OPS),
                       trace.lo, trace.hi)
        gaps = tr.subtract([(trace.lo, trace.hi)], busy)
        idle.append(tr.total(intersect(gaps, spans)))
    return sum(idle) / len(idle)


def idle_share_in_spans(trace: Optional[tr.Trace],
                        names: Collection[str]) -> Optional[float]:
    """``idle_in_spans`` over the traced window, in percent."""
    s = idle_in_spans(trace, names)
    return None if s is None else 100.0 * s / trace.window_s
