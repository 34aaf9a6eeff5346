"""From token stamps on the host clock to the serving metrics.

Every served token is stamped with ``time.perf_counter()`` as the
program appends it to its request's output, which it does after the
device has produced it.  A window runs from ``t0`` to ``deadline``; a
token counts when its stamp falls inside.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence

import numpy as np


@dataclass
class Served:
    """One request as the harness sees it."""
    prompt_len: int
    max_new: int
    times: List[float] = field(default_factory=list)

    def finished(self) -> bool:
        return len(self.times) >= self.max_new


def p95(values: Sequence[float]) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def window_stats(reqs: Sequence[Served], t0: float, deadline: float
                 ) -> Dict[str, object]:
    """Tokens and the gaps between consecutive tokens of one request,
    cut at ``deadline``."""
    tokens = 0
    gaps: List[float] = []
    for r in reqs:
        inside = [t for t in r.times if t0 <= t <= deadline]
        tokens += len(inside)
        gaps.extend(b - a for a, b in zip(inside, inside[1:]))
    return {"tokens": tokens, "seconds": deadline - t0, "itl_s": gaps}


def spread_notes(stats: Dict[str, object], served: Sequence[Served],
                 t0: float, slice_s: float = 6.0) -> Dict[str, object]:
    """What a window's noise is made of: quantiles of the gaps between
    tokens, and the tokens stamped in each ``slice_s`` of the window."""
    gaps = np.asarray(stats["itl_s"], np.float64) * 1e3
    q = (np.percentile(gaps, [50, 90, 95, 99]).tolist() + [gaps.max()]
         if gaps.size else [])
    n = max(1, int(np.ceil(stats["seconds"] / slice_s - 1e-9)))
    slices = [0] * n
    for r in served:
        for t in r.times:
            if t0 <= t <= t0 + stats["seconds"]:
                slices[min(int((t - t0) // slice_s), n - 1)] += 1
    return {"itl_ms_p50_p90_p95_p99_max": [round(x, 4) for x in q],
            "tokens_per_slice": slices, "slice_s": slice_s}


def serve_metrics(stats: Dict[str, object]) -> Dict[str, float]:
    """The end-to-end serving metrics of one window.  A tail with no
    sample is left out."""
    out = {"serve_tokens_per_s": stats["tokens"] / stats["seconds"]}
    if stats["itl_s"]:
        out["itl_p95_ms"] = 1e3 * p95(stats["itl_s"])
    return out
