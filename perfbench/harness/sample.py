"""What a generator hands back, and the statistics taken over it."""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence


@dataclasses.dataclass
class Sample:
    """One run's client-side record.

    ``completions`` / ``latencies_ms`` are the acknowledged operations
    whose reply arrived inside the window (monotonic stamp, latency from
    request written to reply read); ``all_completions`` every
    acknowledged reply since the generator started, sorted (the timeline
    file and the longest gap are taken from it). ``attempted`` and
    ``failed`` count operations SENT inside the window.
    ``unresolved_total`` counts operations since start with no ``+OK``
    (failed, severed or never answered): each may or may not have been
    applied. ``acked`` is the plain reference: key -> value of every
    acknowledged write since start."""

    completions: List[float]
    latencies_ms: List[float]
    all_completions: List[float]
    attempted: int
    failed: int
    unresolved_total: int
    acked: Dict[bytes, bytes]
    report: Dict[str, Optional[float]]


def percentile(values: Sequence[float], q: float) -> float:
    """``q`` in [0, 100], linear interpolation between order statistics
    (numpy's default)."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def longest_gap(stamps: Sequence[float], t0: float, t1: float):
    """Longest time without a completion inside [t0, t1]: (seconds,
    where it started relative to t0)."""
    inside = [t for t in stamps if t0 <= t <= t1]
    edges = [t0] + inside + [t1]
    gap, at = max(((b - a, a) for a, b in zip(edges, edges[1:])),
                  default=(0.0, t0))
    return gap, at - t0
