"""End-to-end arithmetic over one run's host-clock samples.

A tail is the percentile of every sample the window gave, never a
median of chunks; a rate is all the work of the window over all of its
time.  Percentiles use the nearest-rank rule, so a reported tail is one
of the samples.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence


def percentile(samples: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of all samples."""
    if not samples:
        raise ValueError("no samples")
    xs = sorted(samples)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[rank - 1])


def ttft_samples(due: Dict[int, float], first: Dict[int, float]
                 ) -> List[float]:
    """Seconds from when each request was due to its first token."""
    return [first[rid] - t for rid, t in due.items() if rid in first]


def itl_samples(emits: Dict[int, List[float]], t_open: float,
                t_close: float) -> List[float]:
    """Every gap between consecutive tokens of one request whose both
    ends fall inside ``[t_open, t_close]``."""
    out: List[float] = []
    for times in emits.values():
        inside = [t for t in times if t_open <= t <= t_close]
        out.extend(b - a for a, b in zip(inside, inside[1:]))
    return out


def tokens_in(emits: Dict[int, List[float]], t_open: float,
              t_close: float) -> int:
    return sum(1 for times in emits.values() for t in times
               if t_open <= t <= t_close)


def rate(count: float, t_open: float, t_close: float) -> float:
    return count / (t_close - t_open)
