"""Arithmetic over what a measured window recorded.

Every streamed token is one ``(time_s, request, index)`` record on the
host clock.  A rate is the tokens streamed inside the window over the whole
window; the gap between tokens is taken over every pair of consecutive
tokens of every request, both inside the window.
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Sequence, Tuple


def tokens_per_s(token_times: Iterable[float], t0: float, t1: float) -> float:
    n = sum(1 for t in token_times if t0 <= t <= t1)
    return n / (t1 - t0)


def inter_token_gaps(per_request: Dict[int, Sequence[float]], t0: float,
                     t1: float) -> List[float]:
    gaps = []
    for times in per_request.values():
        inside = [t for t in times if t0 <= t <= t1]
        gaps += [b - a for a, b in zip(inside, inside[1:])]
    return gaps


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of a non-empty sample."""
    if not values:
        raise ValueError("no samples")
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def split_window(events: Iterable[Tuple[float, int]], t0: float,
                 t1: float) -> Tuple[List[float], Dict[int, List[float]]]:
    """``(time, request)`` stream records -> all times, and times per request."""
    times, per = [], {}
    for t, r in events:
        times.append(t)
        per.setdefault(r, []).append(t)
    return times, per
