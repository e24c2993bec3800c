"""Percentiles, rates and spreads: the arithmetic every metric goes through.

Kept apart from the program's `engine/metrics.py`, whose percentiles are
interpolated inside histogram buckets; these work on the raw samples.
"""

from __future__ import annotations

import math
import statistics
from typing import Iterable, Sequence


def percentile(values: Iterable[float], q: float) -> float | None:
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks (numpy's default method); None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * (q / 100.0)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values: Iterable[float]) -> float | None:
    return percentile(values, 50.0)


def rate(count: float, seconds: float) -> float | None:
    """`count` things in `seconds`; None when the window has no length."""
    if seconds <= 0:
        return None
    return count / seconds


def share_pct(part: float, whole: float) -> float | None:
    """`part` as a percentage of `whole`; None when there is no whole."""
    if whole <= 0:
        return None
    return 100.0 * part / whole


def time_per_output_token(first_s: float, last_s: float,
                          output_tokens: int) -> float | None:
    """(last frame - first frame) / (tokens - 1): the mean gap between a
    request's output tokens as its client saw them. A request of fewer than
    two tokens has no gap."""
    if output_tokens < 2:
        return None
    return (last_s - first_s) / (output_tokens - 1)


def spread(values: Sequence[float]) -> float | None:
    """The distance between the first and third quartile as a share of the
    median, quartiles as `statistics.quantiles(values, n=4)` gives them —
    the figure a bound is set from (about five times the widest)."""
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    if q2 == 0:
        return None
    return (q3 - q1) / abs(q2)
