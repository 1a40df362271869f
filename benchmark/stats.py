"""Median and percentile arithmetic, with the sample-count rules.

A percentile is reported only with at least ten samples beyond it
(choosing-metrics, section 1): p99 needs 1,000 readings, p95 200.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence


class TooFewSamples(ValueError):
    pass


def median(xs: Sequence[float]) -> float:
    if not xs:
        raise TooFewSamples("median of nothing")
    s = sorted(xs)
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2.0


def min_samples(q: float, beyond: int = 10) -> int:
    """Readings needed so that `beyond` of them lie past quantile `q`."""
    return math.ceil(beyond / (1.0 - q) - 1e-9)


def percentile(xs: Sequence[float], q: float, beyond: int = 10) -> float:
    """The nearest-rank `q` quantile (0 < q < 1) of `xs`; raises
    TooFewSamples unless `beyond` readings lie past it."""
    need = min_samples(q, beyond)
    if len(xs) < need:
        raise TooFewSamples(
            f"p{q * 100:g} needs {need} samples, got {len(xs)}")
    s = sorted(xs)
    return s[min(math.ceil(q * len(s)) - 1, len(s) - 1)]


def percentile_or_none(xs: Sequence[float], q: float) -> Optional[float]:
    """For per-layer readers: nothing to report when the count is short."""
    try:
        return percentile(xs, q)
    except TooFewSamples:
        return None


def spread(xs: Sequence[float]) -> float:
    """Distance between the quartiles over the median (the driver's
    measure of run-to-run spread)."""
    s = sorted(xs)
    n = len(s)

    def at(p: float) -> float:
        k = p * (n - 1)
        lo = int(k)
        hi = min(lo + 1, n - 1)
        return s[lo] + (s[hi] - s[lo]) * (k - lo)

    return (at(0.75) - at(0.25)) / median(s)
