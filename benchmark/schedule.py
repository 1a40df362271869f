"""Arrival schedules drawn from the seed (the open loop's due times)."""

from __future__ import annotations

import random
from typing import List


def poisson(seed: int, rate_per_s: float, seconds: float) -> List[float]:
    """Due times in [0, seconds) of a Poisson process of `rate_per_s`:
    exponential gaps from `random.Random(seed)`, so one seed gives one
    schedule, to the last bit."""
    if rate_per_s <= 0:
        raise ValueError("rate_per_s must be positive")
    rng = random.Random(f"arrivals:{seed}")
    out: List[float] = []
    t = rng.expovariate(rate_per_s)
    while t < seconds:
        out.append(t)
        t += rng.expovariate(rate_per_s)
    return out


def encode(times: List[float]) -> bytes:
    """The schedule as the plan file carries it (repr round-trips)."""
    return "\n".join(repr(t) for t in times).encode()
