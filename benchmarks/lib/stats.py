"""Percentiles and medians over samples that may hold +inf (a failed or
timed-out request is placed at +inf, so it pushes a percentile up)."""
from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated ``q``-th percentile (0..100), numpy's default
    method, written out so +inf samples neither warn nor turn into nan.
    None on an empty sample."""
    xs = sorted(samples)
    if not xs:
        return None
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(xs) - 1)
    if math.isinf(xs[hi]):
        # interpolating towards +inf is +inf unless pos sits exactly on lo
        return xs[lo] if pos == lo else math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(samples: Sequence[float]) -> Optional[float]:
    return percentile(samples, 50.0)


def mean(samples: Iterable[float]) -> Optional[float]:
    xs = list(samples)
    return sum(xs) / len(xs) if xs else None


def spread(samples: Sequence[float]) -> Optional[float]:
    """Distance between the quartiles over the median: the measure the
    bounds in BENCHMARK.json were set from."""
    med = median(samples)
    if not med:
        return None
    return (percentile(samples, 75.0) - percentile(samples, 25.0)) / abs(med)
