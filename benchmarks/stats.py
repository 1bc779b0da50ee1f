"""Metric arithmetic of the benchmark: percentiles, schedule-anchored
latency with "failed = missed", generator lateness, run-to-run spread.

Kept here, under the benchmark's own directory, so no PR that claims a
gain can change how its numbers are computed.  The latency discipline is
``slo/loadgen.py``'s (latency from the *scheduled* send, so a stalled
server cannot hide behind a late generator), on exact samples instead of
histogram buckets.
"""

from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence

#: latency of an operation that was shed, refused, failed or never
#: answered: it missed every limit
MISSED = math.inf


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    percent of all samples at or below it.  ``MISSED`` samples sort
    last, so a tail that reaches into the failures reads ``inf``."""
    data = sorted(values)
    if not data:
        raise ValueError("percentile of no samples")
    if not 0 < q <= 100:
        raise ValueError(f"percentile {q} outside (0, 100]")
    rank = max(1, math.ceil(q / 100.0 * len(data)))
    return data[rank - 1]


def median(values: Iterable[float]) -> float:
    """Middle sample (mean of the middle two for an even count)."""
    data = sorted(values)
    if not data:
        raise ValueError("median of no samples")
    mid = len(data) // 2
    if len(data) % 2:
        return data[mid]
    return 0.5 * (data[mid - 1] + data[mid])


def latency_ms(due_s: float, done_s: Optional[float], ok: bool) -> float:
    """Milliseconds from when the operation was DUE to be sent to its
    answer; ``MISSED`` when it failed or was never answered."""
    if not ok or done_s is None:
        return MISSED
    return max(0.0, done_s - due_s) * 1e3


def finite_or(value: float, cap: float) -> float:
    """``value``, or ``cap`` when the tail reached into the failures —
    the result line carries JSON numbers, and ``cap`` is the longest any
    request of the run could have waited."""
    return value if math.isfinite(value) else cap


def lateness_ms(due_s: Sequence[float], sent_s: Sequence[float]
                ) -> List[float]:
    """How late the generator sent each operation (never negative: an
    operation is not sent before it is due)."""
    return [max(0.0, s - d) * 1e3 for d, s in zip(due_s, sent_s)]


def generator_was_late(late_ms: Sequence[float],
                       mean_interarrival_ms: float) -> bool:
    """A generator whose MEDIAN lateness exceeds 5 % of the mean
    inter-arrival time offered another load than the cell states."""
    return bool(late_ms) and median(late_ms) > 0.05 * mean_interarrival_ms


def spread(values: Sequence[float]) -> float:
    """Run-to-run spread as the driver reads it: the distance between
    the quartiles over the median."""
    data = sorted(values)
    iqr = _quantile(data, 0.75) - _quantile(data, 0.25) if data else 0.0
    if iqr == 0.0:
        return 0.0                  # one run, or runs that agree exactly
    mid = median(data)
    return iqr / mid if mid else math.inf


def _quantile(data: Sequence[float], q: float) -> float:
    """Linearly interpolated quantile of sorted ``data``."""
    pos = q * (len(data) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(data) - 1)
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)
