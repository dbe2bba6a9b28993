"""Percentile and rate arithmetic — the yardstick's own copy.

Kept apart from the program's `ServeMetrics.pct` and `AverageMeter` so a
later PR to the program cannot move what a metric means. Everything here
is pure and takes plain lists; `benchmarks/tests/test_stats.py` checks it
on hand-made samples.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile, p in (0, 100]: the smallest sample with at
    least p % of the samples at or below it. No interpolation, so the
    answer is always a latency some request really had."""
    if not values:
        raise ValueError("percentile of no samples")
    if not 0 < p <= 100:
        raise ValueError(f"p must be in (0, 100], got {p}")
    ordered = sorted(values)
    rank = max(math.ceil(p / 100.0 * len(ordered)), 1)
    return float(ordered[rank - 1])


def mean(values: Sequence[float]) -> Optional[float]:
    return float(sum(values) / len(values)) if values else None


def weighted_mean(pairs: Sequence[tuple]) -> Optional[float]:
    """Mean of `value` weighted by `weight` over (value, weight) pairs;
    None when the weights sum to zero."""
    total = sum(w for _, w in pairs)
    if not total:
        return None
    return float(sum(v * w for v, w in pairs) / total)


def line_rate(lines: Sequence[dict], per_step: float) -> Optional[float]:
    """Work per second between the first and the last of `lines`, each a
    `{"step", "time"}` record: (steps between them) x `per_step` over the
    wall time between them. None with fewer than two lines or no time."""
    if len(lines) < 2:
        return None
    dt = lines[-1]["time"] - lines[0]["time"]
    if dt <= 0:
        return None
    return float((lines[-1]["step"] - lines[0]["step"]) * per_step / dt)
