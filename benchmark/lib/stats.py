"""Percentile arithmetic, copied from the program so that a later PR can
change `ray_tpu/loadgen/report.py` or `ray_tpu/util/metrics.py` and not the
yardstick (PERF.md, Open questions, lists the originals)."""

from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """q-th percentile (q in [0, 100]) with linear interpolation between
    order statistics (numpy's default method). None for no samples."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile must be in [0, 100], got {q}")
    if not samples:
        return None
    ordered = sorted(samples)
    pos = (q / 100.0) * (len(ordered) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return float(ordered[lo] + (pos - lo) * (ordered[hi] - ordered[lo]))


def percentile_from_buckets(
    boundaries: Sequence[float], buckets: Sequence[int], q: float
) -> Optional[float]:
    """q-th percentile from histogram bucket counts (`len(boundaries) + 1`
    of them, the last the overflow bucket), interpolated linearly inside the
    containing bucket; a percentile in the overflow bucket returns the
    highest boundary. None for an empty histogram."""
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"percentile must be in [0, 100], got {q}")
    if len(buckets) != len(boundaries) + 1:
        raise ValueError(
            f"{len(boundaries)} boundaries need {len(boundaries) + 1} "
            f"bucket counts, got {len(buckets)}"
        )
    total = sum(buckets)
    if total <= 0:
        return None
    rank = (q / 100.0) * total
    seen = 0
    for i, n in enumerate(buckets[:-1]):
        if n and seen + n >= rank:
            lo = 0.0 if i == 0 else boundaries[i - 1]
            share = min(max((rank - seen) / n, 0.0), 1.0)
            return lo + share * (boundaries[i] - lo)
        seen += n
    return float(boundaries[-1])


def histogram_window(before: dict, after: dict) -> dict:
    """The observations a cumulative histogram took between two snapshots
    (`{"boundaries", "buckets", "sum", "count"}` each)."""
    if before["boundaries"] != after["boundaries"]:
        raise ValueError("histogram boundaries changed between snapshots")
    return {
        "boundaries": list(after["boundaries"]),
        "buckets": [a - b for a, b in zip(after["buckets"], before["buckets"])],
        "sum": after["sum"] - before["sum"],
        "count": after["count"] - before["count"],
    }
