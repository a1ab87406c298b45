"""Exact order statistics over raw samples (no histogram buckets)."""
from __future__ import annotations


def percentile(vals, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``% of
    the samples at or below it. ``q`` may be fractional; it is scaled to
    1e-4 resolution in integer arithmetic so float noise cannot move the
    rank."""
    if not vals:
        raise ValueError("percentile of no samples")
    s = sorted(vals)
    qi = int(round(q * 10_000))
    rank = min(len(s), max(1, -(-len(s) * qi // 1_000_000)))
    return float(s[rank - 1])

