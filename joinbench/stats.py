"""The arithmetic of the numbers: whole-window rates, never medians of
chunks, and the median that the per-layer timers report."""

from __future__ import annotations

import statistics
from typing import Optional, Sequence


def rate(work: float, seconds: float) -> Optional[float]:
    """All the work over all the time; None for an empty window."""
    if seconds <= 0 or work <= 0:
        return None
    return work / seconds


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None
