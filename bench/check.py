"""The comparison that decides ``correct``.

Completion clocks are compared call by call as the gap relative to the
reference's clock, or to one second where the clock is smaller; counters
exactly.  A cell compares the numbers its traffic file gives a limit.
"""

from __future__ import annotations

import numpy as np

def clock_gap(c: np.ndarray, want: np.ndarray) -> float:
    """Widest relative gap over the calls both sides completed."""
    c, want = np.asarray(c, dtype=np.float64), np.asarray(want, np.float64)
    both = np.isfinite(c) & np.isfinite(want)
    if not both.any():
        return 0.0
    return float(np.max(np.abs(c[both] - want[both])
                        / np.maximum(np.abs(want[both]), 1.0)))


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value": v, "limit": l}})`` over the numbers
    that have a limit; one above its limit, or not finite, fails."""
    shown = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in shown.values())
    return bool(ok), shown
