"""Device peaks and the compulsory traffic of the event step.

The step's compulsory HBM traffic is what any implementation has to move:
each call's arrival record read once (submit time and processing time at
the clock width, function id as int32) and its result written once (start,
finish and priority at the clock width, node as int32).  It depends on
nothing but the number of calls and the clock width, so it reads the same
work whatever kernel does it.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
ARRIVAL_CLOCKS, RESULT_CLOCKS = 2, 3
ARRIVAL_INT32S, RESULT_INT32S = 1, 1


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peak table's entry for ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(path.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def compulsory_bytes(calls: int, clock_bytes: int) -> int:
    if clock_bytes not in (4, 8):
        raise ValueError(f"a clock is 4 or 8 bytes wide, not {clock_bytes}")
    per_call = ((ARRIVAL_CLOCKS + RESULT_CLOCKS) * clock_bytes
                + (ARRIVAL_INT32S + RESULT_INT32S) * 4)
    return int(calls) * per_call
