"""Share of the dispatched event steps that belong to no call, in percent:
1 - the real calls' arrival and completion steps over every cell slot x
step dispatched, padded cells and padded rows included."""


def read(ctx):
    try:
        from repro.core import scan_phase_totals
    except ImportError:          # a program without the step counters
        return None
    totals = scan_phase_totals()
    if not totals.get("step_slots"):
        return None
    return 100.0 * (1.0 - totals["call_steps"] / totals["step_slots"])
