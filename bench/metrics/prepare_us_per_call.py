"""Host time the scan entry spends preparing cells, per simulated call: the
self time of the program's ``fastpath.prepare`` spans in the window (arrival
features, eligibility, bucketing)."""


def read(ctx):
    try:
        from repro.core import scan_phase_totals
    except ImportError:          # a program without the phase spans
        return None
    phase = scan_phase_totals().get("prepare")
    if phase is None or not ctx.calls:
        return None
    return phase["s"] / ctx.calls * 1e6
