"""Host time the scan entry spends issuing chunks to the device, per
simulated call: the self time of the program's ``fastpath.dispatch`` spans
in the window (input copies, plane init, step enqueue)."""


def read(ctx):
    try:
        from repro.core import scan_phase_totals
    except ImportError:          # a program without the phase spans
        return None
    phase = scan_phase_totals().get("dispatch")
    if phase is None or not ctx.calls:
        return None
    return phase["s"] / ctx.calls * 1e6
