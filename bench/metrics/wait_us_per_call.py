"""Time the host spends blocked on the device, per simulated call: the self
time of the program's ``fastpath.wait`` spans in the window."""


def read(ctx):
    try:
        from repro.core import scan_phase_totals
    except ImportError:          # a program without the phase spans
        return None
    phase = scan_phase_totals().get("wait")
    if phase is None or not ctx.calls:
        return None
    return phase["s"] / ctx.calls * 1e6
