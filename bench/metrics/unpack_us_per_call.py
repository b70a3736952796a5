"""Host time the scan entry spends on results, per simulated call: the self
time of the program's ``fastpath.unpack`` spans in the window (copy back,
budget checks, per-cell unpack, the metrics fold)."""


def read(ctx):
    try:
        from repro.core import scan_phase_totals
    except ImportError:          # a program without the phase spans
        return None
    phase = scan_phase_totals().get("unpack")
    if phase is None or not ctx.calls:
        return None
    return phase["s"] / ctx.calls * 1e6
