"""Share of the container-table rows dispatched that no container ever
filled, in percent: 1 - the real cell-nodes' peak resident containers over
the table rows dispatched, padded cells and nodes included."""


def read(ctx):
    try:
        from repro.core import scan_phase_totals
    except ImportError:          # a program without the step counters
        return None
    totals = scan_phase_totals()
    if not totals.get("pool_rows") or not totals.get("pool_peak"):
        return None
    return 100.0 * (1.0 - totals["pool_peak"] / totals["pool_rows"])
