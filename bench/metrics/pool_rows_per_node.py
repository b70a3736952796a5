"""Container-table rows a node carries through the event step, averaged
over the cell-nodes dispatched on the table: the rows every step scans,
padded cells included."""


def read(ctx):
    try:
        from repro.core import scan_phase_totals
    except ImportError:          # a program without the step counters
        return None
    totals = scan_phase_totals()
    if not totals.get("pool_rows") or not totals.get("pool_node_slots"):
        return None
    return totals["pool_rows"] / totals["pool_node_slots"]
