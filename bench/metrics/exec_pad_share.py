"""Share of the event steps the step runs that belong to no call, in
percent: 1 - the real calls' arrival and completion steps over the steps
executed.  The vmapped jnp scan runs every slot it is dispatched; a step
that runs each cell's own steps reads near 0."""


def read(ctx):
    try:
        from repro.core import scan_phase_totals
    except ImportError:          # a program without the step counters
        return None
    totals = scan_phase_totals()
    if not totals.get("exec_steps"):
        return None
    return 100.0 * (1.0 - totals["call_steps"] / totals["exec_steps"])
