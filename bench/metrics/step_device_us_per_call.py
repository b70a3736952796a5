"""Device time of the event-step programs per simulated call, from the
profiler trace of the window."""


def read(ctx):
    if ctx.trace is None or ctx.trace.step_s <= 0 or not ctx.traced_calls:
        return None
    return ctx.trace.step_s / ctx.traced_calls * 1e6
