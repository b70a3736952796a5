"""The event step's share of its HBM roofline: the compulsory bytes of
the window's calls over the step's device time at the chip's peak
bandwidth, in percent."""

from bench.roofline import compulsory_bytes


def read(ctx):
    if (ctx.trace is None or ctx.trace.step_s <= 0 or not ctx.traced_calls
            or ctx.peaks is None):
        return None
    need = compulsory_bytes(ctx.traced_calls, ctx.clock_bytes)
    return need / (ctx.trace.step_s * ctx.peaks["hbm_bytes_per_s"]) * 100.0
