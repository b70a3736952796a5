"""Share of the traced window in which no operation ran on the device, in
percent."""


def read(ctx):
    if (ctx.trace is None or ctx.trace.devices == 0
            or ctx.trace.window_s <= 0):
        return None
    return ctx.trace.idle_share * 100.0
