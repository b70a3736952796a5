"""Peak device memory in use by the end of the window, in MB (1e6 bytes)."""


def read(ctx):
    if ctx.peak_bytes is None:
        return None
    return ctx.peak_bytes / 1e6
