"""JAX compilations inside the measured window, a backend compile or a
load from the persistent cache each; warm-up covers every shape, so this
reads 0."""


def read(ctx):
    return ctx.window_compiles
