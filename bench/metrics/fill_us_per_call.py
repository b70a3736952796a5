"""Host time the program spends filling bucket inputs, per simulated call:
the summed ``build_s`` of the window's bucket records."""


def read(ctx):
    if not ctx.records or not ctx.calls:
        return None
    return sum(r["build_s"] for r in ctx.records) / ctx.calls * 1e6
