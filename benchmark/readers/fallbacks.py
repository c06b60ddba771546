"""Sum of the program's ``kernel_fallback.*`` counters: Pallas gates that
took the XLA path while the cell's programs were traced (0 is the aim)."""


def read(ctx):
    return float(sum(ctx.fallbacks.values()))
