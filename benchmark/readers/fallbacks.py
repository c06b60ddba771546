"""Sum of the program's ``kernel_fallback.<kernel>.<reason>`` counters: Pallas
gates that took the XLA path while the cell's programs were traced (0 is the
aim).  The program counts every refusal under that name and again under
``kernel_fallback.total``; a refusal is one refusal, so the total is left
out."""

TOTAL = "kernel_fallback.total"


def read(ctx):
    return float(sum(v for k, v in ctx.fallbacks.items() if k != TOTAL))
