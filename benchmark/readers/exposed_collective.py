"""Time a collective runs on a chip and no other operation does, over the
traced window, mean of the chips, in percent."""

from benchmark.lib import trace


def read(ctx):
    w = trace.window_s(ctx.trace)
    if w <= 0 or ctx.chips < 2:
        return None
    return 100.0 * trace.exposed_collective_s(ctx.trace) / w
