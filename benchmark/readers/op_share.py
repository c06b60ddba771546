"""Own device time of the operations matching ``pattern`` over the device's
busy time in the traced window, in percent."""

from benchmark.lib import trace


def read(ctx, pattern):
    busy = trace.busy_s(ctx.trace)
    t = trace.op_seconds(ctx.trace, pattern)
    return None if busy <= 0 or t <= 0 else 100.0 * t / busy
