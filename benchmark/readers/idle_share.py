"""1 - (union of the device-operation intervals) / traced window, mean of
the chips, in percent."""

from benchmark.lib import trace


def read(ctx):
    share = trace.idle_share(ctx.trace)
    return None if share is None else 100.0 * share
