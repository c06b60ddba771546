"""The host's own time in one scheduler step: the benchmark's span around
``eng.step()`` less the time the device was busy inside it, median, ms."""

from benchmark.lib import clock, trace


def read(ctx, span):
    return clock.median(trace.span_host_ms(ctx.trace, span))
