"""Median device time of one execution of the compiled program whose name
matches ``pattern`` (the trace's ``XLA Modules`` line), ms."""

from benchmark.lib import clock, trace


def read(ctx, pattern):
    runs = trace.program_runs(ctx.trace, pattern)
    return None if not runs else clock.median([e.dur for e in runs]) / 1e6
