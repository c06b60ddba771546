"""Median length of the program's span ``span`` (``lib/program_spans``)
inside the traced window, ms.  A span around a call that returns before the
device ends (``train.step``) is host time.  None where the program records
no such span."""

from benchmark.lib import clock, program_spans


def read(ctx, span):
    return clock.median(program_spans.lengths_ms(
        ctx.trace, program_spans.of_run(), span))
