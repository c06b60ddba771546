"""Share of the traced window in which the first chip sat idle while the
host was inside one of ``spans`` (the program's own, ``lib/program_spans``),
in percent.  Each idle instant goes to the DEEPEST covering span that some
metric of this reader lists, so the metrics partition the idle time: with
the one whose ``spans`` is empty, which reads what no listed span covers
(the step's own time, the runner between steps), they sum to the device's
idle share.  None where the program records no such span."""

from benchmark.lib import program_spans, trace


def read(ctx, spans):
    idle = _idle_by_span(ctx)
    if idle is None:
        return None
    names = spans or [program_spans.REST]
    return 100.0 * sum(idle.get(n, 0.0) for n in names) \
        / (trace.window_s(ctx.trace) * 1e9)


def _idle_by_span(ctx):
    """One reduction a run, kept on the run's ``ctx`` for the seven metrics
    that read it (a 6 s trace holds 10^5 busy intervals)."""
    if not hasattr(ctx, "_idle_by_span"):
        recorded = program_spans.of_run()
        usable = recorded and ctx.trace.devices \
            and trace.window_s(ctx.trace) > 0
        ctx._idle_by_span = program_spans.idle_by_span(
            ctx.trace, recorded, program_spans.listed_spans()) \
            if usable else None
    return ctx._idle_by_span
