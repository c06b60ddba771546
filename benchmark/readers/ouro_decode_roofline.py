"""How close a looped model's decode program comes to its HBM floor: over
the traced window's ``serve.decode`` spans that launched the decode program
(``rode`` 0) and the executions of that program (``pattern``) that lie in
them, the least time by ``lib/ouro_cost.py`` (every layer's weights once a
pass, the head once, and ``kv_tokens`` cached tokens' K/V a step) over the
program's device time.  None on a configuration that is not a looped model
or where the program notes no ``kv_tokens``."""

from benchmark.lib import ouro_cost, program_spans, trace


def read(ctx, pattern):
    if ctx.peaks is None or "total_ut_steps" not in ctx.config:
        return None
    lo, hi = ctx.trace.window
    spans = [s for s in program_spans.of_run()
             if s.name == "serve.decode" and lo <= s.start and s.end <= hi
             and int(dict(s.facts).get("rode", 1)) == 0
             and int(dict(s.facts).get("kv_tokens", 0)) > 0]
    steps = kv = 0
    took = 0.0
    for run in trace.program_runs(ctx.trace, pattern):
        span = next((s for s in spans
                     if s.start <= run.start and run.end <= s.end), None)
        if span is not None:
            steps += 1
            kv += int(dict(span.facts)["kv_tokens"])
            took += run.dur / 1e9
    if steps == 0 or took <= 0:
        return None
    return 100.0 * ouro_cost.decode_least_seconds(
        ctx.config, ctx.peaks, steps, kv) / took
