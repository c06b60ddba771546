"""Device time in the prefill programs over the prompt tokens prefilled in
the traced window, in ms per thousand tokens."""

from benchmark.lib import trace


def read(ctx, pattern):
    runs = trace.program_runs(ctx.trace, pattern)
    tokens = ctx.facts.get("prefilled_tokens_traced", 0)
    if not runs or not tokens:
        return None
    return sum(e.dur for e in runs) / 1e6 / (tokens / 1000.0)
