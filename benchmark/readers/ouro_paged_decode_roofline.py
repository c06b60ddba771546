"""The page walk's share of its roofline in a looped model: the cached
tokens the window's decode queries read (``kv_tokens`` of each
``serve.decode`` span that launched the decode program, ``kv_tokens_decode``
of each ``serve.prefill`` span whose launch carried the decode rows), at
``lib/ouro_cost.py``'s bytes and operations, over the device time of the
kernel (``pattern``).  None on a configuration that is not a looped model
or where the program notes no such fact."""

from benchmark.lib import ouro_cost, trace
from benchmark.readers.moe_load import facts_in_window


def read(ctx, pattern):
    if ctx.peaks is None or "total_ut_steps" not in ctx.config:
        return None
    took = trace.op_seconds(ctx.trace, pattern)
    tokens = sum(f["kv_tokens"] for f in facts_in_window(
        ctx, ("serve.decode",), ("kv_tokens",))) \
        + sum(f["kv_tokens_decode"] for f in facts_in_window(
            ctx, ("serve.prefill",), ("kv_tokens_decode",)))
    if took <= 0 or tokens <= 0:
        return None
    least = ouro_cost.attention_least_seconds(ctx.config, ctx.peaks, tokens)
    return 100.0 * least["seconds"] / took
