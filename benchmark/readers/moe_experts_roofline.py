"""The grouped expert matmul's share of its roofline.  The kernel runs in
the decode and in the prefill program, so the least time for the traced
window is that of every ``serve.decode`` and ``serve.prefill`` span inside
it, by ``lib/moe_cost.py``: the weights of the held experts that got a token
(``moe_experts_hit``: summed over expert layers and launches) over the
chip's peak HBM bytes/s, or the (token, expert) pairs' operations
(``moe_pairs``) over its peak bf16 FLOP/s, whichever is larger.  The share
is that over the kernel's own device time in the window.  None where the
program has no such kernel or facts."""

from benchmark.lib import moe_cost, trace
from benchmark.readers.moe_load import facts_in_window


def read(ctx, pattern):
    if ctx.peaks is None or "moe_intermediate_size" not in ctx.config:
        return None
    took = trace.op_seconds(ctx.trace, pattern)
    facts = facts_in_window(ctx, ("serve.decode", "serve.prefill"),
                            ("moe_experts_hit", "moe_pairs"))
    if took <= 0 or not facts:
        return None
    least = moe_cost.least_seconds(
        ctx.config, ctx.peaks, sum(f["moe_experts_hit"] for f in facts),
        sum(f["moe_pairs"] for f in facts))
    return None if least["seconds"] <= 0 else 100.0 * least["seconds"] / took
