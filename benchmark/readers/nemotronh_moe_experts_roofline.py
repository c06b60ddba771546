"""The grouped expert matmul's share of its roofline for a ``nemotron_h``
configuration: the construction of ``readers/moe_experts_roofline.py`` with
the counts of ``lib/nemotron_h_cost.py`` (two matrices an expert; that
reader's cost function counts three, under DeepSeek-V3's key names).  Over
every ``serve.decode`` and ``serve.prefill`` span inside the traced window:
the weights of the held experts that got a token (``moe_experts_hit``) over
the chip's peak HBM bytes/s, or the (token, expert) pairs' operations
(``moe_pairs``) over its peak bf16 FLOP/s, whichever is larger, over the
kernel's own device time in the window.  None where the configuration is of
another family, or the program has no such kernel or facts."""

from benchmark.lib import nemotron_h_cost, trace
from benchmark.readers.moe_load import facts_in_window


def read(ctx, pattern):
    if ctx.peaks is None or "hybrid_override_pattern" not in ctx.config:
        return None
    took = trace.op_seconds(ctx.trace, pattern)
    facts = facts_in_window(ctx, ("serve.decode", "serve.prefill"),
                            ("moe_experts_hit", "moe_pairs"))
    if took <= 0 or not facts:
        return None
    least = nemotron_h_cost.experts_least_seconds(
        ctx.config, ctx.peaks, sum(f["moe_experts_hit"] for f in facts),
        sum(f["moe_pairs"] for f in facts))
    return None if least["seconds"] <= 0 else 100.0 * least["seconds"] / took
