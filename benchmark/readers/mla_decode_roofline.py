"""The absorbed-MLA page-walk decode kernel's share of its roofline.  The
least time for the traced window is that of the cached tokens its decode
steps' queries attended over (``latent_tokens`` of each ``serve.decode``
span: summed over rows and latent layers, one query token a row) by
``lib/mla_cost.py``: the larger of their latent rows' bytes over the chip's
peak HBM bytes/s and their score and value operations over its peak bf16
FLOP/s.  The share is that over the kernel's own device time in the window.
Only spans whose decode program ran inside the window are counted, as only
those kernel events are.  None where the program has no such kernel or
fact."""

from benchmark.lib import mla_cost, trace
from benchmark.readers.moe_load import facts_in_window


def read(ctx, pattern):
    if ctx.peaks is None or "kv_lora_rank" not in ctx.config:
        return None
    took = trace.op_seconds(ctx.trace, pattern)
    tokens = sum(f["latent_tokens"] for f in facts_in_window(
        ctx, ("serve.decode",), ("latent_tokens",)))
    if took <= 0 or tokens <= 0:
        return None
    least = mla_cost.least_seconds(ctx.config, ctx.peaks, tokens)
    return 100.0 * least["seconds"] / took
