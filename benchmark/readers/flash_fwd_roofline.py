"""The flash-attention forward kernel's share of its roofline: the least
time the chip could take for one call — the larger of operations over peak
FLOP/s and bytes over peak HBM bytes/s, both from lib/flops.py at this
chip's share of the cell's shapes — over the median device time of a call."""

from benchmark.lib import clock, flops, trace


def read(ctx, pattern):
    events = trace.op_events(ctx.trace, pattern)
    if not events or ctx.peaks is None:
        return None
    shape = flops.shape_of(ctx.config)
    t = ctx.traffic["train"]
    split = ctx.traffic.get("mesh", {})
    mp, data = split.get("mp", 1), split.get("dp", 1)
    ops, nbytes = flops.flash_fwd_ops_bytes(
        t["batch"] // data, t["seq"], shape["heads"] // mp,
        max(shape["kv_heads"] // mp, 1), shape["head_dim"])
    least, _bound = flops.roofline_s(ops, nbytes, ctx.peaks)
    took = clock.median([e.dur for e in events]) / 1e9
    return 100.0 * least / took
