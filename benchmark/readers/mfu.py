"""Model FLOP/s utilization: the operations the forward and backward passes
need per token (the benchmark's own count, lib/flops.py; recomputation not
counted) times tokens/s/chip, over the chip's published bf16 peak."""

from benchmark.lib import flops


def read(ctx):
    rate = ctx.end_to_end.get("train_tok_s_chip")
    if rate is None or ctx.peaks is None:
        return None
    per_token = flops.train_flops_per_token(ctx.config,
                                            ctx.traffic["train"]["seq"])
    return 100.0 * per_token * rate / ctx.peaks["flops_bf16"]
