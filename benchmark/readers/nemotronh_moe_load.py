"""How the decode steps' tokens fell on the routed experts this chip holds,
for a ``nemotron_h`` configuration: ``readers/moe_load.py`` with the expert
blocks counted from ``hybrid_override_pattern`` (that reader counts them
from DeepSeek-V3's ``first_k_dense_replace``).  ``stat`` "mean": (token,
expert) pairs a held expert a block a step; "imbalance": the fullest
expert's pairs over that mean, averaged over the steps that routed anything
here.  None where the configuration is of another family or the program
records no such fact."""

from benchmark.lib import nemotron_h_cost
from benchmark.readers.moe_load import facts_in_window


def read(ctx, stat):
    if "hybrid_override_pattern" not in ctx.config:
        return None
    slots = nemotron_h_cost.held_experts(ctx.config) \
        * nemotron_h_cost.blocks(ctx.config, "E")
    steps = facts_in_window(ctx, ("serve.decode",),
                            ("moe_pairs", "moe_max_load"))
    means = [f["moe_pairs"] / slots for f in steps]
    if stat == "mean":
        return sum(means) / len(means) if means else None
    ratios = [f["moe_max_load"] / m for f, m in zip(steps, means) if m > 0]
    return sum(ratios) / len(ratios) if ratios else None
