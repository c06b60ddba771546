"""How the decode steps' tokens fell on the routed experts this chip holds,
from the ``moe_pairs`` and ``moe_max_load`` facts of the ``serve.decode``
spans inside the traced window.  ``stat`` "mean": (token, expert) pairs a
held expert a layer a step; "imbalance": the fullest expert's pairs over
that mean, averaged over the steps that routed anything here.  None where
the program records no such fact."""

from benchmark.lib import moe_cost, program_spans


def facts_in_window(ctx, names, wanted):
    """The facts of every span of those names that lies inside the traced
    window and carries every wanted fact, as ints."""
    lo, hi = ctx.trace.window
    out = []
    for s in program_spans.of_run():
        if s.name in names and s.start >= lo and s.end <= hi:
            facts = dict(s.facts)
            if all(k in facts for k in wanted):
                out.append({k: int(facts[k]) for k in wanted})
    return out


def read(ctx, stat):
    if "moe_intermediate_size" not in ctx.config:
        return None
    slots = moe_cost.held_experts(ctx.config) \
        * moe_cost.expert_layers(ctx.config)
    steps = facts_in_window(ctx, ("serve.decode",),
                            ("moe_pairs", "moe_max_load"))
    means = [f["moe_pairs"] / slots for f in steps]
    if stat == "mean":
        return sum(means) / len(means) if means else None
    ratios = [f["moe_max_load"] / m for f, m in zip(steps, means) if m > 0]
    return sum(ratios) / len(ratios) if ratios else None
