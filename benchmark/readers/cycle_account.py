"""What the slow gaps between a user's tokens were made of, from the
engine's own account on its ``serve.deliver`` spans.

A flush that delivered tokens ends a *cycle*: every request it hands a token
whose lowest index is >= 1 (the span's ``gaps``) waited from the end of the
flush before to the end of this one.  The population is the judged
metric's: a cycle inside the traced window counts ``gaps`` times at its
length, and a flush that hands one request several tokens (index 0 and 1 in
the step that prefilled it, accepted drafts) adds ``tokens - requests`` gaps
of length zero, as the benchmark's clock sees them.  The *band* is the ranks
90-99 % of that population with fractional ownership, ``lib/clock.tail_mean``'s
rule.

``what``: ``tail_mean`` the mean length over the band, ms (the engine's own
reading of ``itl_tail_mean_ms`` over the traced seconds); ``prefill`` /
``decode`` / ``outside`` the share of the band's time that lay inside
``serve.prefill`` spans (other users' prompts), inside ``serve.decode``
spans (the step itself) and outside every ``serve.step`` (the caller, the
load generator, a frozen host), in percent — with the rest (admission,
delivery, the step's own time) they sum to 100; ``prefill_tokens`` the mean
over the band's gaps of the prompt tokens computed inside the gap (the
span's ``prefill_tokens``).

None where the program's ``serve.deliver`` carries no such facts (a parent
commit), or where fewer than two such flushes lie in the window."""

from benchmark.lib import program_spans, trace

BAND = (90.0, 99.0)
PARTS = {"prefill": "serve.prefill", "decode": "serve.decode"}
STEP = "serve.step"


def read(ctx, what):
    acc = _account(ctx)
    if acc is None:
        return None
    if what in ("tail_mean", "prefill_tokens"):
        return acc[what]
    return 100.0 * acc[what] / acc["time"] if acc["time"] > 0 else None


def _account(ctx):
    """One reduction a run, kept on the run's ``ctx`` for the five metrics
    that read it."""
    if not hasattr(ctx, "_cycle_account"):
        ctx._cycle_account = band_account(program_spans.of_run(),
                                          ctx.trace.window)
    return ctx._cycle_account


def cycles(spans, window):
    """``(start, end, facts)`` of every cycle that lies inside ``window``:
    from the end of one delivering flush to the end of the next.  A flush
    delivered where it carries the account (``seq``): one that handed
    nothing over notes ``gaps=0`` alone, and one that failed notes
    nothing."""
    lo, hi = window
    ends = [(s.end, dict(s.facts)) for s in spans
            if s.name == "serve.deliver" and s.start >= lo and s.end <= hi]
    ends = [(end, facts) for end, facts in ends if "seq" in facts]
    return [(a, b, facts) for (a, _), (b, facts) in zip(ends, ends[1:])]


def band_weights(lengths, counts, zeros, band=BAND):
    """How much of each cycle's ``counts`` gaps lies in the band of the
    population they make with ``zeros`` gaps of length zero: gap ``i`` of
    ``n`` owns the ranks [i/n, (i+1)/n).  Returns the weights in the order
    given and the band's width in gaps."""
    n = zeros + sum(counts)
    a, b = n * band[0] / 100.0, n * band[1] / 100.0
    weights, at = [0.0] * len(lengths), float(zeros)
    for i in sorted(range(len(lengths)), key=lambda i: lengths[i]):
        weights[i] = max(0.0, min(at + counts[i], b) - max(at, a))
        at += counts[i]
    return weights, b - a


def band_account(spans, window):
    found = cycles(spans, window)
    if not found:
        return None
    lengths = [b - a for a, b, _ in found]
    counts = [int(f["gaps"]) for _, _, f in found]
    # the first flush of the window ends no cycle inside it: its own
    # several-token requests are left out with it
    zeros = sum(int(f["tokens"]) - int(f["requests"]) for _, _, f in found)
    if zeros + sum(counts) == 0:
        return None
    weights, width = band_weights(lengths, counts, zeros)
    covered = {name: trace.union((s.start, s.end) for s in spans
                                 if s.name == name)
               for name in (*PARTS.values(), STEP)}
    acc = dict.fromkeys(("time", "outside", "prefill_tokens", *PARTS), 0.0)
    for w, (a, b, facts) in zip(weights, found):
        if w <= 0:
            continue
        acc["time"] += w * (b - a)
        for what, name in PARTS.items():
            acc[what] += w * trace.within(covered[name], (a, b))
        acc["outside"] += w * (b - a - trace.within(covered[STEP], (a, b)))
        acc["prefill_tokens"] += w * int(facts["prefill_tokens"])
    acc["tail_mean"] = acc["time"] / width / 1e6
    acc["prefill_tokens"] /= width
    return acc
