"""Seeded traffic: one general generator, driven by a traffic file.

A traffic file fixes the SCHEDULE: request sizes and arrival gaps, in order,
drawn from its own ``population_seed``.  ``--seed`` fills in the token values
(and, in the builders, the weights).  So every seed offers the same requests
at the same times with other contents: runs with different seeds do the same
work, and a difference between them is noise, not traffic.  (The first sets
of PR 23 permuted the schedule by the seed: which long prompts met moved
the gap tail (``itl_p95_ms``, judged then; ``itl_tail_mean_ms`` is since
PR 33) by +-5 % between seeds, where two runs of one seed agreed to 2 %.
The order is part of the work.)

Length distributions (``dist``): ``lognormal`` (``median``, ``sigma``),
``uniform``, ``fixed`` (``value``); all clipped to ``min``..``max``.
Arrivals: gaps are gamma-distributed with coefficient of variation ``cv``
(1 = Poisson, > 1 = bursty), scaled so that they fill the span exactly.
``prefix`` (optional): ``{"groups": g, "len": {...}}`` makes every prompt
start with one of ``g`` fixed token strings, for prefix-cache mixes."""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Req:
    due_s: float            # offset from the start of the ramp (open loop)
    prompt: np.ndarray      # int32 token ids
    want: int               # tokens to generate


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent streams of one ``--seed`` (any whole number)."""
    return np.random.default_rng([int(seed) % (1 << 63), *stream])


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec["dist"]
    if dist == "lognormal":
        x = np.exp(np.log(spec["median"]) + spec["sigma"]
                   * rng.standard_normal(n))
    elif dist == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n).astype(float)
    elif dist == "fixed":
        x = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", spec.get("value"))
    hi = spec.get("max", spec.get("value"))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gaps(spec: dict, n: int, span_s: float,
         rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps that sum to ``span_s``."""
    cv = float(spec.get("cv", 1.0))
    shape = 1.0 / (cv * cv)
    g = rng.gamma(shape, 1.0 / shape, n)
    return g * (span_s / g.sum())


def tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(1, vocab, int(n)).astype(np.int32)


def population(traffic: dict, n: int):
    """The fixed set of sizes every seed works through."""
    rng = rng_for(traffic["population_seed"], 0)
    return (lengths(traffic["prompt_len"], n, rng),
            lengths(traffic["output_len"], n, rng))


def _prefixes(traffic: dict, vocab: int) -> Optional[List[np.ndarray]]:
    spec = traffic.get("prefix")
    if not spec:
        return None
    rng = rng_for(traffic["population_seed"], 1)
    return [tokens(rng, n, vocab)
            for n in lengths(spec["len"], spec["groups"], rng)]


def requests(traffic: dict, seed: int, n: int, vocab: int,
             span_s: Optional[float] = None) -> List[Req]:
    """The traffic file's ``n`` requests, filled with this seed's tokens.
    With ``span_s`` (open loop) they are due at Poisson/gamma times that
    fill the span; without it (closed loop) every ``due_s`` is 0 and the
    runner sends them as clients come free."""
    p_len, o_len = population(traffic, n)
    if span_s is None:
        due = np.zeros(n)
    else:
        g = gaps(traffic["arrivals"], n, span_s,
                 rng_for(traffic["population_seed"], 2))
        due = np.cumsum(g) - g            # the first is due at 0
    fill = rng_for(seed, 1)
    prefixes = _prefixes(traffic, vocab)
    out = []
    for i in range(n):
        prompt = tokens(fill, p_len[i], vocab)
        if prefixes:
            pre = prefixes[int(fill.integers(len(prefixes)))][:len(prompt) - 1]
            prompt[:len(pre)] = pre
        out.append(Req(float(due[i]), prompt, int(o_len[i])))
    return out
