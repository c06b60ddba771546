"""What the serving runners share: the client-side record of every token,
the warm-up that doubles as the correctness sample, and the arithmetic from
token times to the end-to-end metrics.

The clock is the benchmark's own, read in ``on_token`` — the engine calls it
when a token becomes visible to the client (after the step's journal flush).
First-token time is taken from when a request was DUE (open loop) or sent
(closed loop), so the wait a stall imposes on later arrivals counts; the
engine's ``SLOMeter`` times from ``submit`` and is not the source."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from . import checks, clock, generators


@dataclasses.dataclass
class Sent:
    """One request as the client sees it."""
    req: generators.Req
    due_t: float                    # when it should have been sent
    sent_t: Optional[float] = None
    rid: Optional[int] = None
    admit_t: Optional[float] = None  # the engine's RequestClock.admit_t
    times: List[float] = dataclasses.field(default_factory=list)
    toks: List[int] = dataclasses.field(default_factory=list)
    error: Optional[str] = None

    @property
    def done(self) -> bool:
        return len(self.toks) >= self.req.want

    @property
    def finished_t(self) -> Optional[float]:
        return self.times[-1] if self.done else None


class Client:
    """Submits requests and records their tokens.  ``engine(on_token)``
    builds the engine with this client's sink."""

    def __init__(self, make_engine):
        self.by_rid: Dict[int, Sent] = {}
        self.live = 0
        self.newly_done: List[Sent] = []
        self.eng = make_engine(self._on_token)

    def _on_token(self, rid, idx, tok) -> None:
        s = self.by_rid[rid]
        if not s.times:
            # the engine's own stamp of when it admitted the request, on
            # the same monotonic clock; gone once the request finishes
            s.admit_t = self.eng.meter.clock(rid).admit_t
        s.times.append(clock.now())
        s.toks.append(int(tok))
        if len(s.toks) == s.req.want:
            self.live -= 1
            self.newly_done.append(s)

    def send(self, req: generators.Req, due_t: float) -> Sent:
        from paddle_tpu.serving.admission import Overloaded

        s = Sent(req, due_t, sent_t=clock.now())
        try:
            s.rid = self.eng.submit(req.prompt, max_new_tokens=req.want)
        except (Overloaded, ValueError) as e:
            s.error = f"{type(e).__name__}: {e}"
            return s
        self.by_rid[s.rid] = s
        self.live += 1
        return s

    def take_done(self) -> List[Sent]:
        out, self.newly_done = self.newly_done, []
        return out


# -- the correctness sample, taken while warming up ---------------------------
def warm_up_sample(client: Client, traffic: dict, seed: int, vocab: int,
                   step) -> List[dict]:
    """Serve ``check.prompts`` seeded requests one at a time on the idle
    engine (which compiles or loads both programs: this IS the warm-up) and
    keep, for each decode step, the engine's own logits row of the request.
    The row is found without reading the engine's tables: it is the one row
    whose argmax is the delivered token at every step."""
    spec = traffic["check"]
    rng = generators.rng_for(seed, 2)
    lens = generators.lengths(traffic["prompt_len"], spec["prompts"], rng)
    lens = np.minimum(lens, spec["max_prompt"])
    out = []
    for n in lens:
        req = generators.Req(0.0, generators.tokens(rng, n, vocab),
                             spec["new_tokens"])
        s = client.send(req, clock.now())
        if s.error:
            raise RuntimeError(f"warm-up request refused: {s.error}")
        rows, logits = None, []
        while not s.done:
            seen = len(s.toks)
            step()
            lg = client.eng.last_decode_logits
            for tok in s.toks[max(seen, 1):]:
                # a decode step delivered `tok`; its logits are in lg
                top = np.argmax(np.asarray(lg[:, 0], np.float32), -1)
                match = set(np.flatnonzero(top == tok).tolist())
                rows = match if rows is None else rows & match
                logits.append(np.asarray(lg[:, 0], np.float32))
        client.take_done()
        if not rows or len(rows) != 1:
            raise RuntimeError(f"could not find the request's row in the "
                               f"decode logits (candidates {rows})")
        row = rows.pop()
        out.append({"prompt": req.prompt, "toks": list(s.toks),
                    "decode_logits": np.stack([lg[row] for lg in logits])})
    return out


def compare_with_reference(sample: List[dict], ref_logits,
                           tol: float) -> dict:
    """``ref_logits(ids, positions)`` is the plain reference.  The engine's
    decode logits (prefill, then decoding through the paged cache) must
    agree with the reference's full forward over prompt + generated
    tokens, and every greedy choice must be a near-tie of the reference."""
    errors, worst_tie = [], 0.0
    for s in sample:
        n, toks = len(s["prompt"]), s["toks"]
        ids = np.concatenate([s["prompt"], np.asarray(toks[:-1], np.int32)])
        # position n-1+j predicts generated token j
        ref = np.asarray(ref_logits(ids, np.arange(n - 1, n - 1 + len(toks))))
        worst_tie = max(worst_tie, checks.short_of_best(ref, toks))
        errors.extend(checks.row_errors(s["decode_logits"], ref[1:]))
    verdict = checks.logits_agree(errors, tol)
    verdict["short_of_best"] = worst_tie
    verdict["limits"]["short_of_best"] = checks.NEAR_TIE
    return checks.decide(verdict)


# -- from token times to metrics ------------------------------------------
def latency_metrics(sample: List[Sent], from_due: bool) -> dict:
    """TTFT per request (ms) and every gap between consecutive tokens of one
    request (ms), over the sampled requests that produced tokens."""
    ttft, gaps = [], []
    for s in sample:
        if not s.times:
            continue
        t0 = s.due_t if from_due else s.sent_t
        ttft.append((s.times[0] - t0) * 1e3)
        gaps.extend(np.diff(s.times) * 1e3)
    return {"ttft_ms": ttft, "itl_ms": gaps}


def count_failed(sample: List[Sent]) -> int:
    return sum(1 for s in sample
               if s.error or not s.done or len(s.toks) != s.req.want)


def end_to_end(lat: dict, tokens_completed: int, seconds: float) -> dict:
    """What a serving run measured on the client's side, by name; which of
    these a cell reports as end-to-end metrics, and which as a per-layer
    metric beside them, is ``BENCHMARK.json``'s choice.  Every statistic is
    over ALL sampled requests (or all their gaps)."""
    out = {"serve_tok_s": tokens_completed / seconds,
           "samples": {"ttft_ms": len(lat["ttft_ms"]),
                       "itl_ms": len(lat["itl_ms"])}}
    for k in ("ttft", "itl"):
        xs = lat[k + "_ms"]
        for q in (50, 75, 90, 95, 99):
            out[f"{k}_p{q}_ms"] = clock.percentile(xs, q)
        out[f"{k}_mean_ms"] = float(np.mean(xs)) if xs else None
    # the slow tenth of the gaps less the slowest hundredth (which freezes
    # of the shared host own): a tail that does not step where the share of
    # gaps behind a prefill crosses a percentile
    out["itl_tail_mean_ms"] = clock.tail_mean(lat["itl_ms"], 90.0, 99.0)
    return out


def prefilled_tokens(sent: List[Sent], tracer) -> int:
    """Prompt tokens of the requests whose first token came inside the traced
    interval: a request's whole prefill runs in the engine step that ends
    with its first token."""
    if tracer.started_at is None or tracer.stopped_at is None:
        return 0
    return sum(len(s.req.prompt) for s in sent if s.times
               and tracer.started_at <= s.times[0] <= tracer.stopped_at)
