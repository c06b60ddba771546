"""Per-request SLO metrics for the serving engine.

The serving analogue of :class:`telemetry.StepMeter`: where the train
meter prices a step (tokens/s, MFU), the SLO meter prices a REQUEST —
TTFT (arrival → first token), TPOT (mean inter-token gap over the decode
phase), end-to-end latency — and the fleet-level gauges a capacity planner
reads: queue depth, KV-pool occupancy, sustained requests/s, shed and
deadline-miss rates.

Memory is BOUNDED by design: a serving process lives for weeks, so p50/p99
roll over a fixed window of the most recent finished requests
(``PADDLE_TPU_SERVE_SLO_WINDOW``, default 1024) instead of an append-only
list, per-request clocks are dropped at finish/shed, and no per-token
timestamp list is kept — totals that must be exact (requests finished,
tokens, evictions, sheds) live in O(1) counters.

Everything flows through the telemetry runtime so the existing surfaces
pick it up for free: gauges/counters land in ``telemetry.counters()`` (and
therefore ``prometheus_text()``), and admit/evict/shed/finish transitions
are narrated into the flight recorder (``serve_admit`` / ``serve_evict`` /
``serve_shed`` / ``serve_reject`` / ``serve_finish`` events) so a hung or
thrashing server dumps its recent scheduling story the same way a hung
train step dumps its collectives.

The gap between two of a user's tokens is accounted where it is made: the
engine fills one :class:`Cycle` between two flushes that delivered tokens,
and the meter keeps the longest of them (``summary()["longest_cycles"]``),
so that a run says of its worst gaps whether they lay in a prompt's
prefill, in the decode step, in the delivery, or outside ``step()``.
"""

from __future__ import annotations

import heapq
import time
from collections import deque
from dataclasses import dataclass
from typing import Dict, List, Optional

from ..telemetry import record_event
from ..telemetry.aggregator import Histogram
from ..telemetry.runtime import bump, identity, set_gauge

__all__ = ["RequestClock", "Cycle", "SLOMeter", "FleetMeter"]


def default_slo_window() -> int:
    from ..distributed.checkpoint.replicator import env_int

    return max(1, env_int("PADDLE_TPU_SERVE_SLO_WINDOW", 1024))


@dataclass
class RequestClock:
    """Wall-clock milestones of one request's life (monotonic seconds).
    Lives only while the request is in flight — finish/shed folds it into
    the meter's bounded window and drops it."""

    rid: object
    submit_t: float
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    last_token_t: Optional[float] = None
    n_tokens: int = 0
    evictions: int = 0
    replay_watermark: int = 0   # tokens produced before the last eviction
    # distributed-trace id (telemetry.tracing): minted at the edge, carried
    # through journal replay and fail-over, stamped on every span event
    trace_id: Optional[str] = None

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return self.first_token_t - self.submit_t

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean inter-token gap over the decode phase (first token
        excluded — that one is priced by TTFT)."""
        if self.finish_t is None or self.first_token_t is None \
                or self.n_tokens < 2:
            return None
        return (self.finish_t - self.first_token_t) / (self.n_tokens - 1)

    @property
    def latency_s(self) -> Optional[float]:
        if self.finish_t is None:
            return None
        return self.finish_t - self.submit_t


# EWMA smoothing for the per-replica TPOT trend the fleet frontend's
# latency-outlier ejection reads; matches the straggler detector's
# step-time alpha so both ladders react on the same horizon
_TPOT_EMA_ALPHA = 0.25


def _pct(xs: List[float], q: float) -> Optional[float]:
    if not xs:
        return None
    s = sorted(xs)
    idx = min(len(s) - 1, max(0, int(round(q / 100.0 * (len(s) - 1)))))
    return s[idx]


# the longest prefill any traffic puts ahead of a step is about half a
# second (a 4 096-token prompt): a cycle over a second is a stall and not a
# schedule.  A constant, not a setting.
STALL_NS = 1_000_000_000
LONGEST_CYCLES = 8


class Cycle:
    """The engine's account of one cycle: the time from one
    ``_flush_delivery`` that delivered tokens to the next that does.  Every
    request that gets a token in both waits exactly that long between the
    two, so the account of a cycle is the make-up of its users' gaps.

    ``enter(part)`` is called at the boundaries ``step()`` already has
    spans on: the time since the last boundary goes to the part that was
    running, and ``part`` runs from here.  So the five parts sum to the
    cycle's length, and what a step spends between two phases counts with
    the phase before it.  ``outside`` is the time between two ``step()``
    calls: the caller, the load generator, a host that froze.  Waiting for
    the device (``serve.decode.to_host``) is inside ``decode``.

    The counts are made where the work happens: ``gaps`` the requests whose
    inter-token gap the closing flush ended (its lowest delivered index is
    >= 1), ``gaps_long`` those of them that got no token in the flush
    before (a row that sat out a step: an eviction's replay, a recall),
    ``first_tokens`` the requests whose first token it delivered,
    ``prefill_requests`` / ``prefill_tokens`` / ``prefill_launches`` the
    prompts prefilled, the prompt tokens actually computed (a prefix-cached
    page is not) and the program launches that took, ``decode_rows`` the
    rows the decode steps stepped; ``compiled`` says that a program
    compiled inside the cycle."""

    PARTS = ("outside", "admit", "prefill", "decode", "deliver")
    COUNTS = ("gaps", "gaps_long", "first_tokens", "prefill_requests",
              "prefill_tokens", "prefill_launches", "decode_rows")
    __slots__ = ("_now_ns", "seq", "part", "t", "ns", "compiled") + COUNTS

    def __init__(self, now_ns=time.monotonic_ns):
        self._now_ns = now_ns
        self.seq = 0
        self.part = "outside"
        self.t = now_ns()
        self.begin()

    def begin(self) -> None:
        """The next cycle starts where the last boundary was."""
        self.seq += 1
        self.ns = dict.fromkeys(self.PARTS, 0)
        self.compiled = False
        for name in self.COUNTS:
            setattr(self, name, 0)

    def enter(self, part: str) -> None:
        t = self._now_ns()
        self.ns[self.part] += t - self.t
        self.t, self.part = t, part

    def counts(self) -> Dict[str, int]:
        return {name: getattr(self, name) for name in self.COUNTS}


class SLOMeter:
    """Aggregates :class:`RequestClock` milestones into p50/p99 SLO lines
    over a bounded window and exports live gauges through telemetry."""

    def __init__(self, now=time.monotonic, window: Optional[int] = None):
        self._now = now
        self._clocks: Dict[object, RequestClock] = {}
        # each entry: (finish_t, ttft_s|None, tpot_s|None, latency_s,
        #              deadline_miss True/False/None)
        self._window: deque = deque(
            maxlen=window if window is not None else default_slo_window())
        self._ft_window: deque = deque(maxlen=self._window.maxlen)
        self._t_first_submit: Optional[float] = None
        self._t_last_finish: Optional[float] = None
        self.occupancy_peak = 0.0
        self.state_slots_peak = None     # share of max_batch; None: the
        # model has no state layers
        self.finished_total = 0
        self.prefill_launches_total = 0  # launches of a prefill program
        self.decode_steps_total = 0      # steps that decoded any row
        self.decode_steps_rode = 0       # of them, those whose rows rode a
        # prefill launch (``ServingEngine.rides_prefill``)
        self.decode_logits_fetches_total = 0   # whole [R, S, V] arrays
        # pulled to the host (``ServingEngine.last_decode_logits``)
        self.evictions_total = 0
        self.shed_total = 0
        self.shed_reasons: Dict[str, int] = {}
        self.rejected_total = 0
        self.deadline_misses_total = 0
        # speculative decoding + quantized-KV gauges (ISSUE 13)
        self.spec_proposed_total = 0
        self.spec_accepted_total = 0
        self.spec_emitted_total = 0
        self.spec_verify_steps = 0
        self.spec_rows_total = 0
        self.kv_bytes_per_token: Optional[float] = None
        self.passes = 1     # times the engine walks the model's layers a
        # step (a looped model's passes)
        self.params_relaid = 0          # parameters the engine moved, once,
        self.params_relaid_bytes = 0    # into the layouts its decode
        # program chose (``ServingEngine.param_layout_refusal``)
        # host-RAM KV offload tier (long-context ladder): swap traffic in
        # pages and bytes, plus the token denominator the recall-MBU
        # gauge divides by (replays excluded — recall exists precisely so
        # tokens are NOT recomputed)
        self.offloads_total = 0
        self.recalls_total = 0
        self.offload_stalls_total = 0
        self.offload_bytes_out_total = 0
        self.recall_bytes_in_total = 0
        self.tokens_out_total = 0
        # per-replica decode-speed trend: EWMA of finished requests' TPOT.
        # The fleet frontend compares this against the fleet median to
        # eject a degraded (slow-chip) replica from routing.
        self.tpot_ema_s: Optional[float] = None
        # TTFT/TPOT/latency histograms (telemetry.aggregator.Histogram):
        # mergeable bucket counts the MetricsPusher ships to the depot so
        # the fleet p99 is computed from summed buckets, never averaged
        # percentiles.  Observations also bump `serving.<kind>_hist.*`
        # runtime counters, which prometheus_text() renders as real
        # _bucket/_sum/_count series.
        self.hists: Dict[str, Histogram] = {
            "ttft_s": Histogram(), "tpot_s": Histogram(),
            "latency_s": Histogram()}
        # trace-coverage accounting: of finished requests, how many had a
        # complete traced span chain (counters, not clocks — clocks are
        # dropped at finish)
        self._trace_complete = 0
        # the cycle account (:class:`Cycle`): a min-heap of the longest
        # cycles in which somebody waited and nothing compiled
        self.cycles_total = 0
        self.cycles_compiled = 0
        self.cycles_over_1s = 0
        self._longest_cycles: List[tuple] = []

    def clock(self, rid) -> RequestClock:
        return self._clocks[rid]

    def trace_of(self, rid) -> Optional[str]:
        c = self._clocks.get(rid)
        return None if c is None else c.trace_id

    def _observe(self, kind: str, value: float) -> None:
        h = self.hists[kind]
        h.observe(value)
        for i, ub in enumerate(h.buckets):
            if value <= ub:
                bump(f"serving.{kind}_hist.bucket.{ub}")
                break
        else:
            bump(f"serving.{kind}_hist.bucket_inf")
        bump(f"serving.{kind}_hist.sum", float(value))
        bump(f"serving.{kind}_hist.count")

    def hist_docs(self) -> Dict[str, dict]:
        return {k: h.to_doc() for k, h in self.hists.items()}

    # -- lifecycle ---------------------------------------------------------
    def submit(self, rid, age_s: float = 0.0,
               trace_id: Optional[str] = None) -> None:
        """``age_s`` backdates the clock: a journal-replayed request has
        already waited that long in its previous incarnation, and its
        deadline budgets must keep aging across the crash.  ``trace_id``
        is the request's distributed-trace id (same id across replay and
        fail-over); the submit span and every later span carry it."""
        t = self._now() - max(0.0, float(age_s))
        self._clocks[rid] = RequestClock(rid=rid, submit_t=t,
                                         trace_id=trace_id)
        if self._t_first_submit is None:
            self._t_first_submit = t
        record_event("serve_submit", str(rid), trace=trace_id,
                     age_s=round(float(age_s), 6))
        bump("serving.requests_submitted")

    def admit(self, rid, *, queue_depth: int, pages: int) -> None:
        c = self._clocks[rid]
        c.admit_t = self._now()
        record_event("serve_admit", str(rid), pages=pages,
                     queue_depth=queue_depth, trace=c.trace_id,
                     queued_s=round(c.admit_t - c.submit_t, 6))
        bump("serving.requests_admitted")

    def first_token(self, rid) -> None:
        t = self._now()
        c = self._clocks[rid]
        if c.first_token_t is None:
            c.first_token_t = t     # an eviction-replay re-prefill must
            if c.admit_t is not None:    # not reset the client's TTFT
                self._ft_window.append(t - c.admit_t)
            if c.ttft_s is not None:
                self._observe("ttft_s", c.ttft_s)
            # the prefill span: submit -> first token out
            record_event("serve_first_token", str(rid), trace=c.trace_id,
                         ttft_s=(None if c.ttft_s is None
                                 else round(c.ttft_s, 6)))
        c.last_token_t = t
        c.n_tokens += 1
        self._count_token(c)

    def prefill_launched(self, launches: int) -> None:
        """One prompt's prefill took ``launches`` program launches."""
        self.prefill_launches_total += int(launches)

    def decode_step(self, *, rode: bool) -> None:
        """A step decoded its rows: through the decode program, or riding
        the step's last prefill launch (``rode``)."""
        self.decode_steps_total += 1
        self.decode_steps_rode += int(bool(rode))

    def cycle_closed(self, cy: Cycle, *, step: int) -> None:
        """A flush delivered tokens and so ended ``cy``.  A cycle in which
        a program compiled is counted apart (the warm-up's; none inside a
        measured window); one that closed nobody's gap (an empty engine
        asleep until the next arrival) is nobody's wait.  Of the rest the
        ``LONGEST_CYCLES`` longest are kept, and those over a second
        counted."""
        self.cycles_total += 1
        if cy.compiled:
            self.cycles_compiled += 1
            return
        if cy.gaps <= 0:
            return
        length = sum(cy.ns.values())
        if length > STALL_NS:
            self.cycles_over_1s += 1
        heap = self._longest_cycles
        if len(heap) == LONGEST_CYCLES and length <= heap[0][0]:
            return
        entry = {"seq": cy.seq, "steps_total": step,
                 "end_s": cy.t / 1e9, "ms": length / 1e6}
        entry.update((part + "_ms", ns / 1e6) for part, ns in cy.ns.items())
        entry.update(cy.counts(), compiled=False)
        keep = heapq.heappush if len(heap) < LONGEST_CYCLES \
            else heapq.heapreplace
        keep(heap, (length, cy.seq, entry))     # seq breaks a tie of lengths

    def decode_logits_fetched(self) -> None:
        """Someone read the decode logits: one whole array came to the
        host."""
        self.decode_logits_fetches_total += 1

    def token(self, rid) -> None:
        c = self._clocks[rid]
        c.last_token_t = self._now()
        c.n_tokens += 1
        self._count_token(c)

    def _count_token(self, c: RequestClock) -> None:
        """Recomputing an already-produced token after an eviction is
        replay WORK, not new output — count the two separately so the
        token totals match what the stream actually delivered."""
        if c.n_tokens <= c.replay_watermark:
            bump("serving.tokens_replayed")
        else:
            bump("serving.tokens_generated")
            self.tokens_out_total += 1

    def evict(self, rid, *, reason: str, pages_freed: int) -> None:
        c = self._clocks[rid]
        c.evictions += 1
        self.evictions_total += 1
        # the restarted prefill regenerates from scratch: token milestones
        # reset so TTFT/TPOT price what the CLIENT observes (the retained
        # first_token_t stands — the client saw that token)
        c.replay_watermark = max(c.replay_watermark, c.n_tokens)
        c.n_tokens = 0
        record_event("serve_evict", str(rid), reason=reason,
                     pages_freed=pages_freed, evictions=c.evictions,
                     trace=c.trace_id)
        bump("serving.evictions")

    def offload(self, rid, *, pages: int, shared_pages: int,
                bytes_out: int) -> None:
        """A preempted request's private KV pages swapped to the host
        tier (shared pages stay resident and move zero bytes).  Unlike
        :meth:`evict`, the token milestones STAND — nothing will be
        recomputed; the recall scatter restores the exact cache state."""
        c = self._clocks[rid]
        self.offloads_total += 1
        self.offload_bytes_out_total += int(bytes_out)
        record_event("serve_offload", str(rid), pages=pages,
                     shared_pages=shared_pages, bytes_out=int(bytes_out),
                     trace=c.trace_id)
        bump("serving.kv_offloads_total")
        bump("serving.kv_offload_bytes_out_total", int(bytes_out))

    def recall(self, rid, *, pages: int, bytes_in: int,
               n_tokens: int) -> None:
        """A parked request's frames streamed back from the host tier and
        re-activated — ``n_tokens`` generated tokens resume without
        recompute.  The recall traffic prices into the MBU story through
        :meth:`kv_recall_bytes_per_token`."""
        c = self._clocks[rid]
        self.recalls_total += 1
        self.recall_bytes_in_total += int(bytes_in)
        record_event("serve_recall", str(rid), pages=pages,
                     bytes_in=int(bytes_in), n_tokens=int(n_tokens),
                     trace=c.trace_id)
        bump("serving.kv_recalls_total")
        bump("serving.kv_recall_bytes_in_total", int(bytes_in))
        set_gauge("serving.kv_recall_bytes_per_token",
                  self.kv_recall_bytes_per_token())

    def offload_stall(self, rid) -> None:
        """A parked request whose host frames were LRU-dropped before
        recall: it downgrades to the eviction-replay re-prefill path (the
        failure-matrix "offload stall" row).  Token milestones reset like
        an eviction — the replay recomputes them."""
        c = self._clocks[rid]
        self.offload_stalls_total += 1
        self.evictions_total += 1
        c.evictions += 1
        c.replay_watermark = max(c.replay_watermark, c.n_tokens)
        c.n_tokens = 0
        record_event("serve_offload_stall", str(rid), trace=c.trace_id)
        bump("serving.kv_offload_stalls_total")

    def kv_recall_bytes_per_token(self) -> float:
        """Host→HBM recall traffic amortized over every NEW token the
        engine produced — the term the long-context MBU accounting adds
        on top of ``kv_bytes_per_token`` (0.0 until a recall happens)."""
        if self.tokens_out_total <= 0:
            return 0.0
        return self.recall_bytes_in_total / self.tokens_out_total

    def shed(self, rid, *, reason: str) -> None:
        """A queued request dropped by deadline shedding (or recovery of a
        journaled shed): it will never run — fold its clock away."""
        c = self._clocks.pop(rid, None)
        self.shed_total += 1
        # by-reason split: the autoscaler's overload-pressure signal must
        # exclude "drained" (its OWN scale-in hand-backs), or every
        # scale-in would read as overload and oscillate straight back out
        self.shed_reasons[reason] = self.shed_reasons.get(reason, 0) + 1
        record_event("serve_shed", str(rid), reason=reason,
                     trace=None if c is None else c.trace_id,
                     queued_s=(None if c is None else
                               round(self._now() - c.submit_t, 6)))
        bump("serving.requests_shed_total")

    def reject(self, *, reason: str,
               retry_after_s: Optional[float] = None) -> None:
        """An Overloaded refusal at submit (bounded queue / breaker)."""
        self.rejected_total += 1
        record_event("serve_reject", reason, retry_after_s=retry_after_s)
        bump("serving.requests_rejected_total")

    def defer(self, rid, *, defers: int, need: int, free: int) -> None:
        """The FIFO head was bypassed under pool pressure (a shorter
        request behind it fit; the head keeps its place)."""
        record_event("serve_defer", str(rid), defers=defers,
                     pages_needed=need, pages_free=free)
        bump("serving.admission_defers_total")

    def finish(self, rid, *, n_tokens: int, deadline=None) -> None:
        c = self._clocks.pop(rid)
        c.finish_t = self._now()
        c.n_tokens = n_tokens
        self._t_last_finish = c.finish_t
        self.finished_total += 1
        miss = None
        if deadline is not None:
            miss = bool(
                (deadline.ttft_s is not None and c.ttft_s is not None
                 and c.ttft_s > deadline.ttft_s) or
                (deadline.total_s is not None
                 and c.latency_s > deadline.total_s))
            if miss:
                self.deadline_misses_total += 1
                bump("serving.deadline_misses_total")
        self._window.append((c.finish_t, c.ttft_s, c.tpot_s, c.latency_s,
                             miss))
        if c.tpot_s is not None:
            self._observe("tpot_s", c.tpot_s)
            self.tpot_ema_s = c.tpot_s if self.tpot_ema_s is None else (
                (1.0 - _TPOT_EMA_ALPHA) * self.tpot_ema_s
                + _TPOT_EMA_ALPHA * c.tpot_s)
            set_gauge("serving.tpot_ema_ms", self.tpot_ema_s * 1e3)
        if c.latency_s is not None:
            self._observe("latency_s", c.latency_s)
        # traced span chain complete?  (submit span always exists; admit +
        # first token are the waypoints a lost trace would have dropped)
        if c.trace_id is not None and c.admit_t is not None \
                and c.first_token_t is not None:
            self._trace_complete += 1
        set_gauge("serving.deadline_miss_rate", self.deadline_miss_rate())
        record_event("serve_finish", str(rid), n_tokens=n_tokens,
                     latency_s=round(c.latency_s, 6), trace=c.trace_id,
                     evictions=c.evictions, deadline_miss=miss)
        bump("serving.requests_finished")

    # -- estimates (admission control reads these) -------------------------
    def est_first_token_s(self) -> Optional[float]:
        """Recent mean admit → first-token latency: the optimistic lower
        bound on a queued request's remaining TTFT (even admitted right
        now it still pays prefill)."""
        if not self._ft_window:
            return None
        return sum(self._ft_window) / len(self._ft_window)

    def finish_rate_per_s(self) -> Optional[float]:
        """Finished requests/s over the current window."""
        if len(self._window) < 2:
            return None
        span = self._window[-1][0] - self._window[0][0]
        if span <= 0:
            return None
        return (len(self._window) - 1) / span

    def deadline_miss_rate(self) -> float:
        """Fraction of deadline-carrying finishes in the window that
        missed (0.0 when none carried a deadline)."""
        hits = [m for (_, _, _, _, m) in self._window if m is not None]
        if not hits:
            return 0.0
        return sum(1 for m in hits if m) / len(hits)

    # -- gauges ------------------------------------------------------------
    def set_queue_depth(self, n: int) -> None:
        set_gauge("serving.queue_depth", float(n))

    def set_occupancy(self, frac: float) -> None:
        self.occupancy_peak = max(self.occupancy_peak, float(frac))
        set_gauge("serving.kv_pool_occupancy", float(frac))

    def set_state_slots(self, frac: float) -> None:
        """Share of the row-state slots (one a decode row, ``RowStatePool``)
        in use: the engine's active rows over ``max_batch``."""
        self.state_slots_peak = max(self.state_slots_peak or 0.0,
                                    float(frac))
        set_gauge("serving.state_slots_occupancy", float(frac))

    def set_kv_bytes_per_token(self, b: float) -> None:
        """HBM bytes one KV token slot costs (arena + scales, all layers)
        — the denominator the int8-page halving shows up in."""
        self.kv_bytes_per_token = float(b)
        set_gauge("serving.kv_bytes_per_token", float(b))

    def spec_step(self, *, proposed: int, accepted: int, emitted: int,
                  rows: int) -> None:
        """One speculative verify step's acceptance bookkeeping across
        ``rows`` live batch rows: ``proposed`` drafts went in, ``accepted``
        matched the target's argmax, ``emitted`` tokens came out (always
        >= rows — each row gets at least the target's own next token)."""
        self.spec_proposed_total += int(proposed)
        self.spec_accepted_total += int(accepted)
        self.spec_emitted_total += int(emitted)
        self.spec_rows_total += int(rows)
        self.spec_verify_steps += 1
        set_gauge("serving.spec_acceptance_rate", self.spec_acceptance())
        set_gauge("serving.effective_tokens_per_step",
                  self.effective_tokens_per_step())
        bump("serving.spec_tokens_proposed_total", int(proposed))
        bump("serving.spec_tokens_accepted_total", int(accepted))

    def spec_acceptance(self) -> float:
        """Fraction of drafted tokens the target's own argmax confirmed."""
        if self.spec_proposed_total <= 0:
            return 0.0
        return self.spec_accepted_total / self.spec_proposed_total

    def effective_tokens_per_step(self) -> float:
        """Mean tokens emitted per row per verify step — the speculative
        speedup numerator (serial decode is exactly 1.0)."""
        if self.spec_rows_total <= 0:
            return 0.0
        return self.spec_emitted_total / self.spec_rows_total

    # -- rollup ------------------------------------------------------------
    def summary(self) -> Dict[str, object]:
        """SLO rollup (milliseconds); percentiles over the bounded window,
        totals exact.  The cycle account: ``cycles_total`` (flushes that
        delivered tokens), ``cycles_compiled`` (of them, those in which a
        program compiled), ``cycles_over_1s`` (of the rest, those in which
        somebody waited over a second: stalls) and ``longest_cycles`` (the
        :data:`LONGEST_CYCLES` longest of the rest in which somebody
        waited, longest first; each with ``seq``, ``steps_total``, the
        monotonic second it ended ``end_s``, its length ``ms``, the five
        parts ``outside_ms`` / ``admit_ms`` / ``prefill_ms`` /
        ``decode_ms`` / ``deliver_ms`` that sum to it, and :class:`Cycle`'s
        counts).  ``decode_steps``: steps that decoded rows;
        ``decode_steps_rode``: of them, those whose rows rode the step's
        last prefill launch."""
        ttft = [t * 1e3 for (_, t, _, _, _) in self._window if t is not None]
        tpot = [t * 1e3 for (_, _, t, _, _) in self._window if t is not None]
        lat = [t * 1e3 for (_, _, _, t, _) in self._window if t is not None]
        span = None
        if self._t_first_submit is not None and \
                self._t_last_finish is not None:
            span = max(self._t_last_finish - self._t_first_submit, 1e-9)
        n = self.finished_total
        ident = identity()
        return {
            # self-identification (schema-additive): a summary pushed to
            # the launcher's metrics depot names its replica/rank and its
            # own wall stamp
            "wall_time": time.time(),
            "replica": ident.get("replica"),
            "rank": ident.get("rank"),
            # the CI gate: fraction of finished requests whose traced span
            # chain stayed complete through eviction/replay/fail-over
            "trace_coverage": round(self._trace_complete / n, 4) if n
            else 1.0,
            "requests_finished": n,
            "requests_shed": self.shed_total,
            "shed_reasons": dict(self.shed_reasons),
            "requests_rejected": self.rejected_total,
            "requests_per_sec": round(n / span, 3) if span else None,
            "ttft_ms_p50": _r(_pct(ttft, 50)),
            "ttft_ms_p99": _r(_pct(ttft, 99)),
            "tpot_ms_p50": _r(_pct(tpot, 50)),
            "tpot_ms_p99": _r(_pct(tpot, 99)),
            "latency_ms_p50": _r(_pct(lat, 50)),
            "latency_ms_p99": _r(_pct(lat, 99)),
            "deadline_miss_rate": round(self.deadline_miss_rate(), 4),
            "evictions": self.evictions_total,
            "prefill_launches": self.prefill_launches_total,
            "decode_steps": self.decode_steps_total,
            "decode_steps_rode": self.decode_steps_rode,
            "decode_logits_fetches": self.decode_logits_fetches_total,
            "kv_pool_occupancy_peak": round(self.occupancy_peak, 4),
            "state_slots_peak": (None if self.state_slots_peak is None
                                 else round(self.state_slots_peak, 4)),
            "spec_acceptance": (round(self.spec_acceptance(), 4)
                                if self.spec_verify_steps else None),
            "effective_tokens_per_step": (
                round(self.effective_tokens_per_step(), 4)
                if self.spec_verify_steps else None),
            "kv_bytes_per_token": self.kv_bytes_per_token,
            "passes": self.passes,
            "params_relaid": self.params_relaid,
            "params_relaid_bytes": self.params_relaid_bytes,
            "kv_offloads": self.offloads_total,
            "kv_recalls": self.recalls_total,
            "kv_offload_stalls": self.offload_stalls_total,
            "kv_offload_bytes_out": self.offload_bytes_out_total,
            "kv_recall_bytes_in": self.recall_bytes_in_total,
            "kv_recall_bytes_per_token": round(
                self.kv_recall_bytes_per_token(), 3),
            "tpot_ema_ms": _r(None if self.tpot_ema_s is None
                              else self.tpot_ema_s * 1e3),
            "cycles_total": self.cycles_total,
            "cycles_compiled": self.cycles_compiled,
            "cycles_over_1s": self.cycles_over_1s,
            "longest_cycles": [dict(entry) for _, _, entry in
                               sorted(self._longest_cycles, reverse=True)],
        }


def _r(x: Optional[float]) -> Optional[float]:
    return None if x is None else round(x, 3)


class FleetMeter:
    """Fleet-level counters/gauges for the multi-replica frontend
    (:class:`~paddle_tpu.serving.fleet.ServingFrontend`): live replica
    count, per-replica queue depth, failovers, replayed requests, drain
    hand-backs.  Same runtime seam as :class:`SLOMeter`, so the fleet
    story lands in ``telemetry.counters()`` / ``prometheus_text()`` and
    the flight recorder for free."""

    def __init__(self):
        self.failovers_total = 0
        self.replayed_requests_total = 0
        self.handbacks_total = 0
        self.live_replicas = 0
        self.scale_out_total = 0
        self.scale_in_total = 0
        self.serving_replicas = 0
        self.warming_replicas = 0
        self.draining_replicas = 0
        self.degraded_replicas = 0
        self.degraded_ejects_total = 0
        self.degraded_readmits_total = 0
        self.last_autoscale: Optional[Dict[str, object]] = None
        self.prefill_routed_total = 0
        self.prefill_fallbacks_total = 0
        self.prefix_hit_rate: Optional[float] = None
        self.tier_occupancy: Dict[str, float] = {}

    def set_live_replicas(self, n: int) -> None:
        self.live_replicas = int(n)
        set_gauge("serving.fleet_live_replicas", float(n))

    def set_replica_queue_depth(self, name: str, depth: int) -> None:
        set_gauge(f"serving.fleet_queue_depth.{name}", float(depth))

    def set_fleet_states(self, serving: int, warming: int,
                         draining: int, degraded: int = 0) -> None:
        """Per-state replica gauges (SERVING / WARMING / DRAINING /
        DEGRADED), as the autoscaler's lease scan counts them."""
        self.serving_replicas = int(serving)
        self.warming_replicas = int(warming)
        self.draining_replicas = int(draining)
        self.degraded_replicas = int(degraded)
        set_gauge("serving.fleet_serving_replicas", float(serving))
        set_gauge("serving.fleet_warming_replicas", float(warming))
        set_gauge("serving.fleet_draining_replicas", float(draining))
        set_gauge("serving.fleet_degraded_replicas", float(degraded))

    def degrade(self, name: str, *, tpot_ema_ms: Optional[float],
                median_ms: Optional[float]) -> None:
        """One replica ejected from routing as a latency outlier (EWMA
        TPOT over the fleet median by the straggler factor)."""
        self.degraded_ejects_total += 1
        bump("serving.fleet_degraded_ejects_total")
        record_event("serve_fleet_degraded", str(name),
                     tpot_ema_ms=tpot_ema_ms, median_ms=median_ms)

    def readmit(self, name: str, *,
                tpot_ema_ms: Optional[float] = None) -> None:
        """A previously degraded replica whose probe came back clean
        rejoins the routable pool."""
        self.degraded_readmits_total += 1
        bump("serving.fleet_degraded_readmits_total")
        record_event("serve_fleet_readmit", str(name),
                     tpot_ema_ms=tpot_ema_ms)

    def autoscale(self, direction: str, *, target: int,
                  reason: str) -> None:
        """One autoscale decision acted on (``direction`` is ``out`` or
        ``in``); stamps the flight recorder so the merged black box shows
        WHY capacity moved."""
        if direction == "out":
            self.scale_out_total += 1
            bump("serving.fleet_scale_out_total")
        else:
            self.scale_in_total += 1
            bump("serving.fleet_scale_in_total")
        self.last_autoscale = {"direction": str(direction),
                               "target": int(target),
                               "reason": str(reason)}
        record_event("autoscale_decision", str(direction),
                     target=int(target), reason=str(reason))

    def set_prefix_hit_rate(self, rate: Optional[float]) -> None:
        """Fleet-wide prefix-cache hit rate (token-weighted mean over the
        replicas that publish one; ``None`` when no replica caches)."""
        self.prefix_hit_rate = None if rate is None else float(rate)
        if rate is not None:
            set_gauge("serving.fleet_prefix_hit_rate", float(rate))

    def set_tier_occupancy(self, tier: str, occupancy: float) -> None:
        """Mean load of one serving tier (``prefill`` / ``decode``), as
        the frontend's lease scan measures it — the capacity-planning
        signal for the disaggregated split."""
        self.tier_occupancy[str(tier)] = float(occupancy)
        set_gauge(f"serving.fleet_tier_occupancy.{tier}", float(occupancy))

    def prefill_route(self, name: str, rid: int) -> None:
        """One long prompt routed through the dedicated prefill tier."""
        self.prefill_routed_total += 1
        bump("serving.fleet_prefill_routed_total")
        record_event("fleet_prefill_route", str(name), rid=int(rid))

    def prefill_fallback(self, name: str, rid: int, reason: str) -> None:
        """A prefill-tier attempt abandoned mid-flight (worker death,
        fenced epoch, pruned KV frames) — the request fell back to a
        plain decode-tier prefill, exactly-once preserved."""
        self.prefill_fallbacks_total += 1
        bump("serving.fleet_prefill_fallbacks_total")
        record_event("fleet_prefill_fallback", str(name), rid=int(rid),
                     reason=str(reason))

    def disagg_doc(self) -> Dict[str, object]:
        """The frontend's disaggregation self-report, pushed to the
        metrics depot as the ``disagg`` extra (the report CLI's
        prefix-hit-rate / per-tier occupancy rows; latest ``wall_time``
        wins in the rollup, mirroring ``autoscale``)."""
        return {"prefix_hit_rate": self.prefix_hit_rate,
                "tier_occupancy": dict(self.tier_occupancy),
                "prefill_routed_total": self.prefill_routed_total,
                "prefill_fallbacks_total": self.prefill_fallbacks_total}

    def failover(self, name: str, replayed: int = 0) -> None:
        self.failovers_total += 1
        self.replayed_requests_total += int(replayed)
        bump("serving.fleet_failovers_total")
        if replayed:
            bump("serving.fleet_requests_replayed_total", int(replayed))
        record_event("serve_fleet_failover", str(name),
                     replayed=int(replayed))

    def handback(self, name: str, moved: int = 0) -> None:
        self.handbacks_total += int(moved)
        if moved:
            bump("serving.fleet_handbacks_total", int(moved))
        record_event("serve_fleet_drain", str(name), moved=int(moved))

    def summary(self) -> Dict[str, object]:
        return {"live_replicas": self.live_replicas,
                "failovers": self.failovers_total,
                "replayed_requests": self.replayed_requests_total,
                "handbacks": self.handbacks_total,
                "scale_out": self.scale_out_total,
                "scale_in": self.scale_in_total,
                "serving_replicas": self.serving_replicas,
                "warming_replicas": self.warming_replicas,
                "draining_replicas": self.draining_replicas,
                "degraded_replicas": self.degraded_replicas,
                "degraded_ejects": self.degraded_ejects_total,
                "degraded_readmits": self.degraded_readmits_total,
                "last_autoscale": self.last_autoscale,
                "prefill_routed": self.prefill_routed_total,
                "prefill_fallbacks": self.prefill_fallbacks_total,
                "prefix_hit_rate": self.prefix_hit_rate,
                "tier_occupancy": dict(self.tier_occupancy)}
