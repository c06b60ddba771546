"""The engine's cycle account (``serving.metrics.Cycle``): between two
flushes that delivered tokens, how long, whose gaps it closed and what ran in
between; the ``SLOMeter`` keeps the longest.

A small engine on an INJECTED clock that only the test moves: each phase of
``step()`` is wrapped to "sleep" a known time, ``on_token`` stamps the same
clock, and every delivered token's gap must equal the account of the cycles
that delivered it, exactly.  CPU only; no real time is asserted."""

import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as serving_engine
from paddle_tpu.serving.metrics import LONGEST_CYCLES, STALL_NS, Cycle, \
    SLOMeter

MS = 1_000_000
ADMIT, PREFILL, DECODE, DELIVER, OUTSIDE = 1, 11, 7, 2, 3     # ms a phase


class Clock:
    """Seconds as the engine's ``now``, kept in whole nanoseconds."""

    def __init__(self):
        self.ns = 5_000 * MS

    def __call__(self) -> float:
        return self.ns / 1e9

    def sleep(self, ms: float) -> None:
        self.ns += int(ms * MS)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=96,
                                    max_position_embeddings=128))
    m.eval()
    return m


class Harness:
    """An engine whose phases take the constants above on ``clock`` and
    whose every closed cycle and delivered token is written down."""

    def __init__(self, model, **kw):
        self.clock = clock = Clock()
        self.tokens = {}            # rid -> [(idx, ns, seq of its cycle)]
        self.cycles = {}            # seq -> what the meter was handed
        self.eng = eng = ServingEngine(
            model, now=clock, on_token=self._on_token,
            **dict(dict(max_batch=3, page_tokens=4, num_pages=40,
                        max_pages_per_seq=8), **kw))
        self.deliver_ms = DELIVER

        def slowed(fn, ms):
            def call(*a, **k):
                clock.sleep(ms() if callable(ms) else ms)
                return fn(*a, **k)
            return call

        eng._admit = slowed(eng._admit, ADMIT)
        eng._prefill = slowed(eng._prefill, PREFILL)
        eng._decode_step = slowed(eng._decode_step, DECODE)
        if eng.journal is not None:     # inside the flush, before on_token
            eng.journal.flush = slowed(eng.journal.flush,
                                       lambda: self.deliver_ms)
        closed = eng.meter.cycle_closed

        def keep(cy, *, step):
            self.cycles[cy.seq] = dict(
                cy.counts(), ns=dict(cy.ns), compiled=cy.compiled, step=step,
                end=cy.t)
            closed(cy, step=step)

        eng.meter.cycle_closed = keep

    def _on_token(self, rid, idx, tok):
        self.tokens.setdefault(rid, []).append(
            (idx, self.clock.ns, self.eng._cycle.seq))

    def run(self, outside_ms=OUTSIDE, max_steps=500):
        eng = self.eng
        while eng._queue or eng._active:
            eng.step()
            self.clock.sleep(outside_ms)
            max_steps -= 1
            assert max_steps > 0
        eng.pool.check_leaks(allow_shared=eng.prefix is not None)

    def length(self, seq) -> int:
        return sum(self.cycles[seq]["ns"].values())


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 96, n).astype(np.int32) for n in lens]


@pytest.fixture(scope="module")
def served(model, tmp_path_factory):
    """Three requests, the third arriving mid-stream, a journal so that the
    flush itself takes time."""
    h = Harness(model, journal=str(tmp_path_factory.mktemp("journal")))
    p = _prompts(1, (6, 9, 5))
    h.rids = [h.eng.submit(p[0], max_new_tokens=6),
              h.eng.submit(p[1], max_new_tokens=12)]
    for _ in range(3):
        h.eng.step()
        h.clock.sleep(OUTSIDE)
    h.rids.append(h.eng.submit(p[2], max_new_tokens=4))
    h.run()
    return h


# -- (1) a gap is a cycle ---------------------------------------------------
def test_every_gap_is_the_length_of_the_cycle_that_delivered_it(served):
    h, checked = served, 0
    for rid, toks in h.tokens.items():
        assert [i for i, _, _ in toks] == list(range(len(toks)))
        for (_, t0, s0), (idx, t1, s1) in zip(toks, toks[1:]):
            # the same flush: no wait; the next one: that cycle's length
            assert s1 - s0 in (0, 1), (rid, idx)
            assert t1 - t0 == (h.length(s1) if s1 > s0 else 0), (rid, idx)
            checked += s1 > s0
    assert checked == sum(c["gaps"] for c in h.cycles.values()) > 10
    assert all(c["gaps_long"] == 0 for c in h.cycles.values())


def test_cycles_are_numbered_and_end_where_the_tokens_were_seen(served):
    h = served
    assert sorted(h.cycles) == list(range(1, len(h.cycles) + 1))
    assert h.eng.meter.cycles_total == len(h.cycles)
    seen = {}
    for toks in h.tokens.values():
        for _, t, seq in toks:
            seen.setdefault(seq, set()).add(t)
    # nothing moved the clock between on_token and the cycle's end
    assert all(seen[seq] == {c["end"]} for seq, c in h.cycles.items())
    ends = [h.cycles[s]["end"] for s in sorted(h.cycles)]
    assert [b - a for a, b in zip(ends, ends[1:])] == \
        [h.length(s) for s in sorted(h.cycles)[1:]]


def test_the_five_parts_are_the_phases_that_ran(served):
    h = served
    for seq, c in h.cycles.items():
        ns = c["ns"]
        assert set(ns) == set(Cycle.PARTS)
        assert ns["prefill"] == c["prefill_requests"] * PREFILL * MS
        assert ns["admit"] == ADMIT * MS and ns["deliver"] == DELIVER * MS
        assert ns["decode"] == (DECODE * MS if c["decode_rows"] else 0)
        if seq > 1:     # the first began when the engine was made
            assert ns["outside"] == OUTSIDE * MS


def test_counts_sum_to_the_meters_and_the_requests_own(served):
    h = served
    total = {k: sum(c[k] for c in h.cycles.values()) for k in Cycle.COUNTS}
    n_tokens = {rid: len(t) for rid, t in h.tokens.items()}
    assert n_tokens == dict(zip(h.rids, (6, 12, 4)))
    assert total["first_tokens"] == total["prefill_requests"] == 3
    # a request's tokens are its first, the gaps that cycles closed, and
    # what came in the same flush as the one before (index 1 with index 0)
    same_flush = sum(s1 == s0 for t in h.tokens.values()
                     for (_, _, s0), (_, _, s1) in zip(t, t[1:]))
    assert total["gaps"] + same_flush + 3 == sum(n_tokens.values())
    # the first step's first prompt rode the second's launch with its first
    # token; the second prompt, and the third (alone in its step), stepped
    # from the step after their prefill
    assert same_flush == 1
    assert total["prefill_tokens"] == 6 + 9 + 5
    summary = h.eng.meter.summary()
    assert total["prefill_launches"] == summary["prefill_launches"] == sum(
        len(serving_engine.prefill_plan(-(-n // 4))) for n in (6, 9, 5))
    # a decode step steps every running row: a token each, but index 0
    assert total["decode_rows"] == sum(n_tokens.values()) - 3


def test_only_the_uncached_pages_count_as_prefilled(model):
    h = Harness(model, prefix_cache=True)
    shared = _prompts(4, (8,))[0]                 # two full pages of 4
    tails = _prompts(5, (3, 5))
    first = h.eng.submit(np.concatenate([shared, tails[0]]), max_new_tokens=2)
    h.run()
    assert sum(c["prefill_tokens"] for c in h.cycles.values()) == 11
    second = h.eng.submit(np.concatenate([shared, tails[1]]),
                          max_new_tokens=2)
    h.run()
    assert h.eng.prefix.hits == 1
    per_cycle = [c["prefill_tokens"] for c in h.cycles.values()]
    assert sum(per_cycle) == 11 + 5               # the tail's pages only
    assert {first, second} == set(h.tokens)


# -- (2) rows that sit out, flushes that deliver nothing --------------------
def test_a_row_that_sat_out_a_step_shows_in_gaps_long(model):
    """A pool too small for the load: the evicted request replays its
    tokens (none delivered twice) and its next NEW token closes a gap that
    spans several cycles."""
    h = Harness(model, num_pages=9)
    for p in _prompts(2, (6, 9, 5)):
        h.eng.submit(p, max_new_tokens=10)
    h.run()
    assert h.eng.meter.summary()["evictions"] >= 1
    long_gaps = 0
    for rid, toks in h.tokens.items():
        for (_, t0, s0), (idx, t1, s1) in zip(toks, toks[1:]):
            assert t1 - t0 == sum(h.length(s) for s in range(s0 + 1, s1 + 1))
            long_gaps += s1 - s0 > 1
    assert long_gaps >= 1
    assert sum(c["gaps_long"] for c in h.cycles.values()) == long_gaps
    assert all(c["gaps_long"] <= c["gaps"] for c in h.cycles.values())


def test_a_flush_that_delivers_nothing_ends_no_cycle(model):
    h = Harness(model)
    eng = h.eng
    eng.step()                                  # nothing queued
    eng.step()
    assert h.cycles == {} and eng.meter.cycles_total == 0
    assert eng._cycle.seq == 1
    h.clock.sleep(40)
    rid = eng.submit(_prompts(3, (5,))[0], max_new_tokens=3)
    eng.step()
    assert list(h.cycles) == [1] and eng._cycle.seq == 2
    first = h.cycles[1]
    # the empty steps and the wait are in the cycle the first tokens ended
    assert first["ns"]["admit"] == 3 * ADMIT * MS
    assert first["ns"]["outside"] >= 40 * MS
    assert (first["gaps"], first["first_tokens"]) == (0, 1)
    # the prompt's row steps from the next step on
    assert [i for i, _, _ in h.tokens[rid]] == [0]


# -- (3) what the meter keeps ----------------------------------------------
def _closed(meter, seq, ms, gaps=1, compiled=False, step=0):
    cy = Cycle(lambda: 0)
    cy.seq, cy.gaps, cy.compiled = seq, gaps, compiled
    cy.ns["decode"] = int(ms * MS)
    cy.t = seq * STALL_NS
    meter.cycle_closed(cy, step=step)


def test_the_meter_keeps_the_longest_that_somebody_waited_in():
    meter = SLOMeter()
    lengths = [5, 90, 17, 17, 1200, 3, 44, 260, 8, 61, 2500, 30]
    for seq, ms in enumerate(lengths, 1):
        _closed(meter, seq, ms, step=seq * 2)
    _closed(meter, 13, 9000, gaps=0)        # an idle engine: nobody's gap
    _closed(meter, 14, 7000, compiled=True)     # the warm-up's
    s = meter.summary()
    assert (s["cycles_total"], s["cycles_compiled"], s["cycles_over_1s"]) \
        == (14, 1, 2)
    kept = s["longest_cycles"]
    assert len(kept) == LONGEST_CYCLES == 8
    assert [c["ms"] for c in kept] == sorted(lengths, reverse=True)[:8]
    assert [c["seq"] for c in kept][:3] == [11, 5, 8]
    assert all(c["gaps"] > 0 and c["compiled"] is False for c in kept)
    for c in kept:
        parts = [c[p + "_ms"] for p in Cycle.PARTS]
        assert sum(parts) == pytest.approx(c["ms"], rel=1e-9)
        assert c["steps_total"] == c["seq"] * 2
        assert c["end_s"] == c["seq"] * STALL_NS / 1e9
        assert set(Cycle.COUNTS) <= set(c)
    assert meter.summary()["longest_cycles"] == kept       # a copy each time
    kept[0]["ms"] = 0
    assert meter.summary()["longest_cycles"][0]["ms"] == 2500


def test_a_cycle_that_compiled_is_counted_apart(served):
    h = served
    compiled = [seq for seq, c in h.cycles.items() if c["compiled"]]
    assert compiled == [1]          # both programs compile in the first step
    s = h.eng.meter.summary()
    assert s["cycles_compiled"] == 1 and s["cycles_over_1s"] == 0
    kept = s["longest_cycles"]
    assert 1 not in [c["seq"] for c in kept] and len(kept) == LONGEST_CYCLES
    assert [c["ms"] for c in kept] == sorted(
        (h.length(seq) / 1e6 for seq, c in h.cycles.items()
         if c["gaps"] and not c["compiled"]), reverse=True)[:LONGEST_CYCLES]
    # the cycles that held a prefill are the longest, and say so
    assert kept[0]["prefill_ms"] == PREFILL and kept[0]["prefill_tokens"] == 5
    assert kept[0]["ms"] == ADMIT + PREFILL + DECODE + DELIVER + OUTSIDE


# -- (4) where a stall lands -------------------------------------------------
def test_a_sleep_between_steps_is_outside_and_one_in_the_flush_is_deliver(
        model, tmp_path):
    h = Harness(model, journal=str(tmp_path / "journal"))
    eng = h.eng
    eng.submit(_prompts(6, (5,))[0], max_new_tokens=8)
    eng.step()
    h.clock.sleep(OUTSIDE)
    eng.step()                      # a plain cycle
    plain = h.cycles[2]["ns"]
    h.clock.sleep(1500)             # the caller froze
    eng.step()
    h.clock.sleep(OUTSIDE)
    h.deliver_ms = 2300             # the journal's disk froze
    eng.step()
    h.deliver_ms = DELIVER
    h.run()
    frozen_out, frozen_in = h.cycles[3]["ns"], h.cycles[4]["ns"]
    assert frozen_out == dict(plain, outside=1500 * MS)
    assert frozen_in == dict(plain, deliver=2300 * MS)
    s = eng.meter.summary()
    assert s["cycles_over_1s"] == 2
    worst = s["longest_cycles"][:2]
    assert [(c["seq"], c["deliver_ms"], c["outside_ms"]) for c in worst] == \
        [(4, 2300.0, OUTSIDE), (3, DELIVER, 1500.0)]
    # and each was one user's gap, to the nanosecond
    toks = h.tokens[next(iter(h.tokens))]
    gaps = {s1: t1 - t0 for (_, t0, _), (_, t1, s1) in zip(toks, toks[1:])}
    assert gaps[3] == h.length(3) and gaps[4] == h.length(4)


# -- (5) what left the hot path ---------------------------------------------
def test_the_flush_writes_no_ring_event_and_the_lifecycle_stays(model):
    """A loaded run: more tokens than the ring holds events.  An instant a
    request a flush used to push a request's own story out of the ring a
    crash dump is for, before the request had finished."""
    ring = telemetry.get_flight_recorder()
    # this test's events only: an earlier test of the process (a fleet's
    # own rids) may have left events under the same request names
    since = time.perf_counter_ns()      # the ring stamps this clock
    h = Harness(model, page_tokens=8, max_pages_per_seq=16, num_pages=64)
    rids = [h.eng.submit(p, max_new_tokens=100)
            for p in _prompts(7, (5, 7, 6, 4, 8, 5))]
    h.run()
    assert sum(len(t) for t in h.tokens.values()) == 600 > ring._events.maxlen
    events = ring.events(since_mono_ns=since)
    assert not [e for e in events if e["kind"] == "serve_deliver"]
    for rid in rids:        # the last to finish was submitted 600 tokens ago
        mine = [e["kind"] for e in events if e["name"] == str(rid)]
        assert [k for k in mine if k != "serve_evict"] == [
            "serve_submit", "serve_admit", "serve_first_token",
            "serve_finish"], rid
