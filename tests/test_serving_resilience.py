"""Serving resilience (ISSUE 10): admission control (bounded queue +
retry-after hints, circuit breaker over step failures), deadline attach /
shed / miss accounting, SLO-aware preemption, pool-pressure deferral of
long prompts, idle backoff, bounded SLO-meter memory, the serve fault
family, the crash-recovery journal with exactly-once token delivery, and
the process-isolated SIGKILL → Supervisor relaunch → journal replay chaos
e2e.

Tier-1 ``serving``/``chaos`` lanes; conftest pins the queue bounds,
breaker cooldowns and paged-KV geometry down for CPU.
"""

import json
import os
import signal
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.checkpoint import faults
from paddle_tpu.distributed.fleet.elastic.supervisor import (RestartPolicy,
                                                             Supervisor)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (CircuitBreaker, Deadline, Overloaded,
                                ServingEngine, ServingJournal, SLOMeter,
                                TokenSink)

pytestmark = [pytest.mark.serving, pytest.mark.chaos]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                     max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _solo(model, prompt, max_new, eos=None):
    ids, _ = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                            max_new_tokens=max_new, eos_token_id=eos,
                            pad_token_id=0 if eos is not None else None)
    return ids.numpy()[0]


class FakeClock:
    """Deterministic monotonic clock for deadline tests."""

    def __init__(self, t: float = 1000.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += float(dt)


def _events(kind):
    import paddle_tpu.telemetry as tel

    return [e for e in tel.get_flight_recorder().events()
            if e["kind"] == kind]


# ---------------------------------------------------------------------------
class TestAdmissionControl:
    def test_bounded_queue_rejects_with_retry_hint(self, model):
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4, max_queue=2)
        rng = np.random.default_rng(0)
        p = lambda: rng.integers(1, 96, 5).astype(np.int32)  # noqa: E731
        eng.submit(p(), max_new_tokens=3)
        eng.submit(p(), max_new_tokens=3)
        with pytest.raises(Overloaded) as ei:
            eng.submit(p(), max_new_tokens=3)
        assert ei.value.reason == "queue_full"
        assert ei.value.retry_after_s is not None \
            and ei.value.retry_after_s > 0
        assert eng.meter.rejected_total == 1
        assert _events("serve_reject")
        # the two accepted requests still serve to completion
        outs = eng.run()
        assert len(outs) == 2
        eng.pool.check_leaks()

    def test_retry_hint_uses_measured_drain_rate(self):
        clock = FakeClock()
        m = SLOMeter(now=clock)
        for rid in range(4):
            m.submit(rid)
            m.admit(rid, queue_depth=0, pages=1)
            m.first_token(rid)
            clock.advance(0.5)          # one finish every 0.5s
            m.finish(rid, n_tokens=1)
        assert m.finish_rate_per_s() == pytest.approx(2.0)
        from paddle_tpu.serving import AdmissionController

        ac = AdmissionController(max_queue=4, now=clock)
        # 4 queued at 2 req/s -> ~2s until a slot frees
        assert ac.retry_after_hint(4, m) == pytest.approx(2.0)

    def test_duplicate_rid_rejected(self, model):
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4)
        eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2, rid=7)
        with pytest.raises(ValueError, match="already known"):
            eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2,
                       rid=7)
        eng.run()


class TestCircuitBreaker:
    def test_state_machine(self):
        clock = FakeClock()
        br = CircuitBreaker(threshold=2, cooldown_s=1.0, now=clock)
        assert br.allow() and br.state == "closed"
        br.note_failure()
        assert br.state == "closed" and br.allow()
        br.note_failure()
        assert br.state == "open" and not br.allow()
        assert br.retry_after_s() == pytest.approx(1.0)
        clock.advance(0.5)
        assert not br.allow()
        clock.advance(0.6)
        assert br.allow() and br.state == "half_open"
        br.note_failure()               # half-open probe failed: re-open
        assert br.state == "open"
        clock.advance(1.1)
        assert br.allow()
        br.note_success()
        assert br.state == "closed" and br.open_count == 2

    def test_step_failures_open_breaker_and_pause_admission(self, model):
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4)
        eng.admission.breaker = CircuitBreaker(threshold=3, cooldown_s=60.0)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
        eng.step()      # the prefill: its row decodes from the next step on
        with faults.inject(op="serve_decode", mode="error", times=3) as spec:
            for _ in range(3):          # 3 decode steps flake
                eng.step()
            assert spec.fired == 3
        assert eng.admission.breaker.state == "open"
        with pytest.raises(Overloaded) as ei:
            eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        assert ei.value.reason == "breaker_open"
        # faults exhausted: the next successful step closes the breaker
        # and admission resumes without waiting out the cooldown
        eng.step()
        assert eng.admission.breaker.state == "closed"
        rid2 = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        outs = eng.run()
        assert rid in outs and rid2 in outs
        import paddle_tpu.telemetry as tel

        assert tel.counters().get("serving.step_failures_total", 0) >= 3
        eng.pool.check_leaks()

    def test_injected_crash_propagates(self, model):
        """InjectedCrash models the process dying — the step loop must
        NOT absorb it (the journal/supervisor path owns recovery)."""
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4)
        eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
        with faults.inject(op="serve_prefill", mode="crash"):
            with pytest.raises(faults.InjectedCrash):
                eng.run()

    def test_persistent_failure_eventually_raises(self, model):
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4)
        eng._max_step_failures = 3
        eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
        with faults.inject(op="serve_prefill", mode="error", times=-1):
            with pytest.raises(faults.InjectedIOError):
                eng.run()


# ---------------------------------------------------------------------------
class TestDeadlines:
    def _engine(self, model, clock, **kw):
        kw.setdefault("max_batch", 2)
        kw.setdefault("page_tokens", 8)
        kw.setdefault("num_pages", 24)
        kw.setdefault("max_pages_per_seq", 4)
        return ServingEngine(model, now=clock, **kw)

    def test_expired_ttft_is_shed_not_served(self, model):
        clock = FakeClock()
        eng = self._engine(model, clock)
        rid_dead = eng.submit(np.arange(1, 6, dtype=np.int32),
                              max_new_tokens=3,
                              deadline=Deadline(ttft_s=1.0))
        rid_ok = eng.submit(np.arange(1, 7, dtype=np.int32),
                            max_new_tokens=3)
        clock.advance(2.0)              # rid_dead's TTFT budget is gone
        outs = eng.run()
        assert rid_dead not in outs
        assert eng.shed[rid_dead] == "ttft_expired"
        assert rid_ok in outs and len(outs[rid_ok]) == 3
        evs = _events("serve_shed")
        assert any(e["name"] == str(rid_dead) for e in evs)
        assert eng.meter.shed_total == 1
        eng.pool.check_leaks()

    def test_expired_total_is_shed(self, model):
        clock = FakeClock()
        eng = self._engine(model, clock)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3,
                         deadline=Deadline(total_s=5.0))
        clock.advance(6.0)
        eng.run()
        assert eng.shed[rid] == "total_expired"

    def test_unreachable_ttft_shed_predictively(self, model):
        """A queued request whose remaining TTFT budget is smaller than
        the measured admit->first-token estimate is shed BEFORE its
        budget expires — pages go to requests that can still make it."""
        clock = FakeClock()
        eng = self._engine(model, clock)
        eng.meter._ft_window.append(5.0)    # measured: prefill takes ~5s
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3,
                         deadline=Deadline(ttft_s=8.0))
        clock.advance(4.0)              # 4s budget left < 5s estimate
        eng.run()
        assert eng.shed[rid] == "ttft_unreachable"

    def test_met_deadline_not_shed_and_miss_rate_zero(self, model):
        clock = FakeClock()
        eng = self._engine(model, clock)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3,
                         deadline=Deadline(ttft_s=60.0, total_s=600.0))
        outs = eng.run()
        np.testing.assert_array_equal(
            outs[rid], _solo(model, np.arange(1, 6), 3))
        assert eng.shed == {}
        assert eng.meter.summary()["deadline_miss_rate"] == 0.0

    def test_active_request_finishing_late_counts_miss(self, model):
        """Active requests are never shed — a late finish is counted as a
        deadline miss (meter + prometheus gauge)."""
        clock = FakeClock()
        eng = self._engine(model, clock)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3,
                         deadline=Deadline(total_s=1.0))
        eng.step()                      # admitted + prefilled in time
        clock.advance(5.0)              # ... but decode drags past total_s
        outs = eng.run()
        assert rid in outs              # served, not shed
        s = eng.meter.summary()
        assert s["deadline_miss_rate"] == 1.0
        assert eng.meter.deadline_misses_total == 1
        from paddle_tpu.telemetry import prometheus_text

        txt = prometheus_text()
        assert "paddle_tpu_serving_deadline_miss_rate" in txt
        eng.pool.check_leaks()

    def test_slo_aware_preemption_evicts_most_slack(self, model):
        """With deadlines attached the pool-pressure victim is the request
        with the MOST slack — even when it is the oldest admit (the
        no-deadline policy would have evicted the youngest)."""
        clock = FakeClock()
        eng = self._engine(model, clock, max_batch=2, page_tokens=4,
                           num_pages=6, max_pages_per_seq=6)
        rng = np.random.default_rng(3)
        p_old = rng.integers(1, 96, 5).astype(np.int32)
        p_young = rng.integers(1, 96, 5).astype(np.int32)
        r_old = eng.submit(p_old, max_new_tokens=8,
                           deadline=Deadline(total_s=500.0))   # lots of slack
        eng.step()                      # old admitted + prefilled
        clock.advance(1.0)
        r_young = eng.submit(p_young, max_new_tokens=8,
                             deadline=Deadline(total_s=30.0))  # tight
        outs = eng.run()
        evs = [e for e in _events("serve_evict")
               if e["name"] in (str(r_old), str(r_young))]
        assert evs, "expected at least one eviction"
        assert evs[0]["name"] == str(r_old), \
            "victim should be the most-slack request (the old one)"
        # both still complete token-exact (deterministic replay)
        np.testing.assert_array_equal(outs[r_old],
                                      _solo(model, p_old, 8))
        np.testing.assert_array_equal(outs[r_young],
                                      _solo(model, p_young, 8))
        eng.pool.check_leaks()


# ---------------------------------------------------------------------------
class TestDeferral:
    def test_long_head_deferred_under_pool_pressure(self, model):
        """A long prompt at the FIFO head that does not fit must not wedge
        admission: a shorter request behind it is admitted (serve_defer
        event), and the head still completes once pages free up."""
        eng = ServingEngine(model, max_batch=3, page_tokens=4,
                            num_pages=6, max_pages_per_seq=6)
        rng = np.random.default_rng(5)
        p_busy = rng.integers(1, 96, 9).astype(np.int32)    # 3 pages
        p_long = rng.integers(1, 96, 11).astype(np.int32)   # 3 pages
        p_short = rng.integers(1, 96, 5).astype(np.int32)   # 2 pages
        r_busy = eng.submit(p_busy, max_new_tokens=3)
        eng.step()                      # busy admitted: 2 pages free
        r_long = eng.submit(p_long, max_new_tokens=2)
        r_short = eng.submit(p_short, max_new_tokens=6)
        eng.step()
        active = {r.rid for r in eng._active.values()}
        assert r_short in active, "short request should bypass the head"
        assert r_long not in active
        assert _events("serve_defer")
        assert eng._queue[0].defers >= 1
        outs = eng.run()
        for p, rid in ((p_busy, r_busy), (p_long, r_long),
                       (p_short, r_short)):
            np.testing.assert_array_equal(
                outs[rid], _solo(model, p, len(outs[rid])),
                err_msg=f"rid {rid}")
        eng.pool.check_leaks()

    def test_defer_budget_restores_fifo(self, model):
        """After PADDLE_TPU_SERVE_DEFER_MAX bypasses the head holds strict
        FIFO — later short requests must wait behind it."""
        eng = ServingEngine(model, max_batch=3, page_tokens=4,
                            num_pages=6, max_pages_per_seq=6)
        eng._defer_max = 1
        rng = np.random.default_rng(6)
        r_busy = eng.submit(rng.integers(1, 96, 9).astype(np.int32),
                            max_new_tokens=8)           # holds 3+ pages
        eng.step()
        r_long = eng.submit(rng.integers(1, 96, 11).astype(np.int32),
                            max_new_tokens=2)
        r_s1 = eng.submit(rng.integers(1, 96, 5).astype(np.int32),
                          max_new_tokens=2)
        r_s2 = eng.submit(rng.integers(1, 96, 5).astype(np.int32),
                          max_new_tokens=2)
        eng.step()                      # bypass #1 admits s1 (2 tokens: it
        active = {r.rid for r in eng._active.values()}  # finishes in-step)
        assert r_s1 in active or r_s1 in eng._results
        assert eng._queue[0].rid == r_long and eng._queue[0].defers == 1
        eng.step()                      # budget burned: s2 must NOT bypass
        active = {r.rid for r in eng._active.values()}
        assert r_s2 not in active and r_s2 not in eng._results
        outs = eng.run()
        assert sorted(outs) == sorted([r_busy, r_long, r_s1, r_s2])
        eng.pool.check_leaks()


# ---------------------------------------------------------------------------
class TestIdleBackoff:
    def test_idle_engine_does_not_spin(self, model):
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4)
        t = threading.Thread(target=eng.serve_forever, daemon=True)
        t.start()
        time.sleep(0.3)
        assert eng.steps_total == 0, "idle engine must not step"
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
        deadline = time.time() + 60
        while rid not in eng._results and time.time() < deadline:
            time.sleep(0.02)
        assert rid in eng._results
        s0 = eng.steps_total
        assert s0 > 0
        time.sleep(0.3)                 # drained: counter flat again
        assert eng.steps_total == s0
        eng.stop()
        t.join(timeout=10)
        assert not t.is_alive()
        eng.pool.check_leaks()


    def test_forever_mode_not_killed_by_quiesce_guard(self, model):
        """The batch-mode livelock guard (max_steps) must not execute a
        healthy long-running server: forever mode steps without bound."""
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4)
        t = threading.Thread(
            target=lambda: eng.run(forever=True, max_steps=2), daemon=True)
        t.start()
        rid = eng.submit(np.arange(1, 6, dtype=np.int32),
                         max_new_tokens=6)       # needs well over 2 steps
        deadline = time.time() + 60
        while rid not in eng._results and time.time() < deadline:
            time.sleep(0.02)
        assert rid in eng._results and len(eng._results[rid]) == 6
        eng.stop()
        t.join(timeout=10)
        assert not t.is_alive()


class TestSLOMeterBounded:
    def test_memory_bounded_and_clocks_dropped(self):
        clock = FakeClock()
        m = SLOMeter(now=clock, window=8)
        for rid in range(50):
            m.submit(rid)
            m.admit(rid, queue_depth=0, pages=1)
            m.first_token(rid)
            clock.advance(0.01)
            m.finish(rid, n_tokens=4)
        assert len(m._window) == 8
        assert len(m._ft_window) <= 8
        assert m._clocks == {}, "finished clocks must be dropped"
        s = m.summary()
        assert s["requests_finished"] == 50      # totals stay exact
        assert s["ttft_ms_p99"] is not None

    def test_shed_drops_clock_and_counts(self):
        m = SLOMeter(window=8)
        m.submit("a")
        m.shed("a", reason="ttft_expired")
        assert m._clocks == {} and m.shed_total == 1
        import paddle_tpu.telemetry as tel

        assert tel.counters().get("serving.requests_shed_total", 0) >= 1
        from paddle_tpu.telemetry import prometheus_text

        assert "paddle_tpu_serving_requests_shed_total" in prometheus_text()


# ---------------------------------------------------------------------------
class TestJournal:
    def test_segments_fold_roundtrip(self, tmp_path):
        j = ServingJournal(str(tmp_path / "j"))
        j.submit(0, [1, 2, 3], 4, None, None)
        j.flush()
        j.deliver(0, 0, 11)
        j.deliver(0, 1, 12)
        j.flush()
        j.finish(0)
        j.submit(1, [4, 5], 4, 2, Deadline(ttft_s=2.0))
        j.shed(2, "ttft_expired")
        j.flush()
        st = ServingJournal(str(tmp_path / "j")).load_state()
        assert st.delivered[0] == [11, 12]
        assert 0 in st.finished
        assert st.requests[1]["deadline"]["ttft_s"] == 2.0
        assert st.shed[2] == "ttft_expired"
        assert st.open_rids() == [1]
        assert not st.truncated

    def test_corrupt_segment_stops_fold_at_boundary(self, tmp_path):
        root = tmp_path / "j"
        j = ServingJournal(str(root))
        j.submit(0, [1, 2], 4, None, None)
        j.deliver(0, 0, 9)
        j.flush()
        j.deliver(0, 1, 10)
        j.flush()
        segs = sorted(os.listdir(root))
        (root / segs[-1]).write_bytes(b'[{"t": "deliver", "rid"')  # torn
        st = ServingJournal(str(root)).load_state()
        assert st.truncated
        assert st.delivered[0] == [9], \
            "fold must stop at the previous segment boundary"

    def test_submit_durable_unwind_preserves_other_pending(self, tmp_path):
        """A failed submit flush drops exactly the ghost submit record —
        the serving thread's buffered deliver records (awaiting a
        step-flush retry) must survive the unwind."""
        j = ServingJournal(str(tmp_path / "j"))
        j.deliver(0, 0, 1)
        with faults.inject(op="serve_journal", mode="error", times=4):
            with pytest.raises(OSError):
                j.submit_durable(1, [1, 2], 4, None, None)
        assert j.pending == 1
        j.flush()
        st = ServingJournal(str(tmp_path / "j")).load_state()
        assert 1 not in st.requests
        assert st.delivered[0] == [1]

    def test_token_sink_exactly_once_across_reopen(self, tmp_path):
        path = str(tmp_path / "out.jsonl")
        s1 = TokenSink(path)
        s1(0, 0, 5)
        s1(0, 1, 6)
        s1(1, 0, 7)
        s1(0, 1, 99)                    # duplicate: dropped, value ignored
        assert s1.dropped == 1
        s1.close()
        s2 = TokenSink(path)            # restart: high-water marks reload
        s2(0, 1, 6)                     # replays dedup
        s2(0, 2, 8)                     # new token appends
        with pytest.raises(ValueError, match="gap"):
            s2(1, 5, 0)
        s2.close()
        assert TokenSink.collect(path) == {0: [5, 6, 8], 1: [7]}

    def test_submit_flush_failure_leaves_no_phantom(self, model, tmp_path):
        """An admission whose durability flush fails must fail CLEANLY:
        no queue entry (would serve work the client was told was
        refused), no buffered journal record (would resurrect it after a
        crash), and the engine keeps serving afterwards."""
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4,
                            journal=str(tmp_path / "j"))
        with faults.inject(op="serve_journal", mode="error", times=4):
            with pytest.raises(OSError):
                eng.submit(np.arange(1, 6, dtype=np.int32),
                           max_new_tokens=3, rid=5)
        assert len(eng._queue) == 0
        assert eng.journal.pending == 0
        assert 5 not in eng.journal.load_state().requests
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
        outs = eng.run()
        assert list(outs) == [rid]

    def test_quarantine_unshadows_later_segments(self, tmp_path):
        """A corrupt segment is quarantined at recovery — segments the
        recovered incarnation writes afterwards must be visible to the
        NEXT recovery instead of being shadowed by the corrupt tail."""
        root = tmp_path / "j"
        j = ServingJournal(str(root))
        j.submit(0, [1, 2], 8, None, None)
        j.flush()                                   # seg_0
        j.deliver(0, 0, 9)
        j.flush()                                   # seg_1
        j.deliver(0, 1, 10)
        j.flush()                                   # seg_2
        segs = sorted(p for p in os.listdir(root) if p.endswith(".json"))
        (root / segs[1]).write_bytes(b"garbage")    # seg_1 torn
        j2 = ServingJournal(str(root))
        st = j2.load_state()
        assert st.truncated and st.delivered[0] == []
        # the recovered incarnation keeps serving (regenerates from the
        # earlier high-water mark) and journals on
        j2.deliver(0, 0, 9)
        j2.flush()
        st3 = ServingJournal(str(root)).load_state()
        assert not st3.truncated
        assert st3.delivered[0] == [9]

    def test_journal_flush_flake_absorbed_by_step_loop(self, model,
                                                       tmp_path):
        """A transient storage failure on the journal segment write is a
        step failure: records stay buffered, the next step re-flushes,
        nothing is lost or duplicated."""
        sink = TokenSink(str(tmp_path / "out.jsonl"))
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4,
                            journal=str(tmp_path / "j"), on_token=sink)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
        # storage.write_bytes retries 3x internally; times=4 defeats one
        # whole flush attempt, the step loop retries the next step
        with faults.inject(op="serve_journal", mode="error", times=4):
            outs = eng.run()
        np.testing.assert_array_equal(outs[rid],
                                      _solo(model, np.arange(1, 6), 4))
        assert TokenSink.collect(sink.path)[rid] == list(outs[rid])
        st = eng.journal.load_state()
        assert st.delivered[rid] == list(outs[rid])
        assert rid in st.finished


class TestJournalRecovery:
    def test_in_process_replay_exactly_once(self, model, tmp_path):
        """Engine dies mid-stream (abandoned); a fresh engine recovers
        from the journal: every request completes token-exact, the sink
        holds every delivered token exactly once."""
        jdir, spath = str(tmp_path / "j"), str(tmp_path / "out.jsonl")
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 96, n).astype(np.int32)
                   for n in (5, 9, 7)]
        sink1 = TokenSink(spath)
        eng1 = ServingEngine(model, max_batch=2, page_tokens=8,
                             num_pages=24, max_pages_per_seq=4,
                             journal=jdir, on_token=sink1)
        rids = [eng1.submit(p, max_new_tokens=6) for p in prompts]
        eng1.step()                     # admit + prefill (2 rows) + decode
        eng1.step()
        delivered_before = TokenSink.collect(spath)
        assert delivered_before, "some tokens must be out before the crash"
        assert not eng1._results, "nothing should have finished yet"
        sink1.close()                   # process dies here

        sink2 = TokenSink(spath)
        eng2 = ServingEngine(model, max_batch=2, page_tokens=8,
                             num_pages=24, max_pages_per_seq=4,
                             journal=jdir, on_token=sink2)
        info = eng2.recover()
        assert info["replayed"] == 3 and info["finished"] == 0
        outs = eng2.run()
        streams = TokenSink.collect(spath)   # raises on any duplicate
        for p, rid in zip(prompts, rids):
            expect = _solo(model, p, 6)
            np.testing.assert_array_equal(outs[rid], expect,
                                          err_msg=f"rid {rid}")
            assert streams[rid] == list(expect), f"rid {rid} sink stream"
        eng2.pool.check_leaks()

    def test_final_step_flush_failure_retried_before_exit(self, model,
                                                          tmp_path):
        """A transient flush failure on the step that retires the LAST
        request must not be silently dropped: run() drains the pending
        delivery (retrying the flush) before declaring quiescence."""
        sink = TokenSink(str(tmp_path / "out.jsonl"))
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=24, max_pages_per_seq=4,
                            journal=str(tmp_path / "j"), on_token=sink)
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
        # prefill + decode + retire all land in step 1; times=4 defeats
        # exactly that step's flush (3 internal retries), so the loop's
        # drain pass must re-flush before run() returns
        with faults.inject(op="serve_journal", mode="error", times=4):
            outs = eng.run()
        assert list(outs[rid])
        assert TokenSink.collect(sink.path)[rid] == list(outs[rid])
        st = eng.journal.load_state()
        assert rid in st.finished
        assert st.delivered[rid] == list(outs[rid])

    def test_replayed_deadline_keeps_aging_across_crash(self, model,
                                                        tmp_path):
        """A total_s budget that died while the process was down must shed
        at recovery, not serve a client that gave up long ago — the
        journal's wall-clock submit stamp ages the replayed request."""
        jdir = str(tmp_path / "j")
        eng1 = ServingEngine(model, max_batch=2, page_tokens=8,
                             num_pages=24, max_pages_per_seq=4,
                             journal=jdir)
        rid = eng1.submit(np.arange(1, 6, dtype=np.int32),
                          max_new_tokens=4,
                          deadline=Deadline(total_s=30.0))
        # crash before any step; time-travel the outage 100s into the past
        seg = sorted((tmp_path / "j").glob("seg_*.json"))[0]
        doc = json.loads(seg.read_text())
        doc[0]["submit_wall"] -= 100.0
        seg.write_text(json.dumps(doc))
        eng2 = ServingEngine(model, max_batch=2, page_tokens=8,
                             num_pages=24, max_pages_per_seq=4,
                             journal=jdir)
        assert eng2.recover()["replayed"] == 1
        outs = eng2.run()
        assert rid not in outs
        assert eng2.shed[rid] == "total_expired"

    def test_recover_restores_finished_and_shed(self, model, tmp_path):
        jdir = str(tmp_path / "j")
        clock = FakeClock()
        eng1 = ServingEngine(model, max_batch=2, page_tokens=8,
                             num_pages=24, max_pages_per_seq=4,
                             journal=jdir, now=clock)
        r_done = eng1.submit(np.arange(1, 6, dtype=np.int32),
                             max_new_tokens=2)
        r_shed = eng1.submit(np.arange(1, 8, dtype=np.int32),
                             max_new_tokens=2,
                             deadline=Deadline(ttft_s=1.0))
        clock.advance(5.0)              # r_shed's budget dies in the queue
        outs1 = eng1.run()
        assert r_done in outs1 and r_shed in eng1.shed

        eng2 = ServingEngine(model, max_batch=2, page_tokens=8,
                             num_pages=24, max_pages_per_seq=4,
                             journal=jdir)
        info = eng2.recover()
        assert info["replayed"] == 0
        assert sorted(info["known_rids"]) == sorted([r_done, r_shed])
        np.testing.assert_array_equal(eng2._results[r_done], outs1[r_done])
        assert eng2.shed[r_shed] == "ttft_expired"


# ---------------------------------------------------------------------------
CHILD = """
import json, os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed.checkpoint import faults
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import Deadline, Overloaded, ServingEngine, TokenSink

work = sys.argv[1]
trace = json.load(open(os.path.join(work, "trace.json")))

paddle.seed(3)
cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                 max_position_embeddings=128)
model = LlamaForCausalLM(cfg)
model.eval()

sink = TokenSink(os.path.join(work, "out.jsonl"))
marker = os.path.join(work, "killed")
first_life = not os.path.exists(marker)
count = {"n": 0}

def on_token(rid, idx, tok):
    sink(rid, idx, tok)
    count["n"] += 1
    if first_life and count["n"] >= trace["kill_after_tokens"]:
        open(marker, "w").write("1")
        os.kill(os.getpid(), signal.SIGKILL)   # hard mid-stream death

eng = ServingEngine(model, max_batch=3, page_tokens=8, num_pages=24,
                    max_pages_per_seq=6, max_queue=trace["max_queue"],
                    journal=os.path.join(work, "journal"), on_token=on_token)
info = eng.recover()
known = set(info["known_rids"])

rej_path = os.path.join(work, "rejected.json")
rejected = set(json.load(open(rej_path))) if os.path.exists(rej_path) else set()
for req in trace["requests"]:
    if req["rid"] in known or req["rid"] in rejected:
        continue
    dl = None
    if req.get("ttft_s") is not None or req.get("total_s") is not None:
        dl = Deadline(ttft_s=req.get("ttft_s"), total_s=req.get("total_s"))
    try:
        eng.submit(np.asarray(req["prompt"], np.int32),
                   max_new_tokens=req["max_new"], deadline=dl,
                   rid=req["rid"])
    except Overloaded:
        rejected.add(req["rid"])
json.dump(sorted(rejected), open(rej_path, "w"))

# seeded transient serve faults ride the whole run; the step loop absorbs
with faults.inject(op="serve", mode="error", times=2, seed=7):
    outs = eng.run(watchdog_s=120)

json.dump({"results": {str(k): [int(x) for x in v] for k, v in outs.items()},
           "shed": {str(k): v for k, v in eng.shed.items()},
           "replayed": info["replayed"],
           "ttft_ms_p99": eng.meter.summary()["ttft_ms_p99"]},
          open(os.path.join(work, "final.json"), "w"))
"""


class TestChaosEndToEnd:
    def test_sigkill_relaunch_replay_exactly_once(self, model, tmp_path):
        """ACCEPTANCE: over-capacity mixed-length trace with deadlines +
        seeded serve faults; the engine is SIGKILLed mid-stream, the
        Supervisor relaunches it, the journal replays — every accepted
        request completes exactly once and token-exact, every rejected or
        shed request is explicitly accounted, p99 TTFT of accepted
        requests stays within the configured deadline."""
        work = str(tmp_path)
        rng = np.random.default_rng(42)
        TTFT_BUDGET_S = 120.0
        reqs = []
        for rid in range(8):
            n = int((5, 9, 14, 7, 11, 6, 9, 5)[rid])
            req = {"rid": rid,
                   "prompt": [int(x) for x in rng.integers(1, 96, n)],
                   "max_new": int((4, 5, 6, 4, 5, 4, 4, 4)[rid])}
            if rid in (0, 1):
                req["ttft_s"] = 1e-6      # dead on arrival: must be shed
            else:
                req["ttft_s"] = TTFT_BUDGET_S
            reqs.append(req)
        # queue bound 6: rids 0..5 accepted, 6..7 rejected Overloaded
        trace = {"requests": reqs, "max_queue": 6, "kill_after_tokens": 6}
        with open(os.path.join(work, "trace.json"), "w") as f:
            json.dump(trace, f)
        script = os.path.join(work, "child.py")
        with open(script, "w") as f:
            f.write(textwrap.dedent(CHILD))

        env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
        sup = Supervisor(
            [sys.executable, script, work],
            policy=RestartPolicy(max_restarts=3, backoff_base=0.05,
                                 backoff_cap=0.2),
            restart_codes=(101, -signal.SIGKILL),
            env=env, child_timeout=600)
        assert sup.run() == 0
        assert sup.restarts == 1, sup.exit_codes
        assert os.path.exists(os.path.join(work, "killed"))
        # the relaunch reported its journal replay through the supervisor
        # resume-report protocol
        assert sup.last_resume is not None
        assert sup.last_resume["resume_source"] == "journal"
        assert sup.last_resume["resume_replayed"] >= 1

        final = json.load(open(os.path.join(work, "final.json")))
        rejected = set(json.load(open(os.path.join(work, "rejected.json"))))
        assert rejected == {6, 7}, "over-capacity submits must be refused"
        assert set(map(int, final["shed"])) == {0, 1}
        assert all(v.startswith("ttft") for v in final["shed"].values())
        assert final["replayed"] >= 1, "relaunch must replay the journal"

        accepted = [r for r in reqs if r["rid"] in (2, 3, 4, 5)]
        results = {int(k): v for k, v in final["results"].items()}
        streams = TokenSink.collect(os.path.join(work, "out.jsonl"))
        for req in accepted:
            expect = _solo(model, np.asarray(req["prompt"], np.int32),
                           req["max_new"])
            np.testing.assert_array_equal(
                results[req["rid"]], expect,
                err_msg=f"rid {req['rid']} end-to-end output")
            assert streams[req["rid"]] == list(expect), \
                f"rid {req['rid']}: sink must hold every token exactly once"
        assert set(streams) == {2, 3, 4, 5}, "shed/rejected never emit"
        # p99 TTFT of accepted requests inside the configured budget
        assert final["ttft_ms_p99"] is not None
        assert final["ttft_ms_p99"] <= TTFT_BUDGET_S * 1e3
