"""Fleet observability plane (ISSUE 16): distributed request tracing,
job-level metrics aggregation, and the cross-rank black-box merge.

- trace_id span propagation: mint/passthrough, SLOMeter span events,
  engine submit->run chains, journal replay and depot fold keeping one id.
- Histogram: percentiles vs the numpy oracle (exact to a bucket width),
  merge == combined observe, Prometheus ``_bucket``/``_sum``/``_count``
  rendering with ``le`` + replica labels.
- Aggregator: MetricsPusher push/rollup over the framed-TCP depot AND the
  fleet-store KV fallback; merged-histogram aggregate p99 (never averaged
  percentiles); straggler naming cross-checked against the lease monitor;
  SIGKILL-surviving black-box spills.
- blackbox.merge: causal ordering (ship-before-fold beats a skewed wall
  clock), per-process order, dedup, torn-dump tolerance.
- ``python -m paddle_tpu.telemetry.report`` CLI smoke.
- Chaos e2e: SIGKILL a replica mid-stream; the merged timeline shows the
  dead replica's spans and the survivor's replay under the SAME trace_id,
  with exactly-once token delivery intact.

Tier-1 ``trace`` lane; conftest pins ``PADDLE_TPU_METRICS_PUSH_S`` to
0.2s so the chaos e2e never waits on a push beat.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.telemetry as tel
from paddle_tpu.distributed.checkpoint.replicator import (KVTransport,
                                                          SnapshotClient,
                                                          SnapshotStore)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import Deadline, ServingEngine, ServingJournal, \
    TokenSink
from paddle_tpu.serving.fleet import (JournalShipper, LocalKV,
                                      RemoteReplica, ServingFrontend,
                                      TokenCollector, fold_depot_journal)
from paddle_tpu.serving.metrics import SLOMeter
from paddle_tpu.telemetry import blackbox
from paddle_tpu.telemetry.aggregator import (Histogram, MemoryDepot,
                                             MetricsPusher, local_snapshot,
                                             prometheus_rollup_text, rollup)
from paddle_tpu.telemetry.prometheus import render_histogram
from paddle_tpu.telemetry.tracing import (REQUIRED_SPANS, chrome_trace_events,
                                          mint, spans, trace_coverage,
                                          trace_ids)

pytestmark = [pytest.mark.trace]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENGINE_KW = dict(max_batch=2, page_tokens=8, num_pages=24,
                 max_pages_per_seq=4)


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                     max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture
def depot():
    store = SnapshotStore(host="127.0.0.1")
    client = SnapshotClient("127.0.0.1", store.port)
    yield client
    client.close()
    store.close()


def _solo(model, prompt, max_new, eos=None):
    ids, _ = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                            max_new_tokens=max_new, eos_token_id=eos,
                            pad_token_id=0 if eos is not None else None)
    return ids.numpy()[0]


def _events_since(t0_ns):
    return tel.get_flight_recorder().events(since_mono_ns=t0_ns)


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


# ---------------------------------------------------------------------------
class TestMint:
    def test_format_and_uniqueness(self):
        ids = {mint() for _ in range(256)}
        assert len(ids) == 256
        for t in ids:
            assert len(t) == 16 and int(t, 16) >= 0

    def test_passthrough_never_forks_a_trace(self):
        # every replay site writes mint(rec.get("trace_id")) uniformly
        assert mint("feedfacecafef00d") == "feedfacecafef00d"
        assert mint(None) != mint(None)
        assert len(mint("")) == 16     # falsy -> fresh id


# ---------------------------------------------------------------------------
class TestHistogram:
    def test_percentiles_match_numpy_oracle_within_a_bucket(self, rng):
        samples = rng.uniform(0.0005, 2.0, 500)
        h = Histogram()
        for v in samples:
            h.observe(v)
        bounds = (0.0,) + h.buckets
        for q in (50.0, 90.0, 99.0):
            true = float(np.percentile(samples, q))
            est = h.percentile(q)
            i = next(j for j, ub in enumerate(h.buckets) if true <= ub)
            tol = h.buckets[i] - bounds[i]   # one bucket's width, exactly
            assert abs(est - true) <= tol + 1e-9, (q, est, true, tol)

    def test_merge_equals_combined_observe(self, rng):
        samples = rng.exponential(0.05, 400)
        ha, hb, hall = Histogram(), Histogram(), Histogram()
        for i, v in enumerate(samples):
            (ha if i % 2 else hb).observe(v)
            hall.observe(v)
        merged = Histogram.merged([ha.to_doc(), hb.to_doc()])
        assert merged.counts == hall.counts
        assert merged.inf == hall.inf and merged.count == hall.count
        assert merged.sum == pytest.approx(hall.sum)
        for q in (50.0, 99.0):
            assert merged.percentile(q) == pytest.approx(hall.percentile(q))

    def test_doc_round_trip_and_bucket_mismatch_is_loud(self):
        h = Histogram((0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        h2 = Histogram.from_doc(json.loads(json.dumps(h.to_doc())))
        assert h2.counts == h.counts and h2.inf == 1 and h2.count == 3
        with pytest.raises(ValueError, match="different buckets"):
            h2.merge(Histogram((0.1, 2.0)))

    def test_tail_rank_in_inf_returns_last_finite_bound(self):
        h = Histogram((1.0,))
        h.observe(50.0)
        assert h.percentile(99) == 1.0   # honest: the tail shape is unknown
        assert Histogram().percentile(99) is None

    def test_render_histogram_prometheus_series(self):
        h = Histogram((0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        lines = []
        render_histogram(lines, "x_seconds", "test hist", h.to_doc(),
                         labels={"replica": "r0"})
        text = "\n".join(lines)
        # cumulative buckets with le labels, replica label on every sample
        assert 'x_seconds_bucket{replica="r0",le="0.1"} 1' in text
        assert 'x_seconds_bucket{replica="r0",le="1.0"} 2' in text
        assert 'x_seconds_bucket{replica="r0",le="+Inf"} 3' in text
        assert 'x_seconds_count{replica="r0"} 3' in text
        assert 'x_seconds_sum{replica="r0"}' in text
        assert "# TYPE paddle_tpu_x_seconds histogram" in text


# ---------------------------------------------------------------------------
class TestSpanPropagation:
    def _life(self, m, rid, tid, clock):
        m.submit(rid, trace_id=tid)
        clock.advance(0.01)
        m.admit(rid, queue_depth=0, pages=1)
        clock.advance(0.02)
        m.first_token(rid)
        clock.advance(0.01)
        m.finish(rid, n_tokens=1)

    def test_slo_meter_stamps_every_span(self):
        clock = FakeClock()
        m = SLOMeter(now=clock)
        tid = mint()
        t0 = time.monotonic_ns()
        self._life(m, 7, tid, clock)
        evs = _events_since(t0)
        kinds = {e["kind"] for e in spans(evs, tid)}
        assert set(REQUIRED_SPANS) <= kinds
        assert trace_coverage(evs, finished_rids=[7]) == 1.0
        assert m.summary()["trace_coverage"] == 1.0
        assert tid in trace_ids(evs)

    def test_eviction_detour_keeps_the_trace(self):
        clock = FakeClock()
        m = SLOMeter(now=clock)
        tid = mint()
        t0 = time.monotonic_ns()
        m.submit(3, trace_id=tid)
        m.admit(3, queue_depth=0, pages=2)
        m.first_token(3)
        m.evict(3, reason="pool_pressure", pages_freed=2)
        m.admit(3, queue_depth=0, pages=2)   # replay re-admit
        m.first_token(3)
        m.finish(3, n_tokens=4)
        evs = spans(_events_since(t0), tid)
        assert "serve_evict" in {e["kind"] for e in evs}
        assert trace_coverage(_events_since(t0), finished_rids=[3]) == 1.0

    def test_trace_of_lives_with_the_clock(self):
        m = SLOMeter(now=FakeClock())
        m.submit(1, trace_id="aa" * 8)
        assert m.trace_of(1) == "aa" * 8
        m.admit(1, queue_depth=0, pages=1)
        m.first_token(1)
        m.finish(1, n_tokens=1)
        assert m.trace_of(1) is None      # folded away at finish

    def test_coverage_counts_an_untraced_finish_against_the_gate(self):
        clock = FakeClock()
        m = SLOMeter(now=clock)
        self._life(m, 0, mint(), clock)
        m.submit(1, trace_id=None)        # trace lost at the edge
        m.admit(1, queue_depth=0, pages=1)
        m.first_token(1)
        m.finish(1, n_tokens=1)
        assert m.summary()["trace_coverage"] == 0.5

    def test_event_based_coverage_requires_the_full_chain(self):
        def ev(kind, name, t):
            return {"kind": kind, "name": name, "trace": t,
                    "ts": 0.0, "mono_ns": 0}
        full = [ev(k, "0", "t1") for k in REQUIRED_SPANS]
        assert trace_coverage(full) == 1.0
        broken = [e for e in full if e["kind"] != "serve_admit"]
        assert trace_coverage(broken) == 0.0
        # vacuous truth: nothing finished, nothing to grade
        assert trace_coverage([]) == 1.0
        assert trace_coverage(full, finished_rids=[]) == 1.0

    def test_chrome_trace_events_mergeable_into_profiler_export(self):
        evs = [{"kind": "serve_submit", "name": "4", "trace": "ab" * 8,
                "ts": 100.0, "mono_ns": 5_000_000}]
        out = chrome_trace_events(evs, pid=9)
        assert out == [{"name": "serve_submit:4", "ph": "i", "s": "t",
                        "pid": 9, "tid": "trace:" + "ab" * 8,
                        "ts": 5000.0, "cat": "trace",
                        "args": {"trace": "ab" * 8}}]

    def test_journal_and_depot_fold_carry_the_trace(self, depot, tmp_path):
        tid = mint()
        j = ServingJournal(str(tmp_path / "t"),
                           ship=JournalShipper(depot, "t", 1))
        j.submit(5, [1, 2, 3], 4, None, None, trace_id=tid)
        j.flush()
        # a second journal over the same dir sees the id on disk...
        st = ServingJournal(str(tmp_path / "t")).load_state()
        assert st.requests[5]["trace_id"] == tid
        # ...and the frontend's failover fold sees it through the depot
        st2 = fold_depot_journal(depot, "t", 1)
        assert st2.requests[5]["trace_id"] == tid


# ---------------------------------------------------------------------------
class TestEngineTracePropagation:
    def test_submit_to_finish_is_one_complete_chain(self, model, tmp_path):
        t0 = time.monotonic_ns()
        eng = ServingEngine(model, journal=str(tmp_path / "j"), **ENGINE_KW)
        rng = np.random.default_rng(2)
        rid0 = eng.submit(rng.integers(1, 96, 5).astype(np.int32),
                          max_new_tokens=3)
        tid1 = "feedfacecafebeef"
        rid1 = eng.submit(rng.integers(1, 96, 7).astype(np.int32),
                          max_new_tokens=4, trace_id=tid1)
        eng.run()
        evs = _events_since(t0)
        assert eng.meter.summary()["trace_coverage"] == 1.0
        assert trace_coverage(evs, finished_rids=[rid0, rid1]) == 1.0
        kinds = {e["kind"] for e in spans(evs, tid1)}
        assert set(REQUIRED_SPANS) <= kinds
        # no instant a request a flush any more (they used to evict what
        # follows): the whole lifecycle chain of the finished request is
        # still in the ring after the run, in order
        chain = [e["kind"] for e in spans(evs, tid1)
                 if e["name"] == str(rid1)]
        assert chain == ["serve_submit", "serve_admit", "serve_first_token",
                         "serve_finish"]
        assert "serve_deliver" not in {e["kind"] for e in evs}
        finish = {e["name"]: e["trace"] for e in evs
                  if e["kind"] == "serve_finish"}
        assert finish[str(rid1)] == tid1
        # the edge-minted trace is distinct and well-formed
        assert finish[str(rid0)] != tid1 and len(finish[str(rid0)]) == 16
        eng.pool.check_leaks()

    def test_trace_survives_journal_replay(self, model, tmp_path):
        jdir = str(tmp_path / "j")
        eng1 = ServingEngine(model, journal=jdir, **ENGINE_KW)
        p = np.arange(1, 8, dtype=np.int32)
        rid = eng1.submit(p, max_new_tokens=5)
        tid = eng1.meter.trace_of(rid)
        assert tid is not None and len(tid) == 16
        eng1.step()
        eng1.step()                    # mid-stream; process "dies" here

        t0 = time.monotonic_ns()
        eng2 = ServingEngine(model, journal=jdir, **ENGINE_KW)
        assert eng2.recover()["replayed"] == 1
        # the replayed incarnation rides the ORIGINAL trace id
        assert eng2.meter.trace_of(rid) == tid
        outs = eng2.run()
        np.testing.assert_array_equal(outs[rid], _solo(model, p, 5))
        evs = _events_since(t0)
        kinds = {e["kind"] for e in spans(evs, tid)}
        assert {"serve_submit", "serve_finish"} <= kinds
        assert eng2.meter.summary()["trace_coverage"] == 1.0
        eng2.pool.check_leaks()


# ---------------------------------------------------------------------------
def _slo(req_s, finished):
    return {"requests_per_sec": req_s, "requests_finished": finished,
            "requests_shed": 0, "requests_rejected": 0}


def _two_pushers(transport):
    """Two replicas with disjoint TTFT distributions push through
    ``transport``; returns their local histograms for the oracle."""
    h0, h1 = Histogram(), Histogram()
    for _ in range(100):
        h0.observe(0.004)              # fast replica
        h1.observe(0.9)                # slow replica
    for src, rs, fin, h in (("r0", 2.5, 10, h0), ("r1", 1.5, 20, h1)):
        p = MetricsPusher(transport, slo_source=lambda r=rs, f=fin: _slo(r, f),
                          hists_source=lambda hh=h: {"ttft_s": hh},
                          src=src, epoch_dir=None, interval_s=999.0)
        assert p.push_once()
        assert p.pushes == 1 and p.push_failures == 0
    return h0, h1


class TestAggregator:
    def _check_rollup(self, snaps, h0, h1):
        assert set(snaps) == {"r0", "r1"}
        agg = rollup(snaps)
        # exact sums, never estimates
        assert agg["fleet_agg_req_s"] == pytest.approx(4.0)
        assert agg["requests_finished_total"] == 30
        # aggregate p99 comes from the MERGED buckets: rank 198/200 lands
        # deep in the slow replica's bucket (~0.99s).  Averaging the
        # per-replica p99s (~0.45s) would be off by 2x — assert both the
        # oracle equality and that the wrong fold was not taken.
        oracle = Histogram.merged([h0, h1]).percentile(99) * 1e3
        assert agg["ttft_p99_agg_ms"] == pytest.approx(oracle, rel=1e-6)
        avg_of_p99s = (h0.percentile(99) + h1.percentile(99)) / 2 * 1e3
        assert agg["ttft_p99_agg_ms"] > 1.5 * avg_of_p99s

    def test_rollup_over_memory_depot(self):
        depot = MemoryDepot()
        h0, h1 = _two_pushers(depot)
        self._check_rollup(depot.metrics_pull(), h0, h1)

    def test_rollup_over_framed_tcp_depot(self, depot):
        h0, h1 = _two_pushers(depot)
        self._check_rollup(depot.metrics_pull(), h0, h1)

    def test_rollup_over_kv_fallback_transport(self):
        kv = KVTransport(LocalKV())
        h0, h1 = _two_pushers(kv)
        self._check_rollup(kv.metrics_pull(), h0, h1)

    def test_straggler_named_and_cross_checked(self):
        snaps = {
            "rank0": local_snapshot(
                step_summary={"steps": 10, "total_s": 10.0, "mfu": 0.42},
                extra={"rank": 0}),
            "rank1": local_snapshot(
                step_summary={"steps": 10, "total_s": 20.0, "mfu": 0.30},
                extra={"rank": 1}),
        }
        agg = rollup(snaps, monitor_stragglers=[1])
        assert agg["straggler"] == "rank1"
        assert agg["step_skew"] == pytest.approx(1.0)
        assert agg["straggler_confirmed"] is True   # LeaseMonitor agrees
        assert agg["mfu_spread"] == pytest.approx(0.12)
        # skew blip vs wedged rank: the cross-check distinguishes them
        assert rollup(snaps,
                      monitor_stragglers=[0])["straggler_confirmed"] is False
        assert "straggler_confirmed" not in rollup(snaps)

    def test_prometheus_rollup_exposition(self):
        depot = MemoryDepot()
        _two_pushers(depot)
        text = prometheus_rollup_text(depot.metrics_pull())
        assert "paddle_tpu_fleet_requests_per_second 4.0" in text
        assert "paddle_tpu_fleet_requests_finished_total 30" in text
        assert "paddle_tpu_fleet_ttft_seconds_bucket" in text
        assert 'le="+Inf"' in text
        assert 'paddle_tpu_fleet_replica_requests_per_second' \
               '{replica="r0"} 2.5' in text

    def test_slo_meter_histograms_render_in_prometheus_text(self):
        clock = FakeClock()
        m = SLOMeter(now=clock)
        m.submit(0, trace_id=mint())
        m.admit(0, queue_depth=0, pages=1)
        clock.advance(0.003)
        m.first_token(0)
        m.finish(0, n_tokens=1)
        text = tel.prometheus_text(labels={"replica": "rx"})
        assert "paddle_tpu_serving_ttft_s_seconds_bucket" in text
        assert 'replica="rx"' in text and 'le="+Inf"' in text

    def test_spill_blackbox_survives_between_beats(self, tmp_path):
        tel.record_event("spill_probe", "x", trace=mint())
        p = MetricsPusher(None, src="rs", epoch_dir=str(tmp_path),
                          interval_s=999.0)
        p.push_once()
        path = tmp_path / "flight_rs_periodic.json"
        assert path.exists() and not (tmp_path / (path.name + ".tmp")).exists()
        doc = json.loads(path.read_text())
        assert doc["reason"] == "periodic"
        assert any(e["kind"] == "spill_probe" for e in doc["events"])
        # the next beat supersedes in place (stable name, atomic replace)
        p.push_once()
        assert json.loads(path.read_text())["reason"] == "periodic"

    def test_push_failure_is_counted_never_raised(self):
        class Down:
            def metrics_push(self, src, doc):
                raise ConnectionRefusedError("depot down")

        p = MetricsPusher(Down(), src="r9", epoch_dir=None, interval_s=999.0)
        assert p.push_once() is False
        assert p.push_failures == 1 and p.pushes == 0


# ---------------------------------------------------------------------------
def _write_dump(path, events, *, replica=None, rank=None, host="hostA",
                pid=1):
    ident = {"pid": pid}
    if replica is not None:
        ident["replica"] = replica
    if rank is not None:
        ident["rank"] = rank
    with open(path, "w") as f:
        json.dump({"reason": "test", "host": host, "pid": pid,
                   "identity": ident, "events": events}, f)


def _ev(kind, name, ts, mono_s, **data):
    return {"kind": kind, "name": name, "ts": float(ts),
            "mono_ns": int(mono_s * 1e9), **data}


class TestBlackboxMerge:
    def test_ship_orders_before_fold_despite_skewed_wall_clock(self,
                                                               tmp_path):
        # replica r0's wall clock runs ~115s AHEAD of the frontend's, so
        # naive wall ordering would put its ship AFTER the fold that
        # consumed it.  The store edge must override the clock.
        _write_dump(str(tmp_path / "flight_r0_periodic.json"), [
            _ev("serve_submit", "4", 1120.0, 1.0, trace="cc" * 8),
            _ev("fleet_ship", "r0", 1121.0, 2.0, epoch=1, seq=0),
        ], replica="r0", pid=11)
        _write_dump(str(tmp_path / "flight_fe.json"), [
            _ev("fleet_fence", "r0", 1004.0, 5.0, epoch=1),
            _ev("fleet_fold", "r0", 1005.0, 6.0, epoch=1, high_seq=0),
        ], host="hostB", pid=22)
        merged = blackbox.merge(str(tmp_path))
        order = [(e["kind"], e["src"]) for e in merged["events"]]
        idx = {k: order.index(k) for k in set(order)}
        assert idx[("fleet_ship", "r0")] < idx[("fleet_fold", "hostB:pid22")]
        assert idx[("fleet_fence", "hostB:pid22")] < \
            idx[("fleet_fold", "hostB:pid22")]
        # per-process order preserved under the alignment
        assert idx[("serve_submit", "r0")] < idx[("fleet_ship", "r0")]
        assert os.path.exists(os.path.join(str(tmp_path),
                                           "blackbox_merged.json"))
        assert merged["path"].endswith("blackbox_merged.json")

    def test_src_naming_and_duplicate_spill_dedup(self, tmp_path):
        shared = _ev("serve_admit", "1", 10.0, 1.0, trace="dd" * 8)
        _write_dump(str(tmp_path / "flight_r1_periodic.json"),
                    [shared], replica="r1", pid=5)
        # crash dump from the SAME process overlaps the periodic spill
        _write_dump(str(tmp_path / "flight_r1_crash.json"),
                    [dict(shared),
                     _ev("serve_finish", "1", 11.0, 2.0, trace="dd" * 8)],
                    replica="r1", pid=5)
        _write_dump(str(tmp_path / "flight_rank3.json"),
                    [_ev("step", "train", 10.5, 1.5)], rank=3, pid=6)
        merged = blackbox.merge(str(tmp_path))
        srcs = [e["src"] for e in merged["events"]]
        assert srcs.count("r1") == 2      # deduped, not 3
        assert "rank3" in srcs
        assert len(merged["processes"]) == 3

    def test_torn_dump_skipped_not_fatal(self, tmp_path):
        (tmp_path / "flight_dying.json").write_text('{"events": [{"kind"')
        _write_dump(str(tmp_path / "flight_ok.json"),
                    [_ev("x", "y", 1.0, 1.0)], replica="ok")
        merged = blackbox.merge(str(tmp_path))
        assert [p["src"] for p in merged["processes"]] == ["ok"]
        assert len(merged["events"]) == 1


# ---------------------------------------------------------------------------
class TestReportCLI:
    # main() is argv-driven and returns the exit code, so most paths run
    # in-process; ONE real `python -m paddle_tpu.telemetry.report`
    # subprocess keeps the module entry point honest without paying the
    # full interpreter+jax import three times over on the tier-1 lane.

    def test_smoke_dashboard(self, capsys):
        from paddle_tpu.telemetry import report
        assert report.main(["--smoke"]) == 0
        out = capsys.readouterr().out
        assert "paddle_tpu job rollup" in out
        assert "agg p99 (merged hist)" in out
        assert "straggler=rank1" in out

    def test_smoke_prometheus_and_blackbox_subprocess(self, tmp_path):
        _write_dump(str(tmp_path / "flight_r0.json"),
                    [_ev("serve_submit", "0", 1.0, 1.0, trace="ee" * 8)],
                    replica="r0")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.telemetry.report",
             "--smoke", "--prometheus", "--blackbox", str(tmp_path)],
            env={**os.environ, "PYTHONPATH": REPO},
            capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        assert "paddle_tpu_fleet_ttft_seconds_bucket" in r.stdout
        assert "blackbox: 1 dumps, 1 events" in r.stdout

    def test_no_depot_is_a_loud_exit(self, capsys, monkeypatch):
        from paddle_tpu.telemetry import report
        monkeypatch.delenv("PADDLE_TPU_SNAP_STORE", raising=False)
        assert report.main([]) == 2
        assert "no depot" in capsys.readouterr().err


# ---------------------------------------------------------------------------
class TestRecorderDumpPath:
    def test_default_dump_lands_in_epoch_dir_rank_qualified(self, tmp_path,
                                                            monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_FLIGHT_RECORDER_DIR", raising=False)
        monkeypatch.setenv("PADDLE_TPU_EPOCH_DIR", str(tmp_path))
        monkeypatch.setenv("PADDLE_TPU_SERVE_REPLICA", "rz")
        tel.record_event("dump_probe", "p")
        path = tel.dump_flight_recorder(reason="unit")
        assert path and os.path.dirname(path) == str(tmp_path)
        assert "_rz_" in os.path.basename(path)
        doc = json.loads(open(path).read())
        assert doc["identity"]["replica"] == "rz"
        assert doc["reason"] == "unit"
        # blackbox.merge attributes it to the replica, not the filename
        merged = blackbox.merge(str(tmp_path))
        assert {p["src"] for p in merged["processes"]} == {"rz"}


# ---------------------------------------------------------------------------
CHILD = textwrap.dedent("""
    import os, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving.fleet import run_replica

    work, collector = sys.argv[1], sys.argv[2]
    paddle.seed(3)
    cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                     max_position_embeddings=128)
    model = LlamaForCausalLM(cfg)
    model.eval()
    run_replica(model, collector_addr=collector,
                journal_root=os.path.join(work, "journals"),
                engine_kw=dict(max_batch=2, page_tokens=8, num_pages=24,
                               max_pages_per_seq=6, max_queue=4))
""")


@pytest.mark.chaos
class TestTraceChaosE2E:
    """Acceptance: SIGKILL a replica mid-stream.  The victim's periodic
    black-box spill survives the kill; after fail-over the merged timeline
    shows the dead replica's spans AND the survivor's replay under the
    SAME trace_id, exactly-once delivery holds, and the depot rollup's
    totals are the exact sum of the pulled per-replica counters."""

    def test_sigkill_replica_one_trace_across_the_merge(self, model,
                                                        tmp_path):
        from paddle_tpu.distributed.store import TCPStore

        epoch_dir = tmp_path / "epoch"
        epoch_dir.mkdir()
        store = TCPStore("127.0.0.1", 0, is_master=True)
        snapstore = SnapshotStore(host="127.0.0.1")
        client = SnapshotClient("127.0.0.1", snapstore.port)
        sink = TokenSink(str(tmp_path / "tokens.jsonl"))
        fe = ServingFrontend(store, client, sink=sink)
        coll = TokenCollector(fe)
        # children spill and dump their black boxes into the epoch dir
        # (override the conftest's session-wide recorder tmpdir)
        env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
               "PADDLE_TPU_FLEET_STORE": f"127.0.0.1:{store.port}",
               "PADDLE_TPU_SNAP_STORE": f"127.0.0.1:{snapstore.port}",
               "PADDLE_TPU_EPOCH_DIR": str(epoch_dir),
               "PADDLE_TPU_FLIGHT_RECORDER_DIR": str(epoch_dir)}
        procs, logs = {}, {}
        for i in range(2):
            name = f"r{i}"
            logs[name] = open(str(tmp_path / f"{name}.log"), "w")
            procs[name] = subprocess.Popen(
                [sys.executable, "-c", CHILD, str(tmp_path), coll.address],
                env={**env, "PADDLE_TPU_SERVE_REPLICA": name},
                stdout=logs[name], stderr=subprocess.STDOUT)
        try:
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                fe.scan_once()
                if len(fe.live_replicas()) == 2:
                    break
                time.sleep(0.25)
            assert len(fe.live_replicas()) == 2, \
                f"fleet never formed: {fe.live_replicas()}"

            rng = np.random.default_rng(13)
            dl = Deadline(ttft_s=240.0, total_s=600.0)
            reqs = {}
            long_p = rng.integers(1, 96, 6).astype(np.int32)
            long_rid = fe.submit(long_p, max_new_tokens=24, deadline=dl)
            reqs[long_rid] = (long_p, 24)
            tid = fe.requests[long_rid]["trace_id"]
            assert tid and len(tid) == 16
            for _ in range(3):
                p = rng.integers(1, 96,
                                 int(rng.integers(4, 9))).astype(np.int32)
                mn = int(rng.integers(3, 6))
                reqs[fe.submit(p, max_new_tokens=mn, deadline=dl)] = (p, mn)

            # wait until the long request is streaming AND its replica's
            # periodic spill already carries the trace (the spill is what
            # survives the SIGKILL), then kill that replica
            victim = None
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline:
                fe.scan_once()
                if long_rid in fe.finished_rids():
                    pytest.fail("long request finished before the kill "
                                "window opened")
                if sink.delivered(long_rid) >= 3:
                    owner = fe.assignments[long_rid]
                    spill = epoch_dir / f"flight_{owner}_periodic.json"
                    if spill.exists() and tid in spill.read_text():
                        victim = owner
                        break
                time.sleep(0.05)
            assert victim is not None, "no spilled mid-stream work to kill"
            procs[victim].kill()
            procs[victim].wait(timeout=30)

            assert fe.wait_all(list(reqs), timeout=420), fe.summary()
            assert fe.failovers >= 1

            # exactly-once + token-exact across the failover
            streams = TokenSink.collect(sink.path)
            for rid, (p, mn) in sorted(reqs.items()):
                assert streams.get(rid) == list(_solo(model, p, mn)), rid

            # depot rollup: exact sum of the pulled per-replica counters
            snaps = client.metrics_pull()
            assert victim in snaps        # pushed at least one beat
            agg = rollup(snaps)
            assert agg["requests_finished_total"] == sum(
                int(d["slo"]["requests_finished"]) for d in snaps.values())
            assert agg["fleet_agg_req_s"] >= 0.0

            # one more push beat so the survivor's spill holds the
            # replayed finish, then fold the black boxes together with
            # the frontend's own ring
            time.sleep(0.6)
            tel.dump_flight_recorder(str(epoch_dir / "flight_frontend.json"),
                                     reason="frontend")
            merged = blackbox.merge(str(epoch_dir))
            tr = [e for e in merged["events"] if e.get("trace") == tid]
            srcs = {e["src"] for e in tr}
            # the DEAD replica's spans made it into the merged timeline...
            assert victim in srcs, (srcs, victim)
            # ...and the survivor finished the SAME trace after replay
            finish_srcs = {e["src"] for e in tr
                           if e["kind"] == "serve_finish"
                           and e["name"] == str(long_rid)}
            assert finish_srcs and victim not in finish_srcs, \
                (finish_srcs, victim)
            # the frontend's replay route rides the same id too
            assert any(e["kind"] == "serve_route" and e.get("replay")
                       for e in tr), "no replay route span under the trace"
        finally:
            for h in list(fe.handles.values()):
                if isinstance(h, RemoteReplica):
                    try:
                        h.stop_replica()
                    except OSError:
                        pass
            for pr in procs.values():
                try:
                    pr.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    pr.kill()
                    pr.wait(timeout=10)
            fe.stop()
            coll.close()
            sink.close()
            client.close()
            snapstore.close()
            store.close()
            for f in logs.values():
                f.close()
