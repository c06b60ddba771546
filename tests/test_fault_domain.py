"""Fleet fault domain unit tests: lease TTL math, the unified heartbeat
over both store backends, lease monitor (dead ranks + stragglers), poison
protocol (first-writer-wins, epoch scoping), coordinated abort wiring into
CommWatchdog and HealthGuard, gang barrier deadline."""

import json
import threading
import time

import pytest

pytestmark = pytest.mark.fleet

import paddle_tpu.telemetry as telemetry
from paddle_tpu.distributed import CommWatchdog
from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                  ElasticStatus, FileStore)
from paddle_tpu.distributed.fleet.fault_domain import (FaultDomain,
                                                       HeartbeatLease,
                                                       LeaseMonitor,
                                                       heartbeat_interval,
                                                       lease_expired)
from paddle_tpu.distributed.health import HealthGuard, HealthPolicy
from paddle_tpu.distributed.health.ledger import HealthError
from paddle_tpu.distributed.store import TCPStore


@pytest.fixture
def master():
    s = TCPStore("127.0.0.1", 0, is_master=True, world_size=4, timeout=20.0)
    yield s
    s.close()


def _wait_for(cond, timeout=10.0, interval=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


class TestLeaseTTLMath:
    def test_interval_is_a_third_of_ttl(self):
        assert heartbeat_interval(9.0) == 3.0
        assert heartbeat_interval(9.0, interval=1.0) == 1.0

    def test_interval_floor(self):
        # three missable beats per ttl, but never a busy-loop
        assert heartbeat_interval(0.06) == 0.05
        assert heartbeat_interval(10.0, interval=0.001) == 0.05
        assert heartbeat_interval(10.0, interval=0.2, min_interval=0.5) == 0.5

    def test_expiry(self):
        assert not lease_expired(0.5, 1.0)
        assert lease_expired(1.5, 1.0)
        # a key that never existed is a JOIN problem, not a death
        assert not lease_expired(None, 1.0)


class TestHeartbeatLease:
    def test_filestore_backend_beats_and_stamps_steps(self, tmp_path):
        st = FileStore(str(tmp_path))
        lease = HeartbeatLease(st, "hb/0", ttl=5.0, interval=0.05,
                               payload={"rank": 0}).start()
        assert _wait_for(lambda: lease.beats >= 2)
        assert st.age("hb/0") < 1.0
        lease.note_step(7)
        assert _wait_for(lambda: (st.get("hb/0") or {}).get("step") == 7)
        doc = st.get("hb/0")
        assert doc["rank"] == 0 and doc["step_ts"] > 0
        lease.stop(release=True)
        assert st.get("hb/0") is None

    def test_raw_tcpstore_backend(self, master):
        lease = HeartbeatLease(master, "hb/3", ttl=5.0, interval=0.05,
                               payload={"rank": 3}).start()
        assert _wait_for(lambda: lease.beats >= 1)
        doc = json.loads(master.get("hb/3"))
        assert doc["rank"] == 3 and doc["ttl"] == 5.0
        lease.note_step(11)
        assert _wait_for(
            lambda: json.loads(master.get("hb/3")).get("step") == 11)
        assert master.age("hb/3") < 1.0
        lease.stop()

    def test_store_lost_fires_after_ttl_of_failures(self):
        class DeadKV:
            def put(self, k, v):
                raise OSError("store gone")

            def age(self, k):
                return None

        lost = []
        lease = HeartbeatLease(DeadKV(), "hb/0", ttl=0.1,
                               on_store_lost=lost.append)
        assert lease.beat_now() is False
        assert lost == []  # first failure starts the clock, nothing more
        time.sleep(0.15)
        assert lease.beat_now() is False
        assert len(lost) == 1 and isinstance(lost[0], OSError)
        assert lease.beat_now() is False  # fires ONCE
        assert len(lost) == 1


class TestLeaseMonitor:
    def test_dead_lease_is_poisoned_stragglers_are_not(self, master):
        """Load-proof by construction (the old version flaked under full-
        suite load: 0.4s TTLs + fixed sleeps meant a stalled beat thread
        could age a LIVE lease past expiry and poison the wrong rank).
        Live leases now carry a 30s TTL — only the lease we deliberately
        stop can ever expire (its ttl is shrunk via the payload right
        before the stop, since the monitor honors per-lease ttl) — and
        every phase gates on observed store/monitor state instead of
        sleeping a wall-clock budget."""
        poisons = []
        h0 = HeartbeatLease(master, "hb/0", ttl=30.0, interval=0.05).start()
        h1 = HeartbeatLease(master, "hb/1", ttl=30.0, interval=0.05).start()
        mon = LeaseMonitor(master, 2, ttl=30.0, straggler_after=0.3,
                           poison_fn=lambda **kw: poisons.append(kw))
        h0.note_step(1)
        h1.note_step(1)
        t1 = time.time()  # upper bound on h1's step-stamp age start
        assert _wait_for(  # both stamps visible in the store
            lambda: (json.loads(master.get("hb/0")).get("step") == 1
                     and json.loads(master.get("hb/1")).get("step") == 1))
        assert mon.scan_once() == {"dead": [], "stragglers": [], "slow": []}
        # rank 1 keeps heartbeating but stops stepping → straggler,
        # observed not poisoned; rank 0 keeps stepping.  Event-gated: step
        # h0 inside the poll until the monitor flags exactly rank 1.
        step = [1]

        def h1_flagged_straggler():
            step[0] += 1
            h0.note_step(step[0])
            if time.time() - t1 <= mon.straggler_after:
                return False  # h1's stamp cannot be stale yet
            found = mon.scan_once()
            assert found["dead"] == []  # 30s ttl: nothing may die here
            return found["stragglers"] == [1]

        assert _wait_for(h1_flagged_straggler, timeout=20, interval=0.05)
        assert poisons == []
        # rank 1's heartbeat dies entirely → dead → poisoned with culprit:
        # shrink ITS ttl (payload write confirmed in-store), then stop it
        h1.update_payload(ttl=0.4)
        assert _wait_for(
            lambda: json.loads(master.get("hb/1")).get("ttl") == 0.4)
        h1.stop()
        assert _wait_for(lambda: mon.scan_once()["dead"] == [1], timeout=20)
        assert poisons and poisons[0]["reason"] == "lease_expired"
        assert poisons[0]["culprit"] == 1
        # poisoning is once per dead rank, not once per scan
        mon.scan_once()
        assert len(poisons) == 1
        h0.stop()

    def test_never_registered_rank_is_not_poisoned(self, master):
        poisons = []
        mon = LeaseMonitor(master, 4, ttl=0.2,
                           poison_fn=lambda **kw: poisons.append(kw))
        h0 = HeartbeatLease(master, "hb/0", ttl=0.2, interval=0.05).start()
        time.sleep(0.3)
        assert mon.scan_once()["dead"] == []  # ranks 1-3 never joined
        assert poisons == []
        h0.stop()


class TestPoisonProtocol:
    def _domain(self, store, rank, world=2, **kw):
        kw.setdefault("hb_interval", 0.1)
        kw.setdefault("hb_ttl", 1.0)
        kw.setdefault("poison_poll", 0.05)
        kw.setdefault("monitor", False)
        return FaultDomain(store, rank, world, **kw)

    def test_first_writer_wins_and_check(self, master):
        aborts = []
        d = self._domain(master, 0, on_abort=aborts.append)
        assert d.check_poison() is None
        assert d.poison("watchdog_hang", culprit=0, detail="allreduce") is True
        assert d.poison("health_escalation", culprit=1) is False  # lost race
        doc = d.check_poison()
        assert doc["reason"] == "watchdog_hang" and doc["culprit"] == 0

    def test_epoch_scoping_isolates_pills(self, master):
        d1 = self._domain(master, 0, epoch=1)
        d2 = self._domain(master, 0, epoch=2)
        d1.poison("rank_exit", culprit=3)
        assert d1.check_poison() is not None
        assert d2.check_poison() is None  # the relaunched gang is clean
        d2.clear_poison(epoch=1)
        assert d1.check_poison() is None

    def test_poll_aborts_all_members(self, master):
        aborts = []
        c1 = TCPStore("127.0.0.1", master.port, timeout=10.0)
        d0 = self._domain(master, 0,
                          on_abort=lambda doc: aborts.append((0, doc)))
        d1 = self._domain(c1, 1,
                          on_abort=lambda doc: aborts.append((1, doc)))
        d0.start()
        d1.start()
        try:
            d1.poison("rank_exit", culprit=1, detail="exit -9")
            assert _wait_for(lambda: len(aborts) == 2, timeout=5)
            assert d0.aborted and d1.aborted
            assert {r for r, _ in aborts} == {0, 1}
            assert all(doc["culprit"] == 1 for _, doc in aborts)
        finally:
            d0.stop()
            d1.stop()
            c1.close()

    def test_monitor_converts_dead_lease_to_gang_abort(self, master):
        """The tentpole loop in-process: rank 1 goes silent → rank-0's
        monitor poisons → every member aborts within the poll bound."""
        aborts = []
        c1 = TCPStore("127.0.0.1", master.port, timeout=10.0)
        d0 = FaultDomain(master, 0, 2, hb_interval=0.05, hb_ttl=0.4,
                         poison_poll=0.05, monitor=True,
                         on_abort=lambda doc: aborts.append((0, doc)))
        d1 = FaultDomain(c1, 1, 2, hb_interval=0.05, hb_ttl=0.4,
                         poison_poll=0.05, monitor=False,
                         on_abort=lambda doc: aborts.append((1, doc)))
        d0.start()
        d1.start()
        try:
            d1.note_step(3)
            d1.lease.stop()  # alive process, dead heartbeat
            assert _wait_for(lambda: len(aborts) == 2, timeout=8)
            doc = d0.last_poison
            assert doc["reason"] == "lease_expired" and doc["culprit"] == 1
        finally:
            d0.stop()
            d1.stop()
            c1.close()

    def test_gang_barrier_deadline_names_missing_ranks(self, master):
        d = self._domain(master, 0, world=3)
        with pytest.raises(TimeoutError) as ei:
            d.gang_barrier(timeout=0.4)
        assert "missing ranks" in str(ei.value)
        assert "1" in str(ei.value) and "2" in str(ei.value)


class TestDetectorWiring:
    def test_watchdog_timeout_poisons_the_gang(self, master):
        aborts, infos = [], []
        fd = FaultDomain(master, 0, 2, hb_interval=0.1, hb_ttl=5.0,
                         poison_poll=0.05, monitor=False,
                         on_abort=aborts.append)
        fd.start()
        wd = CommWatchdog(timeout=0.2, poll_interval=0.05,
                          fault_domain=fd, on_timeout=infos.append)
        try:
            with wd.watch("hung_allreduce"):
                time.sleep(0.6)
            doc = fd.check_poison()
            assert doc is not None and doc["reason"] == "watchdog_hang"
            assert doc["culprit"] == 0
            assert infos and infos[0].get("poisoned") is True
            # ... and the poisoned member aborted through its poll
            assert _wait_for(lambda: fd.aborted, timeout=5)
        finally:
            wd.stop()
            fd.stop()

    def test_watchdog_loop_polls_poison_for_wedged_ranks(self, master):
        """A rank parked inside a watchdog-wrapped wait has no chance to
        call poll itself — the watchdog monitor loop must do it. The domain
        here is NOT started (no poll thread of its own), so only the
        watchdog loop can observe the pill."""
        aborts = []
        fd = FaultDomain(master, 1, 2, monitor=False, on_abort=aborts.append)
        wd = CommWatchdog(timeout=60.0, poll_interval=0.05, fault_domain=fd)
        wd.start()
        try:
            fd.poison("rank_exit", culprit=0)
            # returns as soon as the loop has polled; the limit only bounds
            # how long a starved monitor thread may take on a loaded machine
            assert _wait_for(lambda: fd.aborted, timeout=60)
            assert aborts and aborts[0]["culprit"] == 0
        finally:
            wd.stop()
            fd.stop()

    def test_health_escalation_poisons_current_domain(self, master):
        """The default exit path (SystemExit 101 for the supervisor) is
        gang-fatal: the pill lands before the raise so siblings rewind to
        the same checkpoint."""
        aborts = []
        fd = FaultDomain(master, 0, 2, monitor=False, on_abort=aborts.append)
        fd.start()  # registers as the process-current domain
        try:
            guard = HealthGuard(
                HealthPolicy(escalate_after=1, window=10, max_lag=0))
            with pytest.raises(SystemExit) as ei:
                guard.observe_host(1, float("nan"))
            assert ei.value.code == 101
            doc = fd.check_poison()
            assert doc is not None
            assert doc["reason"] == "health_escalation"
            assert doc["culprit"] == 0
        finally:
            fd.stop()

    def test_health_callable_handler_keeps_control_no_poison(self, master):
        """A callable on_escalate owns the recovery decision — the guard
        must NOT poison the gang out from under it."""
        fd = FaultDomain(master, 0, 2, epoch=9, monitor=False,
                         on_abort=lambda doc: None)
        fd.start()
        try:
            handled = []
            guard = HealthGuard(
                HealthPolicy(escalate_after=1, window=10, max_lag=0),
                on_escalate=handled.append)
            guard.observe_host(1, float("nan"))
            assert len(handled) == 1
            assert fd.check_poison() is None
        finally:
            fd.stop()


class TestElasticUnifiedHeartbeat:
    def test_manager_heartbeats_through_the_shared_lease(self, tmp_path):
        m = ElasticManager(FileStore(str(tmp_path)), job_id="j", np=1,
                           host="h0", ttl=1.0)
        assert isinstance(m._lease, HeartbeatLease)
        assert m.hosts() == ["h0"]
        age0 = m.store.age("j/nodes/h0")
        assert age0 < 1.0
        m.exit()
        assert m.store.get("j/nodes/h0") is None  # lease released

    def test_transitions_emit_elastic_events(self, tmp_path):
        rec = telemetry.get_flight_recorder()
        since = time.perf_counter_ns()  # the recorder's mono_ns clock
        m = ElasticManager(FileStore(str(tmp_path)), job_id="j", np=1,
                           host="h0", ttl=5.0)
        assert m.watch_once() == ElasticStatus.RESTART
        m.commit_world()
        assert m.watch_once() == ElasticStatus.HOLD  # steady: no event
        m.exit(completed=True)
        kinds = [e["kind"] for e in rec.events(since_mono_ns=since)]
        assert "elastic_restart" in kinds
        assert "elastic_exit" in kinds
        assert "elastic_hold" not in kinds
