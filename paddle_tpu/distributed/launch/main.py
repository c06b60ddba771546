"""`python -m paddle_tpu.distributed.launch` — per-host process launcher.

Reference: `python/paddle/distributed/launch/main.py` +
`controllers/collective.py:22` (CollectiveController.build_pod). One pod per
host; each worker process gets the PADDLE_* env contract
(`parallel.py:687-710` in the reference) and a per-rank
``log_dir/workerlog.N`` file. The first worker failure tears the pod down
(reference controller watch-loop semantics).

Fleet fault domain (``--fault_domain on|off``, default on, env
``PADDLE_TPU_FAULT_DOMAIN``): the launcher hosts (single-node) or joins
(multi-node: the rendezvous store doubles as it) the job's TCPStore and
exports ``PADDLE_TPU_FLEET_STORE`` so every rank can publish heartbeat
leases and poll the poison key.  The launcher runs the lease monitor — a
rank whose lease expires is poisoned (``lease_expired``) — and its watch
loop is poison-aware in BOTH directions: the first dead child writes the
poison pill (reason ``rank_exit``, culprit = the rank) so siblings wedged
inside an XLA collective convert the hang into a bounded exit-101, and a
pill written by anyone else (a rank's CommWatchdog, a HealthGuard
escalation) tears this pod down even when every local child still looks
healthy.  Teardown is TERM → ``PADDLE_TPU_TEARDOWN_GRACE`` seconds → KILL,
after an initial self-exit window so ranks get to finish their emergency
checkpoints.  ``PADDLE_TPU_EXCLUDE_SLOTS`` (exported by the
``FleetSupervisor`` after an ``sdc_suspect`` quarantine) names physical
slots this launcher must NOT spawn — surviving slots get dense ranks
0..world−1 — and the final poison doc is dumped to
``<log_dir>/poison.json`` so the quarantine decision survives the epoch's
store.

In-memory snapshots (``PADDLE_TPU_SNAP``, default on): the launcher hosts
the :class:`~..checkpoint.replicator.SnapshotStore` — a process-global
depot standing in for per-host RAM, so workers' snapshot copies survive a
SIGKILL'd rank — and exports ``PADDLE_TPU_SNAP_STORE`` so every rank's
:class:`~..checkpoint.Snapshotter` can ship its own copy plus the
ring-neighbor replica.  The watch loop models host loss faithfully: a
child that dies UNCOORDINATED (a signal, any exit other than 0/101) has
its *held* copies dropped (its own snapshot AND the replica it kept for
its ring predecessor), which is exactly what makes the double-fault case
— a rank and its replica holder dying in the same window — fall back to
the committed disk checkpoint instead of silently resuming torn state.
Coordinated exits (the poison-poll's 101) keep their holdings: the "host"
is fine, only the process restarts.

Serving mode (``--mode serve``, env ``PADDLE_TPU_LAUNCH_MODE``): the same
store + depot hosting, but the children are serving replicas
(:func:`paddle_tpu.serving.fleet.run_replica`) supervised by a
:class:`~..fleet.elastic.supervisor.ReplicaPool` — per-replica bounded
relaunch instead of first-failure pod teardown, because a lease-routed
frontend fences a dead replica and replays its work on survivors while
the relaunch (new fencing epoch) takes new traffic.

On TPU the deployment is ONE process per host owning all local chips
(`--nproc_per_node 1`, the default); multi-process-per-host is used by the
CPU "fake cluster" tests.  It cannot run on a chip host today: children
are not pinned to devices, the first one to initialise JAX takes every
local chip and the second finds none.  That includes ``--mode serve`` with
several replicas per host — ROADMAP R3 (one process driving one replica
per device) is the design that runs on chips."""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["launch", "main"]


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="Launch a distributed training job (pod-per-host).")
    p.add_argument("--nnodes", type=int,
                   default=int(os.environ.get("PADDLE_NNODES", "1")),
                   help="number of hosts in the job")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "-1")),
                   help="rank of this host (-1: assigned by the master "
                        "rendezvous when nnodes > 1, else 0)")
    p.add_argument("--nproc_per_node", type=int,
                   default=int(os.environ.get("PADDLE_NPROC_PER_NODE", "1")),
                   help="worker processes on this host (1 = own all chips)")
    p.add_argument("--master", type=str,
                   default=os.environ.get("PADDLE_MASTER"),
                   help="coordinator host:port (default: local free port)")
    p.add_argument("--log_dir", type=str, default="log",
                   help="directory for per-rank workerlog.N files")
    p.add_argument("--job_id", type=str, default="default",
                   help="job name tag (reference parity)")
    p.add_argument("--mode", choices=("train", "serve"),
                   default=os.environ.get("PADDLE_TPU_LAUNCH_MODE", "train"),
                   help="train: SPMD gang (first failure tears the pod "
                        "down); serve: fleet of serving replicas with "
                        "per-replica relaunch (a dead replica restarts "
                        "alone while the frontend fails its work over)")
    p.add_argument("--max_replica_restarts", type=int,
                   default=int(os.environ.get(
                       "PADDLE_TPU_SERVE_MAX_RESTARTS", "5")),
                   help="serve mode: per-replica relaunch budget")
    p.add_argument("--fault_domain", choices=("on", "off"),
                   default=("off" if os.environ.get(
                       "PADDLE_TPU_FAULT_DOMAIN", "1") in ("0", "false")
                       else "on"),
                   help="heartbeat-lease/poison fault domain over the job "
                        "store (default on; env PADDLE_TPU_FAULT_DOMAIN)")
    p.add_argument("script", type=str, help="training script")
    p.add_argument("script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _record_event(name: str, **data) -> None:
    try:  # flight recorder: the pod's watch-loop story
        from ... import telemetry

        telemetry.record_event("gang", name, **data)
    except Exception:
        pass


class _SnapWatch:
    """The launcher's snapshot-store membership: host (or address) the
    depot and translate uncoordinated child deaths into holder drops.
    Best-effort throughout — snapshots degrading must never take a pod
    down."""

    def __init__(self, fleet_kv=None, advertise_host: Optional[str] = None):
        from ..checkpoint import replicator

        self.addr = os.environ.get("PADDLE_TPU_SNAP_STORE")
        if not self.addr and fleet_kv is not None:
            # multi-node: ONE depot for the whole gang, or per-node depots
            # could never assemble a complete generation and a peer
            # replica for a cross-node ring neighbor would die with its
            # own node. The pod hosting the rendezvous store (the master
            # host) hosts the depot too — the SnapshotStore binds wildcard
            # — and publishes its REACHABLE address through the store.
            if getattr(fleet_kv, "is_master", False):
                depot, local = replicator.ensure_host_store()
                self.addr = (f"{advertise_host}:{depot.port}"
                             if advertise_host else local)
                fleet_kv.set("snap/store", self.addr)
            else:
                self.addr = fleet_kv.get("snap/store",
                                         timeout=60.0).decode()
        if not self.addr:
            # single node: host the process-global one (FleetSupervisor
            # epochs re-enter launch() in this same process and find the
            # SAME depot — that persistence is what memory recovery
            # rides on)
            _, self.addr = replicator.ensure_host_store()
        self._client = replicator.SnapshotClient.from_address(self.addr)

    def note_child_exit(self, rank: Optional[int], code: int) -> None:
        """Exit 0 = done, 101 = coordinated abort (poison poll / health
        rewind): the conceptual host RAM survives, holdings stay.  Anything
        else — a signal (negative code), an uncaught crash — models host
        loss: every copy this rank HELD goes, so recovery can only use the
        surviving peer replica (or disk)."""
        if rank is None or code in (0, 101):
            return
        try:
            dropped = self._client.drop_holder(rank)
        except Exception:
            return
        if dropped:
            _record_event("snapshot_holder_dropped", rank=rank,
                          exit_code=code, copies_dropped=dropped)

    def stop(self) -> None:
        if self._client is not None:
            try:
                self._client.close()
            except Exception:
                pass
        # a locally hosted depot is process-global ON PURPOSE: it must
        # outlive this launch() so the FleetSupervisor's next gang epoch
        # finds the copies — never closed here


class _PodWatch:
    """The launcher's membership in the fault domain: store hosting/joining,
    lease monitor, poison pill plumbing. All methods are best-effort — a
    fault-domain hiccup must never take down a healthy pod."""

    def __init__(self, store, world: int, job_id: str, own_store: bool):
        from ..fleet.fault_domain import FaultDomain

        self.own_store = own_store
        self.poisoned: Optional[dict] = None
        self.domain = FaultDomain(
            store, rank=None, world_size=world, job_id=job_id,
            epoch=int(os.environ.get("PADDLE_TPU_GANG_EPOCH", "0")),
            # only the store-hosting launcher monitors leases (one poisoner
            # per gang is enough; the pill is first-writer-wins anyway)
            monitor=own_store,
            on_abort=self._on_poison)
        self.domain.start()

    def _on_poison(self, doc: dict) -> None:
        self.poisoned = doc

    def poison(self, reason: str, culprit: Optional[int], detail: str) -> None:
        try:
            self.domain.poison(reason, culprit=culprit, detail=detail)
        except Exception:
            pass

    def stop(self) -> None:
        try:
            self.domain.stop()
        except Exception:
            pass


def launch(argv=None) -> int:
    args = _parse(argv if argv is not None else sys.argv[1:])
    nproc = args.nproc_per_node
    # every child (train rank or serving replica) compiles into the one
    # placed cache: jax reads this variable at import, and a directory
    # set from outside wins
    from ...compile.cache import cache_dir

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", cache_dir())
    # SDC quarantine (exclude-list relaunch): the FleetSupervisor exports
    # the physical slots it quarantined; this launcher skips them and the
    # surviving slots get DENSE ranks 0..world-1 — downstream the
    # relaunched gang is an ordinary, smaller world
    excluded_slots = set()
    for _tok in os.environ.get("PADDLE_TPU_EXCLUDE_SLOTS", "").split(","):
        _tok = _tok.strip()
        if _tok:
            try:
                excluded_slots.add(int(_tok))
            except ValueError:
                pass
    live_slots = [s for s in range(args.nnodes * nproc)
                  if s not in excluded_slots]
    if not live_slots:
        raise SystemExit("PADDLE_TPU_EXCLUDE_SLOTS excludes every slot")
    world = len(live_slots)
    # link-slow remap (straggler ladder): the FleetSupervisor exports a
    # device-order permutation of dense ranks so ring-neighbor traffic
    # routes around a degraded ICI link — a launch-time remap, not a
    # recompile (the ring programs take ring position as an input).  A
    # permutation that does not match THIS epoch's world (stale after a
    # later exclusion) is dropped loudly, never obeyed.
    device_order: Optional[List[int]] = None
    _ord = os.environ.get("PADDLE_TPU_DEVICE_ORDER", "").strip()
    if _ord:
        try:
            device_order = [int(t) for t in _ord.split(",") if t.strip()]
        except ValueError:
            device_order = None
        if device_order is not None and \
                sorted(device_order) != list(range(world)):
            _record_event("device_order_dropped", order=_ord, world=world)
            device_order = None
    master = args.master
    node_rank = args.node_rank
    store = None
    if master is None:
        if args.nnodes > 1:
            raise SystemExit("--master host:port is required when nnodes > 1")
        master = f"127.0.0.1:{_free_port()}"
    coordinator = None
    if args.nnodes > 1:
        # multi-node: rendezvous through the TCP store served from the
        # master host (reference `controllers/master.py:73` HTTPMaster) —
        # assigns node ranks, publishes hostnames, and barriers all pods
        # before any worker spawns. The store OWNS the master port for the
        # job's lifetime, so jax.distributed's coordinator gets port+1
        # (exported as PADDLE_COORDINATOR, consumed by init_parallel_env).
        from ..store import rendezvous

        store, node_rank = rendezvous(
            master, args.nnodes, job_id=args.job_id,
            node_rank=None if node_rank < 0 else node_rank)
        mhost, mport = master.rsplit(":", 1)
        coordinator = f"{mhost}:{int(mport) + 1}"
    elif node_rank < 0:
        node_rank = 0

    # fleet fault domain: single-node pods host a dedicated store (the
    # master port stays free — init_parallel_env hands it to
    # jax.distributed when nnodes==1); multi-node pods reuse the rendezvous
    # store, whose server already lives on the master host
    fleet_store_addr = None
    watch: Optional[_PodWatch] = None
    fleet_store = store
    if args.fault_domain == "on":
        try:
            from ..store import TCPStore

            if fleet_store is None:
                fleet_store = TCPStore("127.0.0.1", 0, is_master=True,
                                       world_size=world)
                fleet_store_addr = f"127.0.0.1:{fleet_store.port}"
            else:
                fleet_store_addr = master
            watch = _PodWatch(fleet_store, world, args.job_id,
                              own_store=fleet_store.is_master)
        except Exception as e:
            sys.stderr.write(f"[launch] fault domain unavailable: {e!r}\n")
            fleet_store_addr, watch = None, None

    # in-memory snapshot depot: hosted here (or addressed, when a
    # FleetSupervisor/test exported PADDLE_TPU_SNAP_STORE already) and
    # handed to every rank; uncoordinated child deaths drop their holdings
    snap: Optional[_SnapWatch] = None
    if os.environ.get("PADDLE_TPU_SNAP", "1") not in ("0", "false"):
        try:
            snap = _SnapWatch(
                fleet_kv=store if args.nnodes > 1 else None,
                advertise_host=(master.rsplit(":", 1)[0]
                                if args.nnodes > 1 else None))
        except Exception as e:
            sys.stderr.write(f"[launch] snapshot store unavailable: {e!r}\n")
            snap = None
    os.makedirs(args.log_dir, exist_ok=True)
    # the job's "epoch dir": every process (launcher included) defaults
    # its flight-recorder dumps and periodic metric spills here, so
    # telemetry.blackbox.merge can fold ONE causally ordered timeline
    os.environ["PADDLE_TPU_EPOCH_DIR"] = os.path.abspath(args.log_dir)

    if args.mode == "serve":
        # serving pod: same store + depot hosting as a training pod (the
        # depot doubles as the fleet's journal depot), but supervision is
        # PER REPLICA — no gang poisoning, no first-failure teardown
        try:
            return _serve_pod(args, node_rank, fleet_store_addr, snap)
        finally:
            _observability_teardown(args.log_dir, snap)
            if watch is not None:
                watch.stop()
            if snap is not None:
                snap.stop()
            if fleet_store is not None:
                fleet_store.close()

    grace = 10.0
    try:
        grace = float(os.environ.get("PADDLE_TPU_TEARDOWN_GRACE", grace))
    except ValueError:
        pass

    procs: List[subprocess.Popen] = []
    ranks = {}
    logs = []
    try:
        for local in range(nproc):
            slot = node_rank * nproc + local
            if slot in excluded_slots:
                _record_event("slot_excluded", slot=slot, local=local,
                              node_rank=node_rank)
                continue
            rank = live_slots.index(slot)
            env = os.environ.copy()
            env.update({
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "PADDLE_MASTER": master,
                "PADDLE_LOCAL_RANK": str(local),
                "PADDLE_RANK_IN_NODE": str(local),
                "PADDLE_JOB_ID": args.job_id,
                "PADDLE_NNODES": str(args.nnodes),
                "PADDLE_NODE_RANK": str(node_rank),
                **({"PADDLE_COORDINATOR": coordinator} if coordinator else {}),
                **({"PADDLE_TPU_FLEET_STORE": fleet_store_addr,
                    "PADDLE_TPU_FLEET_MONITOR": "launcher"}
                   if fleet_store_addr else {}),
                **({"PADDLE_TPU_SNAP_STORE": snap.addr} if snap else {}),
                # ring position under the (possibly remapped) device order:
                # rank r sits at position order.index(r) of the ring
                **({"PADDLE_TPU_RING_POS": str(device_order.index(rank))}
                   if device_order else {}),
                # multi-process-per-host (CPU fake cluster): keep each worker
                # to its own slice of host devices
                "PADDLE_NPROC_PER_NODE": str(nproc),
            })
            log_path = os.path.join(args.log_dir, f"workerlog.{rank}")
            log_f = open(log_path, "w")
            logs.append(log_f)
            pr = subprocess.Popen(
                [sys.executable, "-u", args.script, *args.script_args],
                env=env, stdout=log_f, stderr=subprocess.STDOUT)
            ranks[pr.pid] = rank
            procs.append(pr)
        _record_event("gang_start", world=world, node_rank=node_rank,
                      nproc=nproc,
                      epoch=int(os.environ.get("PADDLE_TPU_GANG_EPOCH", "0")),
                      fault_domain=args.fault_domain,
                      **({"device_order": device_order}
                         if device_order else {}))
    except BaseException:
        # a failed spawn must not leave earlier workers blocked on a
        # rendezvous that will never complete
        for pr in procs:
            pr.kill()
        for f in logs:
            f.close()
        if watch is not None:
            watch.stop()
        if snap is not None:
            snap.stop()
        if fleet_store is not None:
            fleet_store.close()
        raise

    def _teardown(remaining: List[subprocess.Popen],
                  self_exit_window: float) -> None:
        """Poisoned ranks exit on their own within the poison deadline —
        give them ``self_exit_window`` to finish emergency checkpoints,
        then TERM, then KILL after ``grace`` (reference teardown, hardened:
        a rank wedged in an uninterruptible XLA wait ignores TERM)."""
        deadline = time.time() + self_exit_window
        while remaining and time.time() < deadline:
            remaining = [pr for pr in remaining if pr.poll() is None]
            if remaining:
                time.sleep(0.1)
        for pr in remaining:
            if pr.poll() is None:
                pr.terminate()
        for pr in remaining:
            try:
                pr.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                pr.kill()
        _record_event("gang_teardown", world=world,
                      killed=len(remaining))

    rc = 0
    try:
        while procs:
            for pr in list(procs):
                code = pr.poll()
                if code is None or pr not in procs:
                    continue
                procs.remove(pr)
                _record_event("gang_child_exit", rank=ranks.get(pr.pid),
                              exit_code=code)
                if snap is not None:
                    # spontaneous deaths only — teardown TERM/KILLs below
                    # are launcher-coordinated, the "host RAM" stays
                    snap.note_child_exit(ranks.get(pr.pid), code)
                if code == 0 and watch is not None and \
                        ranks.get(pr.pid) is not None:
                    # a clean exit that never stopped its domain must not
                    # leave a lease behind to expire and poison survivors
                    watch.domain.release_rank(ranks[pr.pid])
                if code != 0:
                    rc = code
                    if snap is not None:
                        # siblings that ALSO died spontaneously in this
                        # same window (double fault: a rank and its
                        # replica holder) lose their holdings too —
                        # sweep BEFORE teardown marks everyone else's
                        # exit as launcher-coordinated
                        for other in procs:
                            oc = other.poll()
                            if oc is not None:
                                snap.note_child_exit(
                                    ranks.get(other.pid), oc)
                    # first failure tears down the pod (reference
                    # CollectiveController watch loop) — poison FIRST so
                    # ranks wedged inside a collective convert the hang
                    # into their own bounded exit + emergency checkpoint
                    if watch is not None and procs:
                        watch.poison("rank_exit", ranks.get(pr.pid),
                                     f"exit code {code}")
                        _teardown(procs, self_exit_window=grace)
                    else:
                        _teardown(procs, self_exit_window=0.0)
                    procs.clear()
            if procs and watch is not None and watch.poisoned is not None:
                # someone ELSE poisoned the gang (a rank's watchdog, a
                # health escalation, a dead lease on another pod): all
                # local children must leave too, even the healthy ones
                doc = watch.poisoned
                _record_event("gang_poisoned",
                              reason=doc.get("reason"),
                              culprit=doc.get("culprit"), by=doc.get("by"))
                _teardown(procs, self_exit_window=grace)
                for pr in procs:
                    code = pr.poll()
                    if code and not rc:
                        rc = code
                procs.clear()
                if not rc:
                    rc = 101  # poisoned gang is not a clean completion
            time.sleep(0.2)
    except KeyboardInterrupt:
        for pr in procs:
            pr.send_signal(signal.SIGINT)
        rc = 130
    finally:
        for f in logs:
            f.close()
        if watch is not None:
            # persist the poison doc for the FleetSupervisor: the pill dies
            # with the epoch's store, but an sdc_suspect quarantine decision
            # must survive teardown — the dump names the culprit rank the
            # exclude-list relaunch removes
            doc = watch.poisoned
            if doc is None:
                try:
                    doc = watch.domain.check_poison()
                except Exception:
                    doc = None
            if doc is not None:
                import json

                try:
                    with open(os.path.join(args.log_dir, "poison.json"),
                              "w") as f:
                        json.dump(doc, f, indent=1)
                except (OSError, TypeError, ValueError):
                    pass
            watch.stop()
        _observability_teardown(args.log_dir, snap)
        if snap is not None:
            snap.stop()
        if fleet_store is not None:
            fleet_store.close()
    return rc


def _observability_teardown(log_dir: str, snap) -> None:
    """Job-level observability epilogue (best-effort, never raises):
    dump the launcher's own flight recorder next to the workers' dumps,
    pull the metrics depot into one ``metrics_rollup.json``, and fold
    every per-process dump into the merged black-box timeline."""
    try:
        from ... import telemetry
        telemetry.dump_flight_recorder(
            os.path.join(log_dir, f"flight_launcher_pid{os.getpid()}.json"),
            reason="launch_teardown")
    except Exception:
        pass
    if snap is not None and getattr(snap, "addr", None):
        try:
            import json

            from ...telemetry.aggregator import rollup
            from ..checkpoint.replicator import SnapshotClient
            cli = SnapshotClient.from_address(snap.addr)
            try:
                snaps = cli.metrics_pull()
            finally:
                cli.close()
            if snaps:
                with open(os.path.join(log_dir, "metrics_rollup.json"),
                          "w") as f:
                    json.dump(rollup(snaps), f, indent=1, default=repr)
        except Exception:
            pass
    try:
        from ...telemetry import blackbox
        blackbox.merge(log_dir)
    except Exception:
        pass


def _serve_pod(args, node_rank: int, fleet_store_addr: Optional[str],
               snap) -> int:
    """Serve-mode watch loop: ``nproc_per_node`` replica children under a
    :class:`~..fleet.elastic.supervisor.ReplicaPool`.  Each child gets the
    fleet env contract (``PADDLE_TPU_FLEET_STORE`` for its heartbeat
    lease, ``PADDLE_TPU_SNAP_STORE`` for journal shipping,
    ``PADDLE_TPU_SERVE_REPLICA`` for its stable name) and is expected to
    call :func:`paddle_tpu.serving.fleet.run_replica`.  A SIGKILL'd or
    101-exiting replica relaunches alone with backoff and adopts a fresh
    fencing epoch; exit 0 (frontend said stop) retires it.

    With ``PADDLE_TPU_AS_ENABLE=1`` (and a fleet store to scan) the pod
    also hosts the :class:`~paddle_tpu.serving.autoscaler.Autoscaler`
    next to this loop: fleet occupancy / shed pressure grows the pool
    through ``scale_to`` (fresh names, fresh fencing epochs, warm starts
    through the shared AOT cache) and shrinks it through the lossless
    retire → re-home → stop drain protocol — drained stops exit 0 and
    burn no restart budget."""
    from ..fleet.elastic.supervisor import ReplicaPool, RestartPolicy

    pool = ReplicaPool(
        policy=RestartPolicy(max_restarts=args.max_replica_restarts),
        restart_codes=(101, -signal.SIGKILL, -signal.SIGTERM))
    argv = [sys.executable, "-u", args.script, *args.script_args]
    base_env = {
        "PADDLE_JOB_ID": args.job_id,
        **({"PADDLE_TPU_FLEET_STORE": fleet_store_addr}
           if fleet_store_addr else {}),
        **({"PADDLE_TPU_SNAP_STORE": snap.addr} if snap else {}),
    }
    # disaggregated tier topology (ISSUE 19): with
    # PADDLE_TPU_DISAGG_PREFILL=K the pod's FIRST K children form a
    # dedicated prefill tier (named prefill{N}, tier=prefill on their
    # lease) and the rest stay decode replicas.  The router prefers
    # prefill capacity for TTFT-bound work and falls back to the whole
    # fleet when the tier is empty — K >= nproc_per_node degrades to a
    # homogeneous (all-prefill-tagged) pod rather than refusing.
    n_prefill = max(0, int(os.environ.get("PADDLE_TPU_DISAGG_PREFILL",
                                          "0") or 0))
    for local in range(args.nproc_per_node):
        idx = node_rank * args.nproc_per_node + local
        tier = "prefill" if local < n_prefill else "decode"
        name = (f"prefill{idx}" if tier == "prefill" else f"replica{idx}")
        pool.add(name, argv,
                 env={**base_env, "PADDLE_LOCAL_RANK": str(local),
                      "PADDLE_TPU_SERVE_TIER": tier},
                 log_path=os.path.join(args.log_dir, f"{name}.log"))
    # scale-outs reuse the same child contract; their names continue the
    # pod's replica index sequence so they can never collide with (or
    # inherit budget from) an existing or retired replica.  Autoscaled
    # capacity is always DECODE tier: the prefill tier is a fixed split.
    pool.set_template(argv, env={**base_env, "PADDLE_LOCAL_RANK": "0",
                                 "PADDLE_TPU_SERVE_TIER": "decode"},
                      log_dir=args.log_dir, name_prefix="replica")
    scaler = None
    if os.environ.get("PADDLE_TPU_AS_ENABLE", "0") == "1" \
            and fleet_store_addr and node_rank == 0:
        try:
            from ...serving.autoscaler import Autoscaler
            from ..checkpoint.replicator import SnapshotClient
            from ..store import TCPStore

            h, p = fleet_store_addr.rsplit(":", 1)
            as_store = TCPStore(h, int(p), is_master=False)
            as_depot = SnapshotClient.from_address(snap.addr) \
                if snap is not None and getattr(snap, "addr", None) else None
            scaler = Autoscaler(as_store, as_depot, pool=pool)
            scaler.start()
        except Exception:
            scaler = None   # autoscaling is additive: never block serving
    _record_event("serve_pod_start", replicas=args.nproc_per_node,
                  node_rank=node_rank, prefill_tier=n_prefill,
                  autoscale=scaler is not None)
    rc = 0
    try:
        pool.start()
        while not pool.all_exited():
            pool.poll_once()
            time.sleep(0.2)
        if pool.given_up:
            rc = 101   # at least one replica burned its relaunch budget
    except KeyboardInterrupt:
        rc = 130
    finally:
        if scaler is not None:
            scaler.stop()
        pool.stop()
        _record_event("serve_pod_done", given_up=sorted(pool.given_up),
                      restarts=dict(pool.restarts),
                      scale_outs=0 if scaler is None else scaler.scale_outs,
                      scale_ins=0 if scaler is None else scaler.scale_ins,
                      rc=rc)
    return rc


def main() -> None:
    raise SystemExit(launch())


if __name__ == "__main__":
    main()
