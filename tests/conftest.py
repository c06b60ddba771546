"""Test fixtures: force an 8-device virtual CPU platform BEFORE jax backend init.

Mirrors the reference's "fake cluster" test strategy (multi-process on one
node, SURVEY.md §4): here a single process sees 8 XLA CPU devices, enough to
exercise every mesh axis (dp/tp/pp/sp) without TPU hardware."""

import os
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# telemetry crash dumps (watchdog-timeout tests fire them) go to a temp dir,
# not the repo checkout
if "PADDLE_TPU_FLIGHT_RECORDER_DIR" not in os.environ:
    os.environ["PADDLE_TPU_FLIGHT_RECORDER_DIR"] = \
        tempfile.mkdtemp(prefix="paddle_tpu_flightrec_")
# the AOT executable cache defaults to a per-run tmpdir under pytest so test
# runs never cross-pollinate each other (or the checkout's own
# .compile_cache/aot); subprocess-spawning tests inherit it, which is
# exactly what the warm-restart e2e wants
if "PADDLE_TPU_COMPILE_CACHE" not in os.environ:
    os.environ["PADDLE_TPU_COMPILE_CACHE"] = \
        tempfile.mkdtemp(prefix="paddle_tpu_xla_cache_")
# the overlap layer's latency-hiding XLA flags are TPU-only (--xla_tpu_*
# aborts the CPU backend on unknown flags) and would change compiled
# schedules between runs — pin them to a no-op so tier-1 stays
# deterministic regardless of what any test calls
os.environ["PADDLE_TPU_XLA_OVERLAP_FLAGS"] = "0"
# fleet fault-domain chaos suite: production default intervals (hb 10s ttl,
# 15s abort deadline) would blow the tier-1 budget — pin heartbeat, poison
# poll and deadlines down so lease expiry → poison → gang exit resolves in
# ~1-2s. setdefault: a test that needs its own timing can still override,
# and launched subprocesses inherit these.
for _k, _v in (("PADDLE_TPU_SP", "1"),
               # sequence parallelism: pin the gate ON (its mp>1 default)
               # so tier-1 compiles don't depend on the developer's shell;
               # the strict-baseline lint mode stays opt-in per test so
               # ad-hoc baselines under lint() don't all have to be fresh
               ("PADDLE_TPU_LINT_STRICT_BASELINE", "0"),
               ("PADDLE_TPU_HB_INTERVAL", "0.25"),
               ("PADDLE_TPU_HB_TTL", "1.5"),
               ("PADDLE_TPU_POISON_POLL", "0.2"),
               ("PADDLE_TPU_ABORT_DEADLINE", "5"),
               ("PADDLE_TPU_GANG_BARRIER_DEADLINE", "20"),
               ("PADDLE_TPU_TEARDOWN_GRACE", "4"),
               # in-memory snapshot chaos suite: production cadence (every
               # 10 steps) and 30s client deadlines would blow the tier-1
               # budget — snapshot every 2 steps, fail transports fast
               ("PADDLE_TPU_SNAP_EVERY", "2"),
               ("PADDLE_TPU_SNAP_TIMEOUT", "10"),
               # SDC defense: production cadence (vote every 16 steps,
               # 10s vote deadline) would make the bitflip chaos e2e idle
               # through most of the tier-1 budget — vote every 2 steps,
               # confirm with 2 replays, give up on an absent voter fast
               ("PADDLE_TPU_SDC_EVERY", "2"),
               ("PADDLE_TPU_SDC_CONFIRM", "2"),
               ("PADDLE_TPU_SDC_VOTE_TIMEOUT", "5"),
               # degraded-hardware defense: production cadence (flag after
               # 3 monitor scans, poll the flag every 8 steps, 10s probe
               # deadline) would leave the slow-rank chaos e2e waiting on
               # clocks — flag after 2 scans, poll every 2 steps, and give
               # up on an absent probe partner fast
               ("PADDLE_TPU_STRAGGLER_FACTOR", "2.0"),
               ("PADDLE_TPU_STRAGGLER_SCANS", "2"),
               ("PADDLE_TPU_STRAGGLER_EVERY", "2"),
               ("PADDLE_TPU_STRAGGLER_PROBE_TIMEOUT", "5"),
               # serving suite: production page/pool sizes (16-token pages,
               # 64-page arenas) allocate real HBM-scale buffers — pin the
               # paged-KV geometry down so the CPU tier-1 engines compile
               # tiny arenas; tests that probe pool pressure override
               ("PADDLE_TPU_PAGE_TOKENS", "8"),
               ("PADDLE_TPU_SERVE_MAX_BATCH", "3"),
               ("PADDLE_TPU_SERVE_PAGES", "24"),
               ("PADDLE_TPU_SERVE_MAX_PAGES_PER_SEQ", "6"),
               # serving resilience: production queue bounds / breaker
               # cooldowns are sized for real traffic — pin them down so
               # the admission-control and chaos suites resolve fast on
               # CPU (tests that probe a specific bound pass ctor args)
               ("PADDLE_TPU_SERVE_MAX_QUEUE", "16"),
               ("PADDLE_TPU_SERVE_BREAKER_THRESHOLD", "3"),
               ("PADDLE_TPU_SERVE_BREAKER_COOLDOWN", "0.2"),
               ("PADDLE_TPU_SERVE_SLO_WINDOW", "256"),
               ("PADDLE_TPU_SERVE_MAX_STEP_FAILURES", "8"),
               # serving fleet: production lease ttl (10s) and scan cadence
               # would make the failover chaos e2e wait most of the tier-1
               # budget on a clock — a dead replica must be fenced and
               # replayed within ~1-2s on the CPU lane
               ("PADDLE_TPU_SERVE_FLEET_TTL", "1.0"),
               ("PADDLE_TPU_SERVE_FLEET_SCAN", "0.2"),
               ("PADDLE_TPU_SERVE_FLEET_STATUS", "0.1"),
               # observability plane: the production 10s metrics push
               # cadence would leave the trace chaos e2e waiting on the
               # victim's first black-box spill — push every 0.2s
               ("PADDLE_TPU_METRICS_PUSH_S", "0.2"),
               # elastic autoscaling: the production 30s cooldown and 5s
               # control-loop cadence would leave the load-ramp chaos e2e
               # idle on a clock — decide every 0.1s, cool down 0.3s, and
               # assume cold replicas warm within ~0.5s on the CPU lane
               ("PADDLE_TPU_AS_COOLDOWN_S", "0.3"),
               ("PADDLE_TPU_AS_INTERVAL_S", "0.1"),
               ("PADDLE_TPU_AS_WARMUP_ETA_S", "0.5"),
               # disaggregated serving: the production prefix-cache budget
               # (64 pages) dwarfs the tiny tier-1 pools — pin it down so
               # LRU eviction is reachable; a short disagg-routing floor
               # (9 tokens ~ 2 pages at the pinned 8-token pages) lets the
               # prefill-tier e2e use small prompts, and a tight TTL keeps
               # depot KV-frame retention tests fast
               ("PADDLE_TPU_PREFIX_PAGES", "8"),
               ("PADDLE_TPU_DISAGG_MIN_PROMPT", "9"),
               ("PADDLE_TPU_DISAGG_TTL", "1.0"),
               # long-context ladder: a small host-RAM offload tier so the
               # LRU-drop ("offload stall") downgrade path is reachable
               # with tier-1-sized traffic; CP degree stays 1 by default —
               # CP tests pass cp=2 explicitly against the 8 virtual
               # devices pinned above
               ("PADDLE_TPU_KV_OFFLOAD_PAGES", "16")):
    os.environ.setdefault(_k, _v)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy tests (many XLA compiles / multi-process); run the fast "
        "lane with -m 'not slow', the heavies with -m slow")
    config.addinivalue_line(
        "markers",
        "longctx: long-context serving ladder (CP prefill, KV offload, fp8 "
        "pages); tier-1 fast lane, select with -m longctx")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def compile_cache_dir(tmp_path, monkeypatch):
    """A fresh, test-local AOT executable-cache root: points
    PADDLE_TPU_COMPILE_CACHE at tmp_path so caches built inside the test
    (and in its subprocesses) stay isolated from the session default."""
    d = str(tmp_path / "xla_cache")
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", d)
    return d
