"""AOT compile service: persistent executable cache, fingerprints, LRU
bounds, corruption/version fallbacks, and the warm-restart supervisor e2e
(cold → kill → relaunch → warm-load with step-for-step identical losses)."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.compile

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
import paddle_tpu.nn as nn  # noqa: E402
import paddle_tpu.nn.functional as F  # noqa: E402
import paddle_tpu.telemetry as telemetry  # noqa: E402
from paddle_tpu.compile import (AOTFunction, ExecutableCache,  # noqa: E402
                                fingerprint, resolve_cache)
from paddle_tpu.distributed.checkpoint import faults  # noqa: E402
from paddle_tpu.distributed.fleet.elastic import (ELASTIC_EXIT_CODE,  # noqa: E402
                                                  RestartPolicy, Supervisor)
from paddle_tpu.jit import _CompileCache  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lowered_text(scale=1.0):
    def f(x, y):
        return (x @ y).sum() * scale

    return jax.jit(f).lower(jnp.ones((8, 8), jnp.float32),
                            jnp.ones((8, 8), jnp.float32)).as_text()


# one canonical program whose fingerprint a subprocess recomputes; any
# process-dependent input (pointers, temp names, dict order) would break
# the warm-restart contract right here
_FP_SNIPPET = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
from paddle_tpu.compile import fingerprint

def f(x, y):
    return (x @ y).sum() * 1.0

low = jax.jit(f).lower(jnp.ones((8, 8), jnp.float32),
                       jnp.ones((8, 8), jnp.float32))
print(fingerprint(low.as_text(), extras={"tag": "t", "k": 1}))
"""


class TestFingerprint:
    def test_deterministic_in_process(self):
        a = fingerprint(_lowered_text(), extras={"tag": "t"})
        b = fingerprint(_lowered_text(), extras={"tag": "t"})
        assert a == b and len(a) == 32

    def test_program_and_extras_discriminate(self):
        base = fingerprint(_lowered_text(), extras={"tag": "t"})
        assert fingerprint(_lowered_text(scale=2.0),
                           extras={"tag": "t"}) != base
        assert fingerprint(_lowered_text(), extras={"tag": "u"}) != base
        assert fingerprint(_lowered_text()) != base

    def test_stable_across_processes(self, tmp_path):
        """The key property of the warm-restart path: the fingerprint a
        fresh process computes for the same program matches this one's."""
        here = fingerprint(_lowered_text(), extras={"tag": "t", "k": 1})
        script = tmp_path / "fp.py"
        script.write_text(textwrap.dedent(_FP_SNIPPET))
        env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run([sys.executable, str(script)], env=env,
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-500:]
        assert out.stdout.strip().splitlines()[-1] == here


class TestExecutableCache:
    def test_roundtrip_and_sidecar(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        payload = b"executable-bytes" * 100
        assert cache.put("fp1", payload, meta={"name": "t"})
        assert len(cache) == 1 and "fp1" in cache
        assert cache.get("fp1") == payload
        doc = cache.meta("fp1")
        assert doc["size"] == len(payload)
        assert doc["jax"] == jax.__version__
        assert doc["meta"] == {"name": "t"}

    def test_miss_returns_none(self, tmp_path):
        assert ExecutableCache(str(tmp_path)).get("nope") is None

    @pytest.mark.parametrize("mutation", ["bitflip", "truncate"])
    def test_corrupt_payload_dropped_silently(self, tmp_path, mutation):
        cache = ExecutableCache(str(tmp_path))
        cache.put("fp1", b"x" * 4096)
        path = os.path.join(str(tmp_path), "fp1.xbin")
        raw = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(raw[:2048] if mutation == "truncate"
                    else bytes([raw[0] ^ 0xFF]) + raw[1:])
        before = telemetry.counters().get(
            "compile_cache_corrupt_dropped_total", 0)
        assert cache.get("fp1") is None        # degrade, never raise
        assert len(cache) == 0                 # poisoned entry removed
        assert telemetry.counters().get(
            "compile_cache_corrupt_dropped_total", 0) == before + 1

    def test_version_mismatch_dropped(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        cache.put("fp1", b"payload")
        sidecar = os.path.join(str(tmp_path), "fp1.json")
        doc = json.load(open(sidecar))
        doc["jax"] = "0.0.0-stale"
        json.dump(doc, open(sidecar, "w"))
        assert cache.get("fp1") is None
        assert len(cache) == 0

    def test_sidecar_without_payload_is_invisible_entry(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        cache.put("fp1", b"payload")
        os.remove(os.path.join(str(tmp_path), "fp1.xbin"))
        assert cache.get("fp1") is None
        assert len(cache) == 0  # dangling sidecar swept

    def test_orphaned_payload_swept_after_grace(self, tmp_path):
        """A crash between the payload write and the sidecar commit leaves
        a sidecar-less .xbin: invisible to get()/entries(), it must still
        be reclaimed (aged) by the next put's sweep — multi-hundred-MB
        blobs can't be allowed to leak outside the LRU cap."""
        cache = ExecutableCache(str(tmp_path))
        orphan = os.path.join(str(tmp_path), "dead.xbin")
        with open(orphan, "wb") as f:
            f.write(b"z" * 64)
        os.utime(orphan, (100.0, 100.0))      # aged far past the grace
        fresh = os.path.join(str(tmp_path), "inflight.xbin")
        with open(fresh, "wb") as f:          # a concurrent put mid-commit
            f.write(b"z" * 64)
        cache.put("fp1", b"ok")               # put() runs the sweep
        assert not os.path.exists(orphan)     # aged orphan reclaimed
        assert os.path.exists(fresh)          # in-flight commit untouched
        assert cache.get("fp1") == b"ok"

    def test_clear_removes_orphans_too(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        cache.put("fp1", b"ok")
        with open(os.path.join(str(tmp_path), "dead.xbin"), "wb") as f:
            f.write(b"z")
        cache.clear()
        assert [n for n in os.listdir(str(tmp_path))
                if n.endswith((".xbin", ".json"))] == []

    def test_lru_eviction_order_and_get_refresh(self, tmp_path):
        cache = ExecutableCache(str(tmp_path), max_entries=2)
        for i, fp in enumerate(["a", "b", "c"]):
            cache.put(fp, b"p" * 16)
            cache._touch(fp, ts=1000.0 + i)  # deterministic recency
        assert "a" not in cache              # oldest evicted at put("c")
        assert "b" in cache and "c" in cache
        cache._touch("b", ts=1010.0)         # what get() does on a hit
        cache.put("d", b"p" * 16)
        cache._touch("d", ts=1020.0)
        assert "c" not in cache              # now the stalest
        assert "b" in cache and "d" in cache

    def test_transient_read_flake_absorbed_by_retries(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        payload = b"q" * 1024
        cache.put("fp1", payload)
        with faults.inject(op="read", pattern="*.xbin", mode="error",
                           times=2):
            assert cache.get("fp1") == payload  # storage-seam retries eat it

    def test_persistent_read_failure_degrades_to_miss(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        cache.put("fp1", b"q" * 1024)
        with faults.inject(op="read", pattern="*.xbin", mode="error",
                           times=-1):
            assert cache.get("fp1") is None     # recompile, not a crash

    def test_write_failure_returns_false_never_raises(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        with faults.inject(op="write", pattern="*.xbin", mode="error",
                           times=-1):
            assert cache.put("fp1", b"q") is False
        assert len(cache) == 0

    def test_resolve_cache_forms(self, tmp_path, compile_cache_dir):
        assert resolve_cache(None) is None
        assert resolve_cache(False) is None
        c = resolve_cache(str(tmp_path))
        assert isinstance(c, ExecutableCache) and c.root == str(tmp_path)
        assert resolve_cache(c) is c
        assert resolve_cache(True).root == compile_cache_dir
        with pytest.raises(TypeError):
            resolve_cache(123)


class TestCachePlacement:
    """One resolver places every compile cache (compile.cache_dir)."""

    def test_outside_placement_wins_and_nothing_else_is_named(
            self, tmp_path, monkeypatch):
        from paddle_tpu.compile import cache_dir, default_root

        monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cache_dir() == str(tmp_path)
        assert default_root() == str(tmp_path / "aot")

    def test_default_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        from paddle_tpu.compile import cache_dir, default_root

        monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        assert cache_dir() == os.path.join(checkout, ".compile_cache")
        assert default_root() == os.path.join(checkout, ".compile_cache",
                                              "aot")

    def test_second_process_hits_what_the_first_compiled(self, tmp_path):
        """enable_persistent_cache(): every jit of the process lands in
        the placed directory, and the next process finds it."""
        child = (
            "import jax, jax.numpy as jnp, json\n"
            "from paddle_tpu.compile import enable_persistent_cache\n"
            "stats = enable_persistent_cache()\n"
            "jax.jit(lambda x: jnp.tanh(x) @ x.T)(jnp.ones((64, 64)))"
            ".block_until_ready()\n"
            "print(json.dumps(stats.as_dict()))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        runs = []
        for _ in range(2):
            out = subprocess.run([sys.executable, "-c", child], env=env,
                                 capture_output=True, text=True, timeout=120)
            assert out.returncode == 0, out.stderr[-800:]
            runs.append(json.loads(out.stdout.strip().splitlines()[-1]))
        assert all(r["dir"] == str(tmp_path) for r in runs)
        assert runs[0]["hits"] == 0 and runs[0]["misses"] >= 1
        assert runs[1]["hits"] >= 1 and runs[1]["misses"] == 0
        assert os.listdir(tmp_path)


class TestJitCompileCacheBound:
    def test_env_bound_and_eviction_counter(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_JIT_CACHE_MAX", "2")
        cc = _CompileCache()
        assert cc.max_entries == 2
        before = telemetry.counters().get("compile_cache_evictions", 0)
        cc.put("a", 1)
        cc.put("b", 2)
        cc.get("a")          # refresh: 'b' becomes the LRU victim
        cc.put("c", 3)
        assert cc.get("b") is None and cc.get("a") == 1 and cc.get("c") == 3
        assert len(cc) == 2 and cc.evictions == 1
        assert telemetry.counters().get("compile_cache_evictions", 0) == \
            before + 1

    def test_static_function_bounded_under_shape_churn(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_JIT_CACHE_MAX", "2")
        sf = paddle.jit.to_static(lambda x: x * 2.0 + 1.0)
        for n in (3, 4, 5, 6):  # 4 distinct shapes > max_entries
            out = sf(paddle.to_tensor(np.ones(n, "float32")))
            np.testing.assert_allclose(out.numpy(), np.full(n, 3.0), rtol=0)
        assert len(sf._cache) == 2  # bounded; un-bounded dict would hold 4
        assert sf._cache.evictions == 2


def _mlp_step(cache, seed=0, steps=3):
    """Tiny guarded-free TrainStep over a fixed data stream; returns
    (losses, step) — the in-process cold/warm probe."""
    paddle.seed(seed)
    model = nn.Linear(8, 4)
    opt = paddle.optimizer.SGD(1e-2, parameters=model.parameters())
    step = paddle.jit.TrainStep(model,
                                lambda m, x, y: F.mse_loss(m(x), y), opt,
                                persistent_cache=cache)
    rng = np.random.default_rng(7)
    losses = []
    for _ in range(steps):
        x = rng.standard_normal((4, 8)).astype("float32")
        y = rng.standard_normal((4, 4)).astype("float32")
        losses.append(float(step(paddle.to_tensor(x),
                                 paddle.to_tensor(y)).numpy()))
    return losses, step


class TestAOTTrainStep:
    def test_cold_then_warm_with_identical_numerics(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        t0 = telemetry.runtime.now()["mono_ns"]
        cold_losses, cold_step = _mlp_step(cache)
        assert cold_step.compile_info["mode"] == "cold"
        assert cold_step.compile_info["persisted"] is True
        assert cold_step.compile_info["seconds"] > 0
        warm_losses, warm_step = _mlp_step(cache)
        assert [e["mode"] for e in warm_step.compile_events] and \
            all(e["mode"] == "warm" for e in warm_step.compile_events)
        # the warm executable is the same XLA binary: bit-identical losses
        assert warm_losses == cold_losses
        assert warm_step.compile_info["fingerprint"] == \
            cold_step.compile_info["fingerprint"]
        # the flight recorder narrates both modes
        ev = [e for e in telemetry.get_flight_recorder().events(t0)
              if e["kind"] == "compile_end"]
        assert {"cold", "warm"} <= {e["mode"] for e in ev}
        assert all(e["seconds"] >= 0 and e["fingerprint"] for e in ev)

    def test_corrupted_entry_recompiles_silently(self, tmp_path):
        cache = ExecutableCache(str(tmp_path))
        cold_losses, _ = _mlp_step(cache)
        for name in os.listdir(str(tmp_path)):     # poison every payload
            if name.endswith(".xbin"):
                p = os.path.join(str(tmp_path), name)
                raw = open(p, "rb").read()
                with open(p, "wb") as f:
                    f.write(raw[:len(raw) // 2])
        losses, step = _mlp_step(cache)
        assert step.compile_info["mode"] == "cold"  # degraded, no crash
        assert losses == cold_losses
        # ...and the recompile re-persisted a good entry
        warm_losses, warm_step = _mlp_step(cache)
        assert warm_step.compile_info["mode"] == "warm"
        assert warm_losses == cold_losses

    def test_cost_analysis_flops_reported(self, tmp_path):
        _, step = _mlp_step(ExecutableCache(str(tmp_path)))
        flops = step.compile_info["flops"]
        assert flops is not None and flops > 0

    def test_aot_function_plain_jit_parity(self, tmp_path):
        jitted = jax.jit(lambda x: jnp.sin(x) * 2.0)
        aot = AOTFunction(jitted, cache=ExecutableCache(str(tmp_path)),
                          name="parity")
        x = jnp.linspace(0, 1, 16)
        np.testing.assert_allclose(np.asarray(aot(x)),
                                   np.asarray(jitted(x)), rtol=0)
        assert aot.last_compile["mode"] == "cold"
        aot2 = AOTFunction(jax.jit(lambda x: jnp.sin(x) * 2.0),
                           cache=ExecutableCache(str(tmp_path)),
                           name="parity")
        np.testing.assert_allclose(np.asarray(aot2(x)),
                                   np.asarray(jitted(x)), rtol=0)
        assert aot2.last_compile["mode"] == "warm"


class TestMultiDeviceWarmLoad:
    """A program sharded over the 8-virtual-device CPU mesh persists and
    warm-loads onto ITS devices like any other (jax 0.9.0: the jaxlib
    0.4.36 crash that once forced these programs cold is gone, and
    ``deserialize_and_load`` is handed the program's own device list)."""

    def test_sharded_program_warm_loads(self, tmp_path):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("a", "b"))
        sh = NamedSharding(mesh, P("a", None))
        cache = ExecutableCache(str(tmp_path))
        x = jax.device_put(jnp.ones((8, 8), jnp.float32), sh)
        modes = []
        for _ in range(2):
            aot = AOTFunction(jax.jit(lambda v: v * 2, in_shardings=sh,
                                      donate_argnums=0),
                              cache=cache, name="sharded")
            y = aot(jax.device_put(jnp.ones((8, 8), jnp.float32), sh))
            # chain: the warm executable consumes donated sharded state
            np.testing.assert_allclose(np.asarray(aot(y)), 4.0)
            modes.append(aot.last_compile["mode"])
        assert modes == ["cold", "warm"]
        assert len(cache) == 1

    def test_one_device_program_on_second_device(self, tmp_path):
        """The execute_sharded regression: a one-device program must load
        onto the device it was lowered for, not 'every local device'."""
        dev = jax.devices()[3]
        cache = ExecutableCache(str(tmp_path))
        x = jax.device_put(jnp.ones((4,), jnp.float32), dev)
        for want in ("cold", "warm"):
            aot = AOTFunction(jax.jit(lambda v: v + 1), cache=cache,
                              name="dev3")
            out = aot(x)
            assert aot.last_compile["mode"] == want
            assert out.devices() == {dev}


class TestSupervisorTimeToFirstStep:
    def test_inprocess_restart_event_carries_ttfs(self):
        t0 = telemetry.runtime.now()["mono_ns"]
        runs = {"n": 0}

        def job():
            _mlp_step(None, steps=1)   # one completed TrainStep → stamp
            runs["n"] += 1
            if runs["n"] == 1:
                raise SystemExit(ELASTIC_EXIT_CODE)

        sup = Supervisor(job, policy=RestartPolicy(max_restarts=2,
                                                   backoff_base=0.001,
                                                   backoff_cap=0.002))
        assert sup.run() == 0
        assert sup.time_to_first_step_s is not None  # last launch's probe
        evs = [e for e in telemetry.get_flight_recorder().events(t0)
               if e["kind"] == "supervisor"]
        restart = [e for e in evs if e["name"] == "supervisor_restart"]
        done = [e for e in evs if e["name"] == "supervisor_done"]
        assert restart and restart[-1]["time_to_first_step_s"] is not None
        assert restart[-1]["time_to_first_step_s"] >= 0
        assert done and done[-1]["time_to_first_step_s"] is not None

    def test_no_trainstep_means_none(self):
        sup = Supervisor(lambda: None, policy=RestartPolicy(max_restarts=0))
        assert sup.run() == 0
        assert sup.time_to_first_step_s is None


# the acceptance e2e: a first process cold-compiles + persists, "dies" with
# exit 101 AFTER logging its losses, the Supervisor relaunches it with the
# same PADDLE_TPU_COMPILE_CACHE, and the relaunch deserializes the
# executable (warm compile_end, zero cold compiles) and reproduces the
# cold run's losses step for step
E2E_CHILD = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.telemetry as telemetry
from paddle_tpu.distributed.fleet.elastic import ELASTIC_EXIT_CODE

out_path, marker = sys.argv[1], sys.argv[2]

paddle.seed(0)
model = nn.Linear(8, 4)
opt = paddle.optimizer.SGD(1e-2, parameters=model.parameters())
step = paddle.jit.TrainStep(model, lambda m, x, y: F.mse_loss(m(x), y), opt,
                            persistent_cache=True)  # root from supervisor env
rng = np.random.default_rng(3)
losses = []
for _ in range(4):
    x = rng.standard_normal((4, 8)).astype("float32")
    y = rng.standard_normal((4, 4)).astype("float32")
    losses.append(float(step(paddle.to_tensor(x), paddle.to_tensor(y)).numpy()))

rec = {
    "losses": losses,
    "modes": [e["mode"] for e in step.compile_events],
    "cold_total": telemetry.counters().get("compile_cold_total", 0),
    "warm_total": telemetry.counters().get("compile_warm_total", 0),
    "recorder_compile_ends": [
        e.get("mode") for e in telemetry.get_flight_recorder().events()
        if e["kind"] == "compile_end"],
}
with open(out_path, "a") as f:
    f.write(json.dumps(rec) + "\\n")
if not os.path.exists(marker):
    open(marker, "w").write("1")
    os._exit(ELASTIC_EXIT_CODE)  # die AFTER the cold compile was persisted
"""


class TestWarmRestartEndToEnd:
    def test_relaunch_warm_loads_and_matches_cold_numerics(self, tmp_path):
        script = tmp_path / "child.py"
        script.write_text(textwrap.dedent(E2E_CHILD))
        out = str(tmp_path / "runs.jsonl")
        marker = str(tmp_path / ".crashed")
        cache_root = str(tmp_path / "xla_cache")
        t0 = telemetry.runtime.now()["mono_ns"]
        env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
               "PADDLE_TPU_FLIGHT_RECORDER_DIR": str(tmp_path / "fr")}
        sup = Supervisor([sys.executable, str(script), out, marker],
                         policy=RestartPolicy(max_restarts=2,
                                              backoff_base=0.01,
                                              backoff_cap=0.02),
                         env=env, compile_cache=cache_root,
                         child_timeout=300)
        assert sup.run() == 0
        assert sup.restarts == 1
        assert sup.exit_codes == [ELASTIC_EXIT_CODE, 0]

        gen1, gen2 = [json.loads(l) for l in open(out).read().splitlines()]
        # generation 1 paid XLA: first compile cold, persisted to the cache
        assert gen1["modes"][0] == "cold" and gen1["cold_total"] >= 1
        assert len(ExecutableCache(cache_root)) >= 1
        # generation 2 warm-loaded BEFORE touching data: every compile is a
        # deserialize, zero cold compiles anywhere in the process
        assert gen2["modes"] and all(m == "warm" for m in gen2["modes"])
        assert gen2["cold_total"] == 0 and gen2["warm_total"] >= 1
        assert gen2["recorder_compile_ends"] and \
            all(m == "warm" for m in gen2["recorder_compile_ends"])
        # warm executable == cold executable: losses identical step for step
        assert gen2["losses"] == gen1["losses"]
        # the parent's goodput trail: the restart event and the final done
        # event both report time-to-first-step (the warm-start win metric)
        evs = [e for e in telemetry.get_flight_recorder().events(t0)
               if e["kind"] == "supervisor"]
        done = [e for e in evs if e["name"] == "supervisor_done"]
        assert done and done[-1]["time_to_first_step_s"] is not None
