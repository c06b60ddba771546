"""Elastic over TCP (no shared filesystem) + preemption-aware resume
(round-2 verdict #5 tail and #7).

Parity targets: reference `fleet/elastic/manager.py` membership semantics on
a TCPStore-backed KV, `launch/controllers/master.py` multi-node rendezvous
through the launch CLI, and SURVEY §5.3's preemption → async checkpoint →
resume story."""

import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from paddle_tpu.distributed.fleet.elastic import (ELASTIC_EXIT_CODE,
                                                  ElasticManager,
                                                  ElasticStatus,
                                                  PreemptionGuard)
from paddle_tpu.distributed.store import TCPKVStore, TCPStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# no test here waits for a lease to lapse (peers leave by `exit()`), so the
# ttl is only how long a starved heartbeat thread may stall before a live
# peer reads as dead: long, or the tests fail on a loaded machine
_TTL = 60.0


class TestElasticOverTCP:
    """ElasticManager with the TCP KV backend: the FileStore contract
    without any shared filesystem (verdict #5 done-criterion)."""

    @pytest.fixture
    def tcp_kv(self):
        master = TCPStore("127.0.0.1", 0, is_master=True, timeout=20.0)
        yield lambda: TCPKVStore(
            TCPStore("127.0.0.1", master.port, timeout=10.0), prefix="el")
        master.close()

    def test_membership_and_restart_detection(self, tcp_kv):
        m1 = ElasticManager(tcp_kv(), job_id="j", np="1:2", host="node-a",
                            ttl=_TTL)
        m2 = ElasticManager(tcp_kv(), job_id="j", np="1:2", host="node-b",
                            ttl=_TTL)
        assert m1.hosts() == ["node-a", "node-b"]
        world = m1.commit_world()
        assert world == ["node-a", "node-b"]
        assert m1.watch_once() == ElasticStatus.HOLD  # steady state
        # peer leaves (still >= np_min) → RESTART with survivors
        m2.exit()
        assert m1.watch_once() == ElasticStatus.RESTART
        m1.exit(completed=True)
        m3 = ElasticManager(tcp_kv(), job_id="j", np=1, host="node-c", ttl=_TTL)
        assert m3.watch_once() == ElasticStatus.COMPLETED
        m3.exit()

    def test_scale_up_detected(self, tcp_kv):
        m1 = ElasticManager(tcp_kv(), job_id="j2", np="1:3", host="a", ttl=_TTL)
        m1.commit_world()
        assert m1.watch_once() == ElasticStatus.HOLD
        m2 = ElasticManager(tcp_kv(), job_id="j2", np="1:3", host="b", ttl=_TTL)
        assert m1.watch_once() == ElasticStatus.RESTART  # joiner → rescale
        m1.exit(); m2.exit()


@pytest.mark.slow
class TestMultiNodeLaunchRendezvous:
    def test_two_pod_launch_over_master(self, tmp_path):
        """Two `launch` pods (nnodes=2) rendezvous through --master, get
        distinct auto-assigned node ranks, and the env contract reaches the
        workers."""
        import socket as socketlib

        with socketlib.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
            import os, sys
            need = ["PADDLE_TRAINER_ID", "PADDLE_TRAINERS_NUM",
                    "PADDLE_MASTER", "PADDLE_NODE_RANK", "PADDLE_NNODES"]
            vals = {k: os.environ[k] for k in need}
            assert vals["PADDLE_TRAINERS_NUM"] == "2", vals
            assert vals["PADDLE_NNODES"] == "2", vals
            print("WORKER_OK", vals["PADDLE_TRAINER_ID"],
                  vals["PADDLE_NODE_RANK"])
        """))
        env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
        pods = [subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--master", f"127.0.0.1:{port}",
             "--job_id", "rdzv_test",
             "--log_dir", str(tmp_path / f"log{i}"), str(script)],
            env=env, cwd=str(tmp_path), stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT) for i in range(2)]
        outs = [p.communicate(timeout=120)[0].decode() for p in pods]
        assert all(p.returncode == 0 for p in pods), outs
        ranks = set()
        for i in range(2):
            log = tmp_path / f"log{i}"
            files = os.listdir(log)
            assert len(files) == 1
            content = (log / files[0]).read_text()
            assert "WORKER_OK" in content, content
            ranks.add(content.split()[1])
        assert ranks == {"0", "1"}


TRAIN_SCRIPT = """
import os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.checkpoint import save_state_dict, load_state_dict
from paddle_tpu.distributed.fleet.elastic import PreemptionGuard

ckpt = sys.argv[1]
total_steps = int(sys.argv[2])
preempt_at = int(sys.argv[3])  # -1: never (baseline / resumed run)
trace_path = sys.argv[4]

paddle.seed(0)
model = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
opt = paddle.optimizer.Adam(1e-2, parameters=model.parameters())
rng = np.random.default_rng(0)
x = paddle.to_tensor(rng.standard_normal((16, 8)).astype(np.float32))
y = paddle.to_tensor(rng.standard_normal((16, 4)).astype(np.float32))

start = 0
state = {"model": model.state_dict(), "opt": opt.state_dict(),
         "step": paddle.to_tensor(np.int64(0))}
if os.path.exists(os.path.join(ckpt, "metadata")):
    load_state_dict(state, ckpt)
    model.set_state_dict(state["model"])
    opt.set_state_dict(state["opt"])
    start = int(np.asarray(state["step"].numpy()))

guard = PreemptionGuard()
losses = []
for step in range(start, total_steps):
    loss = F.mse_loss(model(x), y)
    loss.backward()
    opt.step(); opt.clear_grad()
    losses.append(f"{step}:{float(loss.numpy()):.6f}")
    if step + 1 == preempt_at:
        os.kill(os.getpid(), signal.SIGTERM)  # deliver the notice mid-run
    if guard.preempted:
        with open(trace_path, "a") as f:
            f.write("\\n".join(losses) + "\\n")
        state = {"model": model.state_dict(), "opt": opt.state_dict(),
                 "step": paddle.to_tensor(np.int64(step + 1))}
        guard.checkpoint_and_exit(state, ckpt)
with open(trace_path, "a") as f:
    f.write("\\n".join(losses) + "\\n")
"""


@pytest.mark.slow
class TestPreemptionResume:
    def test_kill_and_resume_matches_uninterrupted(self, tmp_path):
        """Verdict #7 done-criterion: SIGTERM mid-run → async ckpt → restart
        resumes to the SAME loss trajectory as an uninterrupted run."""
        script = tmp_path / "train.py"
        script.write_text(TRAIN_SCRIPT)
        env = {**os.environ, "PYTHONPATH": REPO}

        def run(ckpt, steps, preempt_at, trace):
            return subprocess.run(
                [sys.executable, str(script), str(ckpt), str(steps),
                 str(preempt_at), str(trace)], env=env, timeout=300,
                capture_output=True, text=True)

        base = run(tmp_path / "ckpt_base", 8, -1, tmp_path / "base.txt")
        assert base.returncode == 0, base.stderr

        r1 = run(tmp_path / "ckpt", 8, 4, tmp_path / "trace.txt")
        assert r1.returncode == ELASTIC_EXIT_CODE, (r1.returncode, r1.stderr)
        assert os.path.exists(tmp_path / "ckpt" / "metadata")
        r2 = run(tmp_path / "ckpt", 8, -1, tmp_path / "trace.txt")
        assert r2.returncode == 0, r2.stderr

        def parse(p):
            return {int(l.split(":")[0]): float(l.split(":")[1])
                    for l in open(p).read().split() if l}

        base_losses = parse(tmp_path / "base.txt")
        resumed = parse(tmp_path / "trace.txt")
        assert sorted(resumed) == sorted(base_losses) == list(range(8))
        for s in range(8):
            np.testing.assert_allclose(resumed[s], base_losses[s], rtol=1e-4,
                                       err_msg=f"step {s}")

    def test_guard_flag_and_uninstall(self):
        guard = PreemptionGuard(signals=(signal.SIGUSR1,))
        assert not guard.preempted
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.1)
        assert guard.preempted
        guard.uninstall()
        assert signal.getsignal(signal.SIGUSR1) != guard._on_signal
