"""Every runner end to end at a tiny configuration on the CPU (a rehearsal:
counts and correctness, no metric and no time under a metric's name), the
training runner on four virtual devices, a throw-away cell registered from
fixture files alone, and the refusal to measure without a chip."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.lib import device, registry

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "fixtures")
CELLS = [("t-chat", "tiny-mistral", "tiny-chat", 1),
         ("t-docqa", "tiny-mistral", "tiny-docqa", 1),
         ("t-train", "tiny-gpt", "tiny-seq", 1),
         ("t-hybrid", "tiny-mistral-4dev", "tiny-mp2pp2", 4)]


def _root(tmp_path, cells, extra_dirs=()):
    """A benchmark root whose ``paths`` are the fixtures, any extra
    directory and the real ``benchmark/``: cells of tiny configurations
    over the real builders, runners and readers."""
    with open(os.path.join(registry.ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["paths"] = [*map(str, extra_dirs), FIXTURES,
                   os.path.join(registry.ROOT, "benchmark")]
    bm["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": k,
                        "why": "rehearsal"} for n, c, t, k in cells]
    bm["configs"] = [{"name": c, "source": "fixture", "reduced": [],
                      "why": "rehearsal",
                      "file": _find(c, [*extra_dirs, FIXTURES])}
                     for c in sorted({c for _, c, _, _ in cells})]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bm, f)
    return str(tmp_path)


def _find(config, dirs):
    for d in dirs:
        path = os.path.join(str(d), "configs", config + ".json")
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(config)


@pytest.fixture(autouse=True)
def _leave_the_process_as_it_was():
    """A rehearsal sets two things for its whole process that a real run
    never has to give back: the ``pallas_interpret`` flag and, in the
    hybrid builder, the process's mesh.  Other test files share this
    worker."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import topology

    before = paddle.get_flags("pallas_interpret")
    yield
    paddle.set_flags(before)
    topology._hcg = None


@pytest.mark.parametrize("cell", CELLS, ids=[c[0] for c in CELLS])
def test_runner_rehearsal(tmp_path, cell):
    root = _root(tmp_path, [cell])
    r = run.execute(cell[0], 2**31 + 7, 1.5, False, root=root,
                    rehearsal=True)
    assert r["rehearsal"] and r["metrics"] == {}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    check = r["facts"]["check"]
    assert check["ok"]
    # every number compared, beside its limit: the line's last key
    pairs = run.compared(r)
    assert {"logits_rms_rel_err_median", "logits_rms_rel_err_worst"} <= \
        set(pairs)
    assert all(0 <= c["value"] <= c["limit"] for c in pairs.values()), pairs
    if cell[2] == "tiny-chat":
        assert r["samples"]["ttft_ms"] == r["attempted"]
        assert "measured" not in r      # no time from a CPU run
        assert r["facts"]["requests_completed"] == r["attempted"]
        assert r["facts"]["meter"]["requests_shed"] == 0
    elif cell[2] == "tiny-docqa":
        assert r["facts"]["tokens_completed"] > 0
    else:
        assert r["facts"]["tokens"] == r["attempted"] * 2 * 64
        again = r["facts"]["repeated_batch"]
        assert again[1] < again[0]
    if cell[3] == 4:
        assert r["facts"]["finish"] == {"axes_unused": [],
                                        "on_every_chip": True, "ok": True}


def test_a_cell_is_added_by_files_alone(tmp_path):
    """A new configuration, traffic mix, builder, runner, per-layer metric
    and reader: six new files and entries in BENCHMARK.json, no edit to a
    file that was there."""
    over = tmp_path / "overlay"
    for kind in ("configs", "traffic", "builders", "runners",
                 "layer_metrics", "readers"):
        (over / kind).mkdir(parents=True)
    (over / "configs" / "toy.json").write_text(json.dumps(
        {"builder": "toy_builder", "size": 5}))
    (over / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"runner": "toy_runner", "repeat": 3}))
    (over / "builders" / "toy_builder.py").write_text(
        "def build(config, traffic, seed, devices):\n"
        "    return list(range(config['size']))\n")
    (over / "runners" / "toy_runner.py").write_text(
        "def run(system, traffic, ctx):\n"
        "    ctx.window_opens_at(0.0)\n"
        "    ctx.window_closed()\n"
        "    n = len(system) * traffic['repeat']\n"
        "    return {'end_to_end': {}, 'facts': {'work': n, 'seed': ctx.seed},"
        " 'attempted': n, 'failed': 0, 'correct': True}\n")
    (over / "layer_metrics" / "toy.work.json").write_text(json.dumps(
        {"name": "toy.work", "reader": "toy_reader", "args": {"scale": 2}}))
    (over / "readers" / "toy_reader.py").write_text(
        "def read(ctx, scale):\n    return ctx.facts['work'] * scale\n")
    root = _root(tmp_path, [("toy-cell", "toy", "toy-mix", 1)], [over])
    r = run.execute("toy-cell", 11, 1.0, False, root=root, rehearsal=True)
    assert r["attempted"] == 15 and r["facts"] == {"work": 15, "seed": 11}
    reg = registry.Registry(root)
    spec = reg.layer_metric("toy.work")
    reader = reg.module("readers", spec["reader"])

    class Ctx:
        facts = r["facts"]

    assert reader.read(Ctx, **spec["args"]) == 30
    with pytest.raises(KeyError, match="no workloads entry named 'absent'"):
        reg.workload("absent")
    with pytest.raises(FileNotFoundError):
        reg.traffic("absent")


def test_no_chip_no_number(monkeypatch):
    """Off a TPU the command exits non-zero and prints no result; a TPU of
    a kind the peaks table does not know is refused as well."""
    with pytest.raises(SystemExit) as e:
        device.probe(1)
    assert "no accelerator" in str(e.value)
    with pytest.raises(SystemExit, match="asks for 64 chip"):
        device.probe(64, rehearsal=True)

    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"

    import jax

    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    with pytest.raises(SystemExit, match="not in benchmark/lib/peaks.py"):
        device.probe(1)
    Dev.device_kind = "TPU v5 lite"
    record, devs, peak = device.probe(1)
    assert record.pop("reach_chip_s") >= 0.0
    assert record == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peak["flops_bf16"] == 197e12


def test_setup_leaves_out_the_wait_for_the_chip():
    """``setup_s`` runs from the process's start to the opening of the
    window (which may lie a ramp ahead), less the time the machine took to
    hand over its chips; each phase between two marks is [wall s, CPU s]."""
    import gc

    from benchmark.lib import clock

    age = clock.process_age_s()
    ctxs = [run.Ctx(1, 1.0, True, None, [], reach_chip_s=r)
            for r in (0.0, 7.5)]
    try:
        for c in ctxs:
            c.window_opens_at(clock.now() + 8.0)
    finally:
        gc.enable()
        gc.unfreeze()
    whole, less = (c.setup_s for c in ctxs)
    assert age + 7.98 <= whole <= clock.process_age_s() + 8.02
    assert less == pytest.approx(whole - 7.5, abs=0.1)
    phases = clock.phases()
    assert "warm_up" in phases and all(len(v) == 2 for v in phases.values())


def test_the_command_exits_non_zero_here():
    name = registry.Registry().benchmark["workloads"][0]["name"]
    out = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=registry.ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert not out.stdout.strip().endswith("}")
