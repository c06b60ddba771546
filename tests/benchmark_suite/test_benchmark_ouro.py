"""The cell ``serve-ouro2.6b-chat``: its configuration file against the
published keys and the arithmetic it states, the functions that count a
looped decode step's bytes against hand counts, its traffic against
``chat``'s, a rehearsal of the cell from fixture files at a tiny size on the
CPU, and the readers of its per-layer metrics on a synthetic profile.
Nothing here pins an entry's position or a whole list: later cells append."""

import types

import pytest

from benchmark import run
from benchmark.lib import ouro_cost as cost
from benchmark.lib import program_spans as PS
from benchmark.lib import registry
from benchmark.lib import trace as T
from tests.benchmark_suite.test_benchmark_program_spans import _space
from tests.benchmark_suite.test_benchmark_rehearsal import _root

CELL = "serve-ouro2.6b-chat"
CONFIG = "ouro-2.6b"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl):
# every number and flag of the published config.json
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}
NEW_METRICS = ("decode.hbm_roofline.ouro.serve",
               "kernel.paged_decode_roofline.ouro.serve")
# accepted metrics the cell is appended to: those that move the one
# serving metric it reports beside setup_s, itl_tail_mean_ms
SHARED_METRICS = (
    "dispatch.fallbacks.serve", "sched.host_ms_per_step.serve",
    "sched.itl_p95.serve", "sched.completed_tok_s.serve",
    "decode.step_ms.serve", "device.idle_share.serve",
    "kernel.paged_decode.busy_share.serve")
CYCLE_ACCOUNT = ("sched.gap_tail_mean.serve", "sched.gap_tail_prefill.serve",
                 "sched.gap_tail_decode.serve",
                 "sched.gap_tail_outside.serve",
                 "sched.prefill_tokens_ahead_tail.serve")
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}


@pytest.fixture(scope="module")
def reg():
    return registry.Registry()


@pytest.fixture(scope="module")
def config(reg):
    return reg.config(CONFIG)


def test_the_configuration_keeps_every_published_key(reg, config):
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    entry = reg._entry("configs", CONFIG)
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    assert entry["reduced"] == [] and config["reduced"] == {}
    assert len(entry["why"]) <= 200
    for key in ("norm_placement", "kv_cache_per_pass", "attention_bias",
                "initializer_range", "early_exit_gate"):
        assert key in config["assumed"], key
    assert "4 passes x 48 layers" in config["deployment"]
    assert len(config["guarantees"]) >= 3
    assert set(config["check"]) == {"logit_rms_tol", "near_tie", "why"}
    assert (config["builder"], config["reference"], config["dtype"]) == \
        ("ouro_serve", "ouro", "bfloat16")


def test_the_arithmetic_the_file_states(config):
    """One layer 51.39 M, 48 of them 2.467 B, the whole model 2.668 B
    (5.34 GB in bf16); 8 KiB of K/V a token a layer a pass, 1.5 MiB over
    the 192 layer-passes, 201.3 MB a 128-token page; a decode step's
    weight floor 19.9 GB."""
    layer = cost.layer_params(config)
    assert layer == 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert round(48 * layer / 1e9, 3) == 2.467
    whole = 48 * layer + 2 * cost.head_params(config) + 2 * 2048 + 1
    assert round(whole / 1e9, 3) == 2.668
    assert round(2 * whole / 1e9, 2) == 5.34
    assert cost.kv_bytes_per_token(config) == 2 * 16 * 128 * 2 == 8192
    per_token = cost.kv_bytes_per_token(config) * 48 * 4
    assert per_token == 1.5 * 2 ** 20
    assert round(128 * per_token / 1e6, 1) == 201.3
    assert round(cost.weight_bytes_per_step(config) / 1e9, 1) == 19.9
    assert round(cost.decode_least_seconds(config, PEAKS, 1, 0) * 1e3,
                 1) == 24.3
    for key in ("layer_params", "model_params", "kv_bytes_per_token",
                "page_bytes", "decode_floor"):
        assert key in config["arithmetic"], key


def test_cost_of_the_page_walk(config):
    """One score and one value multiply-add a head over 128 lanes, a cached
    token: 16 x 128 x 4 operations against 8 KiB, far under the ridge."""
    assert cost.flops_per_query_cached_token(config) == 4 * 16 * 128
    least = cost.attention_least_seconds(config, PEAKS, 1000)
    assert least["bound"] == "hbm"
    assert least["seconds"] == pytest.approx(1000 * 8192 / 819e9)


def test_the_builder_builds_what_the_file_says(reg, config):
    cfg = reg.module("builders", config["builder"]).ouro_config(config)
    assert (cfg.num_hidden_layers, cfg.total_ut_steps, cfg.hidden_size,
            cfg.head_dim, cfg.vocab_size) == (48, 4, 2048, 128, 49152)
    assert cfg.early_exit_threshold == 1 and cfg.rope_theta == 1e6
    assert cfg.initializer_range == 0.02 and not cfg.tie_word_embeddings


def test_the_builder_makes_the_weights_from_the_seed():
    """One program a parameter shape: matrices and the embedding normal(0,
    initializer_range), norms 1, the gate's bias 0; the same seed gives the
    same weights and another seed others."""
    import numpy as np

    import paddle_tpu as paddle
    from benchmark.builders import ouro_serve
    from paddle_tpu.models import OuroForCausalLM, ouro_tiny

    cfg = ouro_tiny()

    def factory():
        m = OuroForCausalLM(cfg)
        m.eval()
        return paddle.amp.decorate(m, level="O2", dtype="bfloat16")

    def made(seed):
        m = ouro_serve.construct(factory, seed, cfg.initializer_range)
        return {n: np.asarray(p.value, np.float32)
                for n, p in m.named_parameters()}

    a, b, c = made(2**31 + 7), made(2**31 + 7), made(8)
    assert a.keys() == b.keys() == c.keys()
    for name, v in a.items():
        assert np.array_equal(v, b[name]), name
        if name.endswith("bias"):
            assert not v.any(), name
        elif "norm" in name:
            assert (v == 1).all(), name
        else:
            assert abs(v.std() - cfg.initializer_range) < 0.1 * \
                cfg.initializer_range, name
            assert not np.array_equal(v, c[name]), name


def test_the_cell_and_its_traffic(reg, config):
    cell = reg.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "chat-looped", 1)
    assert len(cell["why"]) <= 200
    traffic = reg.traffic("chat-looped")
    chat = reg.traffic("chat")
    assert traffic["runner"] == "serve_open" and not traffic["prefix_cache"]
    # chat's lengths to the letter, a population of its own
    assert traffic["prompt_len"] == chat["prompt_len"]
    assert traffic["output_len"] == chat["output_len"]
    assert traffic["population_seed"] == 38001
    assert (traffic["ramp_s"], traffic["drain_s"], traffic["trace_s"]) == \
        (16.0, 40.0, 6.0)
    assert traffic["check"] == {"prompts": 3, "max_prompt": 1024,
                                "new_tokens": 32}
    engine = traffic["engine"]
    assert (engine["max_batch"], engine["page_tokens"],
            engine["max_pages_per_seq"]) == (16, 128, 20)
    # the longest request fits its table and the pool
    assert 2048 + 512 <= 20 * 128 and engine["num_pages"] - 1 >= 20
    arrivals = traffic["arrivals"]
    assert arrivals["cv"] == 1.0
    assert arrivals["rate_per_s"] == pytest.approx(
        0.8 * arrivals["knee_per_s"])
    assert len(arrivals["sweep"]) >= 4
    reports = {m["name"] for k in ("end_to_end", "per_layer")
               for m in reg.metrics_of(CELL, k)}
    assert {"itl_tail_mean_ms", "setup_s", "compile.cache_misses",
            *SHARED_METRICS} <= reports
    assert sum(n.startswith("sched.idle_") for n in reports) == 6
    assert not set(CYCLE_ACCOUNT) & reports
    # 17 requests a window: a mean first-token time is not steady enough
    # to be judged (nor a closed loop's 22), nor is anything read for it
    assert "ttft_mean_ms" not in reports
    assert traffic["closed_loop_sweep"]["knee_clients"] >= 1
    assert not {m["moves"] for m in reg.metrics_of(CELL, "per_layer")} - \
        {"itl_tail_mean_ms", "setup_s"}


def test_the_metric_files_agree_with_the_benchmark(reg):
    """The two new metrics' files name their readers; an entry of
    ``BENCHMARK.json`` that lists one agrees with its file.  The entries
    wait for the five cycle-account entries to stop being pinned last."""
    listed = {m["name"]: m for m in reg.benchmark["per_layer"]}
    for name in NEW_METRICS:
        spec = reg.layer_metric(name)
        assert (spec["unit"], spec["better"], spec["source"],
                spec["moves"]) == ("%", "higher", "device_trace",
                                   "itl_tail_mean_ms")
        assert callable(reg.module("readers", spec["reader"]).read)
        if name in listed:
            entry = listed[name]
            assert entry["workloads"] == [CELL]
            assert {k: spec[k] for k in entry if k != "workloads"} == \
                {k: v for k, v in entry.items() if k != "workloads"}
    assert CELL in reg._entry("end_to_end", "itl_tail_mean_ms")["workloads"]
    for name in SHARED_METRICS:
        assert CELL in reg._entry("per_layer", name)["workloads"]
    bm = reg.benchmark
    assert CELL in [w["name"] for w in bm["workloads"]]
    assert CONFIG in [c["name"] for c in bm["configs"]]
    # every cell that reports setup_s reads its compile cache misses
    for cell in bm["workloads"]:
        assert "compile.cache_misses" in {
            m["name"] for m in reg.metrics_of(cell["name"], "per_layer")}
    # the five cycle-account entries stay the last five
    names = [m["name"] for m in bm["per_layer"]]
    assert set(names[-5:]) == set(CYCLE_ACCOUNT)


@pytest.fixture
def _leave_the_process_as_it_was(monkeypatch):
    """A rehearsal sets the ``pallas_interpret`` flag for its process; and
    an earlier file of this worker may have left a hybrid mesh live."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import topology

    monkeypatch.setattr(topology, "_hcg", None)
    before = paddle.get_flags("pallas_interpret")
    yield
    paddle.set_flags(before)


def test_runner_rehearsal(tmp_path, _leave_the_process_as_it_was):
    """The whole run at a tiny size on the CPU, kernels interpreted: 2
    layers x 3 passes, the decode logits against the reference, every
    request complete, the page walk in the programs and no fallback."""
    import paddle_tpu.telemetry as telemetry

    before = dict(telemetry.counters())
    root = _root(tmp_path, [("t-ouro", "tiny-ouro", "tiny-chat-looped", 1)])
    r = run.execute("t-ouro", 2**31 + 13, 1.0, False, root=root,
                    rehearsal=True)
    assert r["rehearsal"] and r["metrics"] == {}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    check = r["facts"]["check"]
    assert check["ok"] and check["rows"] == 2 * 3
    assert r["facts"]["meter"]["passes"] == 3
    assert r["facts"]["requests_completed"] == r["attempted"]
    after = telemetry.counters()
    assert not {k for k in after if k.startswith("kernel_fallback.")
                and after[k] != before.get(k)}


def _ctx(profile, config):
    return types.SimpleNamespace(trace=T.from_profile(profile),
                                 config=config, peaks=PEAKS, facts={})


@pytest.mark.parametrize("slack", [1.0, 2.0], ids=["least-time", "twice"])
def test_readers_of_the_new_metrics(reg, config, monkeypatch, slack):
    """Two decode steps and a riding prefill in the window, a decode step
    before it and one whose rows rode; each decode program and each page
    walk drawn as taking ``slack`` times its least time.  At the least
    time a share reads 100 % and no more."""
    def decode_ns(kv):
        return cost.decode_least_seconds(config, PEAKS, 1, kv) * 1e9

    def walk_ns(kv):
        return cost.attention_least_seconds(config, PEAKS, kv)[
            "seconds"] * 1e9

    steps = [  # (span, start, facts)
        ("serve.decode", -60_000_000, dict(rode=0, kv_tokens=192 * 900)),
        ("serve.decode", 1_000_000, dict(rode=0, kv_tokens=192 * 2000)),
        ("serve.decode", 60_000_000, dict(rode=1)),
        ("serve.prefill", 60_000_000,
         dict(kv_tokens=192 * 300, kv_tokens_decode=192 * 1500)),
        ("serve.decode", 130_000_000, dict(rode=0, kv_tokens=192 * 2600)),
    ]
    ops, modules = [], []
    host = [("bench.window", 0, 200_000_000)]
    for i, (name, t0, facts) in enumerate(steps):
        t = t0 + 100_000
        kv = facts.get("kv_tokens_decode", facts.get("kv_tokens", 0))
        if name == "serve.decode" and facts["rode"] == 0:
            modules.append((f"jit_serve_decode_fn({i})", t,
                            slack * decode_ns(kv)))
        if name == "serve.prefill":
            modules.append((f"jit_serve_prefill_fn({i})", t, 30_000_000))
        if kv:
            ops.append((f"%paged_decode_attention.{i} = bf16[16,1,16,128]"
                        f"{{3,2,1,0}} custom-call(%p.{i})", t,
                        slack * walk_ns(kv)))
        if not (name == "serve.decode" and facts["rode"]):
            host.append((name, t0, 60_000_000, facts))
        else:
            host.append((name, t0, 1_000_000, facts))
    profile = _space({"/device:TPU:0": {"XLA Ops": ops,
                                        "XLA Modules": modules},
                      "/host:CPU": {"main": host}})
    spans = PS.from_profile(profile)
    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)

    def value(name, c):
        spec = reg.layer_metric(name)
        return reg.module("readers", spec["reader"]).read(c, **spec["args"])

    ctx = _ctx(profile, config)
    share = 100.0 / slack
    assert value("decode.hbm_roofline.ouro.serve", ctx) == \
        pytest.approx(share)
    assert value("kernel.paged_decode_roofline.ouro.serve", ctx) == \
        pytest.approx(share)
    # another configuration's cell over the same trace, and a program that
    # notes no kv_tokens (the parent): nothing to read, nothing raises
    for other in ("mistral-7b-v0.3", "nemotron-3-nano-30b-a3b",
                  "deepseek-v3"):
        for name in NEW_METRICS:
            assert value(name, _ctx(profile, reg.config(other))) is None
    bare = _space({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": modules},
                   "/host:CPU": {"main": [
                       ("bench.window", 0, 200_000_000),
                       ("serve.decode", 1_000_000, 60_000_000,
                        {"rows": 3, "rode": 0})]}})
    monkeypatch.setattr(PS, "of_run",
                        lambda root=None: PS.from_profile(bare))
    for name in NEW_METRICS:
        assert value(name, _ctx(bare, config)) is None
