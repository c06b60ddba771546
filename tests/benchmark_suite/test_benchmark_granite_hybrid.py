"""The cell ``serve-granite4hmicro-chat-bursty``: its configuration file
against the published keys, a rehearsal of the cell from fixture files at a
tiny size on the CPU, and the readers of its per-layer metrics on a
synthetic profile."""

import json
import os
import types

import pytest

from benchmark import run
from benchmark.lib import program_spans as PS
from benchmark.lib import registry, ssm_bytes
from benchmark.lib import trace as T
from tests.benchmark_suite.test_benchmark_program_spans import _space
from tests.benchmark_suite.test_benchmark_rehearsal import _root

CELL = "serve-granite4hmicro-chat-bursty"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl):
# every number and flag of the published config.json that says something
# about the model's shape
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
    "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}


@pytest.fixture(scope="module")
def reg():
    return registry.Registry()


@pytest.fixture(scope="module")
def config(reg):
    return reg.config("granite-4.0-h-micro")


def test_the_configuration_is_the_published_one_uncut(reg, config):
    assert {k: config[k] for k in PUBLISHED} == PUBLISHED
    kinds = config["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] == \
        [5, 15, 25, 35]
    assert config["reduced"] == {} and config["published"] == {}
    entry = reg._entry("configs", "granite-4.0-h-micro")
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert {"head_dim", "initializer_range", "mamba_init", "ssm_state_dtype",
            "conv_state_dtype", "ssd_chunk", "weights"} <= \
        set(config["assumed"])
    assert any("slot" in g for g in config["guarantees"])
    assert 0 < config["check"]["logit_rms_tol"] <= 0.1
    assert 0 < config["check"]["state_head_rms_tol"] <= 0.05


def test_the_builder_builds_what_the_file_says(config):
    from benchmark.builders.granite_hybrid_serve import granite_config

    cfg = granite_config(config)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.hidden_size) == \
        (40, 100352, 2048)
    assert (cfg.head_dim, cfg.mamba_d_inner, cfg.mamba_conv_dim) == \
        (64, 4096, 4352)
    assert cfg.layer_types == tuple(config["layer_types"])
    assert cfg.attention_multiplier == 1 / 64 and cfg.initializer_range == 0.02


def test_the_cell_and_its_traffic(reg):
    cell = reg.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("granite-4.0-h-micro", "chat-bursty", 1)
    t = reg.traffic("chat-bursty")
    assert t["runner"] == "serve_open" and t["arrivals"]["cv"] == 2.0
    assert t["arrivals"]["rate_per_s"] == pytest.approx(
        0.8 * t["arrivals"]["knee_per_s"])
    assert t["engine"] == {"max_batch": 64, "page_tokens": 128,
                           "max_pages_per_seq": 20, "num_pages": 900,
                           "max_queue": 1024}
    chat = reg.traffic("chat")
    assert t["prompt_len"] == chat["prompt_len"]
    assert t["output_len"] == chat["output_len"]
    assert t["population_seed"] != chat["population_seed"]
    assert t["check"] == {"prompts": 3, "max_prompt": 1024, "new_tokens": 32}
    assert t["prefix_cache"] is False
    names = {m["name"] for m in reg.metrics_of(CELL, "per_layer")}
    assert {"kernel.ssm_update.busy_share.serve",
            "kernel.ssm_update_roofline.serve", "state.rows_per_step.serve",
            "state.slots_peak.serve", "dispatch.fallbacks.serve",
            "decode.step_ms.serve", "compile.cache_misses",
            # since PR 31 the four attention layers walk their pages
            "kernel.paged_decode.busy_share.serve",
            "sched.itl_p95.serve"} <= names
    assert {m["name"] for m in reg.metrics_of(CELL, "end_to_end")} == \
        {"ttft_mean_ms", "itl_tail_mean_ms", "setup_s"}


def test_state_bytes_from_the_published_keys(config):
    assert ssm_bytes.state_layers(config) == 36
    assert ssm_bytes.state_bytes_per_row_layer(config) == 64 * 64 * 128 * 4
    # 75.5 MB a row, read and written
    assert ssm_bytes.update_bytes_per_row(config) == 2 * 36 * 2097152


@pytest.fixture
def _leave_the_process_as_it_was(monkeypatch):
    """A rehearsal sets the ``pallas_interpret`` flag for its process; and
    an earlier file of this worker may have left a hybrid mesh live, under
    which a one-device engine's kernels would (rightly) be refused."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import topology

    monkeypatch.setattr(topology, "_hcg", None)
    before = paddle.get_flags("pallas_interpret")
    yield
    paddle.set_flags(before)


def test_rehearsal_of_the_cell(tmp_path, _leave_the_process_as_it_was):
    """The whole run at a tiny size on the CPU, kernels interpreted: the
    decode logits (prefill across pages, then decoding through state and
    pages) against the reference, every request complete, the state
    update's kernel and the attention layers' page walk in the program,
    neither refused."""
    import paddle_tpu.telemetry as telemetry

    before = dict(telemetry.counters())
    root = _root(tmp_path, [("t-hybrid-chat", "tiny-granite-hybrid",
                             "tiny-chat-bursty", 1)])
    r = run.execute("t-hybrid-chat", 2**31 + 11, 1.5, False, root=root,
                    rehearsal=True)
    assert r["rehearsal"] and r["metrics"] == {}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    check = r["facts"]["check"]
    assert check["ok"] and check["rows"] == 2 * 5
    assert check["short_of_best"] <= check["near_tie_limit"] <= 0.25
    assert check["ref_logits_rms"] > 0
    # the recurrent state itself
    assert 0 < check["state_rms_rel_err_first_layer"] <= 0.02
    assert 0 < check["state_rms_rel_err_last_layer"] <= 0.02
    assert 0 < check["state_head_rms_rel_err_worst"] <= 0.02
    meter = r["facts"]["meter"]
    assert 0 < meter["state_slots_peak"] <= 1.0
    assert r["facts"]["requests_completed"] == r["attempted"]
    after = telemetry.counters()
    grew = {k: after[k] - before.get(k, 0) for k in after
            if k.startswith("kernel_fallback.") and after[k] != before.get(k)}
    # the page walk takes the model's own score scale since PR 31: no
    # attention layer falls back to the gather, under any reason
    assert not any(k.startswith("kernel_fallback.paged_decode_attention.")
                   for k in grew), grew
    assert not any("ssm_state_update" in k for k in grew)


def test_verify_holds_the_recurrent_state_itself(
        _leave_the_process_as_it_was):
    """Logits that pass do not clear a run: the builder copies each check
    request's SSM state from its row and holds the first state layer's
    worst head to ``check.state_head_rms_tol``.  One head of one prompt's
    state off by a few percent (what too few bits give a slowly decaying
    head) is refused with every logits row as it was."""
    import numpy as np

    from benchmark.lib import device, program, serving

    fixtures = os.path.join(os.path.dirname(__file__), "fixtures")
    reg = registry.Registry()
    reg.dirs.insert(0, fixtures)
    with open(os.path.join(fixtures, "configs",
                           "tiny-granite-hybrid.json")) as f:
        config = json.load(f)
    traffic = reg.traffic("tiny-chat-bursty")
    _, devices, _ = device.probe(1, True)
    program.use_kernels(True)
    system = reg.module("builders", config["builder"]).build(
        config, traffic, 2**31 + 5, devices)
    client = serving.Client(system.engine)
    sample = serving.warm_up_sample(client, traffic, 2**31 + 5,
                                    system.vocab, client.eng.step)
    del client
    assert len(system.check_states) == traffic["check"]["prompts"]
    sound = system.verify(sample)
    assert sound["ok"], sound
    state = np.array(system.check_states[1], np.float32)
    state[0, 2] *= 1.05
    system.check_states[1] = state
    off = system.verify(sample)
    assert not off["ok"]
    assert off["state_head_rms_rel_err_worst"] == pytest.approx(0.05, rel=0.2)
    assert off["logits_rms_rel_err_median"] == \
        sound["logits_rms_rel_err_median"]


def _ctx(profile, spans, config, meter=None):
    return types.SimpleNamespace(
        trace=T.from_profile(profile), config=config,
        peaks={"hbm_bytes_per_s": 819e9}, facts={"meter": meter or {}})


def _kernel(i):
    return (f"%ssm_state_update.{i} = (f32[64,64,64,128]{{3,2,1,0}}) "
            f"custom-call(f32[64,64,64,128]{{3,2,1,0}} %p.{i})")


def test_readers_of_the_new_metrics(reg, config, monkeypatch):
    row_us = ssm_bytes.update_bytes_per_row(config) / 819e9 * 1e6
    assert row_us == pytest.approx(184.4, abs=0.1)
    # two decode steps in the window (3 and 5 live rows) and one before it;
    # the kernel's 36 calls a step are drawn as one event at half the peak
    steps = [(-3_000_000, 2), (1_000_000, 3), (4_000_000, 5)]
    ops, host = [], [("bench.window", 0, 10_000_000)]
    for i, (t0, rows) in enumerate(steps):
        dur = 2 * rows * row_us * 1e3                       # ns
        ops.append((_kernel(i), t0 + 100_000, dur))
        ops.append((f"%fusion.{i} = f32[8]{{0}} fusion()", t0 + 100_000 + dur,
                    dur))
        host.append(("serve.decode", t0, 3_000_000 if t0 > 0 else 800_000,
                     {"rows": rows, "state_rows": rows}))
    profile = _space({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": []},
                      "/host:CPU": {"main": host}})
    spans = PS.from_profile(profile)
    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)
    ctx = _ctx(profile, spans, config, {"state_slots_peak": 0.4375})

    def value(name, c=ctx):
        spec = reg.layer_metric(name)
        return reg.module("readers", spec["reader"]).read(c, **spec["args"])

    assert value("kernel.ssm_update_roofline.serve") == pytest.approx(50.0)
    assert value("kernel.ssm_update.busy_share.serve") == pytest.approx(50.0)
    assert value("state.rows_per_step.serve") == pytest.approx(4.0)
    assert value("state.slots_peak.serve") == pytest.approx(43.75)
    # a program without the kernel, the facts or the slots (the parent, a
    # Llama-shaped cell): nothing to read, and no reader raises
    bare = _space({"/device:TPU:0": {"XLA Ops": ops[1::2], "XLA Modules": []},
                   "/host:CPU": {"main": [
                       ("bench.window", 0, 10_000_000),
                       ("serve.decode", 1_000_000, 3_000_000, {"rows": 3})]}})
    monkeypatch.setattr(PS, "of_run",
                        lambda root=None: PS.from_profile(bare))
    mistral = reg.config("mistral-7b-v0.3")
    for cfg in (config, mistral):
        c = _ctx(bare, None, cfg, {"kv_pool_occupancy_peak": 0.1})
        for name in ("kernel.ssm_update_roofline.serve",
                     "kernel.ssm_update.busy_share.serve",
                     "state.rows_per_step.serve", "state.slots_peak.serve"):
            assert value(name, c) is None
    monkeypatch.setattr(PS, "of_run", lambda root=None: [])
    assert value("kernel.ssm_update_roofline.serve") is None
    assert value("state.rows_per_step.serve") is None


def test_the_metric_files_agree_with_the_benchmark(reg):
    for name in ("kernel.ssm_update.busy_share.serve",
                 "kernel.ssm_update_roofline.serve",
                 "state.rows_per_step.serve", "state.slots_peak.serve"):
        entry = reg._entry("per_layer", name)
        assert entry["workloads"] == [CELL]
        spec = reg.layer_metric(name)
        assert {k: spec[k] for k in entry if k != "workloads"} == \
            {k: v for k, v in entry.items() if k != "workloads"}
    with open(os.path.join(registry.ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    # by membership: a later PR appends its cell and configuration
    assert CELL in [w["name"] for w in bm["workloads"]]
    assert "granite-4.0-h-micro" in [c["name"] for c in bm["configs"]]
