"""The cell ``serve-deepseekv3-ep16-reasoning``: its configuration file
against the published keys and the cut's arithmetic, the functions that
count its kernels' bytes and operations, a rehearsal of the cell from
fixture files at a tiny size on the CPU, and the readers of its per-layer
metrics on a synthetic profile."""

import types

import pytest

from benchmark import run
from benchmark.lib import mla_cost, moe_cost
from benchmark.lib import program_spans as PS
from benchmark.lib import registry
from benchmark.lib import trace as T
from tests.benchmark_suite.test_benchmark_program_spans import _space
from tests.benchmark_suite.test_benchmark_rehearsal import _root

CELL = "serve-deepseekv3-ep16-reasoning"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl):
# every number and flag of the published config.json that says something
# about the model's shape
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v3", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}
REDUCED = {"num_hidden_layers": 6, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 16160,
           "num_nextn_predict_layers": 0}
NEW_METRICS = ("kernel.mla_decode.busy_share.serve",
               "kernel.mla_decode_roofline.serve",
               "kernel.moe_experts.busy_share.serve",
               "kernel.moe_experts_roofline.serve",
               "moe.pairs_per_expert.serve", "moe.imbalance.serve")
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}


@pytest.fixture(scope="module")
def reg():
    return registry.Registry()


@pytest.fixture(scope="module")
def config(reg):
    return reg.config("deepseek-v3")


def test_the_configuration_keeps_every_published_width(reg, config):
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    entry = reg._entry("configs", "deepseek-v3")
    assert sorted(entry["reduced"]) == sorted(REDUCED) == \
        sorted(config["reduced"])
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # the cut is of depth, experts held, vocabulary and the MTP block: no
    # width is among the keys
    assert not any(k.endswith(("_dim", "_rank", "_size")) and
                   k != "vocab_size" for k in REDUCED)
    # the router keeps its published width; the chip holds a sixteenth
    assert config["router_experts"] == PUBLISHED["n_routed_experts"]
    assert config["experts_held"] == [0, 16]
    assert config["token_id_limit"] == config["vocab_size"] == 129280 // 8
    assert "16 v5e chips" in config["deployment"]
    for key in ("initializer_range", "router_bias_range", "rope_pair_layout",
                "latent_row", "router_dtype"):
        assert key in config["assumed"], key


def test_the_cut_arithmetic(config):
    """The parameters this chip holds, from the file's keys: what the
    ``reduced`` entries state."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    ql, kvl = config["q_lora_rank"], config["kv_lora_rank"]
    mla = h * ql + ql * heads * (nope + rope) + h * (kvl + rope) \
        + kvl * heads * (nope + v) + heads * v * h
    expert = 3 * h * config["moe_intermediate_size"]
    dense = mla + 3 * h * config["intermediate_size"]
    router = h * config["router_experts"]
    outside = mla + router + config["n_shared_experts"] * expert
    assert round(mla / 1e6, 1) == 187.1 and round(expert / 1e6, 2) == 44.04
    assert round(dense / 1e6, 1) == 583.5
    assert round(outside / 1e6, 1) == 233.0
    layer = outside + config["n_routed_experts"] * expert
    assert round(layer / 1e6, 1) == 937.6
    vocab = 2 * config["vocab_size"] * h
    dense_layers = config["first_k_dense_replace"]
    total = dense_layers * dense \
        + (config["num_hidden_layers"] - dense_layers) * layer + vocab
    assert round(total / 1e9, 2) == 5.50 and round(2 * total / 1e9, 2) == 11.01
    # the guide's floors: a period and four more layers, 8 experts, 1/8 vocab
    assert config["num_hidden_layers"] - dense_layers >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_builder_builds_what_the_file_says(reg, config):
    cfg = reg.module("builders", config["builder"]).deepseek_config(config)
    assert cfg.n_routed_experts == 256 and cfg.experts_held == (0, 16)
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.vocab_size) == (6, 1, 16160)
    assert cfg.initializer_range == 0.02 and cfg.router_bias_range == 0.02
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.3689 ** 2,
                                              rel=1e-4)


def test_the_cell_and_its_traffic(reg):
    cell = reg.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("deepseek-v3", "reasoning", 1)
    traffic = reg.traffic("reasoning")
    assert traffic["runner"] == "serve_open" and not traffic["prefix_cache"]
    assert traffic["engine"] == {"max_batch": 128, "page_tokens": 128,
                                 "max_pages_per_seq": 40, "num_pages": 3000,
                                 "max_queue": 1024}
    arrivals = traffic["arrivals"]
    assert arrivals["cv"] == 1.0
    assert arrivals["rate_per_s"] == pytest.approx(
        0.8 * arrivals["knee_per_s"])
    assert len(arrivals["sweep"]) >= 4
    assert traffic["prompt_len"] == {"dist": "lognormal", "median": 512,
                                     "sigma": 1.0, "min": 64, "max": 4096}
    assert traffic["output_len"] == {"dist": "lognormal", "median": 384,
                                     "sigma": 0.7, "min": 64, "max": 1024}
    # the longest request fits a row's table exactly
    assert 4096 + 1024 == 40 * 128
    assert traffic["check"] == {"prompts": 3, "max_prompt": 1024,
                                "new_tokens": 32}
    reports = {m["name"] for k in ("end_to_end", "per_layer")
               for m in reg.metrics_of(CELL, k)}
    assert {"ttft_mean_ms", "itl_tail_mean_ms", "setup_s",
            "sched.itl_p95.serve", "decode.step_ms.serve",
            "dispatch.fallbacks.serve", *NEW_METRICS} <= reports
    # the engine's spans are this cell's too: its idle time is split
    assert sum(n.startswith("sched.idle_") for n in reports) == 7


def test_cost_functions_from_the_published_keys(config):
    assert mla_cost.row_bytes(config) == 576 * 2
    assert mla_cost.flops_per_query_cached_token(config) == \
        2 * 128 * (576 + 512)
    # 1152 B against 278 528 operations a cached token: the MXU bounds it
    least = mla_cost.least_seconds(config, PEAKS, 1_000_000)
    assert least["bound"] == "mxu"
    assert least["seconds"] == pytest.approx(278528e6 / 197e12)
    assert moe_cost.expert_bytes(config) == 88_080_384
    assert moe_cost.flops_per_pair(config) == 6 * 7168 * 2048
    assert (moe_cost.expert_layers(config),
            moe_cost.held_experts(config)) == (5, 16)
    # 4 pairs an expert: the weights bound it; the ridge is at 240
    few = moe_cost.least_seconds(config, PEAKS, 80, 320)
    assert few["bound"] == "hbm"
    assert few["seconds"] == pytest.approx(80 * 88_080_384 / 819e9)
    many = moe_cost.least_seconds(config, PEAKS, 80, 80 * 300)
    assert many["bound"] == "mxu"


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


def test_runner_rehearsal(tmp_path, _leave_the_process_as_it_was):
    """The whole run at a tiny size on the CPU, kernels interpreted: the
    decode logits (prefill across pages, then decoding through the latent
    pages) against the reference, every request complete, both kernels in
    the programs and no fallback counted."""
    import paddle_tpu.telemetry as telemetry

    before = dict(telemetry.counters())
    root = _root(tmp_path, [("t-deepseek", "tiny-deepseek-v3",
                             "tiny-reasoning", 1)])
    r = run.execute("t-deepseek", 2**31 + 13, 1.5, False, root=root,
                    rehearsal=True)
    assert r["rehearsal"] and r["metrics"] == {}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    check = r["facts"]["check"]
    assert check["ok"] and check["rows"] == 2 * 3
    # the reference followed the programs' experts at every token, and at
    # the compared rows (a prompt's last token too: 2 prompts, 2 expert
    # layers) each choice lies within the limit of the reference's own
    assert check["route_pairs"] == (check["rows"] + 2) * 2
    assert check["route_flips"] <= 0.3 * check["route_pairs"]
    assert check["route_tie_width_worst"] <= 0.06
    assert r["facts"]["requests_completed"] == r["attempted"]
    after = telemetry.counters()
    assert not {k for k in after if k.startswith("kernel_fallback.")
                and after[k] != before.get(k)}


def _ctx(profile, config):
    return types.SimpleNamespace(trace=T.from_profile(profile),
                                 config=config, peaks=PEAKS, facts={})


def _call(name, i):
    return f"%{name}.{i} = bf16[128,128,512]{{2,1,0}} custom-call(%p.{i})"


def test_readers_of_the_new_metrics(reg, config, monkeypatch):
    """Two decode steps and a prefill in the window, a decode step before
    it; each kernel's calls of a launch drawn as one event that takes twice
    its least time."""
    def mla_ns(tokens):
        return mla_cost.least_seconds(config, PEAKS, tokens)["seconds"] * 1e9

    def moe_ns(hit, pairs):
        return moe_cost.least_seconds(config, PEAKS, hit, pairs)[
            "seconds"] * 1e9

    launches = [  # (span, start, facts)
        ("serve.decode", -40_000_000,
         dict(latent_tokens=90000, moe_pairs=160, moe_experts_hit=60,
              moe_max_load=5)),
        ("serve.decode", 1_000_000,
         dict(latent_tokens=300000, moe_pairs=320, moe_experts_hit=80,
              moe_max_load=8)),
        ("serve.prefill", 30_000_000,
         dict(latent_pages=960, latent_tokens=0, moe_pairs=1280,
              moe_experts_hit=80, moe_max_load=40)),
        ("serve.decode", 60_000_000,
         dict(latent_tokens=500000, moe_pairs=160, moe_experts_hit=70,
              moe_max_load=6)),
    ]
    ops, host = [], [("bench.window", 0, 90_000_000)]
    for i, (name, t0, facts) in enumerate(launches):
        t = t0 + 100_000
        if name == "serve.decode":
            dur = 2 * mla_ns(facts["latent_tokens"])
            ops.append((_call("mla_paged_decode_attention", i), t, dur))
            t += dur
        dur = 2 * moe_ns(facts["moe_experts_hit"], facts["moe_pairs"])
        ops.append((_call("moe_grouped_matmul", i), t, dur))
        ops.append((f"%fusion.{i} = f32[8]{{0}} fusion()", t + dur, 1000))
        host.append((name, t0, 25_000_000 if t0 > 0 else 5_000_000, facts))
    profile = _space({"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": []},
                      "/host:CPU": {"main": host}})
    spans = PS.from_profile(profile)
    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)
    ctx = _ctx(profile, config)

    def value(name, c=ctx):
        spec = reg.layer_metric(name)
        return reg.module("readers", spec["reader"]).read(c, **spec["args"])

    assert value("kernel.mla_decode_roofline.serve") == pytest.approx(50.0)
    assert value("kernel.moe_experts_roofline.serve") == pytest.approx(50.0)
    busy = T.busy_s(ctx.trace)
    mla_s = 2 * (mla_ns(300000) + mla_ns(500000)) / 1e9
    assert value("kernel.mla_decode.busy_share.serve") == \
        pytest.approx(100 * mla_s / busy)
    assert 0 < value("kernel.moe_experts.busy_share.serve") < 100
    # decode steps only: (320 + 160) / 2 pairs over 16 x 5 held experts
    assert value("moe.pairs_per_expert.serve") == pytest.approx(3.0)
    assert value("moe.imbalance.serve") == pytest.approx((8 / 4 + 6 / 2) / 2)
    # a program without the kernels or the facts (the parent, another
    # configuration's cell): nothing to read, and no reader raises
    bare = _space({"/device:TPU:0": {"XLA Ops": [o for o in ops
                                                  if "fusion" in o[0]],
                                     "XLA Modules": []},
                   "/host:CPU": {"main": [
                       ("bench.window", 0, 90_000_000),
                       ("serve.decode", 1_000_000, 3_000_000, {"rows": 3})]}})
    monkeypatch.setattr(PS, "of_run",
                        lambda root=None: PS.from_profile(bare))
    for cfg in (config, reg.config("mistral-7b-v0.3")):
        for name in NEW_METRICS:
            assert value(name, _ctx(bare, cfg)) is None
    monkeypatch.setattr(PS, "of_run", lambda root=None: [])
    for name in NEW_METRICS:
        assert value(name, _ctx(bare, config)) is None


def test_the_metric_files_agree_with_the_benchmark(reg):
    names = {"serve-granite4hmicro-chat-bursty": (
        "kernel.ssm_update.busy_share.serve",
        "kernel.ssm_update_roofline.serve", "state.rows_per_step.serve",
        "state.slots_peak.serve"), CELL: NEW_METRICS}
    for cell, metrics in names.items():
        for name in metrics:
            entry = reg._entry("per_layer", name)
            assert entry["workloads"] == [cell]
            spec = reg.layer_metric(name)
            assert {k: spec[k] for k in entry if k != "workloads"} == \
                {k: v for k, v in entry.items() if k != "workloads"}
    bm = reg.benchmark
    assert CELL in [w["name"] for w in bm["workloads"]]
    assert "deepseek-v3" in [c["name"] for c in bm["configs"]]
    # every roofline share of the benchmark keeps its unit and its name
    for m in bm["per_layer"]:
        if "roofline" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
