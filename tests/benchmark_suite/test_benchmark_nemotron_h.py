"""The cell ``serve-nemotron3nano-ep8-reasoning``: its configuration file
against the published keys and the cut's arithmetic, the functions that
count its kernels' bytes and operations against hand counts, a rehearsal of
the cell from fixture files at a tiny size on the CPU whose verdict does not
depend on the machine's speed, and the readers of its per-layer metrics on a
synthetic profile."""

import types

import pytest

from benchmark import run
from benchmark.lib import nemotron_h_cost as cost
from benchmark.lib import program_spans as PS
from benchmark.lib import registry
from benchmark.lib import trace as T
from tests.benchmark_suite.test_benchmark_program_spans import _space
from tests.benchmark_suite.test_benchmark_rehearsal import _root

CELL = "serve-nemotron3nano-ep8-reasoning"
CONFIG = "nemotron-3-nano-30b-a3b"
# the catalog row's ``config`` (model-configs guide, architectures.jsonl):
# every number and flag of the published config.json
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"n_routed_experts": 16, "vocab_size": 16384}
NEW_METRICS = ("kernel.ssm_update_roofline.nemotronh.serve",
               "kernel.moe_experts_roofline.nemotronh.serve",
               "moe.pairs_per_expert.nemotronh.serve",
               "moe.imbalance.nemotronh.serve")
# four accepted metrics whose readers would serve this cell as they are, but
# whose ``workloads`` two accepted test files pin to ONE cell
# (test_benchmark_granite_hybrid.py, test_benchmark_deepseek_v3.py: files a
# model PR may not edit): this cell reads them under names of its own
TWINS = {"kernel.ssm_update.busy_share.nemotronh.serve":
         "kernel.ssm_update.busy_share.serve",
         "state.rows_per_step.nemotronh.serve": "state.rows_per_step.serve",
         "state.slots_peak.nemotronh.serve": "state.slots_peak.serve",
         "kernel.moe_experts.busy_share.nemotronh.serve":
         "kernel.moe_experts.busy_share.serve"}
# the accepted metrics the cell is appended to
SHARED_METRICS = ("dispatch.fallbacks.serve", "decode.step_ms.serve",
                  "prefill.ms_per_ktok.serve", "kv.occupancy_peak.serve",
                  "device.idle_share.serve",
                  "kernel.paged_decode.busy_share.serve")
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}


@pytest.fixture(scope="module")
def reg():
    return registry.Registry()


@pytest.fixture(scope="module")
def config(reg):
    return reg.config(CONFIG)


def test_the_configuration_keeps_every_published_key(reg, config):
    for key, value in PUBLISHED.items():
        assert config[key] == REDUCED.get(key, value), key
    entry = reg._entry("configs", CONFIG)
    assert entry["source"] == config["source"]
    assert sorted(entry["reduced"]) == sorted(REDUCED) == \
        sorted(config["reduced"])
    assert config["published"] == {k: PUBLISHED[k] for k in REDUCED}
    # the depth is NOT cut, and no width is among the cut keys
    assert config["num_hidden_layers"] == 52 == len(
        config["hybrid_override_pattern"])
    assert not any(k.endswith(("_dim", "_rank", "_size")) and
                   k != "vocab_size" for k in REDUCED)
    # the router keeps its published width; the chip holds an eighth
    assert config["router_experts"] == PUBLISHED["n_routed_experts"]
    assert config["experts_held"] == [0, 128 // 8]
    assert config["token_id_limit"] == config["vocab_size"] == 131072 // 8
    assert "v5e-8" in config["deployment"]
    assert "without its exchange" in config["deployment"]
    for key in ("initializer_range", "mamba_init", "rescale_prenorm_residual",
                "ssm_state_dtype", "conv_state_dtype", "position_encoding",
                "expand", "ssd_chunk", "router_bias_range"):
        assert key in config["assumed"], key
    assert len(config["guarantees"]) >= 4
    assert set(config["check"]) == {"logit_rms_tol", "state_head_rms_tol",
                                    "route_flip_share", "route_tie", "why"}


def test_the_cut_arithmetic(config):
    """The parameters of the whole model and of this chip's share, from the
    file's keys: what the ``reduced`` entries state."""
    h = config["hidden_size"]
    pattern = config["hybrid_override_pattern"]
    n_m, n_e, n_a = (pattern.count(c) for c in "ME*")
    assert (n_m, n_e, n_a) == (23, 23, 6)
    d_inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    conv_dim = d_inner + 2 * config["n_groups"] * config["ssm_state_size"]
    assert (d_inner, conv_dim) == (4096, 6144)
    heads = config["mamba_num_heads"]
    mamba = h * (d_inner + conv_dim + heads) + conv_dim * (
        config["conv_kernel"] + 1) + 3 * heads + d_inner + d_inner * h + h
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    attn = h * q + 2 * h * kv + q * h + h
    expert = 2 * h * config["moe_intermediate_size"]
    shared = 2 * h * config["moe_shared_expert_intermediate_size"]
    router = h * config["router_experts"] + config["router_experts"]
    assert round(mamba / 1e6, 2) == 38.74 and round(attn / 1e6, 2) == 23.40
    assert round(expert / 1e6, 3) == 9.978 and round(shared / 1e6, 2) == 19.96

    def total(experts, vocab):
        return n_m * mamba + n_a * attn \
            + n_e * (experts * expert + shared + router + h) \
            + 2 * vocab * h + h

    assert round(total(128, 131072) / 1e9, 2) == 31.58      # published 31.6 B
    held = total(config["n_routed_experts"], config["vocab_size"])
    assert round(held / 1e9, 3) == 5.258 and round(2 * held / 1e9, 2) == 10.52
    # a decode row's state and a token's K/V
    row = n_m * (heads * config["mamba_head_dim"] * config["ssm_state_size"]
                 * 4 + (config["conv_kernel"] - 1) * conv_dim * 2)
    assert round(row / 1e6, 1) == 49.1
    token = n_a * 2 * kv * 2
    assert token == 6144
    # the guide's floors: a whole pattern, 8 experts, 1/8 of the vocabulary
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= PUBLISHED["vocab_size"]


def test_the_builder_builds_what_the_file_says(reg, config):
    cfg = reg.module("builders", config["builder"]).nemotron_config(config)
    assert cfg.n_routed_experts == 128 and cfg.experts_held == (0, 16)
    assert (cfg.num_hidden_layers, cfg.vocab_size) == (52, 16384)
    assert cfg.initializer_range == 0.02 and cfg.router_bias_range == 0.02
    dims = cfg.mamba_dims
    assert (dims.n_groups, dims.norm_groups, dims.chunk) == (8, 8, 128)


def test_the_cell_and_its_traffic(reg):
    cell = reg.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "reasoning-hybrid", 1)
    assert len(cell["why"]) <= 200
    traffic = reg.traffic("reasoning-hybrid")
    assert traffic["runner"] == "serve_open" and not traffic["prefix_cache"]
    assert traffic["population_seed"] == 34001
    assert traffic["engine"] == {"max_batch": 64, "page_tokens": 128,
                                 "max_pages_per_seq": 40, "num_pages": 1500,
                                 "max_queue": 1024}
    arrivals = traffic["arrivals"]
    assert arrivals["cv"] == 1.0
    assert arrivals["rate_per_s"] == pytest.approx(
        0.8 * arrivals["knee_per_s"])
    assert len(arrivals["sweep"]) >= 4
    # ``reasoning``'s lengths to the letter
    other = reg.traffic("reasoning")
    for key in ("prompt_len", "output_len", "check", "ramp_s", "drain_s",
                "trace_s"):
        assert traffic[key] == other[key], key
    assert 4096 + 1024 == 40 * 128
    reports = {m["name"] for k in ("end_to_end", "per_layer")
               for m in reg.metrics_of(CELL, k)}
    assert {"ttft_mean_ms", "itl_tail_mean_ms", "setup_s",
            "sched.itl_p95.serve", "compile.cache_misses",
            *SHARED_METRICS, *NEW_METRICS, *TWINS} <= reports
    assert sum(n.startswith("sched.idle_") for n in reports) == 7
    # the readers that count by another family's keys are left off
    assert not {"kernel.ssm_update_roofline.serve",
                "kernel.moe_experts_roofline.serve",
                "moe.pairs_per_expert.serve", "moe.imbalance.serve",
                *TWINS.values()} & reports


def test_cost_functions_against_hand_counts(config):
    assert (cost.blocks(config, "M"), cost.blocks(config, "E"),
            cost.blocks(config, "*")) == (23, 23, 6)
    assert cost.state_bytes_per_row_layer(config) == 64 * 64 * 128 * 4
    assert cost.update_bytes_per_row(config) == 2 * 23 * 64 * 64 * 128 * 4 \
        == 96_468_992
    assert cost.expert_bytes(config) == 2 * 2688 * 1856 * 2 == 19_955_712
    assert cost.flops_per_pair(config) == 4 * 2688 * 1856
    assert cost.held_experts(config) == 16
    # one pair an expert: the weights bound it; the ridge is at 240 pairs
    few = cost.experts_least_seconds(config, PEAKS, 100, 100)
    assert few["bound"] == "hbm"
    assert few["seconds"] == pytest.approx(100 * 19_955_712 / 819e9)
    ridge = cost.expert_bytes(config) / 819e9 \
        / (cost.flops_per_pair(config) / 197e12)
    assert 235 < ridge < 245
    many = cost.experts_least_seconds(config, PEAKS, 100, 100 * 300)
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
    decode logits (prefill across pages, then decoding through state, pages
    and held experts) against the reference, the first state block's state,
    the choices of experts, every request complete, all three kernels in
    the programs and no fallback counted.  The fixture offers 2 requests a
    second over a 1.5 s window and lets them drain for a quarter of an
    hour: no verdict here depends on how fast the machine is."""
    import paddle_tpu.telemetry as telemetry

    before = dict(telemetry.counters())
    root = _root(tmp_path, [("t-nemotron", "tiny-nemotron-h",
                             "tiny-reasoning-hybrid", 1)])
    r = run.execute("t-nemotron", 2**31 + 13, 1.0, False, root=root,
                    rehearsal=True)
    assert r["rehearsal"] and r["metrics"] == {}
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    check = r["facts"]["check"]
    assert check["ok"] and check["rows"] == 2 * 3
    assert set(check["limits"]) == {
        "logits_rms_rel_err_median", "logits_rms_rel_err_worst",
        "short_of_best", "state_head_rms_rel_err_mean",
        "route_flip_share", "route_tie_width_worst"}
    # the reference followed the programs' experts at every token; at the
    # compared rows (a prompt's last token too: 2 prompts, 2 expert blocks)
    assert check["route_pairs"] == (check["rows"] + 2) * 2
    assert check["route_flips"] <= 0.3 * check["route_pairs"]
    assert check["state_head_rms_rel_err_mean"] <= \
        check["state_head_rms_rel_err_worst"] <= 0.02
    assert r["facts"]["requests_completed"] == r["attempted"]
    assert r["facts"]["meter"]["state_slots_peak"] > 0
    after = telemetry.counters()
    assert not {k for k in after if k.startswith("kernel_fallback.")
                and after[k] != before.get(k)}


def _ctx(profile, config):
    return types.SimpleNamespace(trace=T.from_profile(profile),
                                 config=config, peaks=PEAKS, facts={})


def _call(name, i):
    return f"%{name}.{i} = f32[64,64,64,128]{{3,2,1,0}} custom-call(%p.{i})"


@pytest.mark.parametrize("slack", [1.0, 2.0], ids=["least-time", "twice"])
def test_readers_of_the_new_metrics(reg, config, monkeypatch, slack):
    """Two decode steps and a prefill in the window, a decode step before
    it; each kernel's calls of a launch drawn as one event that takes
    ``slack`` times its least time.  At the least time itself a roofline
    share reads 100 % and no more."""
    def ssm_ns(rows):
        return rows * cost.update_bytes_per_row(config) / 819e9 * 1e9

    def moe_ns(hit, pairs):
        return cost.experts_least_seconds(config, PEAKS, hit, pairs)[
            "seconds"] * 1e9

    launches = [  # (span, start, facts)
        ("serve.decode", -40_000_000,
         dict(state_rows=9, moe_pairs=1242, moe_experts_hit=300,
              moe_max_load=9)),
        ("serve.decode", 1_000_000,
         dict(state_rows=20, moe_pairs=2944, moe_experts_hit=350,
              moe_max_load=16)),
        ("serve.prefill", 30_000_000,
         dict(moe_pairs=70656, moe_experts_hit=368, moe_max_load=400)),
        ("serve.decode", 60_000_000,
         dict(state_rows=10, moe_pairs=1472, moe_experts_hit=320,
              moe_max_load=12)),
    ]
    ops, host = [], [("bench.window", 0, 90_000_000)]
    for i, (name, t0, facts) in enumerate(launches):
        t = t0 + 100_000
        if name == "serve.decode":
            dur = slack * ssm_ns(facts["state_rows"])
            ops.append((_call("ssm_state_update", i), t, dur))
            t += dur
        dur = slack * moe_ns(facts["moe_experts_hit"], facts["moe_pairs"])
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

    share = 100.0 / slack
    assert value("kernel.ssm_update_roofline.nemotronh.serve") == \
        pytest.approx(share)
    assert value("kernel.moe_experts_roofline.nemotronh.serve") == \
        pytest.approx(share)
    # even the prefill's 192 pairs an expert hit stay under the ridge of
    # 240: the weights bound every launch of the window
    assert cost.experts_least_seconds(config, PEAKS, 368, 70656)["bound"] \
        == "hbm"
    # decode steps only: (2944 + 1472) / 2 pairs over 16 x 23 held experts
    assert value("moe.pairs_per_expert.nemotronh.serve") == \
        pytest.approx(6.0)
    assert value("moe.imbalance.nemotronh.serve") == \
        pytest.approx((16 / 8 + 12 / 4) / 2)
    # the accepted readers, under this cell's names, read the same trace
    assert 0 < value("kernel.ssm_update.busy_share.nemotronh.serve") < 100
    assert 0 < value("kernel.moe_experts.busy_share.nemotronh.serve") < 100
    assert value("state.rows_per_step.nemotronh.serve") == \
        pytest.approx(15.0)
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
    for cfg in (config, reg.config("granite-4.0-h-micro"),
                reg.config("deepseek-v3")):
        for name in NEW_METRICS:
            assert value(name, _ctx(bare, cfg)) is None
    # another family's configuration over a trace that HAS the kernels
    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)
    for name in NEW_METRICS:
        assert value(name, _ctx(profile, reg.config("deepseek-v3"))) is None


def test_the_metric_files_agree_with_the_benchmark(reg):
    for name in (*NEW_METRICS, *TWINS):
        entry = reg._entry("per_layer", name)
        assert entry["workloads"] == [CELL]
        spec = reg.layer_metric(name)
        assert {k: spec[k] for k in entry if k != "workloads"} == \
            {k: v for k, v in entry.items() if k != "workloads"}
        if "roofline" in name:
            assert entry["unit"] == "%" and entry["better"] == "higher"
    # a twin is the accepted metric under another name: the same reader,
    # arguments, unit and layer
    for twin, accepted in TWINS.items():
        a, b = reg.layer_metric(twin), reg.layer_metric(accepted)
        assert {k: v for k, v in a.items() if k != "name"} == \
            {k: v for k, v in b.items() if k != "name"}
    for name in ("ttft_mean_ms", "itl_tail_mean_ms"):
        assert reg._entry("end_to_end", name)["workloads"][-1] == CELL
    for name in SHARED_METRICS:
        assert reg._entry("per_layer", name)["workloads"][-1] == CELL
    bm = reg.benchmark
    assert bm["workloads"][-1]["name"] == CELL
    assert bm["configs"][-1]["name"] == CONFIG
    assert {m["name"] for m in bm["per_layer"][-8:]} == \
        {*NEW_METRICS, *TWINS}
