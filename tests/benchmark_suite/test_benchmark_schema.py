"""``BENCHMARK.json`` against the contract it is checked by, and against the
files it names."""

import importlib
import json
import os
import re

import pytest

from benchmark.lib import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def reg():
    return registry.Registry()


@pytest.fixture(scope="module")
def bm(reg):
    return reg.benchmark


def test_top_level_keys_and_command(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bm["command"]) <= 32
    assert not any(w.startswith("/") or ".." in w for w in bm["command"])
    assert bm["paths"] == ["benchmark", "tests/benchmark_suite"]
    rs = bm["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells has to fit the driver's 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert os.path.getsize(os.path.join(reg_root(), "BENCHMARK.json")) < 65536


def reg_root():
    return registry.ROOT


def test_every_name_and_unit_is_well_formed(bm):
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        group = [e["name"] for e in bm[key]]
        assert len(group) == len(set(group)), f"duplicate name in {key}"
        names += group
    for w in bm["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in bm["configs"]:
        names += c["reduced"]
    assert all(NAME.match(n) for n in names), \
        [n for n in names if not NAME.match(n)]
    metrics = bm["end_to_end"] + bm["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for e in bm["configs"] + bm["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"], e["name"]


def test_entries_have_just_the_keys_of_the_contract(bm):
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200


def test_cells_configs_and_four_chip_share(bm):
    cells = bm["workloads"]
    assert 1 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in bm["configs"]}, "a config no cell uses"
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(len(cells) // 4, 1)
    files = [c["file"] for c in bm["configs"]]
    assert len(files) == len(set(files))
    banned = ("llama", "gemma", "gpt-oss", "qwen3.5")
    assert not any(b in c["source"].lower() for c in bm["configs"]
                   for b in banned)


def test_every_cell_reports_what_the_contract_asks(reg, bm):
    e2e_names = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e_names
    cell_names = {w["name"] for w in bm["workloads"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert set(m.get("workloads", cell_names)) <= cell_names, m["name"]
    for w in bm["workloads"]:
        e2e = {m["name"] for m in reg.metrics_of(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = reg.metrics_of(w["name"], "per_layer")
        assert layer
        for m in layer:     # what a layer metric moves is reported here
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_named_file_exists_and_agrees(reg, bm):
    for c in bm["configs"]:
        assert c["file"].startswith("benchmark/configs/")
        cfg = reg.config(c["name"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert key in cfg["published"] and cfg[key] != cfg["published"][key]
            assert not re.search(r"(_size$|_dim$|_rank$|^n_inner$|^n_embd$|"
                                 r"per_tok)", key) or key == "vocab_size"
        assert {"builder", "reference", "assumed", "deployment",
                "guarantees"} <= set(cfg)
        assert hasattr(reg.module("builders", cfg["builder"]), "build")
        importlib.import_module("benchmark.reference." + cfg["reference"])
    for w in bm["workloads"]:
        traffic = reg.traffic(w["traffic"])
        assert hasattr(reg.module("runners", traffic["runner"]), "run")
    for m in bm["per_layer"]:
        spec = reg.layer_metric(m["name"])
        # the file holds what never changes; which cells report the metric
        # is BENCHMARK.json's alone, so a new cell edits no file
        assert "workloads" not in spec
        assert {k: spec[k] for k in m if k != "workloads"} == \
            {k: v for k, v in m.items() if k != "workloads"}, m["name"]
        assert hasattr(reg.module("readers", spec["reader"]), "read")


def test_metrics_of_one_layer_name_it_alike(bm):
    layers = {m["layer"] for m in bm["per_layer"]}
    with open(os.path.join(registry.ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_no_published_width_is_changed(reg):
    mistral = {"hidden_size": 4096, "intermediate_size": 14336,
               "num_attention_heads": 32, "num_key_value_heads": 8,
               "head_dim": 128, "vocab_size": 32768, "rope_theta": 1e6,
               "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
               "tie_word_embeddings": False, "sliding_window": None}
    cfg = json.load(open(os.path.join(
        registry.ROOT, "benchmark", "configs", "mistral-7b-v0.3.json")))
    assert {k: cfg[k] for k in mistral} == mistral
    assert cfg["published"] == {"num_hidden_layers": 32}
    gpt = json.load(open(os.path.join(
        registry.ROOT, "benchmark", "configs", "cerebras-gpt-1.3b.json")))
    assert {k: gpt[k] for k in ("n_embd", "n_head", "n_inner", "n_positions",
                                "layer_norm_epsilon")} == \
        {"n_embd": 2048, "n_head": 16, "n_inner": 8192, "n_positions": 2048,
         "layer_norm_epsilon": 1e-5}
    assert gpt["published"] == {"n_layer": 24, "vocab_size": 50257}
    assert gpt["token_id_limit"] == 50257 and gpt["vocab_size"] % 128 == 0


def test_the_gap_tail_that_is_judged_and_the_one_beside_it(reg, bm):
    """Since PR 33 the serving cells are judged on ``itl_tail_mean_ms``;
    ``itl_p95_ms``, which every earlier line of the ledger carries, is read
    in every traced run by ``sched.itl_p95.serve``."""
    serving = [w["name"] for w in bm["workloads"]
               if w["name"].startswith("serve-")]
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "itl_p95_ms" not in e2e
    assert e2e["itl_tail_mean_ms"]["workloads"] == serving
    assert (e2e["itl_tail_mean_ms"]["unit"], e2e["itl_tail_mean_ms"]["better"],
            e2e["itl_tail_mean_ms"]["source"]) == ("ms", "lower", "host_clock")
    assert reg._entry("per_layer", "sched.itl_p95.serve")["workloads"] == \
        serving
    spec = reg.layer_metric("sched.itl_p95.serve")
    assert (spec["reader"], spec["args"]) == ("measured",
                                              {"name": "itl_p95_ms"})
    import types
    ctx = types.SimpleNamespace(end_to_end={"itl_p95_ms": 24.5,
                                            "itl_tail_mean_ms": 27.0})
    assert reg.module("readers", "measured").read(ctx, **spec["args"]) == 24.5
    assert not any(m["moves"] == "itl_p95_ms" for m in bm["per_layer"])


@pytest.mark.parametrize("counters, refusals", [
    ({}, 0.0),
    # the program counts a refusal under its own name and under the total
    ({"kernel_fallback.paged_decode_attention.scale": 4,
      "kernel_fallback.total": 4}, 4.0),
    ({"kernel_fallback.paged_decode_attention.head_dim": 4,
      "kernel_fallback.ssm_state_update.rows": 1,
      "kernel_fallback.total": 5}, 5.0),
])
def test_a_refusal_is_counted_once(reg, counters, refusals):
    import types
    ctx = types.SimpleNamespace(fallbacks=counters)
    assert reg.module("readers", "fallbacks").read(ctx) == refusals
