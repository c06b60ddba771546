"""The spans and program names of ``ServingEngine`` for a model with state
layers: ``serve.decode`` carries ``state_rows``, and the two compiled
programs keep the names the benchmark's patterns find."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GraniteHybridForCausalLM, LlamaForCausalLM,
                               granite_hybrid_tiny, llama_tiny)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as serving_engine

PROMPTS = {0: 5, 1: 9, 2: 14}


def _record(model, tmp_path_factory, **knobs):
    from jax.profiler import ProfileData

    from benchmark.lib import program_spans
    from benchmark.lib import trace as bench_trace

    eng = ServingEngine(model, max_batch=2, page_tokens=4, num_pages=32,
                        max_pages_per_seq=8, **knobs)
    compiled = []
    compile_ = eng._compile
    eng._compile = lambda fn, args, name, **kw: (
        compiled.append(name), compile_(fn, args, name, **kw))[1]
    rng = np.random.default_rng(0)
    out = str(tmp_path_factory.mktemp("xplane"))
    jax.profiler.start_trace(out)
    try:
        for rid, n in PROMPTS.items():
            eng.submit(rng.integers(1, 96, n).astype(np.int32),
                       max_new_tokens=4, rid=rid)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    profile = ProfileData.from_file(bench_trace.newest_xplane(out))
    spans = {}
    for s in program_spans.from_profile(profile):
        spans.setdefault(s.name, []).append(dict(s.facts))
    return eng, spans, compiled


@pytest.fixture(scope="module")
def hybrid(tmp_path_factory):
    paddle.seed(5)
    model = GraniteHybridForCausalLM(granite_hybrid_tiny(vocab_size=96))
    model.eval()
    return _record(model, tmp_path_factory)


@pytest.fixture(scope="module")
def llama(tmp_path_factory):
    paddle.seed(5)
    model = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=96,
                                        max_position_embeddings=128))
    model.eval()
    return _record(model, tmp_path_factory)


def test_decode_span_counts_the_rows_whose_state_it_updates(hybrid):
    eng, spans, _ = hybrid
    ran = [s for s in spans["serve.decode"] if s["rows"]]
    assert ran and all(s["state_rows"] == s["rows"] for s in ran)
    assert len({s["state_rows"] for s in ran}) > 1     # rows come and go
    # three state layers, each a conv tail [3, conv_dim] (this model is
    # float32) and a float32 state [H, P, N]: what a row's update moves
    cfg = eng.model.config
    assert eng.state.bytes_per_row == 3 * 4 * (
        3 * cfg.mamba_conv_dim
        + cfg.mamba_n_heads * cfg.mamba_d_head * cfg.mamba_d_state)


def test_state_slots_peak_is_the_rows_held_at_once(hybrid, llama):
    """A state slot is the decode row: the meter's peak is the most rows
    active at once over ``max_batch``, and the largest ``state_rows`` a
    decode span saw cannot pass it."""
    eng, spans, _ = hybrid
    peak = eng.meter.summary()["state_slots_peak"]
    assert 0 < peak <= 1.0
    assert max(s["state_rows"] for s in spans["serve.decode"]) \
        <= round(peak * eng.max_batch)
    assert llama[0].meter.summary()["state_slots_peak"] is None


def test_a_model_without_state_layers_reports_none(llama):
    ran = [s for s in llama[1]["serve.decode"] if s["rows"]]
    assert ran and all(s["state_rows"] == 0 for s in ran)


@pytest.mark.parametrize("which", ["hybrid", "llama"])
def test_both_programs_compile_once_under_their_names(which, request):
    eng, _, compiled = request.getfixturevalue(which)
    assert compiled == [serving_engine.DECODE_PROGRAM] + \
        [serving_engine.PREFILL_PROGRAM] * len(serving_engine.PREFILL_WIDTHS)
    assert (serving_engine.DECODE_PROGRAM, serving_engine.PREFILL_PROGRAM) \
        == ("serve_decode_fn", "serve_prefill_fn")
    assert eng._decode_compiles == 1
