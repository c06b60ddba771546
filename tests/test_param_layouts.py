"""The serving engine lays its weights out once, in the layouts its decode
program chooses (``ServingEngine._compile`` with ``choose_layouts``), and
every later program reads them as they lie.

On the CPU the compiler keeps every weight as it lies (row-major), so
nothing would move here: the engine of these tests asks for the 2-D weights
column-major instead (``_param_formats``), the way the TPU compiler takes q,
k and v. The path is then the chip's: the decode program is compiled for
those layouts, the weights move one at a time, and the prefill programs
compile against the moved arrays."""

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as serving_engine

pytestmark = pytest.mark.serving

KNOBS = dict(max_batch=4, page_tokens=4, num_pages=32, max_pages_per_seq=8)
PROMPTS = [np.arange(1, 6, dtype=np.int32), np.arange(7, 16, dtype=np.int32),
           np.array([3, 1, 4], np.int32)]
NEW = 5
COLUMN_MAJOR = (1, 0)


def _column_major(pa):
    """The request of the chip's compiler for q / k / v, made of every 2-D
    weight: column-major; the rest is left to the compiler."""
    from jax.experimental.layout import Format, Layout

    return [Format(Layout(major_to_minor=COLUMN_MAJOR), a.sharding)
            if a.ndim == 2 else Format(Layout.AUTO, a.sharding) for a in pa]


def _model():
    paddle.seed(3)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=96,
                                    max_position_embeddings=128))
    m.eval()
    return m


def _serve(eng):
    rids = [eng.submit(p, max_new_tokens=NEW) for p in PROMPTS]
    out = eng.run()
    return [out[r].tolist() for r in rids]


def _greedy(model, prompt):
    """The model's own greedy continuation: one eager forward over the whole
    sequence a token."""
    ids = list(prompt)
    for _ in range(NEW):
        logits = model(paddle.to_tensor(np.asarray(ids, np.int32)[None]))
        ids.append(int(np.argmax(np.asarray(logits.numpy())[0, -1])))
    return ids[len(prompt):]


def _layout(p):
    return p._value.format.layout.major_to_minor


def _matrices(model):
    return [p for _, p in model.named_parameters() if p._value.ndim == 2]


@pytest.fixture
def column_major(monkeypatch):
    monkeypatch.setattr(ServingEngine, "_param_formats",
                        staticmethod(_column_major))


@pytest.fixture
def spans(monkeypatch):
    """Every span the engine opens, with its facts."""
    seen, real = [], serving_engine._span

    def spy(name, **facts):
        sp = real(name, **facts)
        seen.append((name, sp))
        return sp

    monkeypatch.setattr(serving_engine, "_span", spy)
    return seen


def test_the_engine_relays_the_weights_once(column_major, spans):
    model = _model()
    matrices = _matrices(model)
    assert matrices and all(_layout(p) == (0, 1) for p in matrices)
    eng = ServingEngine(model, **KNOBS)
    assert eng.param_layout_refusal is None
    first = _serve(eng)
    moved_bytes = sum(p._value.nbytes for p in matrices)
    summary = eng.meter.summary()
    assert (summary["params_relaid"], summary["params_relaid_bytes"]) == \
        (len(matrices), moved_bytes)
    assert all(_layout(p) == COLUMN_MAJOR for p in matrices)
    # the choosing program's span says so; no other program moved anything
    compiled = [sp.facts for name, sp in spans if name == "serve.compile"]
    assert [f["program"] for f in compiled] == \
        [serving_engine.DECODE_PROGRAM] + \
        [serving_engine.PREFILL_PROGRAM] * len(serving_engine.PREFILL_WIDTHS)
    assert (compiled[0]["relaid"], compiled[0]["relaid_bytes"]) == \
        (len(matrices), moved_bytes)
    assert all("relaid" not in f for f in compiled[1:])
    # a second stream compiles and moves nothing
    assert _serve(eng) == first
    assert eng._decode_compiles == 1
    assert eng.meter.summary()["params_relaid"] == len(matrices)
    assert len([1 for name, _ in spans if name == "serve.compile"]) == \
        len(compiled)


def test_greedy_tokens_match_the_eager_forward(column_major):
    model = _model()
    want = [_greedy(model, p) for p in PROMPTS]
    eng = ServingEngine(model, **KNOBS)
    assert _serve(eng) == want
    assert eng.meter.summary()["params_relaid"] > 0
    # and the eager forward over the moved weights still says the same
    assert [_greedy(model, p) for p in PROMPTS] == want


def test_the_model_is_unchanged_outside_the_engine(column_major):
    model = _model()
    before = {k: np.asarray(v) for k, v in model.state_dict().items()}
    ids = paddle.to_tensor(PROMPTS[1][None])
    logits = np.asarray(model(ids).numpy())
    _serve(ServingEngine(model, **KNOBS))
    after = model.state_dict()
    assert sorted(after) == sorted(before)
    assert any(_layout(p) == COLUMN_MAJOR for p in _matrices(model))
    for k, v in after.items():
        np.testing.assert_array_equal(np.asarray(v), before[k], err_msg=k)
    np.testing.assert_allclose(np.asarray(model(ids).numpy()), logits,
                               rtol=1e-5, atol=1e-5)


def test_a_second_engine_serves_the_moved_weights_as_they_lie(column_major):
    model = _model()
    first = ServingEngine(model, **KNOBS)
    want = _serve(first)
    layouts = [_layout(p) for p in _matrices(model)]
    second = ServingEngine(model, **KNOBS)
    assert _serve(second) == want
    assert second.param_layout_refusal == "laid_out"
    assert second.meter.summary()["params_relaid"] == 0
    assert [_layout(p) for p in _matrices(model)] == layouts
    # the first engine's programs still take the weights as they lie
    assert _serve(first) == want


def test_an_engine_under_a_tp_mesh_keeps_the_layouts_and_says_why(
        column_major):
    if len(jax.devices()) < 2:
        pytest.skip("needs two devices for the model mesh")
    from paddle_tpu.distributed import topology

    prior = topology._hcg
    topology._hcg = None          # an earlier distributed test's mesh
    try:
        model = _model()
        want = [_greedy(model, p) for p in PROMPTS]
        eng = ServingEngine(model, tp=2, **KNOBS)
        assert eng.param_layout_refusal == "mesh"
        assert _serve(eng) == want
        assert eng.meter.summary()["params_relaid"] == 0
        assert all(_layout(p) == (0, 1) for p in _matrices(model))
    finally:
        topology._hcg = prior


def test_default_layout_tells_a_moved_array_apart():
    from jax.experimental.layout import Format, Layout

    from paddle_tpu.framework.jax_compat import default_layout

    a = jax.numpy.ones((8, 16))
    moved = jax.device_put(a, Format(Layout(major_to_minor=COLUMN_MAJOR),
                                     a.sharding))
    assert default_layout(a) and not default_layout(moved)
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(a))


@pytest.mark.parametrize("was", [True, False])
def test_the_moves_compile_with_the_persistent_cache_off(was):
    from paddle_tpu.framework.jax_compat import persistent_cache_off

    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", was)
    try:
        with persistent_cache_off():
            assert jax.config.jax_enable_compilation_cache is False
        assert jax.config.jax_enable_compilation_cache is was
    finally:
        jax.config.update("jax_enable_compilation_cache", prior)
