"""``ServingEngine`` over a model whose layers run several times a step
(Ouro: 2 layers x 3 passes here).  The walk is one loop in the compiled
programs, each (pass, layer) keeps K/V in pages of its own, every decode
logits row (a riding launch's too) is held to the benchmark's float32
reference, and what does not compose with passes is refused by name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.builders import ouro_serve as builder
from benchmark.reference import ouro as reference
from paddle_tpu.models import OuroForCausalLM, ouro_tiny
from paddle_tpu.serving import PassesUnsupported, ServingEngine
from paddle_tpu.serving import engine as engine_mod
from tests.test_serving_state import prompt, serve_alone, spy_on_decode

PAGE = 16
N = 24
KNOBS = dict(page_tokens=PAGE, num_pages=N, max_pages_per_seq=8, lint=True)
PASSES, LAYERS = 3, 2


@pytest.fixture(scope="module")
def model():
    paddle.seed(5)
    m = OuroForCausalLM(ouro_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def ref_logits(model):
    weights = builder.reference_weights(model)
    cfg = dataclasses.asdict(model.config)
    return lambda ids, pos=None, **kw: np.asarray(reference.logits(
        weights, cfg, np.asarray(ids, np.int32), pos, **kw))


def held_to_reference(ref_logits, p, toks, rows, **control):
    ids = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
    n = len(p)
    want = ref_logits(ids, np.arange(n - 1, n - 1 + len(toks)), **control)
    if control:
        return want
    assert np.argmax(want, -1).tolist() == toks
    np.testing.assert_allclose(rows, want[1:], atol=3e-4 * np.abs(want).max())
    return want


@pytest.mark.parametrize("n", [1, PAGE, PAGE + 1, 4 * PAGE + 2],
                         ids=["one", "page", "page+1", "5pages"])
def test_decode_logits_agree_with_the_reference(model, ref_logits, n):
    """Prefill across pages (a launch of four pages and launches of one),
    then 11 tokens, every pass attending over its own pages, against the
    reference's full forward of every pass over prompt + generated
    tokens."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    p = prompt(n)
    toks, rows = serve_alone(eng, p, 11)
    assert rows.shape[0] == 10
    held_to_reference(ref_logits, p, toks, rows)
    assert eng._decode_compiles == 1
    assert eng.meter.summary()["passes"] == PASSES


def test_rows_that_ride_a_prefill_launch_agree_too(model, ref_logits):
    """A prompt that arrives while another request decodes carries that
    request's row on its last launch: both parts' tables are offset by the
    pass inside the program."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    a, b = prompt(PAGE + 5), prompt(9, 1)
    seen = spy_on_decode(eng)
    ra = eng.submit(a, max_new_tokens=12)
    for _ in range(3):
        eng.step()
    rb = eng.submit(b, max_new_tokens=6)
    out = {rid: v.tolist() for rid, v in eng.run().items()}
    assert eng.rides_prefill and eng.meter.summary()["decode_steps_rode"] >= 1
    for rid, p in ((ra, a), (rb, b)):
        held_to_reference(ref_logits, p, out[rid],
                          np.stack([row for _, row in seen[rid]]))


def test_every_control_is_refused_by_the_limit(model, ref_logits):
    """The engine's rows against the reference made wrong in each of the
    ways the chip's check must catch: far above what the engine reads."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    p = prompt(2 * PAGE + 3)
    toks, rows = serve_alone(eng, p, 9)
    sound = held_to_reference(ref_logits, p, toks, rows)[1:]

    def err(want):
        return float(np.sqrt(np.mean((rows - want) ** 2))
                     / np.sqrt(np.mean(want ** 2)))

    cfg = dataclasses.asdict(model.config)
    assert err(sound) < 1e-4
    for name in reference.CONTROLS:
        bad = held_to_reference(ref_logits, p, toks, rows,
                                **reference.control_kwargs(cfg, name))[1:]
        assert err(bad) > 0.02, name


def test_each_pass_keeps_pages_of_its_own(model):
    """One arena a layer of ``passes x num_pages`` pages; the pool, the
    tables and the price count ``num_pages`` pages of every layer and
    pass."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    assert eng.passes == PASSES and eng.pool.num_pages == N
    d = model.config.hidden_size            # 4 heads of 16, merged
    assert {k: len(v) for k, v in eng._arenas.items()} == {"k": LAYERS,
                                                           "v": LAYERS}
    assert eng._arenas["k"][0].shape == (PASSES * N, PAGE, d)
    # priced in the configured page dtype (bf16); the CPU's arenas keep
    # the model's float32
    assert eng.pool.bytes_per_page == PASSES * LAYERS * 2 * PAGE * d * 2
    assert eng._arena_bytes == 2 * LAYERS * PASSES * N * PAGE * d * 4
    p = prompt(PAGE + 5)
    rid = eng.submit(p, max_new_tokens=3)
    eng.step()
    page = eng.pool.table(rid)[0]
    eng.run()
    k = np.asarray(eng._arenas["k"][1]).reshape(PASSES, N, PAGE, d)
    # every pass wrote the page, each its own values
    assert all(np.abs(k[t, page]).sum() > 0 for t in range(PASSES))
    assert not np.allclose(k[0, page], k[1, page])
    assert not np.allclose(k[1, page], k[2, page])


def _decode_text(eng):
    pa, ba = eng._param_arrays()
    R, MP = eng.max_batch, eng.max_pages_per_seq
    return jax.jit(eng._decode_fn).lower(
        pa, ba, eng._arenas, jnp.zeros((R, 1), jnp.int32),
        jnp.zeros((R,), jnp.int32), jnp.zeros((R, MP), jnp.int32),
        jnp.ones((R,), jnp.int32)).as_text()


def test_the_programs_hold_each_layer_once(model, monkeypatch):
    """The number of matrix products in the lowered decode program does not
    grow with the passes: the walk is one loop, and a model walked once
    lowers no loop at all."""
    looped = _decode_text(ServingEngine(model, max_batch=2, **KNOBS))
    monkeypatch.setattr(model, "serve_passes", lambda: 1)
    once = ServingEngine(model, max_batch=2, **KNOBS)
    assert once.passes == 1 and once._arenas["k"][0].shape[0] == N
    flat = _decode_text(once)
    assert looped.count("stablehlo.dot_general") == \
        flat.count("stablehlo.dot_general") > LAYERS * 7
    assert looped.count("stablehlo.while") == 1
    assert flat.count("stablehlo.while") == 0


def test_a_looped_model_walked_once_still_ends_its_pass(model, ref_logits,
                                                        monkeypatch):
    """With one pass there is no loop, and the step between passes (the
    shared norm) still closes the one pass before the head."""
    monkeypatch.setattr(model, "serve_passes", lambda: 1)
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    p = prompt(PAGE + 4)
    toks, rows = serve_alone(eng, p, 6)
    want = held_to_reference(ref_logits, p, toks, rows, passes=1)
    assert np.argmax(want, -1).tolist() == toks
    np.testing.assert_allclose(rows, want[1:], atol=3e-4 * np.abs(want).max())


def test_spans_count_the_cached_tokens_every_pass_reads(model, monkeypatch):
    spans = []

    class Span:
        def __init__(self, name, **facts):
            self.name, self.facts = name, dict(facts)
            spans.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def note(self, **facts):
            self.facts.update(facts)

    monkeypatch.setattr(engine_mod, "_span", Span)
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    a = prompt(20)
    eng.submit(a, max_new_tokens=6)
    eng.step()
    eng.step()
    eng.submit(prompt(9, 2), max_new_tokens=3)
    eng.run()
    per_token = LAYERS * PASSES
    prefill = [s.facts for s in spans if s.name == "serve.prefill"]
    # two launches of one page: 16 tokens, then all 20
    assert prefill[0]["passes"] == PASSES
    assert prefill[0]["kv_tokens"] == (16 + 20) * per_token
    rider = [f for f in prefill if "kv_tokens_decode" in f]
    assert len(rider) == 1 and rider[0]["kv_tokens_decode"] > 0
    decode = [s.facts for s in spans if s.name == "serve.decode"
              and s.facts.get("rows") and not s.facts["rode"]]
    assert decode and all(f["passes"] == PASSES for f in decode)
    # the first decode step: a's one row at position 20 sees 21 tokens
    assert decode[0]["kv_tokens"] == 21 * per_token
    assert all(f["kv_tokens"] % per_token == 0 for f in decode)


@pytest.mark.parametrize("kwargs,feature", [
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(kv_dtype="fp8"), "kv_dtype='fp8'"),
    (dict(tp=2), "tp > 1"), (dict(cp=2), "cp > 1"),
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(offload=True), "offload"),
    (dict(speculative=2), "speculative"),
], ids=["int8", "fp8", "tp", "cp", "prefix", "offload", "speculative"])
def test_what_does_not_compose_with_passes_is_refused(model, kwargs,
                                                      feature):
    with pytest.raises(PassesUnsupported) as e:
        ServingEngine(model, max_batch=2, **KNOBS, **kwargs)
    assert e.value.feature == feature
    assert "several times a step" in str(e.value)


@pytest.mark.parametrize("call", ["prefill_export", "submit_prefilled"])
def test_page_frames_are_refused(model, call):
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    args = (prompt(5),) if call == "prefill_export" else (prompt(5), 1, [])
    with pytest.raises(PassesUnsupported, match=call):
        getattr(eng, call)(*args)


def test_a_count_made_inside_a_looped_walk_is_refused(model, monkeypatch):
    """``io.note`` / ``io.keep`` have no way out of the loop yet: the trace
    raises, naming them."""
    inner = model.serve_layer

    def counting(i, x, shared, io):
        io.note("tokens_seen", 1)
        return inner(i, x, shared, io)

    monkeypatch.setattr(model, "serve_layer", counting)
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    eng.submit(prompt(5), max_new_tokens=2)
    with pytest.raises(TypeError, match=r"io.note / io.keep .*tokens_seen"):
        eng.step()


def test_state_layers_in_a_looped_walk_are_refused(monkeypatch):
    from paddle_tpu.models import (GraniteHybridForCausalLM,
                                   granite_hybrid_tiny)

    paddle.seed(0)
    m = GraniteHybridForCausalLM(granite_hybrid_tiny())
    monkeypatch.setattr(m, "serve_passes", lambda: 2, raising=False)
    with pytest.raises(PassesUnsupported, match="state or latent"):
        ServingEngine(m, max_batch=2, **KNOBS)
