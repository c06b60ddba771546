"""``ServingEngine`` over a model with state layers (the Granite-4.0-H
hybrid): per-row recurrent state beside the paged KV pool.  Every decode
logits row is held to the benchmark's plain reference (the recurrence by
``lax.scan`` over tokens), for prompts that end inside, on and past a page
boundary; slots are reused without leaking; eviction-replay stays
token-exact; what cannot be right yet is refused by name."""

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.builders import granite_hybrid_serve as builder
from benchmark.reference import granite_hybrid as reference
from paddle_tpu.models import (GraniteHybridForCausalLM, LlamaForCausalLM,
                               granite_hybrid_tiny, llama_tiny)
from paddle_tpu.serving import (OffloadPool, RowStatePool, ServingEngine,
                                StateLayer, StateLayersUnsupported)
from tests.test_granite_hybrid import as_config_dict

PAGE = 16
KNOBS = dict(page_tokens=PAGE, num_pages=40, max_pages_per_seq=8, lint=True)


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = GraniteHybridForCausalLM(granite_hybrid_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def ref_logits(model):
    weights = builder.reference_weights(model)
    cfg = as_config_dict(model.config)
    return lambda ids, pos=None: np.asarray(
        reference.logits(weights, cfg, np.asarray(ids, np.int32), pos))


def prompt(n, seed=0):
    return np.random.default_rng(seed + n).integers(1, 256, n) \
        .astype(np.int32)


def spy_on_decode(eng):
    """``{rid: [(row, the engine's own logits row) of every decode step]}``,
    filled as the engine steps."""
    seen, sample = {}, eng._decode_sample

    def spy(stepped, choice, n_tok, drafts):
        logits = eng.last_decode_logits
        for r in stepped:
            seen.setdefault(r.rid, []).append(
                (r.row, np.asarray(logits[r.row, 0], np.float32)))
        return sample(stepped, choice, n_tok, drafts)

    eng._decode_sample = spy
    return seen


def serve_alone(eng, p, new_tokens):
    """Serve one request on an otherwise idle engine; its tokens and the
    engine's own logits row of every decode step."""
    seen = spy_on_decode(eng)
    rid = eng.submit(p, max_new_tokens=new_tokens)
    toks = eng.run()[rid].tolist()
    eng._decode_sample = eng.__class__._decode_sample.__get__(eng)
    return toks, np.stack([row for _, row in seen[rid]])


def held_to_reference(ref_logits, p, toks, rows):
    ids = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
    n = len(p)
    want = ref_logits(ids, np.arange(n - 1, n - 1 + len(toks)))
    assert np.argmax(want, -1).tolist() == toks
    scale = np.abs(want).max()
    np.testing.assert_allclose(rows, want[1:], atol=3e-4 * scale)


@pytest.mark.parametrize("n", [1, PAGE, PAGE + 1, 3 * PAGE - 5],
                         ids=["one", "page", "page+1", "3pages-5"])
def test_decode_logits_agree_with_the_token_recurrence(model, ref_logits, n):
    """Prefill across pages (state carried in the slot, the last page's
    junk tail kept out of it), then 17 tokens through state and pages."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    p = prompt(n)
    toks, rows = serve_alone(eng, p, 17)
    assert rows.shape[0] == 16
    held_to_reference(ref_logits, p, toks, rows)
    assert eng._decode_compiles == 1
    assert not eng._active


def test_a_reused_slot_gives_the_logits_of_the_request_alone(model,
                                                             ref_logits):
    """Continuous batching: rows admitted and retired at different steps;
    the request that takes a retired row's slot must not see its state."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    a, b, c = prompt(20), prompt(PAGE + 3, 1), prompt(9, 2)
    ra = eng.submit(a, max_new_tokens=4)
    rb = eng.submit(b, max_new_tokens=12)
    rc = eng.submit(c, max_new_tokens=9)      # waits for a row
    seen = spy_on_decode(eng)
    out = {rid: v.tolist() for rid, v in eng.run().items()}
    assert {row for row, _ in seen[rc]} == {0}        # a's row, reused
    held_to_reference(ref_logits, c, out[rc],
                      np.stack([row for _, row in seen[rc]]))
    alone = ServingEngine(model, max_batch=2, **KNOBS)
    for rid, p, n in ((ra, a, 4), (rb, b, 12), (rc, c, 9)):
        assert serve_alone(alone, p, n)[0] == out[rid]
    # a state slot is the decode row: both rows were held at once
    assert eng.meter.summary()["state_slots_peak"] == 1.0
    assert not eng._active


def test_eviction_replay_is_token_exact_with_state_layers(model):
    """Pool pressure evicts the youngest; its replay recomputes the state
    from the prompt and regenerates exactly what the client saw."""
    roomy = ServingEngine(model, max_batch=3, **KNOBS)
    prompts = [prompt(PAGE - 2, s) for s in range(3)]
    want = {}
    for p in prompts:
        rid = roomy.submit(p, max_new_tokens=3 * PAGE)
        want[rid] = None
    want = {k: v.tolist() for k, v in roomy.run().items()}
    tight = ServingEngine(model, max_batch=3, page_tokens=PAGE, num_pages=8,
                          max_pages_per_seq=8)
    rids = [tight.submit(p, max_new_tokens=3 * PAGE) for p in prompts]
    got = tight.run()
    assert tight.meter.summary()["evictions"] >= 1
    assert [got[r].tolist() for r in rids] == list(want.values())


@pytest.mark.parametrize("kwargs,feature", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(offload=True), "offload"),
    (dict(offload=OffloadPool()), "offload"),
    (dict(speculative=2), "speculative"),
    (dict(tp=2), "tp > 1"),
    (dict(cp=2), "cp > 1"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(kv_dtype="fp8"), "kv_dtype='fp8'")])
def test_what_cannot_be_right_yet_is_refused_by_name(model, kwargs, feature):
    with pytest.raises(StateLayersUnsupported) as e:
        ServingEngine(model, max_batch=2, **KNOBS, **kwargs)
    assert e.value.feature == feature and feature in str(e.value)


def test_env_defaults_are_refused_too(model, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PREFIX_CACHE", "1")
    with pytest.raises(StateLayersUnsupported, match="prefix_cache"):
        ServingEngine(model, max_batch=2, **KNOBS)


def test_disaggregated_entry_points_are_refused(model):
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    with pytest.raises(StateLayersUnsupported, match="prefill_export"):
        eng.prefill_export(prompt(5))
    with pytest.raises(StateLayersUnsupported, match="submit_prefilled"):
        eng.submit_prefilled(prompt(5), 1, [])


def test_a_model_that_does_not_describe_its_layers_is_refused():
    class NotServable:
        pass

    with pytest.raises(TypeError, match="serve_layers"):
        ServingEngine(NotServable())


def test_llama_takes_the_same_walk_and_keeps_no_state():
    paddle.seed(3)
    m = LlamaForCausalLM(llama_tiny())
    m.eval()
    eng = ServingEngine(m, max_batch=2, **KNOBS)
    assert eng.state is None and set(eng._arenas) == {"k", "v"}
    p = prompt(PAGE + 2)
    toks, _ = serve_alone(eng, p, 6)
    ids = list(p)
    for t in toks:
        assert int(np.argmax(m(paddle.to_tensor(
            np.asarray(ids)[None])).numpy()[0, -1])) == t
        ids.append(t)
    assert eng.meter.summary()["state_slots_peak"] is None


def test_row_state_of_a_running_request_agrees_with_the_recurrence(model):
    """``row_state``: a running request's recurrent state as the last
    program left it (the benchmark holds it to the reference: a state kept
    in too few bits hides in the logits).  Token j is delivered after the
    step that consumed token j - 1, so at token j the state covers the
    prompt and j generated tokens."""
    p, new, kept = prompt(PAGE + 5), 6, {}

    def sink(rid, idx, tok):
        if idx == new - 2:
            kept.update(eng.row_state(rid), toks=idx)

    eng = ServingEngine(model, max_batch=2, on_token=sink, **KNOBS)
    rid = eng.submit(p, max_new_tokens=new)
    toks = eng.run()[rid].tolist()
    ids = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
    _, want = reference.logits_and_states(
        builder.reference_weights(model), as_config_dict(model.config), ids,
        state_after=len(p) + kept["toks"])
    n_state = sum(k == "mamba" for k in model.config.layer_types)
    assert kept["ssm"].shape == (n_state,) + want.shape[1:]
    assert kept["ssm"].dtype == np.float32
    assert kept["conv"].shape[:2] == (n_state, model.config.mamba_d_conv - 1)
    np.testing.assert_allclose(kept["ssm"], want,
                               atol=3e-4 * np.abs(want).max())
    # one token later the state has moved on: the comparison has teeth
    _, later = reference.logits_and_states(
        builder.reference_weights(model), as_config_dict(model.config), ids)
    assert np.abs(later - want).max() > 1e-2 * np.abs(want).max()
    with pytest.raises(KeyError, match="holds no row state"):
        eng.row_state(rid)                    # retired: the row is free


def test_row_state_pool_arenas():
    layer = StateLayer.of(conv=((3, 8), "bfloat16"),
                          ssm=((2, 4, 128), "float32"))
    pool = RowStatePool(4, [layer, layer])
    arenas = pool.zeros()
    assert sorted(arenas) == ["conv", "ssm"] and len(arenas["ssm"]) == 2
    assert arenas["ssm"][0].shape == (4, 2, 4, 128)
    assert arenas["conv"][1].dtype == np.dtype("bfloat16")
    assert pool.bytes_per_row == 2 * (3 * 8 * 2 + 2 * 4 * 128 * 4)
    assert pool.nbytes == 4 * pool.bytes_per_row
    with pytest.raises(ValueError, match="same named arrays"):
        RowStatePool(2, [layer, StateLayer.of(ssm=((2,), "float32"))])


def test_decode_program_aliases_the_state_arenas(model):
    """The donation lint covers the row-state arenas: dropped donation is
    refused, and the real program aliases pages and state alike."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving import check_decode_donation

    eng = ServingEngine(model, max_batch=2, **KNOBS)
    serve_alone(eng, prompt(5), 3)
    assert eng.lint_report is not None and eng.lint_report.ok
    mem = eng._decode_exec.memory_analysis()
    assert int(mem.alias_size_in_bytes) >= eng._arena_bytes + eng.state.nbytes
    pa, ba = eng._param_arrays()
    args = (pa, ba, eng._arenas, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2, 8), jnp.int32),
            jnp.ones((2,), jnp.int32))
    bad = jax.jit(eng._decode_fn).lower(*args).compile()
    with pytest.raises(RuntimeError, match="row-state arenas"):
        check_decode_donation(bad, eng._arena_bytes,
                              state_bytes=eng.state.nbytes)
