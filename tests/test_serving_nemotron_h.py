"""``ServingEngine`` over a model whose blocks are ONE part each (Nemotron-H):
state layers, paged attention layers and expert layers that keep nothing
per request, in one walk.  Every decode logits row is held to the
benchmark's plain reference's full forward; slots are reused without
leaking; the fourth layer kind is given no arena and refuses, by name, the
calls that are another kind's; what state layers refuse this model
refuses."""

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.builders import nemotron_h_serve as builder
from benchmark.reference import nemotron_h as reference
from paddle_tpu.models import NemotronHForCausalLM, nemotron_h_tiny
from paddle_tpu.serving import (AttentionLayer, OffloadPool, ServingEngine,
                                StateLayer, StateLayersUnsupported,
                                StatelessLayer)
from paddle_tpu.serving.engine import _LayerIO
from tests.test_nemotron_h import as_config_dict
from tests.test_serving_state import prompt, serve_alone, spy_on_decode

PAGE = 16
KNOBS = dict(page_tokens=PAGE, num_pages=40, max_pages_per_seq=8, lint=True)
HELD = (2, 4)       # experts 2..5 of 8


@pytest.fixture(scope="module")
def model():
    paddle.seed(7)
    m = NemotronHForCausalLM(nemotron_h_tiny(experts_held=HELD))
    m.eval()
    return m


@pytest.fixture(scope="module")
def ref_logits(model):
    weights = builder.reference_weights(model)
    cfg = as_config_dict(model.config)
    return lambda ids, pos=None: np.asarray(
        reference.logits(weights, cfg, np.asarray(ids, np.int32), pos))


def held_to_reference(ref_logits, p, toks, rows):
    ids = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
    n = len(p)
    want = ref_logits(ids, np.arange(n - 1, n - 1 + len(toks)))
    assert np.argmax(want, -1).tolist() == toks
    np.testing.assert_allclose(rows, want[1:], atol=3e-4 * np.abs(want).max())


@pytest.mark.parametrize("n", [1, PAGE, PAGE + 1, 3 * PAGE - 5,
                               4 * PAGE + 2],
                         ids=["one", "page", "page+1", "3pages-5",
                              "5pages"])
def test_decode_logits_agree_with_the_reference(model, ref_logits, n):
    """Prefill across pages (a launch of four pages and launches of one),
    then 17 tokens through state, pages and held experts, against the
    reference's full forward over prompt + generated tokens."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    p = prompt(n)
    toks, rows = serve_alone(eng, p, 17)
    assert rows.shape[0] == 16
    held_to_reference(ref_logits, p, toks, rows)
    assert eng._decode_compiles == 1
    assert not eng._active


def test_a_reused_slot_gives_the_logits_of_the_request_alone(model,
                                                             ref_logits):
    """Continuous batching: the request that takes a retired row's slot
    must not see its state, and rows of different lengths share the expert
    layers' sort."""
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
    held_to_reference(ref_logits, b, out[rb],
                      np.stack([row for _, row in seen[rb]]))
    alone = ServingEngine(model, max_batch=2, **KNOBS)
    for rid, p, n in ((ra, a, 4), (rb, b, 12), (rc, c, 9)):
        assert serve_alone(alone, p, n)[0] == out[rid]
    assert eng.meter.summary()["state_slots_peak"] == 1.0


def test_every_block_is_described_and_only_what_keeps_memory_has_an_arena(
        model):
    """Five blocks, four kinds of memory: the expert blocks are walked and
    counted nowhere — two state layers' arenas, one attention layer's."""
    eng = ServingEngine(model, max_batch=3, **KNOBS)
    kinds = [type(sp) for sp in eng._layers]
    assert kinds == [StateLayer, StatelessLayer, StateLayer, AttentionLayer,
                     StatelessLayer]
    assert eng._family_index == [0, None, 1, 0, None]
    assert len(eng.state.layers) == 2
    assert {k: len(v) for k, v in eng._arenas.items()} == \
        {"k": 1, "v": 1, "conv": 2, "ssm": 2}
    # a row's state: 2 blocks x (a 3-token conv tail + [8, 16, 128]), float32
    assert eng.state.bytes_per_row == 2 * 4 * (3 * 640 + 8 * 16 * 128)


def test_spans_carry_the_rows_and_what_the_expert_blocks_counted(model):
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    noted, facts = [], eng._step_facts

    def spy(*a):
        noted.append(facts(*a))
        return noted[-1]

    eng._step_facts = spy
    eng.submit(prompt(PAGE + 3), max_new_tokens=4)
    eng.run()
    assert eng._note_reduce == {"moe_experts_hit": "sum",
                                "moe_max_load": "max", "moe_pairs": "sum"}
    prefill, decode = noted[0], noted[1:]
    picked = eng.last_prefill_kept["moe_choice"]
    assert picked.shape == (2, 2 * PAGE, 2)     # [expert blocks, tokens, k]
    assert (picked[:, :PAGE + 3] >= 0).all() \
        and (picked[:, PAGE + 3:] == -1).all()
    held = (picked >= HELD[0]) & (picked < sum(HELD))
    assert int(held.sum()) == prefill["moe_pairs"] > 0
    assert len(decode) == 3 and "latent_pages" not in prefill
    for f in decode:
        # one live row: at most its 2 choices a block, over 2 expert blocks
        assert 0 <= f["moe_pairs"] <= 4 and f["moe_max_load"] <= 1
        assert f["moe_experts_hit"] == f["moe_pairs"]


class _Stub:
    """An engine the refusals never reach."""


@pytest.mark.parametrize("call,args", [
    ("attend", (None, None, None)),
    ("attend_latent", (None,) * 6),
    ("read_state", ("ssm",)),
    ("write_state", ("ssm", None))])
def test_a_layer_that_keeps_nothing_refuses_the_other_kinds_calls(call, args):
    import jax.numpy as jnp

    io = _LayerIO(_Stub(), StatelessLayer(), {}, None, None, None, None,
                  jnp.array([2, 0]), None, None, {}, {})
    with pytest.raises(TypeError) as e:
        getattr(io, call)(*args)
    assert f"io.{call}" in str(e.value) and "StatelessLayer" in str(e.value)
    # what every kind may use
    assert io.live.tolist() == [True, False] and io.n_valid.tolist() == [2, 0]
    io.note("pairs", 3)
    io.note("pairs", 4)
    io.keep("choice", jnp.zeros((2, 1)))
    assert int(io._notes["pairs"][0]) == 7 and len(io._kept["choice"]) == 1


def test_a_state_layer_may_not_attend_either():
    import jax.numpy as jnp

    io = _LayerIO(_Stub(), StateLayer.of(ssm=((2, 2), "float32")), {}, 0,
                  None, None, None, jnp.array([1]), None, None, {}, {})
    with pytest.raises(TypeError, match="io.attend is a AttentionLayer's"):
        io.attend(None, None, None)


@pytest.mark.parametrize("kwargs,feature", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(offload=True), "offload"),
    (dict(offload=OffloadPool()), "offload"),
    (dict(speculative=2), "speculative"),
    (dict(tp=2), "tp > 1"),
    (dict(cp=2), "cp > 1"),
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(kv_dtype="fp8"), "kv_dtype='fp8'")])
def test_what_state_layers_refuse_this_model_refuses_by_name(model, kwargs,
                                                             feature):
    with pytest.raises(StateLayersUnsupported) as e:
        ServingEngine(model, max_batch=2, **KNOBS, **kwargs)
    assert e.value.feature == feature and feature in str(e.value)


def test_disaggregated_entry_points_are_refused(model):
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    with pytest.raises(StateLayersUnsupported, match="prefill_export"):
        eng.prefill_export(prompt(5))
    with pytest.raises(StateLayersUnsupported, match="submit_prefilled"):
        eng.submit_prefilled(prompt(5), 1, [])


def test_the_constructor_names_four_kinds_and_the_served_models():
    class OnlyExperts:
        config = None

        def serve_layers(self):
            return [StatelessLayer()]

        serve_begin = serve_layer = serve_end = None

    with pytest.raises(TypeError, match="StatelessLayer") as e:
        ServingEngine(OnlyExperts())
    assert "at least one of them a layer that keeps pages" in str(e.value)

    class NotServable:
        pass

    with pytest.raises(TypeError, match="NemotronHForCausalLM"):
        ServingEngine(NotServable())
