"""``ServingEngine`` over a model with latent-attention layers
(``deepseek_v3_tiny``): pages of one latent row a token, the absorbed form
through the page-walk kernel in decode and the expanded form in prefill,
held to the benchmark's plain reference (expanded MLA over the whole
sequence, a loop over the held experts); slots are reused without leaking;
what is not built for latent rows is refused by name."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.builders import deepseek_v3_serve as builder
from benchmark.reference import deepseek_v3 as reference
from paddle_tpu import telemetry
from paddle_tpu.models import (DeepseekV3ForCausalLM, LatentAttentionLayer,
                               deepseek_v3_tiny)
from paddle_tpu.ops.pallas.mla_paged_decode_attention import KERNEL_NAME
from paddle_tpu.serving import (LatentLayersUnsupported, OffloadPool,
                                ServingEngine)
from paddle_tpu.serving.kv_quant import layer_page_bytes
from tests.test_serving_state import (held_to_reference, prompt, serve_alone,
                                      spy_on_decode)

PAGE = 16
KNOBS = dict(page_tokens=PAGE, num_pages=40, max_pages_per_seq=8, lint=True)
HELD = (4, 8)       # experts 4..11 of 16: two groups' worth, neither whole


def as_config_dict(cfg) -> dict:
    import dataclasses

    d = dataclasses.asdict(cfg)
    d["router_experts"] = d["n_routed_experts"]
    d["experts_held"] = list(cfg.experts_held or (0, cfg.n_routed_experts))
    return d


@pytest.fixture(autouse=True)
def _one_device_process(monkeypatch):
    """An earlier file of this worker may have left a hybrid mesh live,
    under which a one-device engine's kernels would (rightly) be refused."""
    from paddle_tpu.distributed import topology

    monkeypatch.setattr(topology, "_hcg", None)


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = DeepseekV3ForCausalLM(deepseek_v3_tiny(experts_held=HELD))
    m.eval()
    return m


@pytest.fixture(scope="module")
def ref_logits(model):
    weights = builder.reference_weights(model)
    cfg = as_config_dict(model.config)
    return lambda ids, pos=None: np.asarray(
        reference.logits(weights, cfg, np.asarray(ids, np.int32), pos))


@pytest.fixture(params=[False, True], ids=["einsum", "kernel"])
def kernels(request):
    """The decode program by the gather + einsum, and by the interpreted
    kernels (the page walk and the grouped matmul)."""
    paddle.set_flags({"pallas_interpret": request.param})
    yield request.param
    paddle.set_flags({"pallas_interpret": False})


def test_the_model_forward_is_the_reference(model, ref_logits):
    ids = prompt(3 * PAGE + 5)
    got = model(paddle.to_tensor(ids[None])).value[0]
    want = ref_logits(ids)
    np.testing.assert_allclose(got, want, atol=3e-4 * np.abs(want).max())


def test_decode_logits_agree_with_the_reference(model, ref_logits, kernels):
    """A prompt across four pages (the last one part full: a wide launch
    and a narrow one), then nine tokens through the latent pages."""
    eng = ServingEngine(model, max_batch=4, **KNOBS)
    p = prompt(3 * PAGE + 5)
    before = telemetry.counters().get("kernel_fallback.total", 0)
    toks, rows = serve_alone(eng, p, 10)
    assert rows.shape[0] == 9
    held_to_reference(ref_logits, p, toks, rows)
    assert eng._decode_compiles == 1 and not eng._active
    # where kernels run, both of them took every call
    assert telemetry.counters().get("kernel_fallback.total", 0) == before
    decode = str(jax.make_jaxpr(eng._decode_fn)(
        *eng._param_arrays(), eng._arenas, jnp.zeros((4, 1), jnp.int32),
        jnp.zeros((4,), jnp.int32), jnp.zeros((4, 8), jnp.int32),
        jnp.ones((4,), jnp.int32)))
    assert (KERNEL_NAME in decode) == kernels
    assert ("moe_grouped_matmul" in decode) == kernels


def test_two_rows_of_different_lengths_share_a_batch(model, ref_logits):
    """Continuous batching through the interpreted kernels: rows admitted
    and retired at different steps, a request that takes a retired row:
    each is held to the reference of the request alone."""
    paddle.set_flags({"pallas_interpret": True})
    try:
        _two_rows(model, ref_logits)
    finally:
        paddle.set_flags({"pallas_interpret": False})


def _two_rows(model, ref_logits):
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    a, b, c = prompt(2 * PAGE + 7), prompt(5, 1), prompt(PAGE, 2)
    ra = eng.submit(a, max_new_tokens=9)
    rb = eng.submit(b, max_new_tokens=4)
    rc = eng.submit(c, max_new_tokens=6)        # waits for a row
    seen = spy_on_decode(eng)
    out = {rid: v.tolist() for rid, v in eng.run().items()}
    assert {row for row, _ in seen[rc]} == {1}          # b's row, reused
    for rid, p in ((ra, a), (rb, b), (rc, c)):
        held_to_reference(ref_logits, p, out[rid],
                          np.stack([row for _, row in seen[rid]]))
    assert not eng._active


def test_absorbed_and_expanded_attend_alike_on_the_same_latent(model):
    """One launch of the engine's latent attention in both forms over the
    same pages: W_UK in the query and W_UV on the output, or per-head K/V
    expanded from the gathered rows."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    spec = eng._latent
    rng = np.random.default_rng(0)
    R, s, h = 2, 3, spec.heads

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    pages = jnp.zeros(eng._arena_shape, jnp.float32).at[1:6, :, :40].set(
        arr(5, PAGE, 40))
    args = (arr(R, s, h, spec.nope_dim), arr(R, s, h, spec.rope_dim),
            arr(R, s, spec.latent_dim), arr(R, s, spec.rope_dim),
            arr(spec.latent_dim, h, spec.nope_dim),
            arr(spec.latent_dim, h, spec.v_dim), pages,
            jnp.asarray([[1, 2, 3, 0, 0, 0, 0, 0], [4, 5, 0, 0, 0, 0, 0, 0]],
                        jnp.int32),
            jnp.asarray([2 * PAGE + 1, PAGE - 2], jnp.int32),
            jnp.asarray([3, 2], jnp.int32))
    a, pa = eng._attend_latent(*args, spec=spec, absorbed=True)
    e, pe = eng._attend_latent(*args, spec=spec, absorbed=False)
    assert a.shape == (R, s, h, spec.v_dim)
    np.testing.assert_allclose(a[0], e[0], atol=2e-5)
    np.testing.assert_allclose(a[1, :2], e[1, :2], atol=2e-5)
    np.testing.assert_array_equal(pa, pe)


def test_the_cache_holds_one_latent_row_a_token_and_nothing_per_head(model):
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    spec = model.serve_layers()[0]
    assert isinstance(spec, LatentAttentionLayer)
    assert (spec.latent_dim, spec.rope_dim, spec.row_width) == (32, 8, 128)
    assert set(eng._arenas) == {"c"} and len(eng._arenas["c"]) == 3
    assert eng._arenas["c"][0].shape == (40, PAGE, 128)
    # a page is priced by its layer's kind, and the donation gate by that
    per_layer = layer_page_bytes(spec, PAGE, "bf16")
    assert per_layer == PAGE * 128 * 2
    assert eng.pool.bytes_per_page == 3 * per_layer
    # the gate counts the planes as allocated (float32 here, on the CPU)
    assert eng._arena_bytes == 3 * 40 * PAGE * 128 * 4
    eng.submit(prompt(PAGE + 3), max_new_tokens=3)
    eng.run()
    assert eng.lint_report.ok
    real = LatentAttentionLayer(128, 512, 64, 128, 128, 0.1)
    assert real.row_width == 640
    assert layer_page_bytes(real, 128, "bf16") == 128 * 1280
    assert layer_page_bytes(real, 128, "fp8") == 128 * 640


def test_spans_carry_what_the_program_counted(model):
    """``serve.decode`` / ``serve.prefill`` facts: pages of latent rows, the
    pairs computed on held experts, the fullest expert.  The counts ride
    the step's one fetch, beside the token ids; every token's chosen
    experts stay on the device and come when asked for."""
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    noted, chose = [], []
    facts = eng._step_facts

    def spy(*a):
        noted.append(facts(*a))
        chose.append(eng.last_decode_kept)
        return noted[-1]

    eng._step_facts = spy
    eng.submit(prompt(PAGE + 3), max_new_tokens=4)
    eng.run()
    prefill, decode = noted[0], noted[1:]
    # the prompt's launches: two pages of tokens, -1 on the padding
    picked = eng.last_prefill_kept["moe_choice"]
    assert picked.shape == (2, 2 * PAGE, 4)
    assert (picked[:, :PAGE + 3] >= 0).all() and (picked[:, PAGE + 3:] == -1).all()
    held = (picked >= HELD[0]) & (picked < sum(HELD))
    assert int(held.sum()) == prefill["moe_pairs"]
    for kept, f in zip(chose[1:], decode):
        # [expert layers, rows, 1, k]: the live row's experts, -1 the idle
        picked = kept["moe_choice"]
        assert picked.shape == (2, 2, 1, 4) and (picked[:, 1] == -1).all()
        held = (picked[:, 0] >= HELD[0]) & (picked[:, 0] < sum(HELD))
        assert int(held.sum()) == f["moe_pairs"]
    assert eng._note_reduce == {"moe_experts_hit": "sum",
                                "moe_max_load": "max", "moe_pairs": "sum"}
    # two launches of one page: the prompt's pages so far, 1 then 2
    assert prefill["latent_pages"] == (1 + 2) * 3
    assert 0 < prefill["moe_pairs"] <= (PAGE + 3) * 4 * 2
    assert len(decode) == 3
    for i, f in enumerate(decode):
        assert f["latent_pages"] == 2 * 3       # two live pages, 3 layers
        assert f["latent_tokens"] == (PAGE + 4 + i) * 3
        # one live row: at most its 4 choices a layer, over 2 expert layers
        assert 0 <= f["moe_pairs"] <= 8 and f["moe_max_load"] <= 1
        assert f["moe_experts_hit"] == f["moe_pairs"]


@pytest.mark.parametrize("kwargs,feature", [
    (dict(kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(tp=2), "tp > 1"), (dict(cp=2), "cp > 1"),
    (dict(offload=OffloadPool()), "offload"),
])
def test_what_is_not_built_for_latent_rows_is_refused_by_name(
        model, kwargs, feature):
    with pytest.raises(LatentLayersUnsupported, match=feature) as e:
        ServingEngine(model, max_batch=2, **KNOBS, **kwargs)
    assert e.value.feature == feature


def test_disaggregated_prefill_is_refused_by_name(model):
    eng = ServingEngine(model, max_batch=2, **KNOBS)
    with pytest.raises(LatentLayersUnsupported, match="prefill_export"):
        eng.prefill_export(prompt(5))
    with pytest.raises(LatentLayersUnsupported, match="submit_prefilled"):
        eng.submit_prefilled(prompt(5), 1, [])


def test_prefix_cache_adopts_latent_pages(model):
    """The prefix cache keys pages, not layers: a second request with the
    same first two pages adopts them and generates what it would alone."""
    shared = prompt(2 * PAGE)
    a = np.concatenate([shared, prompt(5, 1)])
    b = np.concatenate([shared, prompt(7, 2)])
    alone = ServingEngine(model, max_batch=2, **KNOBS)
    want = serve_alone(alone, b, 5)[0]
    eng = ServingEngine(model, max_batch=2, prefix_cache=True, **KNOBS)
    eng.submit(a, max_new_tokens=3)
    eng.run()
    rid = eng.submit(b, max_new_tokens=5)
    assert eng.run()[rid].tolist() == want
    assert eng.prefix.hits == 1


def test_fp8_latent_pages_run_through_the_gather(model):
    """fp8 pages quantize on the scatter and dequantize at the gather; the
    kernel's gate refuses them by name and the einsum attends."""
    paddle.set_flags({"pallas_interpret": True})
    try:
        before = telemetry.counters().get(
            f"kernel_fallback.{KERNEL_NAME}.kv_dtype", 0)
        eng = ServingEngine(model, max_batch=2, kv_dtype="fp8", **KNOBS)
        p = prompt(PAGE + 5)
        toks, rows = serve_alone(eng, p, 4)
        assert telemetry.counters()[
            f"kernel_fallback.{KERNEL_NAME}.kv_dtype"] > before
    finally:
        paddle.set_flags({"pallas_interpret": False})
    assert eng._arenas["c"][0].dtype == jnp.float8_e4m3fn
    exact = serve_alone(ServingEngine(model, max_batch=2, **KNOBS), p, 4)[1]
    err = np.sqrt(np.mean((rows - exact) ** 2) / np.mean(exact ** 2))
    assert 1e-4 < err < 0.2


def test_speculation_verifies_through_latent_pages(model):
    """A verify step of 1 + k positions a row: the stream is the serial
    one's, token for token."""
    ps = [prompt(PAGE + 3), prompt(7, 1)]
    serial = ServingEngine(model, max_batch=2, **KNOBS)
    rids = [serial.submit(p, max_new_tokens=8) for p in ps]
    want = [serial.run()[r].tolist() for r in rids]
    eng = ServingEngine(model, max_batch=2, speculative=2, **KNOBS)
    rids = [eng.submit(p, max_new_tokens=8) for p in ps]
    outs = eng.run()
    assert [outs[r].tolist() for r in rids] == want
