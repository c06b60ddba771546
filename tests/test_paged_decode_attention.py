"""The page-walking decode kernel against the gather + einsum it replaces in
the serving engine's decode program (interpret mode, CPU).

Kernel level: ``paged_decode_attention`` must give what
``ServingEngine._paged_attention``'s einsum gives on every VALID query of
every row (a row's queries past ``n_tok`` are junk on both paths), zero for
idle rows, and never touch a page past a row's live ones.  Engine level: a
tiny Llama served with ``pallas_interpret`` on emits the einsum engine's
tokens from one decode compile, and quantized pools fall back loudly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.telemetry as telemetry
from paddle_tpu.models import (GraniteHybridForCausalLM, LlamaForCausalLM,
                               granite_hybrid_tiny, llama_tiny)
from paddle_tpu.ops.pallas import (paged_decode_attention,
                                   paged_decode_attention_refusal)
from paddle_tpu.ops.pallas.paged_decode_attention import KERNEL_NAME
from paddle_tpu.serving import ServingEngine, TRASH_PAGE

pytestmark = pytest.mark.serving

P, MP, N = 16, 5, 40          # page tokens, table slots a row, pool pages


def einsum_attention(q, k, v, tables, positions, scale=None):
    """``_paged_attention`` after its scatter: gather the whole padded
    table, dense scores, causal mask, softmax."""
    R, s, h, d = q.shape
    kv = k.shape[2]
    C = tables.shape[1] * k.shape[1]
    kk = k[tables].reshape(R, C, kv, d)
    vv = v[tables].reshape(R, C, kv, d)
    q5 = q.reshape(R, s, kv, h // kv, d).astype(kk.dtype)
    scores = jnp.einsum("bskgd,bckd->bkgsc", q5, kk,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(float(d)) if scale is None \
        else scores * float(scale)
    pos_js = positions[:, None] + jnp.arange(s)[None, :]
    scores = jnp.where(jnp.arange(C)[None, None, None, None, :]
                       <= pos_js[:, None, None, :, None], scores,
                       jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bkgsc,bckd->bskgd",
                     jax.nn.softmax(scores, axis=-1).astype(vv.dtype), vv,
                     preferred_element_type=jnp.float32)
    return out.reshape(R, s, h, d).astype(q.dtype)


# rows of one decode step as (position, n_tok); n_tok 0 is an idle row
ROWS = {
    "one_live_page": [(3, 1)],
    "page_boundary": [(P - 1, 1), (P, 1), (2 * P - 1, 1), (2 * P, 1)],
    "full_table": [(MP * P - 1, 1)],
    "idle_row": [(0, 0), (11, 1), (0, 0)],
    "ragged_batch": [(0, 1), (MP * P - 1, 1), (0, 0), (17, 1), (P, 1)],
    "all_idle": [(0, 0), (0, 0)],
}
SPEC_ROWS = {
    # S = 3: a row's queries straddle a page boundary; a short row carries
    # fewer valid queries than the width
    "speculative": [(P - 2, 3), (20, 1), (0, 0), (MP * P - 3, 3), (5, 2)],
}


def _case(rows, width, h, kv, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    R = len(rows)
    q = jnp.asarray(rng.normal(size=(R, width, h, d)), dtype)
    # the pool is POISONED where no row lives: a page the kernel must not
    # visit (trash page, free pages) would show as NaN
    k = np.full((N, P, kv, d), np.nan, np.float32)
    v = np.full((N, P, kv, d), np.nan, np.float32)
    tables = np.full((R, MP), TRASH_PAGE, np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    for r, (pos, n) in enumerate(rows):
        if n == 0:
            continue
        for j in range(-(-(pos + n) // P)):
            page = free.pop()
            tables[r, j] = page
            k[page] = rng.normal(size=(P, kv, d))
            v[page] = rng.normal(size=(P, kv, d))
    positions = np.asarray([p for p, _ in rows], np.int32)
    n_tok = np.asarray([n for _, n in rows], np.int32)
    return (q, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(tables), jnp.asarray(positions), jnp.asarray(n_tok))


# (heads, kv heads, head width, the arena keeps a token's heads merged,
# score scale): the merged cases are 64-wide heads on a flat arena, as the
# engine keeps granite's, one of them under a scale that is not d ** -0.5
HEADS = {"gqa32_8": (32, 8, 16, False, None), "mha": (4, 4, 16, False, None),
         "merged8_2": (8, 2, 64, True, None),
         "merged32_8_scaled": (32, 8, 64, True, 1 / 64)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("name,width", [(n, 1) for n in ROWS]
                         + [(n, 3) for n in SPEC_ROWS])
def test_kernel_matches_einsum(name, width, heads, dtype):
    h, kv, d, merged, scale = HEADS[heads]
    rows = (ROWS | SPEC_ROWS)[name]
    q, k, v, tables, positions, n_tok = _case(rows, width, h, kv, d, dtype)
    # what the kernel is handed: the arena as the engine keeps it
    flat = (lambda a: a.reshape(N, P, kv * d)) if merged else (lambda a: a)
    assert paged_decode_attention_refusal(
        q.shape, flat(k).shape, tables.shape, dtype, interpret=True) is None
    got = np.asarray(paged_decode_attention(
        q, flat(k), flat(v), tables, positions, n_tok, scale=scale,
        interpret=True), np.float32)
    # the einsum sees every slot of the padded table: give it a pool with
    # the poison cleared (masked columns must be finite there)
    clear = lambda a: jnp.nan_to_num(a, nan=0.0)
    want = np.asarray(einsum_attention(q, clear(k), clear(v), tables,
                                       positions, scale), np.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-6
    assert np.all(np.isfinite(got)), "a page outside a row's live ones was read"
    for r, (_, n) in enumerate(rows):
        if n == 0:
            assert not got[r].any()
        else:
            np.testing.assert_allclose(got[r, :n], want[r, :n], atol=tol,
                                       rtol=tol)


@pytest.mark.parametrize("case,reason", [
    (dict(d=16, interpret=False), "head_dim"),       # one lane wide on chip
    (dict(d=128, interpret=False), None),
    (dict(h=6, kv=4), "shape"),
    (dict(kv=1, dtype=jnp.bfloat16, page=8), "page_rows"),
    (dict(rows=512, slots=64), "table_size"),
    (dict(rows=256, width=8, h=32, kv=8, d=128, page=128), "vmem"),
    # 64-wide heads: a token's merged heads are whole lane registers, the
    # 4-D arena's rows are half of one
    (dict(h=32, kv=8, d=64, page=128, flat=True, interpret=False), None),
    (dict(h=32, kv=8, d=64, page=128, interpret=False), "head_dim"),
    (dict(h=6, kv=3, d=64, page=128, flat=True, interpret=False),
     "head_dim"),
    (dict(h=6, kv=4, d=64, flat=True), "shape"),
    # q and out are whole in VMEM at the merged width: a speculative width
    # of 3 at granite's cell no longer fits
    (dict(rows=64, width=1, h=32, kv=8, d=64, page=128, flat=True,
          dtype=jnp.bfloat16, interpret=False), None),
    (dict(rows=64, width=3, h=32, kv=8, d=64, page=128, flat=True,
          dtype=jnp.bfloat16, interpret=False), "vmem"),
])
def test_gate(case, reason):
    c = dict(rows=4, width=1, h=8, kv=4, d=16, page=8, slots=MP,
             dtype=jnp.float32, interpret=True, flat=False) | case
    arena = (N, c["page"], c["kv"] * c["d"]) if c["flat"] \
        else (N, c["page"], c["kv"], c["d"])
    assert paged_decode_attention_refusal(
        (c["rows"], c["width"], c["h"], c["d"]), arena,
        (c["rows"], c["slots"]), c["dtype"],
        interpret=c["interpret"]) == reason


# -- the engine -------------------------------------------------------------
@pytest.fixture(scope="module")
def model():
    paddle.seed(5)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=96,
                                    max_position_embeddings=128))
    m.eval()
    return m


@pytest.fixture
def interpreted():
    from paddle_tpu.distributed import topology

    prior = paddle.get_flags(["pallas_interpret"])
    prior_hcg = topology.get_hybrid_communicate_group()
    topology._hcg = None          # an earlier distributed test's mesh
    paddle.set_flags({"pallas_interpret": True})
    telemetry.reset()
    yield
    paddle.set_flags(prior)
    topology._hcg = prior_hcg


def _serve(model, **engine_kw):
    eng = ServingEngine(model, max_batch=3, page_tokens=8, num_pages=24,
                        max_pages_per_seq=6, **engine_kw)
    rng = np.random.default_rng(1)
    rids = [eng.submit(rng.integers(1, 96, n).astype(np.int32),
                       max_new_tokens=new)
            for n, new in ((5, 12), (9, 20), (16, 7), (3, 30))]
    outs = eng.run()
    return eng, [outs[r].tolist() for r in rids]


def _fallbacks():
    return {k: v for k, v in telemetry.counters().items()
            if k.startswith(f"kernel_fallback.{KERNEL_NAME}")}


def test_engine_tokens_match_einsum_engine(model, interpreted):
    eng, got = _serve(model)
    paddle.set_flags({"pallas_interpret": False})
    eng_plain, plain = _serve(model)
    paddle.set_flags({"pallas_interpret": True})
    assert got == plain
    assert eng._decode_compiles == eng_plain._decode_compiles == 1
    assert not _fallbacks()
    # the kernel is in the interpreted engine's decode program and not in
    # the plain one's, nor in any prefill program
    pa, ba = eng._param_arrays()
    args = (pa, ba, eng._arenas, jnp.zeros((3, 1), jnp.int32),
            jnp.zeros((3,), jnp.int32), jnp.zeros((3, 6), jnp.int32),
            jnp.ones((3,), jnp.int32))
    assert KERNEL_NAME in str(jax.make_jaxpr(eng._decode_fn)(*args))
    paddle.set_flags({"pallas_interpret": False})
    assert KERNEL_NAME not in str(jax.make_jaxpr(eng._decode_fn)(*args))
    paddle.set_flags({"pallas_interpret": True})
    prefill = (pa, ba, eng._arenas, jnp.zeros((1, 8), jnp.int32),
               jnp.int32(0), jnp.zeros((1, 6), jnp.int32), jnp.int32(7))
    assert KERNEL_NAME not in str(jax.make_jaxpr(eng._prefill_fn)(*prefill))


def test_merged_pages_under_the_layers_own_scale_walk(interpreted):
    """A model whose heads are narrower than a lane register (the engine
    keeps a token's heads merged) and whose attention layers scale their
    scores by ``attention_multiplier``: decode walks the pages where they
    lie and emits the einsum engine's tokens."""
    paddle.seed(7)
    # weights wide enough that attention moves the logits
    hybrid = GraniteHybridForCausalLM(
        granite_hybrid_tiny(initializer_range=0.1))
    hybrid.eval()
    (spec,) = [la for la in hybrid.serve_layers() if hasattr(la, "heads")]
    assert spec.scale != spec.head_dim ** -0.5
    eng, got = _serve(hybrid)
    assert eng._flat_pages and eng._arenas["k"][0].ndim == 3
    paddle.set_flags({"pallas_interpret": False})
    eng_plain, plain = _serve(hybrid)
    paddle.set_flags({"pallas_interpret": True})
    assert got == plain
    assert eng._decode_compiles == eng_plain._decode_compiles == 1
    assert not _fallbacks()
    pa, ba = eng._param_arrays()
    args = (pa, ba, eng._arenas, jnp.zeros((3, 1), jnp.int32),
            jnp.zeros((3,), jnp.int32), jnp.zeros((3, 6), jnp.int32),
            jnp.ones((3,), jnp.int32))
    assert KERNEL_NAME in str(jax.make_jaxpr(eng._decode_fn)(*args))

    # tokens forgive a wrong scale; the logits do not (5e-3 apart under
    # ``d ** -0.5``, 1e-7 under the layer's own)
    def live_logits():
        eng = ServingEngine(hybrid, max_batch=3, page_tokens=8, num_pages=24,
                            max_pages_per_seq=6)
        rng = np.random.default_rng(2)
        for n in (9, 16):
            eng.submit(rng.integers(1, 96, n).astype(np.int32),
                       max_new_tokens=12)
        for _ in range(6):
            eng.step()
        assert len(eng._active) == 2
        return eng.last_decode_logits[:2]

    walked = live_logits()
    paddle.set_flags({"pallas_interpret": False})
    np.testing.assert_allclose(walked, live_logits(), rtol=1e-5, atol=1e-5)


def _walking_programs(eng) -> int:
    """The programs whose decode rows ask the page walk: the decode
    program, and the prefill width whose launches the rows ride."""
    return 1 + sum(map(eng._carries_rows, eng._prefill_widths))


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_pools_fall_back_loudly(model, interpreted, kv_dtype):
    eng, got = _serve(model, kv_dtype=kv_dtype)
    assert all(len(g) for g in got) and eng._decode_compiles == 1
    # one count an attention layer a program that walks decode rows: each
    # of them gathers, in the decode program and in the decode part of
    # every riding prefill width
    assert _fallbacks() == {f"kernel_fallback.{KERNEL_NAME}.kv_dtype":
                            model.config.num_hidden_layers
                            * _walking_programs(eng)}


def test_live_hybrid_mesh_falls_back_loudly(model, interpreted):
    """The engine's program is a one-device program: with a hybrid mesh
    live the dispatch says "mesh", and the decode program gathers."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import topology

    strategy = dist.fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": -1}
    dist.fleet.init(is_collective=True, strategy=strategy)
    try:
        eng, got = _serve(model)
    finally:
        topology._hcg = None
    assert all(len(g) for g in got) and eng._decode_compiles == 1
    assert _fallbacks() == {f"kernel_fallback.{KERNEL_NAME}.hybrid_mesh":
                            model.config.num_hidden_layers
                            * _walking_programs(eng)}


def test_speculative_width_walks_pages(model, interpreted):
    eng, got = _serve(model, speculative=2)
    paddle.set_flags({"pallas_interpret": False})
    _, plain = _serve(model, speculative=2)
    assert got == plain and eng._decode_compiles == 1
    assert not _fallbacks()


def test_mesh_engine_programs_refuse_kernels_by_name(monkeypatch):
    """A TP / CP engine's programs are traced inside ``gspmd_program()``
    (``ServingEngine._compile``): GSPMD cannot partition a Mosaic call, so
    on a TPU every kernel dispatch in them is refused and counted as
    ``no_mesh``.  A one-device engine's programs get their kernels and
    count nothing.  (The platform check is steered here, in the test: the
    CPU answers "not a TPU" before the scope is ever asked.)"""
    import paddle_tpu.ops as ops

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (virtual) devices")

    def build():        # TP shards the params in place: a model an engine
        paddle.seed(5)
        m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=96,
                                        max_position_embeddings=128))
        m.eval()
        return m

    kw = dict(max_batch=3, page_tokens=8, num_pages=24, max_pages_per_seq=6)
    seen = []

    def probe(params, buffers, arenas):
        seen.append(ops.pallas_eligible("use_decode_attention"))
        return arenas

    args = (jnp.zeros(()), jnp.zeros(()), jnp.zeros((2,)))
    key = "kernel_fallback.use_decode_attention.no_mesh"
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    prior = paddle.get_flags(["pallas_interpret"])
    paddle.set_flags({"pallas_interpret": False})
    telemetry.reset()
    try:
        ServingEngine(build(), **kw)._compile(probe, args, "probe")
        assert seen == [True] and key not in telemetry.counters()
        ServingEngine(build(), tp=2, **kw)._compile(probe, args, "probe")
        assert seen == [True, False] and telemetry.counters()[key] == 1
    finally:
        paddle.set_flags(prior)
