"""The decode step chooses its tokens on the device: the program returns the
greedy ids ``[R, S]`` with a non-finite position flagged, the host fetches
those, and the logits ``[R, S, V]`` stay on the chip until
``ServingEngine.last_decode_logits`` is read.  Held here: the tokens are the
ones numpy would choose from the same logits (serial, speculative, an int8
pool, a model with state layers), the tripwire still names rid and
``kv_dtype`` and looks at live positions only, and every fetch of the whole
array is counted."""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import SpecConfig
from paddle_tpu.models import (GraniteHybridForCausalLM, LlamaForCausalLM,
                               granite_hybrid_tiny, llama_tiny)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.engine import NON_FINITE, greedy_choice
from tests.test_speculative import _OracleDrafter

pytestmark = [pytest.mark.serving]

VOCAB = 96
KNOBS = dict(max_batch=3, page_tokens=8, num_pages=24, max_pages_per_seq=6,
             lint=True)
NEW_TOKENS = 9


@pytest.fixture(scope="module")
def llama():
    paddle.seed(3)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=VOCAB,
                                    max_position_embeddings=128))
    m.eval()
    return m


@pytest.fixture(scope="module")
def hybrid():
    paddle.seed(7)
    m = GraniteHybridForCausalLM(granite_hybrid_tiny())
    m.eval()
    return m


def prompts(vocab=VOCAB, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, n).astype(np.int32) for n in (5, 9, 3, 8)]


class HalfWrongDrafter(_OracleDrafter):
    """Drafts the serial stream's own continuation with every second token
    wrong, so a verify step accepts part of a draft and rejects the rest."""

    def propose(self, k):
        return [t if i % 2 == 0 else (t + 1) % VOCAB
                for i, t in enumerate(super().propose(k))]


def serve(model, ps, sample=None, **kw):
    """Serve ``ps`` to the end.  ``sample(eng, stepped, choice, n_tok,
    drafts)`` stands in for the engine's ``_decode_sample`` where given."""
    eng = ServingEngine(model, **{**KNOBS, **kw})
    if sample is not None:
        eng._decode_sample = lambda *a: sample(eng, *a)
    rids = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in ps]
    outs = eng.run()
    eng.pool.check_leaks()
    return eng, [outs[r].tolist() for r in rids]


def checked_sample(eng, stepped, choice, n_tok, drafts):
    """The engine's own booking, with every step's tokens held to numpy's
    choice from the logits the engine kept."""
    logits = eng.last_decode_logits
    want, before = {}, {}
    for r in stepped:
        nv = int(n_tok[r.row])
        assert np.isfinite(np.asarray(logits[r.row, :nv], np.float32)).all()
        want[r.rid] = np.argmax(logits[r.row, :nv], -1).tolist()
        assert choice[r.row, :nv].tolist() == want[r.rid]
        before[r.rid] = len(r.generated)
    ServingEngine._decode_sample(eng, stepped, choice, n_tok, drafts)
    for r in stepped:
        emitted = r.generated[before[r.rid]:]
        assert 1 <= len(emitted) <= len(want[r.rid])
        assert emitted == want[r.rid][:len(emitted)]
    eng.steps_checked = getattr(eng, "steps_checked", 0) + 1


def host_sample(eng, stepped, choice, n_tok, drafts):
    """As the parent chose: the whole array to the host, ``np.isfinite`` and
    ``np.argmax`` a live row at a time.  Positions nobody may look at are
    handed on as NON_FINITE."""
    logits = eng.last_decode_logits
    host = np.full(choice.shape, NON_FINITE, np.int32)
    for r in stepped:
        nv = int(n_tok[r.row])
        row = logits[r.row, :nv]
        if np.all(np.isfinite(row)):
            host[r.row, :nv] = [int(np.argmax(row[i])) for i in range(nv)]
    ServingEngine._decode_sample(eng, stepped, host, n_tok, drafts)


def half_wrong(model, ps):
    _, serial = serve(model, ps)
    streams = {tuple(int(t) for t in p): out for p, out in zip(ps, serial)}
    return SpecConfig(k=3, drafter=lambda: HalfWrongDrafter(streams))


CASES = {
    "serial": ("llama", lambda m, ps: {}),
    "speculative": ("llama", lambda m, ps: {"speculative": half_wrong(m, ps)}),
    "int8": ("llama", lambda m, ps: {"kv_dtype": "int8"}),
    "state": ("hybrid", lambda m, ps: {"page_tokens": 16, "num_pages": 40,
                                       "max_pages_per_seq": 8}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_tokens_are_numpys_choice_from_the_kept_logits(case, request):
    fixture, knobs = CASES[case]
    model = request.getfixturevalue(fixture)
    ps = prompts(model.config.vocab_size)
    kw = knobs(model, ps)
    eng, got = serve(model, ps, checked_sample, **kw)
    assert eng.steps_checked > 0 and eng._decode_compiles == 1
    assert eng.lint_report is not None and eng.lint_report.ok
    ref, want = serve(model, ps, host_sample, **kw)
    assert got == want
    assert all(len(t) == NEW_TOKENS for t in got)
    if case == "speculative":
        s = eng.meter.summary()
        assert 0.0 < s["spec_acceptance"] < 1.0     # drafts partly wrong
        for key in ("spec_acceptance", "effective_tokens_per_step"):
            assert s[key] == ref.meter.summary()[key]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_greedy_choice_is_np_argmax_with_non_finite_flagged(dtype):
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 3, 50)).astype(np.float32)
    x[0, 0, [7, 31]] = 9.0                      # a tie: the first index
    x[1, 1, 5] = np.nan
    x[2, 0, 9] = np.inf
    x[3, 2, 0] = -np.inf
    x = jnp.asarray(x, dtype)
    got = greedy_choice(x)
    assert got.dtype == jnp.int32 and got.shape == (4, 3)
    # held in the dtype the program has: bfloat16 rounds many values onto
    # one, so ties are common there
    host = np.asarray(x)
    want = np.where(np.isfinite(host.astype(np.float32)).all(-1),
                    np.argmax(host, -1), NON_FINITE)
    assert np.asarray(got).tolist() == want.tolist()
    assert got[0, 0] == 7
    assert [int(got[1, 1]), int(got[2, 0]), int(got[3, 2])] == \
        [NON_FINITE] * 3
    assert (np.asarray(got) == NON_FINITE).sum() == 3


@pytest.mark.parametrize("kv_dtype, plane", [("bf16", "k"), ("int8", "ks")])
def test_poisoned_cache_raises_naming_rid_and_kv_dtype(llama, kv_dtype, plane):
    eng = ServingEngine(llama, **{**KNOBS, "kv_dtype": kv_dtype})
    rid = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=6)
    eng.step()                          # prefill + first decode step
    page = eng.pool.table(rid)[0]
    eng._arenas[plane][0] = eng._arenas[plane][0].at[page].set(jnp.nan)
    with pytest.raises(RuntimeError, match=(
            rf"non-finite decode logits for rid {rid} "
            rf"\(kv_dtype={kv_dtype}\): corrupted KV page or scale buffer")):
        for _ in range(4):
            eng.step()


def test_junk_positions_and_idle_rows_never_raise(llama):
    """Non-finite values where no live position is — idle rows, and a row's
    positions past its ``n_tok`` — leave the stream as it was."""
    ps = prompts()[:2]                  # 2 live rows of 3
    spec = half_wrong(llama, ps)
    _, clean = serve(llama, ps, speculative=spec)

    eng = ServingEngine(llama, **KNOBS, speculative=spec)
    run, seen = eng._run_decode, {"dead": 0, "steps": 0}

    def poisoned(tokens, positions, tables, n_tok):
        choice = run(tokens, positions, tables, n_tok)
        live = jnp.arange(choice.shape[1])[None] < n_tok[:, None]
        seen["dead"] += int((~live).sum())
        seen["steps"] += 1
        eng._decode_logits = jnp.where(live[..., None], eng._decode_logits,
                                       jnp.nan)
        return jnp.where(live, choice, NON_FINITE)

    eng._run_decode = poisoned
    rids = [eng.submit(p, max_new_tokens=NEW_TOKENS) for p in ps]
    outs = eng.run()
    assert [outs[r].tolist() for r in rids] == clean
    # idle rows in every step, and drafts shorter than the verify width
    assert seen["dead"] > seen["steps"] * eng._spec_width
    logits = eng.last_decode_logits
    assert np.isnan(np.asarray(logits, np.float32)).any()


def test_logits_are_fetched_on_request_and_every_fetch_is_counted(llama):
    eng = ServingEngine(llama, **KNOBS, speculative=2)
    assert eng.last_decode_logits is None
    assert eng.meter.summary()["decode_logits_fetches"] == 0
    for p in prompts():
        eng.submit(p, max_new_tokens=NEW_TOKENS)
    eng.run()
    # a run nobody read the logits of
    assert eng.meter.summary()["decode_logits_fetches"] == 0
    with pytest.raises(AttributeError):
        eng.last_decode_logits = None   # read-only
    for n in (1, 2, 3):
        got = eng.last_decode_logits
        assert isinstance(got, np.ndarray)
        assert got.shape == (KNOBS["max_batch"], 3, VOCAB)
        assert got.dtype == eng._decode_logits.dtype == np.float32
        assert eng.meter.summary()["decode_logits_fetches"] == n
    # the engine holds the latest step's array and no other
    np.testing.assert_array_equal(got, np.asarray(eng._decode_logits))
