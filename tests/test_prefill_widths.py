"""The prefill program at a ladder of launch widths
(``serving.engine.PREFILL_WIDTHS`` pages a launch) against a one-page-a-launch
prefill of the same engine: first-token logits, every page, the rows' state
and the greedy continuation, for the Llama block and the tiny Granite hybrid;
the trash page takes what a wide launch holds past a prompt's pages; a
prefix-cache hit resumes at any page; exported frames are a local prefill's;
every width compiles once, on the first prefill, under one module name.
CPU, float32, tiny models."""

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import (GraniteHybridForCausalLM, LlamaForCausalLM,
                               granite_hybrid_tiny, llama_tiny)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as serving_engine
from paddle_tpu.serving.engine import PREFILL_WIDTHS, prefill_plan
from paddle_tpu.serving.kv_pool import TRASH_PAGE

P = 8
W = PREFILL_WIDTHS[-1]                  # the widest launch, in pages
# a table that a prompt can fill so that its last launch runs past it, where
# the ladder has such a gap (else two of the widest launches and a page)
MP = next((n for n in range(W + 1, 3 * W)
           if sum(prefill_plan(n)) > n), 2 * W + 1)
VOCAB = 96
KNOBS = dict(max_batch=2, page_tokens=P, num_pages=4 * MP + 2,
             max_pages_per_seq=MP)
LENGTHS = {"one": 1, "P-1": P - 1, "P": P, "P+1": P + 1,
           "wP-1": W * P - 1, "wP": W * P, "wP+1": W * P + 1,
           "table": MP * P}


@pytest.fixture(scope="module")
def llama():
    paddle.seed(11)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=VOCAB,
                                    max_position_embeddings=4 * (W + 2) * P))
    m.eval()
    return m


@pytest.fixture(scope="module")
def hybrid():
    paddle.seed(11)
    m = GraniteHybridForCausalLM(granite_hybrid_tiny(vocab_size=VOCAB))
    m.eval()
    return m


def engine(model, *, page_a_launch=False, **knobs):
    eng = ServingEngine(model, **dict(KNOBS, **knobs))
    if page_a_launch:           # what the engine did before it had a ladder
        eng._prefill_widths = (1,)
    return eng


def prompt(n, seed=0):
    return np.random.default_rng(seed + n).integers(1, VOCAB, n) \
        .astype(np.int32)


def prefill_alone(eng, p, row=1, key="alone"):
    """Run ``p``'s prefill on pages of its own through state slot ``row``;
    the last token's logits, the launches, and the page ids."""
    import jax.numpy as jnp

    eng.pool.alloc(key, eng.pool.pages_for(len(p)))
    table = jnp.asarray(eng._padded_table(key)[None])
    logits, launches = eng._prefill_chunks(p, table, 0, row)
    return np.asarray(logits), launches, list(eng.pool.table(key))


def arenas(eng):
    return {f"{key}[{li}]": np.asarray(a)
            for key, arrs in eng._arenas.items() for li, a in enumerate(arrs)}


def close(got, want, what=""):
    scale = max(float(np.abs(want).max()), 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5 * scale,
                               err_msg=what)


# -- the plan ---------------------------------------------------------------
@pytest.mark.parametrize("pages", range(1, 2 * W + 3))
def test_plan_covers_the_pages_and_only_its_last_launch_runs_past(pages):
    plan = prefill_plan(pages)
    assert set(plan) <= set(PREFILL_WIDTHS)
    assert sum(plan[:-1]) < pages <= sum(plan)
    # a launch is more than half full, or it is the narrowest there is
    junk = sum(plan) - pages
    assert 2 * junk < plan[-1] or plan[-1] == PREFILL_WIDTHS[0]
    assert len(plan) >= -(-pages // W)


@pytest.mark.parametrize("pages, widths, plan", [
    (1, (1, 4), [1]), (2, (1, 4), [1, 1]), (3, (1, 4), [4]),
    (4, (1, 4), [4]), (5, (1, 4), [4, 1]), (6, (1, 4), [4, 1, 1]),
    (7, (1, 4), [4, 4]), (17, (1, 4), [4, 4, 4, 4, 1]),
    (1, (2, 4), [2]), (3, (2, 4), [4]), (6, (2, 4), [4, 2]),
    (3, (1, 2, 4), [4]), (2, (1, 2, 4), [2]), (4, (1, 8), [1, 1, 1, 1]),
    (5, (1, 8), [8]), (9, (1,), [1] * 9), (3, (4,), [4]), (5, (4,), [4, 4]),
])
def test_plan_of_a_given_ladder(pages, widths, plan):
    assert prefill_plan(pages, widths) == plan


def test_the_ladder_is_short_and_starts_at_what_a_table_can_hold(llama):
    assert 1 <= len(PREFILL_WIDTHS) <= 3
    assert list(PREFILL_WIDTHS) == sorted(set(PREFILL_WIDTHS))
    assert engine(llama)._prefill_widths == PREFILL_WIDTHS
    # a launch wider than a row's table could never be full
    narrow = engine(llama, max_pages_per_seq=1, num_pages=8)
    assert narrow._prefill_widths == tuple(
        w for w in PREFILL_WIDTHS if w <= max(1, PREFILL_WIDTHS[0]))


# -- (a) a wide prefill is a page-a-launch prefill --------------------------
@pytest.mark.parametrize("which", ["llama", "hybrid"])
@pytest.mark.parametrize("name", list(LENGTHS))
def test_wide_prefill_equals_a_page_a_launch(which, name, request):
    model = request.getfixturevalue(which)
    n = LENGTHS[name]
    p = prompt(n)
    wide, narrow = engine(model), engine(model, page_a_launch=True)
    got, launches, pages = prefill_alone(wide, p)
    want, pages_run, pages_narrow = prefill_alone(narrow, p)
    assert pages == pages_narrow and pages_run == len(pages) == -(-n // P)
    assert launches == len(prefill_plan(len(pages)))
    close(got, want, "first-token logits")
    assert int(np.argmax(got)) == int(np.argmax(want))
    a, b = arenas(wide), arenas(narrow)
    keep = np.arange(wide.num_pages) != TRASH_PAGE
    for key in a:
        if key.startswith(("k[", "v[")):
            # every page the prompt owns (its last page's junk tail too),
            # and no other page written
            close(a[key][keep], b[key][keep], key)
        else:
            # the row's conv tail and recurrent state after the prompt
            close(a[key], b[key], key)
            assert np.abs(a[key][1]).max() > 0 and not a[key][0].any()
    # the greedy continuation, through the scheduler
    new = min(6, MP * P - n)
    if new:
        for eng in (wide, narrow):
            eng.pool.free("alone")
        rid_w = wide.submit(p, max_new_tokens=new)
        rid_n = narrow.submit(p, max_new_tokens=new)
        assert wide.run()[rid_w].tolist() == narrow.run()[rid_n].tolist()
        wide.pool.check_leaks()


# -- (b) what runs past a prompt's pages lands in the trash page -------------
@pytest.mark.parametrize("which", ["llama", "hybrid"])
def test_a_full_table_prompt_touches_no_page_but_its_own(which, request):
    """Another row is live beside a prompt that fills its whole table: a
    position past the table must not be clipped onto the table's last page,
    nor anything land on the neighbour's pages or state."""
    model = request.getfixturevalue(which)
    eng = engine(model)
    a = prompt(P + 3, 5)
    rid = eng.submit(a, max_new_tokens=9)
    for _ in range(3):
        eng.step()              # row 0 is decoding
    assert [r.row for r in eng._active.values()] == [0]
    before = arenas(eng)
    p = prompt(MP * P, 7)
    got, launches, pages = prefill_alone(eng, p, row=1)
    assert len(pages) == MP and sum(prefill_plan(MP)) >= MP
    after = arenas(eng)
    others = np.ones(eng.num_pages, bool)
    others[pages + [TRASH_PAGE]] = False
    assert set(eng.pool.table(rid)) <= set(np.flatnonzero(others))
    for key in before:
        if key.startswith(("k[", "v[")):
            np.testing.assert_array_equal(after[key][others],
                                          before[key][others], key)
        else:
            np.testing.assert_array_equal(after[key][0], before[key][0], key)
    # its own pages hold what a page-a-launch prefill writes
    narrow = engine(model, page_a_launch=True)
    want, _, pages_narrow = prefill_alone(narrow, p, row=1, key="other")
    close(got, want, "first-token logits")
    b = arenas(narrow)
    for key in after:
        if key.startswith(("k[", "v[")):
            close(after[key][pages], b[key][pages_narrow], key)
    # and the neighbour goes on as if alone
    eng.pool.free("alone")
    alone = engine(model, page_a_launch=True)
    rid_alone = alone.submit(a, max_new_tokens=9)
    assert eng.run()[rid].tolist() == alone.run()[rid_alone].tolist()


# -- (c) a prefix-cache hit resumes at any page ------------------------------
@pytest.mark.parametrize("cached_pages", sorted({1, W - 1, W + 1} - {0}))
def test_prefix_hit_at_a_page_that_is_no_multiple_of_the_width(llama,
                                                               cached_pages):
    room = dict(max_pages_per_seq=2 * W + 4, num_pages=6 * W + 12)
    eng = engine(llama, prefix_cache=True, **room)
    narrow = engine(llama, page_a_launch=True, **room)
    shared = prompt(cached_pages * P, 3)
    first = np.concatenate([shared, prompt(5, 1)])
    second = np.concatenate([shared, prompt((W + 1) * P + 3, 2)])
    seen = []
    run_chunks = eng._prefill_chunks

    def spy(p, table, c0=0, row=0, ride=None):
        out = run_chunks(p, table, c0, row, ride)
        seen.append((c0, out[1]))
        return out

    eng._prefill_chunks = spy
    out = {}
    for p in (first, second):
        rid, rid_n = (e.submit(p, max_new_tokens=5) for e in (eng, narrow))
        out[rid] = (eng.run()[rid].tolist(), narrow.run()[rid_n].tolist())
    assert all(got == want for got, want in out.values())
    (c0_first, _), (c0, launches) = seen
    assert c0_first == 0 and c0 == cached_pages
    if W > 1:
        assert c0 % W != 0
    assert launches == len(prefill_plan(-(-len(second) // P) - c0))


# -- (d) exported frames are a local prefill's -------------------------------
@pytest.mark.parametrize("name", ["P+1", "wP+1", "table"])
def test_prefill_export_reproduces_a_local_prefill(llama, name):
    n = LENGTHS[name]
    p = prompt(n, 4)
    pre, ref = engine(llama), engine(llama, page_a_launch=True)
    first, frames = pre.prefill_export(p)
    first_ref, frames_ref = ref.prefill_export(p)
    assert first == first_ref and len(frames) == -(-n // P)
    for f, g in zip(frames, frames_ref):
        for key in f:
            close(f[key], g[key], key)
    assert pre.meter.summary()["prefill_launches"] == \
        len(prefill_plan(len(frames)))
    pre.pool.check_leaks()
    new = min(5, MP * P - n)
    if new:
        dec, local = engine(llama), engine(llama, page_a_launch=True)
        rid = dec.submit_prefilled(p, first, frames, max_new_tokens=new)
        rid_l = local.submit(p, max_new_tokens=new)
        assert dec.run()[rid].tolist() == local.run()[rid_l].tolist()
        # imported pages: no program launched
        assert dec.meter.summary()["prefill_launches"] == 0


# -- (e) one module name, every width compiled on the first prefill ----------
@pytest.mark.parametrize("which", ["llama", "hybrid"])
def test_every_width_compiles_once_on_the_first_prefill(which, request):
    eng = engine(request.getfixturevalue(which))
    compiled, compile_ = [], eng._compile

    def spy(fn, args, name, **kw):
        exe = compile_(fn, args, name, **kw)
        compiled.append((name, args[3].shape, exe))
        return exe

    eng._compile = spy
    eng.submit(prompt(3), max_new_tokens=1)
    eng.run()                   # one page, one token: prefill only
    ladder = eng._prefill_widths
    # the decode program compiles first, whether or not the rows ride the
    # launches (it chooses the weights' layouts), then every width
    assert eng.rides_prefill == (which == "llama")
    assert [(name, shape) for name, shape, _ in compiled] == \
        [(serving_engine.DECODE_PROGRAM, (KNOBS["max_batch"], 1))] + \
        [(serving_engine.PREFILL_PROGRAM, (1, w * P)) for w in ladder]
    assert sorted(eng._prefill_exec) == list(ladder)
    for name, _, exe in compiled:
        assert exe.as_text().splitlines()[0].split()[1].rstrip(",") == \
            "jit_" + name
    for n in (1, P + 1, W * P, MP * P - 3):
        eng.submit(prompt(n, 9), max_new_tokens=3)
    eng.run()
    assert [name for name, _, _ in compiled] == \
        [serving_engine.DECODE_PROGRAM] \
        + [serving_engine.PREFILL_PROGRAM] * len(ladder)
    assert len(ladder) <= len(PREFILL_WIDTHS)


# -- (f) the span and the meter count launches -------------------------------
def test_prefill_span_and_meter_count_launches(llama, tmp_path):
    from jax.profiler import ProfileData

    from benchmark.lib import program_spans
    from benchmark.lib import trace as bench_trace

    eng = engine(llama)
    lengths = {1: 3, 2: W * P, 3: MP * P - 2}
    jax.profiler.start_trace(str(tmp_path))
    try:
        for rid, n in lengths.items():
            eng.submit(prompt(n, 6), max_new_tokens=2, rid=rid)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    profile = ProfileData.from_file(bench_trace.newest_xplane(str(tmp_path)))
    facts = {dict(s.facts)["rid"]: dict(s.facts)
             for s in program_spans.from_profile(profile)
             if s.name == "serve.prefill"}
    assert set(facts) == set(lengths)
    for rid, n in lengths.items():
        pages = -(-n // P)
        assert facts[rid]["chunks"] == pages
        assert facts[rid]["launches"] == len(prefill_plan(pages))
    assert eng.meter.summary()["prefill_launches"] == \
        sum(f["launches"] for f in facts.values())
    if W > 1:                   # the wide program engaged
        assert sum(f["chunks"] for f in facts.values()) > \
            sum(f["launches"] for f in facts.values())
