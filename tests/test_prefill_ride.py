"""A step that prefills carries its decode rows on its LAST prefill launch
(``ServingEngine.rides_prefill``): the prompt's tokens and the rows' tokens
are one row of the riding program, so the weights are read once, and the
step fetches once.  Held here against the two-program schedule of the same
engine (``rides_prefill = False`` before the first step): the greedy
streams of overlapping requests, the rows' logits, eviction while a step
will ride, speculative drafts; the programs it compiles; a model with state
layers, which keeps the two programs and says why; the spans a rode step
leaves and what the readers make of them.  CPU, float32, tiny models."""

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.models import (DeepseekV3ForCausalLM,
                               GraniteHybridForCausalLM, LlamaForCausalLM,
                               deepseek_v3_tiny, granite_hybrid_tiny,
                               llama_tiny)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as serving_engine
from paddle_tpu.serving.engine import PREFILL_WIDTHS, RUNNING

P = 8
VOCAB = 96
KNOBS = dict(max_batch=4, page_tokens=P, num_pages=72, max_pages_per_seq=12)
# prompts from one token to several wide launches (9 pages: 4 + 4 + 1)
LENGTHS = (1, P, 5 * P + 3, 3, 2 * P + 1, 9 * P, P + 2, 4)


@pytest.fixture(scope="module")
def llama():
    paddle.seed(11)
    m = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=VOCAB,
                                    max_position_embeddings=256))
    m.eval()
    return m


@pytest.fixture(scope="module")
def deepseek():
    paddle.seed(11)
    m = DeepseekV3ForCausalLM(deepseek_v3_tiny(vocab_size=VOCAB))
    m.eval()
    return m


@pytest.fixture(scope="module")
def hybrid():
    paddle.seed(11)
    m = GraniteHybridForCausalLM(granite_hybrid_tiny(vocab_size=VOCAB))
    m.eval()
    return m


def engine(model, *, ride=True, **knobs):
    eng = ServingEngine(model, **dict(KNOBS, **knobs))
    if not ride:        # the two programs apart, as before the engine rode
        eng.rides_prefill = False
    return eng


def stream(eng, seed=0, lengths=LENGTHS, new=None):
    """A seeded stream of overlapping requests: each arrives after 0-2
    steps of the ones before it.  Every request's tokens, in order."""
    rng = np.random.default_rng(seed)
    rids = []
    for i, n in enumerate(lengths):
        rids.append(eng.submit(rng.integers(1, VOCAB, n).astype(np.int32),
                               max_new_tokens=new or 3 + (3 * i) % 8))
        for _ in range(int(rng.integers(0, 3))):
            eng.step()
    out = eng.run()
    eng.pool.check_leaks()
    return [out[r].tolist() for r in rids]


def _rode_steps(eng):
    """Count the steps whose rows rode, and those whose rider the
    preparation evicted (the decode program ran instead)."""
    seen = {"rode": 0, "rider_evicted": 0}
    step = eng._decode_step

    def spy(rider=None):
        ride = step(rider)
        seen["rode"] += ride is not None
        seen["rider_evicted"] += rider is not None and \
            rider.state != RUNNING
        return ride

    eng._decode_step = spy
    return seen


# -- (a) the same tokens as the two-program schedule ------------------------
@pytest.mark.parametrize("which", ["llama", "deepseek"])
def test_a_stream_rides_to_the_same_tokens(which, request):
    model = request.getfixturevalue(which)
    rides, apart = engine(model), engine(model, ride=False)
    seen = _rode_steps(rides)
    assert rides.rides_prefill and rides.rides_prefill_refusal is None
    assert stream(rides) == stream(apart)
    assert seen["rode"] >= 3
    got, want = rides.meter.summary(), apart.meter.summary()
    assert got["decode_steps_rode"] == seen["rode"] > 0
    assert want["decode_steps_rode"] == 0 and want["decode_steps"] > 0
    # a rode step launched no decode program: the rider's launch did it
    assert got["decode_steps"] - got["decode_steps_rode"] \
        < want["decode_steps"]
    assert got["prefill_launches"] == want["prefill_launches"]


def test_the_rider_is_a_prompt_whose_last_launch_is_the_narrowest(llama):
    """Only the narrowest launch carries a decode part: a step whose only
    fresh prompt ends in a wide launch runs the decode program, and of two
    fresh prompts the one that ends narrow rides, prefilled last."""
    eng = engine(llama)
    rng = np.random.default_rng(4)

    def prompt(n):
        return rng.integers(1, VOCAB, n).astype(np.int32)

    eng.submit(prompt(P + 3), max_new_tokens=8)         # launches [1, 1]
    eng.step()
    eng.submit(prompt(3 * P), max_new_tokens=4)         # [4]
    eng.step()
    assert eng.meter.summary()["decode_steps_rode"] == 0
    order, run = [], eng._prefill_request

    def spy(r, ride=None):
        order.append((len(r.prompt), ride is not None))
        return run(r, ride)

    eng._prefill_request = spy
    eng.submit(prompt(2 * P), max_new_tokens=4)         # [1, 1]
    eng.submit(prompt(4 * P), max_new_tokens=4)         # [4]
    eng.step()
    assert order == [(4 * P, False), (2 * P, True)]
    assert eng.meter.summary()["decode_steps_rode"] == 1


@pytest.mark.parametrize("which", ["llama", "deepseek"])
def test_the_rows_logits_after_a_rode_step_are_the_decode_programs(
        which, request):
    """The rows' logits a riding launch leaves (``last_decode_logits``) are
    what the decode program makes of the same rows on the same pages."""
    model = request.getfixturevalue(which)
    eng = engine(model)
    rng = np.random.default_rng(3)
    a = eng.submit(rng.integers(1, VOCAB, 2 * P + 3).astype(np.int32),
                   max_new_tokens=8)
    eng.step()
    eng.step()          # a decodes alone: the decode program
    assert eng.meter.summary()["decode_steps_rode"] == 0
    eng.submit(rng.integers(1, VOCAB, 5 * P).astype(np.int32),
               max_new_tokens=4)
    eng.step()          # b's launches; a rides the last
    assert eng.meter.summary()["decode_steps_rode"] == 1
    ra = next(r for r in eng._active.values() if r.rid == a)
    rode = np.asarray(eng.last_decode_logits)
    kept = eng.last_decode_kept
    # the decode program over the same row, token and pages (it writes the
    # same K/V at the same position again)
    R, S = eng.max_batch, eng._spec_width
    tokens = np.zeros((R, S), np.int32)
    tokens[ra.row, 0] = ra.generated[-2]
    positions = np.zeros((R,), np.int32)
    positions[ra.row] = ra.pos - 1
    tables = np.full((R, eng.max_pages_per_seq), 0, np.int32)
    tables[ra.row] = eng._padded_table(ra.rid)
    n_tok = np.zeros((R,), np.int32)
    n_tok[ra.row] = 1
    (choice, _), (logits, kept_decode), _ = jax.jit(eng._decode_fn)(
        *eng._param_arrays(), eng._arenas, jnp.asarray(tokens),
        jnp.asarray(positions), jnp.asarray(tables), jnp.asarray(n_tok))
    want = np.asarray(logits)[ra.row]
    np.testing.assert_allclose(rode[ra.row], want, rtol=1e-4,
                               atol=2e-5 * np.abs(want).max())
    assert int(np.asarray(choice)[ra.row, 0]) == ra.generated[-1]
    assert set(kept) == set(kept_decode)
    for name in kept:
        assert kept[name].shape == kept_decode[name].shape
        np.testing.assert_array_equal(kept[name][:, ra.row],
                                      np.asarray(kept_decode[name])[:, ra.row])


# -- (b) eviction while a step will ride, drafts that ride --------------------
@pytest.mark.parametrize("which", ["llama", "deepseek"])
def test_eviction_while_a_step_will_ride_stays_token_exact(which, request):
    """A pool too small for the stream: the preparation of a step that
    would ride evicts rows — the rider itself among them, whose step then
    runs the decode program."""
    model = request.getfixturevalue(which)
    small = dict(num_pages=10)
    rides, apart = engine(model, **small), engine(model, ride=False, **small)
    seen = _rode_steps(rides)
    lengths = (2 * P + 1, 3, P + 3, 2 * P, 5, P + 6, 4)
    assert stream(rides, 5, lengths, 20) == stream(apart, 5, lengths, 20)
    assert rides.meter.summary()["evictions"] >= 1
    assert apart.meter.summary()["evictions"] >= 1
    assert seen["rode"] >= 1 and seen["rider_evicted"] >= 1


@pytest.mark.parametrize("which", ["llama", "deepseek"])
def test_drafts_ride_to_the_same_tokens(which, request):
    model = request.getfixturevalue(which)
    rides = engine(model, speculative=2)
    apart = engine(model, ride=False, speculative=2)
    seen = _rode_steps(rides)
    assert rides._spec_width == 3
    assert stream(rides, 7) == stream(apart, 7)
    assert seen["rode"] >= 1
    got = rides.meter.summary()
    assert got["spec_acceptance"] is not None


# -- (c) the programs ---------------------------------------------------------
def test_the_engine_compiles_the_decode_program_and_one_a_width(llama):
    eng = engine(llama)
    compiled, compile_ = [], eng._compile

    def spy(fn, args, name, **kw):
        compiled.append((name, args[3].shape, len(args)))
        return compile_(fn, args, name, **kw)

    eng._compile = spy
    stream(eng)
    ladder = eng._prefill_widths
    assert ladder == PREFILL_WIDTHS
    # the decode program first (it chooses the weights' layouts); the
    # narrowest width takes the decode part as one more argument, the wider
    # ones are the plain program
    assert compiled == \
        [(serving_engine.DECODE_PROGRAM, (KNOBS["max_batch"], 1), 7)] + \
        [(serving_engine.PREFILL_PROGRAM, (1, w * P), 9 + (w == ladder[0]))
         for w in ladder]
    assert [eng._carries_rows(w) for w in ladder] == \
        [w == ladder[0] for w in ladder]
    assert eng._decode_compiles == 1 and sorted(eng._prefill_exec) == \
        list(ladder)
    # the decode part is the decode program's four inputs
    pa, ba = eng._param_arrays()
    R, MP = KNOBS["max_batch"], KNOBS["max_pages_per_seq"]
    ride = (jnp.zeros((R, 1), jnp.int32), jnp.zeros((R,), jnp.int32),
            jnp.zeros((R, MP), jnp.int32), jnp.zeros((R,), jnp.int32))
    (row, _, choice), (kept, logits, _), _ = jax.eval_shape(
        eng._prefill_fn, pa, ba, eng._arenas, jnp.zeros((1, P), jnp.int32),
        jnp.int32(0), jnp.zeros((1, MP), jnp.int32), jnp.int32(3),
        jnp.int32(0), jnp.int32(4), ride)
    assert row.shape == (VOCAB,) and choice.shape == (R, 1)
    assert logits.shape == (R, 1, VOCAB)


def test_a_model_with_state_layers_keeps_its_schedule_and_says_why(hybrid):
    before = dict(telemetry.counters())
    since = time.perf_counter_ns()      # the ring is bounded: not an index
    eng = engine(hybrid)
    assert eng.rides_prefill is False
    assert eng.rides_prefill_refusal == "state_layers"
    events = telemetry.get_flight_recorder().events(since)
    assert [(e["kind"], e["name"]) for e in events] == [
        ("serve_rides_prefill", "state_layers")]
    rid = eng.submit(np.arange(1, 2 * P, dtype=np.int32), max_new_tokens=5)
    eng.step()
    # the prompt's row decoded in the step of its prefill, as before
    assert eng._active and len(next(iter(eng._active.values())).generated) \
        == 2
    assert len(eng.run()[rid]) == 5
    summary = eng.meter.summary()
    assert summary["decode_steps"] == 4 and summary["decode_steps_rode"] == 0
    # its prefill launches carried no decode part
    assert eng._idle_ride is None and eng._prefill_exec
    after = telemetry.counters()
    assert not {k for k in after if k.startswith("kernel_fallback.")
                and after[k] != before.get(k)}


# -- (d) the spans of a rode step and what the readers make of them -----------
@pytest.fixture(scope="module")
def recorded(deepseek, tmp_path_factory):
    """A profiler session around a riding stream of the latent model:
    ``(spans, engine)``."""
    from jax.profiler import ProfileData

    from benchmark.lib import program_spans
    from benchmark.lib import trace as bench_trace

    eng = engine(deepseek)
    out = str(tmp_path_factory.mktemp("xplane"))
    jax.profiler.start_trace(out)
    try:
        stream(eng, 2)
    finally:
        jax.profiler.stop_trace()
    profile = ProfileData.from_file(bench_trace.newest_xplane(out))
    return program_spans.from_profile(profile), eng


def _facts(spans, name):
    return [dict(s.facts) for s in spans if s.name == name]


def test_a_rode_step_has_one_decode_span_with_its_facts(recorded):
    spans, eng = recorded
    steps = [s for s in spans if s.name == "serve.step"]
    decodes = [s for s in spans if s.name == "serve.decode"]
    assert len(steps) == eng.steps_total
    # one serve.decode a step at most, and none inside a serve.prefill:
    # the two kinds never overlap
    for st in steps:
        assert sum(st.start <= d.start and d.end <= st.end
                   for d in decodes) <= 1
    prefills = [s for s in spans if s.name == "serve.prefill"]
    assert not [(d, p) for d in decodes for p in prefills
                if d.start < p.end and p.start < d.end]
    rode = [d for d in decodes if dict(d.facts)["rode"]]
    assert len(rode) == eng.meter.summary()["decode_steps_rode"] > 0
    for d in rode:
        facts = dict(d.facts)
        assert facts["rows"] > 0 and facts["n_tok"] >= facts["rows"]
        assert {"live_pages", "table_pages", "state_rows", "latent_pages",
                "latent_tokens"} <= set(facts)
        assert facts["state_rows"] == 0
        # the launch, its fetch and the rows' booking lie in the rider's
        # serve.prefill: no decode program was launched under this span
        assert not [s for s in spans if s.name.startswith("serve.decode.")
                    and s.name != "serve.decode.prep"
                    and d.start <= s.start and s.end <= d.end]
        assert "moe_pairs" not in facts
    launched = [d for d in decodes if dict(d.facts)["rows"]
                and not dict(d.facts)["rode"]]
    assert len(_facts(spans, "serve.decode.dispatch")) == len(launched)
    assert all("moe_pairs" in dict(d.facts) for d in launched)


def test_a_launchs_notes_are_counted_on_one_span(recorded, deepseek):
    """Every expert layer routes each real token to its ``k`` experts, all
    held here: over the spans, the pairs are the real tokens of every
    launch, each counted once — the prompts' tokens and the rows' tokens
    of the decode program and of the riding launches."""
    spans, _ = recorded
    cfg = deepseek.config
    expert_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    prompt = sum(f["prompt_tokens"] for f in _facts(spans, "serve.prefill"))
    rows = sum(f["n_tok"] for f in _facts(spans, "serve.decode"))
    pairs = sum(f.get("moe_pairs", 0)
                for name in ("serve.prefill", "serve.decode")
                for f in _facts(spans, name))
    assert pairs == cfg.num_experts_per_tok * expert_layers * (prompt + rows)


def test_the_readers_shares_still_partition(recorded, monkeypatch):
    """On a run that holds rode steps: the cycle account's parts are
    disjoint and with the rest make the band's whole time, and the idle
    split sums to the idle share (the device busy, here, in every launch
    span: any intervals will do)."""
    from benchmark.lib import program_spans as PS
    from benchmark.lib import registry
    from benchmark.lib import trace as T

    spans, _ = recorded
    steps = [s for s in spans if s.name == "serve.step"]
    window = (steps[0].start, steps[-1].end)
    ops = [T.Event("fusion", s.start, s.end) for s in spans
           if s.name in ("serve.prefill.dispatch", "serve.decode.dispatch")]
    tr = T.Trace({0: {"ops": ops, "modules": []}}, [], window)
    monkeypatch.setattr(PS, "of_run", lambda root=None: spans)
    ctx = types.SimpleNamespace(trace=tr)
    reg = registry.Registry()

    def read(name):
        spec = reg.layer_metric(name)
        return reg.module("readers", spec["reader"]).read(ctx,
                                                          **spec["args"])

    acc = reg.module("readers", "cycle_account").band_account(spans, window)
    assert acc is not None and acc["time"] > 0
    inside = {name: T.union((s.start, s.end) for s in spans
                            if s.name == name)
              for name in ("serve.prefill", "serve.decode", "serve.step")}
    both = T.union(inside["serve.prefill"] + inside["serve.decode"])
    assert T.total(both) == pytest.approx(
        T.total(inside["serve.prefill"]) + T.total(inside["serve.decode"]))
    shares = [read(f"sched.gap_tail_{p}.serve")
              for p in ("prefill", "decode", "outside")]
    assert all(0.0 <= s <= 100.0 for s in shares)
    assert sum(shares) <= 100.0 + 1e-9
    assert shares[0] == pytest.approx(100 * acc["prefill"] / acc["time"])
    idle = [read(m["name"]) for m in reg.benchmark["per_layer"]
            if m["name"].startswith("sched.idle_")]
    assert len(idle) == 7 and None not in idle
    assert sum(idle) == pytest.approx(
        reg.module("readers", "idle_share").read(ctx))
