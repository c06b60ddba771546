"""The program's own spans (``paddle_tpu.profiler.span`` / ``RecordEvent``)
and the names of its compiled programs.

One primitive, no switch: a span always enters a ``TraceAnnotation``, which
records only while SOME profiler session is live.  So the spans of
``ServingEngine.step()`` and ``TrainStep.__call__`` are checked under a plain
``jax.profiler.start_trace`` that this file starts itself, the way the
benchmark and a TensorBoard capture do.  CPU only; no time is asserted."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu import jit as pjit
from paddle_tpu import profiler, telemetry
from paddle_tpu.distributed.health import HealthGuard, HealthPolicy
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as serving_engine

# span -> (the span it lies in, the facts it carries), as PERF.md tables it
SPANS = {
    "serve.step": (None, {"step", "active", "queued"}),
    "serve.shed_scan": ("serve.step", set()),
    "serve.admit": ("serve.step", {"admitted"}),
    "serve.prefill": ("serve.step", {"rid", "trace", "prompt_tokens",
                                     "chunks", "launches",
                                     "cached_tokens", "passes",
                                     "kv_tokens"}),
    "serve.prefill.dispatch": ("serve.prefill", set()),
    "serve.prefill.to_host": ("serve.prefill", set()),
    "serve.prefill.sample": ("serve.prefill", set()),
    "serve.decode": ("serve.step", {"rows", "n_tok", "live_pages",
                                    "table_pages", "rode", "passes"}),
    "serve.decode.prep": ("serve.decode", set()),
    "serve.decode.dispatch": ("serve.decode", set()),
    "serve.decode.to_host": ("serve.decode", {"bytes"}),
    "serve.decode.sample": ("serve.decode", set()),
    "serve.deliver": ("serve.step", {"requests", "tokens", "gaps"}),
    "serve.compile": (None, {"program"}),
    "train.step": (None, {"step"}),
    "train.marshal": ("train.step", set()),
    "train.launch": ("train.step", {"program"}),
    "train.rebind": ("train.step", set()),
    "train.guard": ("train.step", set()),
}
PROMPTS = {101: 5, 102: 9, 103: 3}          # rid -> prompt tokens
NEW_TOKENS = 4
TRAIN_STEPS = 3


# -- (a) the primitive ------------------------------------------------------
def _ring():
    return len(telemetry.get_flight_recorder().events())


def test_span_without_a_session_records_nowhere_and_survives_a_raise():
    assert profiler._active_profiler is None
    before = _ring()
    with pytest.raises(ValueError):
        with profiler.span("serve.step", step=1) as sp:
            sp.note(active=2)
            raise ValueError("in the body")
    assert sp._annotation is None and sp._start_ns is None
    assert sp.facts == {"step": 1, "active": 2}
    assert _ring() == before


def test_span_is_a_record_event_and_begin_end_pair():
    sp = profiler.span("train.guard")
    assert type(sp) is profiler.RecordEvent and sp.event_type == "UserDefined"
    sp.end()                        # never begun: nothing to close
    sp.begin()
    assert sp._annotation is not None
    sp.end()
    assert sp._annotation is None


def test_span_lands_in_the_paddle_timeline_with_its_facts():
    before = _ring()
    with profiler.Profiler(targets=[profiler.ProfilerTarget.CPU]) as prof:
        with profiler.span("serve.decode", rows=5) as sp:
            sp.note(n_tok=7)
        with profiler.RecordEvent("work"):
            pass
    events = {e.name: e for e in prof._last_window()}
    assert events["serve.decode"].args == {"rows": 5, "n_tok": 7}
    assert events["serve.decode"].end_ns >= events["serve.decode"].start_ns
    assert events["work"].event_type == "UserDefined"
    assert _ring() == before        # a step-phase span is no ring event


def test_end_closes_the_annotation_when_the_profiler_stopped_meanwhile():
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    sp = profiler.RecordEvent("straddles")
    sp.begin()
    prof.stop()
    sp.end()
    assert sp._annotation is None and sp._start_ns is None
    assert "straddles" not in [e.name for e in prof._last_window()]


def test_timer_only_profiler_keeps_no_timeline():
    with profiler.Profiler(timer_only=True) as prof:
        with profiler.span("train.step", step=1):
            pass
    assert prof._last_window() == []


# -- (b) every span of the table, under a plain jax trace ------------------
class _Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 2)

    def forward(self, x):
        return self.fc(x)


@pytest.fixture(scope="module")
def engine_model():
    paddle.seed(3)
    model = LlamaForCausalLM(llama_tiny(num_hidden_layers=2, vocab_size=96,
                                        max_position_embeddings=128))
    model.eval()
    return model


@pytest.fixture(scope="module")
def recorded(engine_model, tmp_path_factory):
    """One profiler session around three served requests and three guarded
    train steps: ``(spans by name, engine, losses)``."""
    from jax.profiler import ProfileData

    from benchmark.lib import program_spans
    from benchmark.lib import trace as bench_trace

    eng = ServingEngine(engine_model, max_batch=4, page_tokens=4,
                        num_pages=32, max_pages_per_seq=8)
    paddle.seed(7)
    net = _Net()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    guard = HealthGuard(HealthPolicy(escalate_after=3, window=20, cooldown=5,
                                     max_lag=0, min_history=10 ** 6),
                        on_escalate="raise")
    step = pjit.TrainStep(net, lambda m, x, y: F.mse_loss(m(x), y), opt,
                          health_guard=guard)
    x = paddle.to_tensor(np.ones((8, 4), np.float32))
    y = paddle.to_tensor(np.zeros((8, 2), np.float32))
    rng = np.random.default_rng(0)
    ring_before = [e["kind"] for e in
                   telemetry.get_flight_recorder().events()]
    # the pool's own count of the stepped rows' pages, decode step by step
    pool_pages, prep = [], eng._decode_prep

    def counted_prep():
        batch = prep()
        if batch is not None:
            pool_pages.append(sum(len(eng.pool.table(r.rid))
                                  for r in batch[0]))
        return batch

    eng._decode_prep = counted_prep

    out = str(tmp_path_factory.mktemp("xplane"))
    jax.profiler.start_trace(out)
    try:
        for rid, n in PROMPTS.items():
            eng.submit(rng.integers(1, 96, n).astype(np.int32),
                       max_new_tokens=NEW_TOKENS, rid=rid,
                       trace_id=f"trace-{rid}")
        results = eng.run()
        losses = [float(step(x, y).numpy()) for _ in range(TRAIN_STEPS)]
    finally:
        jax.profiler.stop_trace()

    profile = ProfileData.from_file(bench_trace.newest_xplane(out))
    assert [p.name for p in profile.planes].count(bench_trace.HOST_PLANE) == 1
    spans = {}
    for s in program_spans.from_profile(profile):       # the host plane's
        spans.setdefault(s.name, []).append((s.start, s.end, dict(s.facts)))
    ring_after = [e["kind"] for e in telemetry.get_flight_recorder().events()]
    return {"spans": spans, "engine": eng, "results": results,
            "losses": losses, "pool_pages": pool_pages, "ring_kinds": set(ring_after) - set(ring_before)}


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_is_on_the_host_plane_nested_and_with_its_facts(recorded, name):
    spans = recorded["spans"]
    parent, facts = SPANS[name]
    assert name in spans, sorted(spans)
    for start, end, stats in spans[name]:
        assert facts <= set(stats), (name, stats)
        if parent is not None:
            assert any(ps <= start and end <= pe
                       for ps, pe, _ in spans[parent]), \
                f"{name} lies in no {parent}"


def test_no_span_beyond_the_table(recorded):
    assert set(recorded["spans"]) == set(SPANS)


def test_serving_span_counts_match_steps_and_requests(recorded):
    spans, eng = recorded["spans"], recorded["engine"]
    assert {rid: len(t) for rid, t in recorded["results"].items()} == \
        {rid: NEW_TOKENS for rid in PROMPTS}
    steps = spans["serve.step"]
    assert len(steps) == eng.steps_total
    assert [s[2]["step"] for s in steps] == list(range(1, len(steps) + 1))
    for name in ("serve.shed_scan", "serve.admit", "serve.deliver"):
        assert len(spans[name]) == len(steps), name
    assert sum(s[2]["admitted"] for s in spans["serve.admit"]) == len(PROMPTS)
    # every request's first token comes from prefill, the rest from decode
    decodes = spans["serve.decode"]
    assert sum(s[2]["n_tok"] for s in decodes) == \
        len(PROMPTS) * (NEW_TOKENS - 1)
    ran = [s for s in decodes if s[2]["rows"] > 0]
    # the first step's rows (the two prompts prefilled ahead of the third)
    # rode the third's launch: that step launched no decode program
    rode = [s for s in ran if s[2]["rode"]]
    assert len(rode) == 1 and rode[0][2]["rows"] == len(PROMPTS) - 1
    for name in ("serve.decode.dispatch", "serve.decode.to_host",
                 "serve.decode.sample"):
        assert len(spans[name]) == len(ran) - len(rode), name
    assert len(spans["serve.decode.prep"]) == len(decodes)
    summary = eng.meter.summary()
    assert (summary["decode_steps"], summary["decode_steps_rode"]) == \
        (len(ran), len(rode))
    # what a step fetches: one int32 a position [R, S], never the logits
    assert {s[2]["bytes"] for s in spans["serve.decode.to_host"]} == \
        {eng.max_batch * 1 * 4}
    assert eng.meter.summary()["decode_logits_fetches"] == 0
    assert sum(s[2]["tokens"] for s in spans["serve.deliver"]) == \
        len(PROMPTS) * NEW_TOKENS


def test_deliver_span_carries_the_cycle_account(recorded):
    """Counts only: the durations are the spans' own stamps."""
    eng = recorded["engine"]
    flushes = [s[2] for s in recorded["spans"]["serve.deliver"]]
    closing = [f for f in flushes if f["requests"] > 0]
    account = {"seq", "gaps", "gaps_long", "first_tokens",
               "prefill_requests", "prefill_tokens", "prefill_launches",
               "decode_rows", "compiled"}
    assert all(account <= set(f) for f in closing)
    assert all(f["gaps"] == 0 for f in flushes if f["requests"] == 0)
    assert [f["seq"] for f in closing] == list(range(1, len(closing) + 1))
    assert len(closing) == eng.meter.summary()["cycles_total"]
    total = {k: sum(f[k] for f in closing) for k in account}
    assert total["first_tokens"] == total["prefill_requests"] == len(PROMPTS)
    assert total["prefill_tokens"] == sum(PROMPTS.values())
    assert total["prefill_launches"] == \
        eng.meter.summary()["prefill_launches"]
    assert total["gaps_long"] == 0
    assert all(f["gaps"] + f["first_tokens"] == f["requests"]
               for f in closing)
    # a token each is a first token, a gap a cycle closed, or came with the
    # one before it (index 1 in the flush of index 0)
    assert total["gaps"] + sum(f["tokens"] - f["requests"] for f in closing) \
        + len(PROMPTS) == len(PROMPTS) * NEW_TOKENS
    assert total["decode_rows"] == sum(
        s[2]["rows"] for s in recorded["spans"]["serve.decode"])
    # the warm-up: both programs compile in the first cycle, none later
    assert [f["compiled"] for f in closing] == [1] + [0] * (len(closing) - 1)
    assert eng.meter.summary()["cycles_compiled"] == 1


def test_decode_span_counts_live_pages_as_the_pool_does(recorded):
    eng = recorded["engine"]
    ran = [s[2] for s in recorded["spans"]["serve.decode"] if s[2]["rows"]]
    assert [s["live_pages"] for s in ran] == recorded["pool_pages"]
    for s in ran:
        assert s["rows"] <= s["live_pages"] <= s["table_pages"] \
            == eng.max_batch * eng.max_pages_per_seq
    # prompts of 5, 9 and 3 tokens on pages of 4 grow past a page boundary
    assert len({s["live_pages"] for s in ran}) > 1


def test_prefill_span_carries_the_requests_trace_id(recorded):
    prefills = recorded["spans"]["serve.prefill"]
    assert {s[2]["rid"]: s[2]["trace"] for s in prefills} == \
        {rid: f"trace-{rid}" for rid in PROMPTS}
    assert {s[2]["rid"]: s[2]["prompt_tokens"] for s in prefills} == PROMPTS
    page = recorded["engine"].page_tokens
    assert all(s[2]["chunks"] == -(-s[2]["prompt_tokens"] // page)
               and s[2]["cached_tokens"] == 0 for s in prefills)
    # the launches of the engine's plan; the meter counts what the spans say
    launches = [s[2]["launches"] for s in prefills]
    assert launches == [len(serving_engine.prefill_plan(s[2]["chunks"]))
                        for s in prefills]
    assert recorded["engine"].meter.summary()["prefill_launches"] == \
        sum(launches)
    for child in ("dispatch", "to_host", "sample"):
        assert len(recorded["spans"][f"serve.prefill.{child}"]) == \
            len(PROMPTS)


def test_compile_span_says_which_program_compiled(recorded):
    spans = recorded["spans"]["serve.compile"]
    programs = [s[2]["program"] for s in spans]
    # the decode program first, which chooses the weights' layouts, then
    # the prefill program once a width
    assert programs == [serving_engine.DECODE_PROGRAM] + \
        [serving_engine.PREFILL_PROGRAM] * len(serving_engine.PREFILL_WIDTHS)
    # how many weights moved into them, and their bytes: the choosing
    # program's span alone says (none move on the CPU, where the compiler
    # keeps every weight as it lies)
    assert [(s[2].get("relaid"), s[2].get("relaid_bytes")) for s in spans] \
        == [(0, 0)] + [(None, None)] * len(serving_engine.PREFILL_WIDTHS)


def test_train_span_counts_and_program(recorded):
    spans = recorded["spans"]
    assert all(np.isfinite(recorded["losses"]))
    assert [s[2]["step"] for s in spans["train.step"]] == \
        list(range(1, TRAIN_STEPS + 1))
    for name in ("train.marshal", "train.launch", "train.rebind",
                 "train.guard"):
        assert len(spans[name]) == TRAIN_STEPS, name
    assert {s[2]["program"] for s in spans["train.launch"]} == \
        {pjit.GUARDED_STEP_PROGRAM}


def test_step_phase_spans_write_nothing_to_the_flight_recorder(recorded):
    # what the ring gained are the request instants and step events it
    # always had (README: crash dumps, trace_coverage), none named a phase
    assert not any(k.startswith(("serve.", "train."))
                   for k in recorded["ring_kinds"])
    names = {e["name"] for e in telemetry.get_flight_recorder().events()}
    assert not names & set(SPANS)


# -- (c) the programs' names are constants ---------------------------------
def _module_name(lowered) -> str:
    first = lowered.as_text().splitlines()[0]
    assert first.startswith("module @"), first
    return first.split()[1].lstrip("@")


@pytest.fixture(scope="module")
def engine_programs(engine_model):
    """The lowered text of the engine's programs, named as ``_compile``
    names them."""
    eng = ServingEngine(engine_model, max_batch=2, page_tokens=4,
                        num_pages=16, max_pages_per_seq=4)
    pa, ba = eng._param_arrays()
    tables = jnp.zeros((2, 4), jnp.int32)

    def lower(fn, name, *args):
        return jax.jit(pjit.named_program(fn, name),
                       donate_argnums=(2,)).lower(pa, ba, eng._arenas, *args)

    return {
        serving_engine.DECODE_PROGRAM: lower(
            eng._decode_fn, serving_engine.DECODE_PROGRAM,
            jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
            tables, jnp.ones((2,), jnp.int32)),
        serving_engine.PREFILL_PROGRAM: lower(
            eng._prefill_fn, serving_engine.PREFILL_PROGRAM,
            jnp.zeros((1, 4), jnp.int32), jnp.int32(0), tables[:1],
            jnp.int32(3)),
    }


@pytest.mark.parametrize("constant, name, accepted_pattern", [
    ("DECODE_PROGRAM", "serve_decode_fn", "_decode_fn"),
    ("PREFILL_PROGRAM", "serve_prefill_fn", "_prefill_fn"),
    ("CP_PREFILL_PROGRAM", "serve_cp_prefill_fn", "_prefill_fn"),
])
def test_engine_program_names_are_pinned(engine_programs, constant, name,
                                         accepted_pattern):
    import re

    assert getattr(serving_engine, constant) == name
    # the benchmark's accepted patterns (decode.step_ms.serve,
    # prefill.ms_per_ktok.serve) still find the module
    assert re.search(accepted_pattern, f"jit_{name}(123)")
    assert not re.search("_decode_fn", "jit_" + serving_engine.PREFILL_PROGRAM)
    if name in engine_programs:
        assert _module_name(engine_programs[name]) == f"jit_{name}"


def test_the_engine_compiles_under_those_names(engine_model, monkeypatch):
    seen = []
    real = pjit.named_program
    monkeypatch.setattr(serving_engine, "named_program",
                        lambda fn, name: seen.append(name) or real(fn, name))
    eng = ServingEngine(engine_model, max_batch=2, page_tokens=4,
                        num_pages=16, max_pages_per_seq=4)
    eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    eng.run()
    assert seen == [serving_engine.DECODE_PROGRAM] + \
        [serving_engine.PREFILL_PROGRAM] * len(serving_engine.PREFILL_WIDTHS)


@pytest.mark.parametrize("variant, name", [
    ("plain", "train_step"),
    ("guarded", "train_step_guarded"),
    ("checked", "train_step_checked"),
])
def test_train_step_program_names_are_pinned(variant, name):
    paddle.seed(11)
    net = _Net()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    step = pjit.TrainStep(net, lambda m, x, y: F.mse_loss(m(x), y), opt)
    x = paddle.to_tensor(np.ones((8, 4), np.float32))
    y = paddle.to_tensor(np.zeros((8, 2), np.float32))
    jitted = {"plain": step._compiled, "guarded": step._get_guarded(),
              "checked": step._compiled_checked}[variant]
    constant = {"plain": pjit.STEP_PROGRAM,
                "guarded": pjit.GUARDED_STEP_PROGRAM,
                "checked": pjit.CHECKED_STEP_PROGRAM}[variant]
    assert constant == name
    args = step._marshal_args((x, y), key=jax.random.PRNGKey(0))
    assert _module_name(jitted.lower(*args)) == f"jit_{name}"


def test_distributed_train_step_uses_the_same_names():
    from paddle_tpu.distributed import DistributedTrainStep
    from paddle_tpu.distributed.topology import HybridCommunicateGroup

    hcg = HybridCommunicateGroup(dp=-1)      # every visible device
    paddle.seed(13)
    net = _Net()
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    step = DistributedTrainStep(net, lambda m, x, y: F.mse_loss(m(x), y),
                                opt, hcg)
    x = paddle.to_tensor(np.ones((8, 4), np.float32))
    y = paddle.to_tensor(np.zeros((8, 2), np.float32))
    assert _module_name(step.lower(x, y)) == "jit_train_step"
    args = step._marshal_args((x, y), key=jax.random.PRNGKey(0))
    assert _module_name(step._get_guarded().lower(*args)) == \
        "jit_train_step_guarded"
