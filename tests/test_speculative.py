"""Speculative decoding + int8 KV pages (ISSUE 13): drafter units,
rejection-sampling correctness (Monte Carlo), standalone loop token-exact
vs ``model.generate``, the serving-engine composition (token-exact under
eviction chaos, journal replay, one compiled verify-width program),
int8 page round-trip + decode-logits tolerance vs the bf16 oracle,
scale-corruption loud failure, and the extended donation lint.

Tier-1 ``spec`` lane; conftest pins PADDLE_TPU_PAGE_TOKENS /
PADDLE_TPU_SERVE_* down so the compiled engines stay CPU-sized.
"""

import json
import os
import signal
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.generation import (AdaptiveK, DraftModelDrafter,
                                   NGramDrafter, ShallowExitDrafter,
                                   SpecConfig, rejection_sample_step,
                                   speculative_generate)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (ServingEngine, check_decode_donation,
                                dequantize_kv, kv_cache_dtype,
                                kv_page_bytes, kv_scale_page_bytes,
                                observe_kv_absmax, quantize_kv)

pytestmark = [pytest.mark.spec, pytest.mark.serving]

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                     max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _solo(model, prompt, max_new, eos=None):
    ids, _ = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                            max_new_tokens=max_new, eos_token_id=eos,
                            pad_token_id=0 if eos is not None else None)
    return ids.numpy()[0]


# ---------------------------------------------------------------------------
# drafter units
# ---------------------------------------------------------------------------
class TestDrafters:
    def test_ngram_proposes_looping_continuation(self):
        dr = NGramDrafter(max_ngram=3)
        dr.begin([5, 6, 7, 5, 6, 7, 5, 6])
        assert dr.propose(3) == [7, 5, 6]

    def test_ngram_prefers_longest_suffix_match(self):
        dr = NGramDrafter(max_ngram=3)
        # suffix [2, 3] matched at start beats the shorter [3] at index 4
        dr.begin([2, 3, 9, 8, 3, 7, 2, 3])
        assert dr.propose(2) == [9, 8]

    def test_ngram_no_match_is_empty(self):
        dr = NGramDrafter()
        dr.begin([1, 2, 3, 4])
        assert dr.propose(4) == []
        assert dr.propose(0) == []

    def test_ngram_observe_extends_context(self):
        dr = NGramDrafter()
        dr.begin([9, 1])
        dr.observe([2, 9, 1])
        assert dr.propose(2) == [2, 9]

    def test_adaptive_k_shrinks_and_recovers(self):
        ctrl = AdaptiveK(k_max=4, adaptive=True, decay=0.5)
        assert ctrl.k() == 4                  # optimistic start
        for _ in range(6):
            ctrl.update(accepted=0, proposed=4)
        assert ctrl.k() == 1                  # cold streak floors at 1
        for _ in range(8):
            ctrl.update(accepted=4, proposed=4)
        assert ctrl.k() == 4                  # recovery grows back
        fixed = AdaptiveK(k_max=3, adaptive=False)
        fixed.update(0, 3)
        assert fixed.k() == 3

    def test_model_drafters_propose_model_argmax(self, model):
        """A draft-model drafter whose draft model IS the target proposes
        exactly the target's greedy continuation; the shallow-exit drafter
        produces tokens from the truncated stack (valid vocab range)."""
        prompt = [3, 11, 7, 29, 5]
        expect = _solo(model, np.asarray(prompt, np.int32), 4)
        dr = DraftModelDrafter(model, capacity=32)
        dr.begin(prompt)
        assert dr.propose(4) == [int(t) for t in expect[:4]]

        sh = ShallowExitDrafter(model, capacity=32, draft_layers=1)
        sh.begin(prompt)
        toks = sh.propose(3)
        assert len(toks) == 3
        assert all(0 <= t < model.config.vocab_size for t in toks)


# ---------------------------------------------------------------------------
# rejection sampling
# ---------------------------------------------------------------------------
class TestRejectionSampling:
    def _empirical(self, p, q, draft_dist, n=20000, seed=0):
        rng = np.random.default_rng(seed)
        counts = np.zeros_like(p)
        for _ in range(n):
            d = int(rng.choice(len(draft_dist), p=draft_dist))
            _, tok = rejection_sample_step(p, q, d, rng)
            counts[tok] += 1
        return counts / n

    def test_output_distribution_matches_target(self):
        """Monte Carlo (Leviathan et al.): whatever q proposes, the
        emitted token is distributed as p."""
        p = np.array([0.5, 0.3, 0.15, 0.05])
        q = np.array([0.1, 0.2, 0.3, 0.4])       # badly miscalibrated
        emp = self._empirical(p, q, draft_dist=q)
        np.testing.assert_allclose(emp, p, atol=0.02)

    def test_one_hot_draft_distribution(self):
        """q=None (deterministic drafter) = one-hot proposal; output must
        still be exactly p-distributed."""
        p = np.array([0.6, 0.25, 0.1, 0.05])
        emp = self._empirical(p, None,
                              draft_dist=np.array([0.0, 1.0, 0.0, 0.0]))
        np.testing.assert_allclose(emp, p, atol=0.02)

    def test_matching_draft_always_accepted(self):
        rng = np.random.default_rng(1)
        p = np.array([0.0, 1.0, 0.0])
        ok, tok = rejection_sample_step(p, None, 1, rng)
        assert ok and tok == 1


# ---------------------------------------------------------------------------
# standalone loop
# ---------------------------------------------------------------------------
class TestSpeculativeGenerate:
    @pytest.mark.parametrize("drafter", ["ngram", "shallow", "draft_model"])
    def test_greedy_token_exact_vs_generate(self, model, drafter):
        """ACCEPTANCE: greedy speculative output is bit-identical to the
        serial compiled decode for every drafter flavor."""
        cap = 64
        factory = {"ngram": "ngram",
                   "shallow": lambda: ShallowExitDrafter(model, cap,
                                                         draft_layers=1),
                   "draft_model": lambda: DraftModelDrafter(model, cap),
                   }[drafter]
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 96, 6).astype(np.int32),
                   np.asarray([4, 9, 2, 4, 9, 2, 4, 9], np.int32)]
        for prompt in prompts:
            ids, stats = speculative_generate(
                model, paddle.to_tensor(prompt[None]),
                max_new_tokens=10, drafter=factory, k=3)
            expect = _solo(model, prompt, 10)
            np.testing.assert_array_equal(ids.numpy()[0], expect,
                                          err_msg=f"drafter={drafter}")
            assert stats["verify_steps"] >= 1
            assert stats["effective_tokens_per_step"] > 0

    def test_oracle_drafter_accepts_everything(self, model):
        """Draft model == target model: acceptance 1.0 and >1 effective
        tokens per step — the speedup mechanism demonstrably engages."""
        prompt = np.asarray([3, 11, 7, 29, 5, 18], np.int32)
        ids, stats = speculative_generate(
            model, paddle.to_tensor(prompt[None]), max_new_tokens=12,
            drafter=lambda: DraftModelDrafter(model, 64), k=4,
            adaptive=False)
        np.testing.assert_array_equal(ids.numpy()[0],
                                      _solo(model, prompt, 12))
        assert stats["acceptance_rate"] == 1.0
        assert stats["effective_tokens_per_step"] > 1.0

    def test_eos_latch_and_padding(self, model):
        prompt = np.asarray([4, 9, 2, 4, 9, 2], np.int32)
        expect = _solo(model, prompt, 12)
        eos = int(expect[3])        # force an early stop at a real token
        ids, _ = speculative_generate(
            model, paddle.to_tensor(prompt[None]), max_new_tokens=12,
            k=3, eos_token_id=eos, pad_token_id=0)
        row = ids.numpy()[0]
        cut = list(row).index(eos)
        np.testing.assert_array_equal(row[:cut + 1], expect[:cut + 1])
        assert all(t == 0 for t in row[cut + 1:])

    def test_sampling_path_runs(self, model):
        prompt = np.asarray([4, 9, 2, 4, 9, 2], np.int32)
        ids, stats = speculative_generate(
            model, paddle.to_tensor(prompt[None]), max_new_tokens=8,
            drafter=lambda: DraftModelDrafter(model, 64), k=3,
            do_sample=True, temperature=0.8, seed=7)
        row = ids.numpy()[0]
        assert row.shape == (8,)
        assert all(0 <= t < model.config.vocab_size for t in row)

    def test_rope_overhang_guard(self, model):
        """prompt + max_new at the rope table edge must raise instead of
        letting the clamped verify window corrupt the cache."""
        max_pos = model.config.max_position_embeddings
        prompt = np.ones((max_pos - 4,), np.int32)
        with pytest.raises(ValueError, match="max_position_embeddings"):
            speculative_generate(model, paddle.to_tensor(prompt[None]),
                                 max_new_tokens=4, k=4)



# ---------------------------------------------------------------------------
# serving-engine composition
# ---------------------------------------------------------------------------
def _serve(model, prompts, max_new=10, **kw):
    eng = ServingEngine(model, max_batch=3, page_tokens=8, num_pages=24,
                        max_pages_per_seq=6, **kw)
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    outs = eng.run()
    eng.pool.check_leaks()
    return eng, [outs[r] for r in rids]


def _mixed_prompts(seed=7):
    rng = np.random.default_rng(seed)
    ps = [rng.integers(1, 96, n).astype(np.int32) for n in (5, 9, 3)]
    ps.append(np.asarray([7, 8, 9, 7, 8, 9, 7, 8], np.int32))  # loopy
    return ps


class _OracleDrafter:
    """Drafts the serial stream's own continuation.  Under greedy decoding
    every such draft must be accepted, so the acceptance the engine reports
    is its accounting alone — not what a drafter happens to guess against a
    random-weight model."""

    def __init__(self, streams):
        self._streams = streams         # prompt -> the serial engine's tokens

    def begin(self, context):
        self._want = self._streams[tuple(int(t) for t in context)]
        self._seen = 0

    def observe(self, tokens):
        self._seen += len(tokens)

    def propose(self, k):
        return [int(t) for t in self._want[self._seen:self._seen + k]]


class TestEngineSpeculative:
    @pytest.mark.parametrize("drafter", ["ngram", "oracle"])
    def test_token_exact_vs_serial_one_compile(self, model, drafter):
        """ACCEPTANCE: the speculative engine emits the exact serial
        stream and compiles its decode program ONCE (adaptation never
        recompiles).  With the oracle drafter every draft is accepted and
        a verify step emits more than one token a row; what the n-gram
        drafter guesses is the seed's business and is not asserted."""
        prompts = _mixed_prompts()
        _, serial = _serve(model, prompts)
        streams = {tuple(int(t) for t in p): out
                   for p, out in zip(prompts, serial)}
        spec_cfg = 4 if drafter == "ngram" else SpecConfig(
            k=4, drafter=lambda: _OracleDrafter(streams))
        eng, spec = _serve(model, prompts, speculative=spec_cfg)
        for i, (a, b) in enumerate(zip(serial, spec)):
            np.testing.assert_array_equal(a, b, err_msg=f"request {i}")
        assert eng._decode_compiles == 1
        s = eng.meter.summary()
        assert s["spec_acceptance"] is not None
        assert s["effective_tokens_per_step"] >= 1.0
        if drafter == "oracle":
            assert s["spec_acceptance"] == 1.0
            assert s["effective_tokens_per_step"] > 1.0

    def test_serial_summary_leaves_spec_fields_none(self, model):
        eng, _ = _serve(model, _mixed_prompts()[:1], max_new=3)
        s = eng.meter.summary()
        assert s["spec_acceptance"] is None
        assert s["effective_tokens_per_step"] is None
        assert s["kv_bytes_per_token"] == eng.pool.bytes_per_token()

    def test_token_exact_under_eviction_chaos(self, model):
        """A pool too small for the offered load forces mid-verify
        evictions; the replayed speculative streams must still match the
        serial engine exactly and leak no page."""
        rng = np.random.default_rng(2)
        prompts = [rng.integers(1, 96, n).astype(np.int32)
                   for n in (6, 9, 5)]

        def run(**kw):
            eng = ServingEngine(model, max_batch=3, page_tokens=4,
                                num_pages=9, max_pages_per_seq=8, **kw)
            rids = [eng.submit(p, max_new_tokens=10) for p in prompts]
            outs = eng.run()
            eng.pool.check_leaks()
            return eng, [outs[r] for r in rids]

        _, serial = run()
        eng, spec = run(speculative=3)
        assert eng.meter.summary()["evictions"] >= 1, \
            "pool was sized to force eviction; none happened"
        for i, (a, b) in enumerate(zip(serial, spec)):
            np.testing.assert_array_equal(a, b, err_msg=f"request {i}")

    def test_journal_replay_token_exact(self, model, tmp_path):
        """Crash-stop after a speculative run: a fresh engine recovering
        from the journal reports the same finished streams."""
        jdir = str(tmp_path / "j")
        prompts = _mixed_prompts(5)
        eng1, outs1 = _serve(model, prompts, speculative=3, journal=jdir)
        eng2 = ServingEngine(model, max_batch=3, page_tokens=8,
                             num_pages=24, max_pages_per_seq=6,
                             speculative=3, journal=jdir)
        eng2.recover()
        for r, out in zip(sorted(eng2._results), outs1):
            np.testing.assert_array_equal(eng2._results[r], out)

    def test_spec_config_resolution(self, model, monkeypatch):
        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=16, max_pages_per_seq=4,
                            speculative=SpecConfig(k=2, adaptive=False))
        assert eng._spec_width == 3 and not eng._adapt.adaptive
        with pytest.raises(TypeError):
            ServingEngine(model, max_batch=2, page_tokens=8, num_pages=16,
                          max_pages_per_seq=4, speculative="yes")
        monkeypatch.setenv("PADDLE_TPU_SPEC_K", "3")
        eng2 = ServingEngine(model, max_batch=2, page_tokens=8,
                             num_pages=16, max_pages_per_seq=4)
        assert eng2.spec is not None and eng2._spec_width == 4


CHILD_SPEC = """
import json, os, signal, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax; jax.config.update("jax_platforms", "cpu")
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import ServingEngine, TokenSink

work = sys.argv[1]
trace = json.load(open(os.path.join(work, "trace.json")))

paddle.seed(3)
cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                 max_position_embeddings=128)
model = LlamaForCausalLM(cfg)
model.eval()

sink = TokenSink(os.path.join(work, "out.jsonl"))
marker = os.path.join(work, "killed")
first_life = not os.path.exists(marker)
count = {"n": 0}

def on_token(rid, idx, tok):
    sink(rid, idx, tok)
    count["n"] += 1
    if first_life and count["n"] >= trace["kill_after_tokens"]:
        open(marker, "w").write("1")
        os.kill(os.getpid(), signal.SIGKILL)   # death mid-verify stream

eng = ServingEngine(model, max_batch=3, page_tokens=8, num_pages=24,
                    max_pages_per_seq=6, speculative=3,
                    journal=os.path.join(work, "journal"),
                    on_token=on_token)
info = eng.recover()
known = set(info["known_rids"])
for req in trace["requests"]:
    if req["rid"] not in known:
        eng.submit(np.asarray(req["prompt"], np.int32),
                   max_new_tokens=req["max_new"], rid=req["rid"])
outs = eng.run(watchdog_s=120)
json.dump({"results": {str(k): [int(x) for x in v] for k, v in outs.items()},
           "replayed": info["replayed"]},
          open(os.path.join(work, "final.json"), "w"))
"""


class TestSpecChaosEndToEnd:
    def test_sigkill_mid_verify_exactly_once(self, model, tmp_path):
        """ACCEPTANCE: the speculative engine is SIGKILLed mid-stream
        (several multi-token verify steps already delivered), the
        Supervisor relaunches it, the journal replays — every stream
        finishes token-exact vs serial generation and the sink holds each
        token exactly once."""
        from paddle_tpu.distributed.fleet.elastic.supervisor import (
            RestartPolicy, Supervisor)
        from paddle_tpu.serving import TokenSink

        work = str(tmp_path)
        rng = np.random.default_rng(13)
        reqs = [{"rid": i,
                 "prompt": [int(x) for x in rng.integers(1, 96, n)],
                 "max_new": 8}
                for i, n in enumerate((5, 9, 6))]
        reqs.append({"rid": 3, "prompt": [7, 8, 9, 7, 8, 9, 7, 8],
                     "max_new": 8})
        trace = {"requests": reqs, "kill_after_tokens": 7}
        with open(os.path.join(work, "trace.json"), "w") as f:
            json.dump(trace, f)
        script = os.path.join(work, "child.py")
        with open(script, "w") as f:
            f.write(textwrap.dedent(CHILD_SPEC))

        env = {**os.environ, "PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"}
        sup = Supervisor(
            [sys.executable, script, work],
            policy=RestartPolicy(max_restarts=3, backoff_base=0.05,
                                 backoff_cap=0.2),
            restart_codes=(101, -signal.SIGKILL),
            env=env, child_timeout=600)
        assert sup.run() == 0
        assert sup.restarts == 1, sup.exit_codes
        final = json.load(open(os.path.join(work, "final.json")))
        assert final["replayed"] >= 1
        results = {int(k): v for k, v in final["results"].items()}
        streams = TokenSink.collect(os.path.join(work, "out.jsonl"))
        for req in reqs:
            expect = _solo(model, np.asarray(req["prompt"], np.int32),
                           req["max_new"])
            np.testing.assert_array_equal(results[req["rid"]], expect,
                                          err_msg=f"rid {req['rid']}")
            assert streams[req["rid"]] == list(expect), \
                f"rid {req['rid']}: exactly-once violated"


# ---------------------------------------------------------------------------
# int8 KV pages
# ---------------------------------------------------------------------------
class TestInt8Pages:
    def test_dtype_resolution(self, monkeypatch):
        assert kv_cache_dtype(None) == "bf16"
        assert kv_cache_dtype("int8") == "int8"
        monkeypatch.setenv("PADDLE_TPU_KV_DTYPE", "int8")
        assert kv_cache_dtype() == "int8"
        # the fp8 seam is wired now (ISSUE 20): e4m3fn aliases resolve,
        # the e5m2 flavor stays an explicit not-implemented
        assert kv_cache_dtype("fp8") == "fp8"
        assert kv_cache_dtype("f8e4m3fn") == "fp8"
        with pytest.raises(NotImplementedError, match="e4m3fn"):
            kv_cache_dtype("f8e5m2")
        with pytest.raises(ValueError):
            kv_cache_dtype("int4")

    def test_quantize_roundtrip_tolerance(self):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((4, 16, 2, 8)).astype(np.float32) * 3.0
        q, s = quantize_kv(x)
        assert np.asarray(q).dtype == np.int8
        back = np.asarray(dequantize_kv(q, s))
        amax = np.abs(x).max(axis=-1, keepdims=True)
        assert np.all(np.abs(back - x) <= amax / 127 * 0.5 + 1e-7)
        # zeros (trash-page writes) round-trip exactly
        qz, sz = quantize_kv(np.zeros((1, 2, 8), np.float32))
        assert np.all(np.asarray(qz) == 0)
        assert np.all(np.asarray(dequantize_kv(qz, sz)) == 0.0)

    def test_page_bytes_priced_via_dtype_bytes(self):
        bf = kv_page_bytes(8, 2, 16, "bf16", n_layers=2)
        i8 = kv_page_bytes(8, 2, 16, "int8", n_layers=2)
        assert i8 * 2 == bf, "int8 pages must halve the arena bytes"
        assert kv_scale_page_bytes(8, 2, "bf16", n_layers=2) == 0
        assert kv_scale_page_bytes(8, 2, "int8", n_layers=2) \
            == 2 * 2 * 8 * 2 * 4

    def test_observe_kv_absmax(self):
        xs = [paddle.to_tensor(np.full((2, 4), v, np.float32))
              for v in (0.5, 3.0, 1.5)]
        assert observe_kv_absmax(xs) == pytest.approx(3.0)

    def test_engine_pool_bytes_halved(self, model):
        """ACCEPTANCE: the pool accountant measures int8 pages at exactly
        half the bf16 arena bytes (scales priced separately), and the
        physical arena allocation agrees."""
        e_bf, _ = _serve(model, _mixed_prompts()[:1], max_new=2)
        e_i8, _ = _serve(model, _mixed_prompts()[:1], max_new=2,
                         kv_dtype="int8")
        assert e_i8.pool.bytes_per_page * 2 == e_bf.pool.bytes_per_page
        assert e_i8.pool.scale_bytes_per_page > 0
        assert e_bf.pool.scale_bytes_per_page == 0
        assert e_i8.pool.kv_dtype == "int8"
        # physical arenas agree: int8 slots are 1 byte vs the native
        # compute dtype's width (f32 on the CPU smoke, bf16 on TPU)
        assert e_i8._arenas["k"][0].dtype == np.int8
        native = e_bf._arenas["k"][0].dtype.itemsize
        assert e_i8._arena_bytes * native == e_bf._arena_bytes
        assert e_i8.meter.summary()["kv_bytes_per_token"] \
            == e_i8.pool.bytes_per_token()

    def test_decode_logits_within_tolerance_of_bf16(self, model):
        """int8 decode logits must track the bf16 oracle within the
        harness tolerance on the very same request stream."""
        prompts = _mixed_prompts(3)[:2]
        e_bf, outs_bf = _serve(model, prompts, max_new=6)
        e_i8, outs_i8 = _serve(model, prompts, max_new=6, kv_dtype="int8")
        a, b = e_bf.last_decode_logits, e_i8.last_decode_logits
        assert a is not None and b is not None and a.shape == b.shape
        scale = max(np.abs(a).max(), 1.0)
        assert np.abs(a - b).max() / scale < 0.08, \
            "int8 decode logits drifted beyond the harness tolerance"
        # on this tiny smoke the greedy stream itself should survive
        for x, y in zip(outs_bf, outs_i8):
            np.testing.assert_array_equal(x, y)

    def test_int8_composes_with_speculation(self, model):
        prompts = _mixed_prompts(9)
        _, serial = _serve(model, prompts)
        eng, spec8 = _serve(model, prompts, speculative=3, kv_dtype="int8")
        s = eng.meter.summary()
        assert s["spec_acceptance"] is not None
        for x, y in zip(serial, spec8):
            np.testing.assert_array_equal(x, y)

    def test_scale_corruption_fails_loudly(self, model):
        """SEEDED-BAD: poisoning one scale page with NaN must raise the
        non-finite-logits RuntimeError on the next decode step instead of
        silently emitting junk tokens."""
        import jax.numpy as jnp

        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=16, max_pages_per_seq=4,
                            kv_dtype="int8")
        rid = eng.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=6)
        eng.step()                      # prefill + first decode step
        page = eng.pool.table(rid)[0]
        eng._arenas["ks"][0] = eng._arenas["ks"][0].at[page].set(jnp.nan)
        with pytest.raises(RuntimeError, match="non-finite"):
            for _ in range(4):
                eng.step()

    def test_donation_lint_covers_scale_buffers(self, model):
        """The compiled int8 decode program must alias arenas AND scale
        planes; seeded-bad (no donation) trips the extended gate with the
        scale-aware message."""
        import jax
        import jax.numpy as jnp

        eng = ServingEngine(model, max_batch=2, page_tokens=8,
                            num_pages=16, max_pages_per_seq=4,
                            kv_dtype="int8")
        rid = eng.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=3)
        eng.run()
        assert eng.lint_report is not None and eng.lint_report.ok
        mem = eng._decode_exec.memory_analysis()
        assert int(mem.alias_size_in_bytes) \
            >= eng._arena_bytes + eng._scale_bytes
        assert eng._scale_bytes > 0
        del rid

        pa, ba = eng._param_arrays()
        args = (pa, ba, eng._arenas,
                jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
                jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32))
        bad = jax.jit(eng._decode_fn).lower(*args).compile()
        with pytest.raises(RuntimeError, match="scale"):
            check_decode_donation(bad, eng._arena_bytes,
                                  scale_bytes=eng._scale_bytes)
