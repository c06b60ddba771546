"""Long-context serving ladder (ISSUE 20): context-parallel prefill
token-exact vs the chunked solo oracle (with kernel_fallback events on
every CP gate rejection), host-RAM KV offload swap-out/recall token-exact
vs the all-in-HBM oracle (plus the LRU-drop "offload stall" downgrade),
OffloadPool / PagedKVPool park-plan units (shared pages never copy), and
fp8 KV pages: exactly half the bf16 pool bytes, an engine that serves
end to end on them, and the loud non-finite tripwire naming the dtype.

Tier-1 ``longctx`` lane; conftest pins PADDLE_TPU_KV_OFFLOAD_PAGES and the
PADDLE_TPU_SERVE_* geometry down so the engines stay CPU-sized; CP tests
pass ``cp=2`` explicitly against the 8 virtual devices.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (OffloadPool, PagedKVPool, PoolExhausted,
                                ServingEngine, default_fp8_scale,
                                default_offload_pages, dequantize_kv_fp8,
                                kv_scale_page_bytes, quantize_kv_fp8)

pytestmark = pytest.mark.longctx


@pytest.fixture(scope="module")
def cfg():
    return llama_tiny(num_hidden_layers=2, vocab_size=96,
                      max_position_embeddings=128)


def _fresh(cfg):
    """Fresh same-seeded model per engine: a cp>1 ctor commits the params
    to the ring mesh in place, so engines never share a module."""
    paddle.seed(3)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def model(cfg):
    return _fresh(cfg)


def _expect(model, prompt, max_new):
    ids, _ = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                            max_new_tokens=max_new)
    return ids.numpy()[0]


def _frame(fill=1.0):
    return {"k": np.full((2, 8, 2, 4), fill, np.float32),
            "v": np.full((2, 8, 2, 4), fill, np.float32)}


# ---------------------------------------------------------------------------
# OffloadPool units: stage/publish atomicity, LRU budget, recall pricing
# ---------------------------------------------------------------------------
class TestOffloadPool:
    def test_stage_publish_atomicity(self):
        op = OffloadPool(max_pages=4)
        op.stage("a", 0, _frame())
        # staged-but-unpublished is invisible: a crash mid-spill never
        # leaves a torn frame a later recall could read
        assert not op.holds("a", 0)
        assert op.frames_held() == 0
        assert op.get("a", 0) is None
        assert op.publish() == []
        assert op.holds("a", 0) and op.frames_held() == 1
        with pytest.raises(RuntimeError, match="no staged frame"):
            op.publish()

    def test_lru_drop_returns_owner(self):
        op = OffloadPool(max_pages=2)
        assert op.put("a", 0, _frame()) == []
        assert op.put("b", 0, _frame()) == []
        assert op.put("c", 0, _frame()) == [("a", 0)]
        assert op.pages_dropped == 1 and op.frames_held() == 2
        assert not op.holds("a", 0)
        assert op.holds("b", 0) and op.holds("c", 0)

    def test_touch_rescues_near_recall_frames(self):
        op = OffloadPool(max_pages=2)
        op.put("a", 0, _frame())
        op.put("b", 0, _frame())
        assert op.touch("a") == 1       # "a" nears the admission head
        assert op.put("c", 0, _frame()) == [("b", 0)]
        assert op.holds("a", 0)

    def test_get_pops_and_prices_recall(self):
        op = OffloadPool(max_pages=4)
        fr = _frame(2.0)
        nbytes = sum(v.nbytes for v in fr.values())
        op.put("a", 1, fr)
        assert op.bytes_out == nbytes and op.pages_out == 1
        got = op.get("a", 1)
        assert got is not None
        np.testing.assert_array_equal(got["k"], fr["k"])
        assert op.pages_in == 1 and op.bytes_in == nbytes
        assert op.get("a", 1) is None   # popped: recall is exactly-once
        assert op.frames_held() == 0

    def test_drop_discards_every_frame_of_owner(self):
        op = OffloadPool(max_pages=8)
        op.put("a", 0, _frame())
        op.put("a", 1, _frame())
        op.put("b", 0, _frame())
        assert op.drop("a") == 2
        assert op.frames_held() == 1 and op.holds("b", 0)
        assert op.summary()["frames_held"] == 1

    def test_budget_from_env(self, monkeypatch):
        assert default_offload_pages() == 16      # the conftest pin
        monkeypatch.setenv("PADDLE_TPU_KV_OFFLOAD_PAGES", "3")
        assert OffloadPool().max_pages == 3


# ---------------------------------------------------------------------------
# PagedKVPool park plan: swap_out/swap_in, shared pages never copy
# ---------------------------------------------------------------------------
class TestParkPlan:
    def test_private_pages_free_and_refill(self):
        pool = PagedKVPool(num_pages=8, page_tokens=8)
        pool.alloc("a", 3)
        assert pool.swap_out("a") == [None, None, None]
        assert pool.pages_free == 7          # private bytes live on host
        assert pool.is_parked("a")
        assert pool.parked_plan("a") == [None, None, None]
        table, refill = pool.swap_in("a")
        assert [j for j, _ in refill] == [0, 1, 2]
        assert pool.table("a") == table and len(table) == 3
        pool.free("a")
        pool.check_leaks()

    def test_shared_page_retains_ref_never_copies(self):
        pool = PagedKVPool(num_pages=8, page_tokens=8)
        pages = pool.alloc("a", 2)
        pool.incref(pages)                   # second holder (prefix trie)
        plan = pool.swap_out("a")
        assert plan == pages                 # resident: zero copies
        assert all(pool.refcount(p) == 2 for p in pages)
        table, refill = pool.swap_in("a")
        assert table == pages and refill == []
        pool.free("a")
        assert pool.decref(pages) == 2
        pool.check_leaks()

    def test_swap_in_all_or_nothing(self):
        pool = PagedKVPool(num_pages=4, page_tokens=8)   # capacity 3
        pool.alloc("a", 3)
        pool.swap_out("a")
        pool.alloc("b", 2)
        with pytest.raises(PoolExhausted):
            pool.swap_in("a")
        assert pool.is_parked("a")           # still recallable later
        pool.free("b")
        _, refill = pool.swap_in("a")
        assert len(refill) == 3
        pool.free("a")
        pool.check_leaks()

    def test_drop_parked_releases_shared_refs(self):
        pool = PagedKVPool(num_pages=8, page_tokens=8)
        pages = pool.alloc("a", 2)
        pool.incref(pages)
        pool.swap_out("a")
        assert pool.drop_parked("a") == 0    # trie ref keeps them resident
        assert all(pool.refcount(p) == 1 for p in pages)
        assert pool.decref(pages) == 2
        pool.check_leaks()

    def test_park_bookkeeping_is_loud(self):
        pool = PagedKVPool(num_pages=4, page_tokens=8)
        pool.alloc("a", 1)
        pool.swap_out("a")
        with pytest.raises(AssertionError, match="parked"):
            pool.check_leaks()
        with pytest.raises(KeyError):
            pool.swap_out("a")               # already parked
        with pytest.raises(KeyError):
            pool.swap_in("missing")
        pool.drop_parked("a")
        pool.check_leaks()


# ---------------------------------------------------------------------------
# Context-parallel prefill (cp=2 over the sep ring)
# ---------------------------------------------------------------------------
class TestCPPrefill:
    def test_cp2_token_exact_vs_solo_and_serial(self, cfg, model):
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 96, n).astype(np.int32)
                   for n in (40, 33)]
        solo = ServingEngine(_fresh(cfg), max_batch=2)
        cpe = ServingEngine(_fresh(cfg), max_batch=2, cp=2)
        rs = [solo.submit(p, max_new_tokens=8) for p in prompts]
        rc = [cpe.submit(p, max_new_tokens=8) for p in prompts]
        outs_s, outs_c = solo.run(), cpe.run()
        for p, a, b in zip(prompts, rs, rc):
            exp = _expect(model, p, 8)
            np.testing.assert_array_equal(outs_s[a], exp)
            np.testing.assert_array_equal(outs_c[b], exp)
        # the ring program ran — and 40 and 33 tokens both pad to the same
        # 48-token signature, so ONE executable served both
        assert len(cpe._cp_execs) == 1
        assert all(r.ok for r in cpe.cp_lint_reports.values())

    def test_cp2_fp8_matches_chunked_fp8(self, cfg):
        """Quantized pools roundtrip through the page dtype BEFORE the
        ring, so CP stays token-exact vs the chunked path's own fp8."""
        rng = np.random.default_rng(12)
        p = rng.integers(1, 96, 40).astype(np.int32)
        solo = ServingEngine(_fresh(cfg), max_batch=1, kv_dtype="fp8")
        cpe = ServingEngine(_fresh(cfg), max_batch=1, cp=2, kv_dtype="fp8")
        a = solo.submit(p, max_new_tokens=6)
        b = cpe.submit(p, max_new_tokens=6)
        np.testing.assert_array_equal(solo.run()[a], cpe.run()[b])
        assert cpe._cp_execs

    def test_gate_short_prompt_falls_back_with_event(self, cfg, model):
        import paddle_tpu.telemetry as tel

        eng = ServingEngine(_fresh(cfg), max_batch=1, cp=2)
        key = "kernel_fallback.serving_cp_prefill.short_prompt"
        before = tel.counters().get(key, 0)
        p = np.arange(1, 9, dtype=np.int32)   # one chunk < cp=2
        r = eng.submit(p, max_new_tokens=4)
        outs = eng.run()
        np.testing.assert_array_equal(outs[r], _expect(model, p, 4))
        assert tel.counters().get(key, 0) == before + 1
        assert not eng._cp_execs              # chunked path served it
        events = [e for e in tel.get_flight_recorder().events()
                  if e["kind"] == "kernel_fallback"]
        assert any(e["name"] == "serving_cp_prefill"
                   and e.get("reason") == "short_prompt" for e in events)

    def test_gate_prefix_cached_falls_back_with_event(self, cfg):
        import paddle_tpu.telemetry as tel

        eng = ServingEngine(_fresh(cfg), max_batch=1, cp=2,
                            prefix_cache=True)
        rng = np.random.default_rng(13)
        p = rng.integers(1, 96, 24).astype(np.int32)
        r1 = eng.submit(p, max_new_tokens=4)
        out1 = eng.run()[r1]
        key = "kernel_fallback.serving_cp_prefill.prefix_cached"
        before = tel.counters().get(key, 0)
        r2 = eng.submit(p, max_new_tokens=4)  # hits the prefix trie
        out2 = eng.run()[r2]
        assert tel.counters().get(key, 0) == before + 1
        np.testing.assert_array_equal(out1, out2)

    def test_gate_kv_import_falls_back_with_event(self, cfg, model):
        import paddle_tpu.telemetry as tel

        rng = np.random.default_rng(14)
        p = rng.integers(1, 96, 40).astype(np.int32)
        donor = ServingEngine(_fresh(cfg), max_batch=1)
        first, frames = donor.prefill_export(p)
        eng = ServingEngine(_fresh(cfg), max_batch=1, cp=2)
        key = "kernel_fallback.serving_cp_prefill.kv_import"
        before = tel.counters().get(key, 0)
        r = eng.submit_prefilled(p, first, frames, max_new_tokens=4)
        outs = eng.run()
        assert tel.counters().get(key, 0) == before + 1
        np.testing.assert_array_equal(outs[r], _expect(model, p, 4))

    def test_cp_mesh_conflicts_are_loud(self, cfg):
        with pytest.raises(ValueError, match="cannot combine"):
            ServingEngine(_fresh(cfg), tp=2, cp=2)
        with pytest.raises(ValueError, match="devices"):
            ServingEngine(_fresh(cfg), cp=16)


# ---------------------------------------------------------------------------
# Host-RAM offload: swap-out/recall token-exact, stall downgrade
# ---------------------------------------------------------------------------
class TestOffloadEngine:
    def test_offload_recall_token_exact_zero_recompute(self, cfg, model):
        # two 20-token prompts both admit (3 pages each of capacity 8)
        # then outgrow the pool at max_new=20 (5 pages each): preemption
        # MUST swap through the host tier and recall, with no replay
        eng = ServingEngine(_fresh(cfg), max_batch=2, page_tokens=8,
                            num_pages=9, max_pages_per_seq=8, offload=True)
        rng = np.random.default_rng(7)
        prompts = [rng.integers(1, 96, 20).astype(np.int32)
                   for _ in range(2)]
        rids = [eng.submit(p, max_new_tokens=20) for p in prompts]
        outs = eng.run()
        for p, r in zip(prompts, rids):
            np.testing.assert_array_equal(outs[r], _expect(model, p, 20))
        s = eng.meter.summary()
        assert s["kv_offloads"] >= 1 and s["kv_recalls"] >= 1
        assert s["kv_offload_stalls"] == 0
        assert s["evictions"] == 0            # recall replays NOTHING
        assert s["kv_recall_bytes_per_token"] > 0
        assert s["kv_offload_bytes_out"] > 0
        assert eng.offload.frames_held() == 0  # all recalled or retired
        eng.pool.check_leaks()

    def test_lru_drop_downgrades_to_replay_token_exact(self, cfg, model):
        # a 2-frame host tier cannot hold one victim's 3+ spilled pages:
        # the put LRU-drops the victim's own frames, recall downgrades to
        # the eviction-replay re-prefill ("offload stall") — still exact
        eng = ServingEngine(_fresh(cfg), max_batch=2, page_tokens=8,
                            num_pages=9, max_pages_per_seq=8,
                            offload=OffloadPool(max_pages=2))
        rng = np.random.default_rng(8)
        prompts = [rng.integers(1, 96, 20).astype(np.int32)
                   for _ in range(2)]
        rids = [eng.submit(p, max_new_tokens=20) for p in prompts]
        outs = eng.run()
        for p, r in zip(prompts, rids):
            np.testing.assert_array_equal(outs[r], _expect(model, p, 20))
        s = eng.meter.summary()
        assert s["kv_offloads"] >= 1
        assert s["kv_offload_stalls"] >= 1
        assert eng.offload.pages_dropped >= 1


# ---------------------------------------------------------------------------
# fp8 KV pages: half the bf16 bytes, end-to-end serving, loud failure
# ---------------------------------------------------------------------------
class TestFp8Pages:
    def test_pool_bytes_exactly_half_of_bf16(self, cfg):
        kw = dict(max_batch=1, page_tokens=8, num_pages=8,
                  max_pages_per_seq=6)
        e16 = ServingEngine(_fresh(cfg), **kw)
        e8 = ServingEngine(_fresh(cfg), kv_dtype="fp8", **kw)
        ei8 = ServingEngine(_fresh(cfg), kv_dtype="int8", **kw)
        assert e8.pool.bytes_per_page * 2 == e16.pool.bytes_per_page
        # no scale planes (unlike int8): fp8's per-token total is
        # strictly under int8's pages-plus-scales
        assert e8.pool.scale_bytes_per_page == 0
        assert ei8.pool.scale_bytes_per_page > 0
        assert e8.pool.bytes_per_token() < ei8.pool.bytes_per_token()
        assert kv_scale_page_bytes(8, 2, "fp8", n_layers=2) == 0

    def test_fp8_engine_serves_end_to_end(self, cfg):
        eng = ServingEngine(_fresh(cfg), max_batch=1, kv_dtype="fp8")
        rng = np.random.default_rng(9)
        r = eng.submit(rng.integers(1, 96, 12).astype(np.int32),
                       max_new_tokens=6)
        assert len(eng.run()[r]) == 6

    def test_static_scale_env(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KV_FP8_SCALE", "2.5")
        assert default_fp8_scale() == 2.5
        monkeypatch.setenv("PADDLE_TPU_KV_FP8_SCALE", "0")
        with pytest.raises(ValueError, match="must be > 0"):
            default_fp8_scale()

    def test_quantize_roundtrip_saturates(self):
        import jax.numpy as jnp

        x = jnp.asarray([[0.5, -0.25, 600.0, -600.0]], jnp.float32)
        q = quantize_kv_fp8(x, 1.0)
        assert q.dtype == jnp.float8_e4m3fn
        d = np.asarray(dequantize_kv_fp8(q, 1.0))
        # e4m3fn has no inf: overflow saturates at ±448, never NaN
        np.testing.assert_allclose(d[0, 2:], [448.0, -448.0])
        np.testing.assert_allclose(d[0, :2], [0.5, -0.25], rtol=0.07)
        d2 = np.asarray(dequantize_kv_fp8(quantize_kv_fp8(x, 2.0), 2.0))
        np.testing.assert_allclose(d2[0, 2:], [600.0, -600.0], rtol=0.07)

    def test_nonfinite_decode_is_loud_and_names_dtype(self, cfg):
        eng = ServingEngine(_fresh(cfg), max_batch=1, kv_dtype="fp8")
        eng.submit(np.arange(1, 13, dtype=np.int32), max_new_tokens=6)
        eng.step()                          # admit + prefill
        import jax.numpy as jnp

        eng._arenas = {key: [jnp.full_like(a, jnp.nan) for a in arrs]
                       for key, arrs in eng._arenas.items()}
        with pytest.raises(RuntimeError, match=r"kv_dtype=fp8"):
            for _ in range(8):
                eng.step()


# ---------------------------------------------------------------------------
# varlen flash prefill at 16K rows (the CP ring's per-shard block size)
# ---------------------------------------------------------------------------
class TestVarlen16K:
    def test_varlen_16k_gqa_block_boundary_pads(self):
        """16384-row left-padded prefill with valid-lengths ON the kernel
        block boundary (0, blk, blk+1, nearly-full) and GQA heads, vs the
        masked dense oracle.  The oracle is checked on targeted 256-row
        slabs — the slab straddling each row's padding boundary, one
        mid-sequence, and the tail — because a dense [s, s] score matrix
        at 16K rows would not fit the tier-1 budget."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import (flash_attention_varlen,
                                           flash_attention_varlen_supported)

        b, s, hq, hkv, d = 4, 16384, 2, 1, 8
        blk, slab = 4096, 256
        rng = np.random.default_rng(3)
        q = jnp.asarray(rng.standard_normal((b, s, hq, d)),
                        jnp.float32) * 0.5
        k = jnp.asarray(rng.standard_normal((b, s, hkv, d)),
                        jnp.float32) * 0.5
        v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
        pads = np.asarray([0, blk, blk + 1, s - 3], np.int32)
        assert flash_attention_varlen_supported(q.shape, k.shape,
                                                block_q=blk, block_k=blk)
        out = np.asarray(flash_attention_varlen(
            q, k, v, jnp.asarray(pads), block_q=blk, block_k=blk,
            interpret=True))
        assert np.isfinite(out[0]).all()      # pad=0: every row is valid

        kr = np.repeat(np.asarray(k), hq // hkv, axis=2)
        vr = np.repeat(np.asarray(v), hq // hkv, axis=2)
        qn = np.asarray(q)
        sc = 1.0 / np.sqrt(d)
        for ib in range(b):
            pad = int(pads[ib])
            starts = {min(max(pad - slab // 2, 0), s - slab),  # boundary
                      (s // 2 // slab) * slab,                 # steady state
                      s - slab}                                # tail
            for q0 in sorted(starts):
                rows = np.arange(q0, q0 + slab)
                scores = np.einsum("qhd,khd->hqk", qn[ib, rows],
                                   kr[ib]) * sc
                col = np.arange(s)[None, None, :]
                mask = (col <= rows[None, :, None]) & (col >= pad)
                scores = np.where(mask, scores, -np.inf)
                m = scores.max(-1, keepdims=True)
                p = np.exp(scores - np.where(np.isinf(m), 0.0, m))
                p /= np.maximum(p.sum(-1, keepdims=True), 1e-30)
                ref = np.einsum("hqk,khd->qhd", p, vr[ib])
                valid = rows >= pad           # in-pad rows are undefined
                np.testing.assert_allclose(out[ib, rows][valid],
                                           ref[valid],
                                           rtol=2e-5, atol=2e-5)
