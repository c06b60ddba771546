"""Disaggregated serving (ISSUE 19): COW page refcounts, prefix-cache
radix units + engine integration token-exact vs the re-prefill oracle,
TP-sharded decode vs TP=1, depot KV-page streaming exactly-once, the
PrefillWorker -> decode import e2e with chaos fallback, and the router's
tier preference.

Tier-1 ``disagg`` lane; conftest pins PADDLE_TPU_PAGE_TOKENS /
PADDLE_TPU_PREFIX_PAGES / PADDLE_TPU_DISAGG_* down so the compiled
engines stay CPU-sized and the prefill-tier e2e routes small prompts.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.checkpoint import faults
from paddle_tpu.distributed.checkpoint.replicator import (FencedEpoch,
                                                          SnapshotClient,
                                                          SnapshotStore)
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (PagedKVPool, PrefixCache, ServingEngine,
                                TRASH_PAGE)
from paddle_tpu.serving.disagg import (DisaggCoordinator, PrefillWorker,
                                       decode_mesh, pack_kv_frame,
                                       take_prefilled, unpack_kv_frame)
from paddle_tpu.serving.metrics import FleetMeter
from paddle_tpu.serving.router import ReplicaStatus, Router

pytestmark = pytest.mark.disagg

KW = dict(max_batch=3, page_tokens=8, num_pages=32, max_pages_per_seq=6)


@pytest.fixture(scope="module")
def model():
    paddle.seed(3)
    cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                     max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture
def tp_model():
    """A PRIVATE model instance for TP engines: shard_llama_params
    commits shardings onto the params in place, so the shared module
    fixture must never be handed to a TP engine (same seed -> identical
    weights, token-exact comparable with the shared model's outputs)."""
    paddle.seed(3)
    cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                     max_position_embeddings=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture
def depot():
    store = SnapshotStore(host="127.0.0.1")
    client = SnapshotClient("127.0.0.1", store.port)
    yield client
    client.close()
    store.close()


def _solo(model, prompt, max_new, eos=None):
    ids, _ = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                            max_new_tokens=max_new, eos_token_id=eos,
                            pad_token_id=0 if eos is not None else None)
    return ids.numpy()[0]


def _expect(model, prompt, max_new, eos=None):
    row = _solo(model, prompt, max_new, eos)
    if eos is not None:
        hits = np.flatnonzero(row == eos)
        if hits.size:
            return row[:hits[0] + 1]
    return row


# -- COW refcounts (satellite: kv_pool edge cases) ---------------------------

class TestCOWPool:
    def test_alloc_takes_one_ref_free_drops_it(self):
        pool = PagedKVPool(num_pages=8, page_tokens=4)
        pages = pool.alloc("a", 3)
        assert all(pool.refcount(p) == 1 for p in pages)
        assert pool.shared_pages() == 0
        assert pool.free("a") == 3
        assert all(pool.refcount(p) == 0 for p in pages)
        pool.check_leaks()

    def test_adopt_shares_and_survives_first_free(self):
        pool = PagedKVPool(num_pages=8, page_tokens=4)
        pages = pool.alloc("a", 2)
        assert pool.adopt("b", pages) == pages
        assert all(pool.refcount(p) == 2 for p in pages)
        assert pool.shared_pages() == 2
        assert pool.free("a") == 0          # still referenced by b
        assert pool.pages_used == 2         # shared pages count ONCE
        assert pool.free("b") == 2
        pool.check_leaks()

    def test_double_free_of_shared_page_raises(self):
        """ACCEPTANCE (satellite c): dropping a page's refcount below
        zero is a loud KeyError, never silent corruption."""
        pool = PagedKVPool(num_pages=8, page_tokens=4)
        [p] = pool.alloc("a", 1)
        pool.incref([p])                    # trie reference
        pool.free("a")                      # request's ref drops
        assert pool.decref([p]) == 1        # trie's ref drops -> freed
        with pytest.raises(KeyError):
            pool.decref([p])                # double-free of the now-free page
        pool.check_leaks()

    def test_trash_page_never_refcounted(self):
        """ACCEPTANCE (satellite c): page 0 is compiled-shape overhead —
        every refcount operation on it raises."""
        pool = PagedKVPool(num_pages=8, page_tokens=4)
        assert pool.refcount(TRASH_PAGE) == 0
        with pytest.raises(ValueError):
            pool.incref([TRASH_PAGE])
        with pytest.raises(ValueError):
            pool.decref([TRASH_PAGE])
        with pytest.raises(ValueError):
            pool.adopt("a", [TRASH_PAGE])
        pool.check_leaks()

    def test_incref_of_free_page_raises(self):
        pool = PagedKVPool(num_pages=8, page_tokens=4)
        with pytest.raises(KeyError):
            pool.incref([3])
        with pytest.raises(KeyError):
            pool.adopt("a", [3])
        pool.check_leaks()

    def test_leak_check_counts_shared_pages_once(self):
        """ACCEPTANCE (satellite c): the quiesced invariant is
        free ⊎ referenced == all pages — a page with three holders must
        not triple-count, and surviving trie refs are only legal under
        ``allow_shared``."""
        pool = PagedKVPool(num_pages=8, page_tokens=4)
        pages = pool.alloc("a", 3)
        pool.adopt("b", pages)
        pool.adopt("c", pages[:1])
        pool.free("a")
        pool.free("b")
        pool.free("c")
        pool.check_leaks()                  # everything freed: clean
        # now simulate the trie holding a page past engine shutdown
        [p] = pool.alloc("r", 1)
        pool.incref([p])                    # trie pin
        pool.free("r")
        with pytest.raises(AssertionError):
            pool.check_leaks()              # surviving ref is a leak...
        pool.check_leaks(allow_shared=True)  # ...unless a cache owns it
        pool.decref([p])
        pool.check_leaks()

    def test_evicted_request_pages_stay_while_trie_holds(self):
        """ACCEPTANCE (satellite c): freeing a request whose pages the
        prefix trie still references must NOT return them to the free
        list — a later alloc can never hand out a page the trie would
        serve to the next hit."""
        pool = PagedKVPool(num_pages=4, page_tokens=4)
        pages = pool.alloc("victim", 2)
        pool.incref(pages)                  # trie holds both
        assert pool.free("victim") == 0     # eviction: nothing freed
        got = pool.alloc("next", 1)         # only the 3rd page remains
        assert set(got).isdisjoint(pages)
        pool.free("next")
        assert pool.decref(pages) == 2
        pool.check_leaks()


# -- prefix cache units ------------------------------------------------------

class TestPrefixCache:
    def test_match_never_covers_the_last_token(self):
        """The page holding the last prompt token is never matched: its
        forward pass must run to produce the first output logits."""
        pool = PagedKVPool(num_pages=16, page_tokens=4)
        pc = PrefixCache(pool, max_pages=8)
        prompt = list(range(1, 9))          # exactly 2 full pages
        table = pool.alloc("a", 2)
        assert pc.insert(prompt, table) == 2
        pages, n_tok = pc.match(prompt)     # same 8 tokens
        assert len(pages) == 1 and n_tok == 4   # cap = (8-1)//4 = 1
        pages, n_tok = pc.match(prompt + [9])
        assert len(pages) == 2 and n_tok == 8   # 9 tokens: both pages ok
        assert pc.match([5, 6, 7, 8]) == ([], 0)  # different chunk key
        pool.free("a")
        pc.clear()
        pool.check_leaks()

    def test_insert_skips_partial_tail_page(self):
        pool = PagedKVPool(num_pages=16, page_tokens=4)
        pc = PrefixCache(pool, max_pages=8)
        prompt = list(range(1, 11))         # 10 tokens: 2 full + 1 partial
        table = pool.alloc("a", 3)
        assert pc.insert(prompt, table) == 2
        assert pool.refcount(table[2]) == 1    # tail page NOT pinned
        pool.free("a")
        assert pool.refcount(table[0]) == 1    # trie keeps full pages
        pc.clear()
        pool.check_leaks()

    def test_lru_evicts_leaves_only(self):
        """Over budget, the LRU LEAF goes first — a surviving node's
        prefix path stays fully cached."""
        pool = PagedKVPool(num_pages=16, page_tokens=2)
        pc = PrefixCache(pool, max_pages=2)
        t_a = pool.alloc("a", 2)
        pc.insert([1, 2, 3, 4], t_a)        # chain: (1,2) -> (3,4)
        t_b = pool.alloc("b", 1)
        pc.insert([9, 9], t_b)              # third node: over budget
        assert pc.pages_held() == 2
        assert pc.pages_evicted == 1
        # the leaf (3,4) was oldest-LRU; root (1,2) must survive
        assert pc.match([1, 2, 9]) == ([t_a[0]], 2)
        assert pool.refcount(t_a[1]) == 1   # only "a" holds it now
        pool.free("a")
        pool.free("b")
        pc.clear()
        pool.check_leaks()

    def test_clear_releases_every_trie_ref(self):
        pool = PagedKVPool(num_pages=16, page_tokens=2)
        pc = PrefixCache(pool, max_pages=8)
        t = pool.alloc("a", 3)
        pc.insert([1, 2, 3, 4, 5, 6], t)
        pool.free("a")
        assert pc.clear() == 3
        assert pc.pages_held() == 0
        pool.check_leaks()

    def test_note_drives_hit_rate_not_match(self):
        pool = PagedKVPool(num_pages=16, page_tokens=4)
        pc = PrefixCache(pool, max_pages=8)
        pc.match([1, 2, 3, 4, 5])           # probes never count
        assert (pc.hits, pc.misses) == (0, 0)
        pc.note(False)
        pc.note(True, n_tokens=8)
        assert (pc.hits, pc.misses) == (1, 1)
        assert pc.hit_rate() == 0.5 and pc.tokens_saved == 8


# -- prefix cache x engine ---------------------------------------------------

class TestPrefixEngine:
    def test_hits_are_token_exact_vs_reprefill_oracle(self, model):
        """ACCEPTANCE: requests sharing a system-prompt prefix hit the
        cache (tokens_saved > 0) and their outputs equal the re-prefill
        oracle exactly."""
        rng = np.random.default_rng(0)
        sys_prompt = list(rng.integers(1, 96, 17))
        prompts = [np.asarray(sys_prompt + list(rng.integers(1, 96, n)),
                              np.int32) for n in (6, 9, 4)]
        eng = ServingEngine(model, prefix_cache=True, **KW)
        r0 = eng.submit(prompts[0], max_new_tokens=5)
        outs = dict(eng.run())              # first prefill fills the trie
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts[1:]]
        outs.update(eng.run())
        for p, r in zip(prompts, [r0] + rids):
            np.testing.assert_array_equal(outs[r], _expect(model, p, 5),
                                          err_msg=f"rid {r}")
        s = eng.prefix.summary()
        assert s["hits"] == 2 and s["misses"] == 1
        assert s["tokens_saved"] >= 2 * (len(sys_prompt)
                                         // eng.page_tokens) * 8
        eng.pool.check_leaks(allow_shared=True)
        eng.prefix.clear()
        eng.pool.check_leaks()

    def test_eviction_interplay_token_exact_no_leaks(self, model):
        """ACCEPTANCE: mid-flight preemption (pool pressure) composes
        with trie pins — outputs stay token-exact and the only surviving
        references at shutdown are the trie's."""
        rng = np.random.default_rng(2)
        shared = list(rng.integers(1, 96, 9))
        prompts = [np.asarray(shared + list(rng.integers(1, 96, n)),
                              np.int32) for n in (5, 7, 3)]
        eng = ServingEngine(model, max_batch=3, page_tokens=4,
                            num_pages=12, max_pages_per_seq=8,
                            prefix_cache=16)
        r0 = eng.submit(prompts[0], max_new_tokens=12)
        outs = dict(eng.run())
        rids = [eng.submit(p, max_new_tokens=12) for p in prompts[1:]]
        outs.update(eng.run())
        assert eng.meter.summary()["evictions"] >= 1, \
            "pool was sized to force eviction; none happened"
        for p, r in zip(prompts, [r0] + rids):
            np.testing.assert_array_equal(outs[r], _expect(model, p, 12),
                                          err_msg=f"rid {r}")
        eng.pool.check_leaks(allow_shared=True)
        eng.prefix.clear()
        eng.pool.check_leaks()


# -- TP-sharded decode -------------------------------------------------------

class TestTPDecode:
    def test_ragged_tp_raises_at_construction(self, tp_model):
        with pytest.raises(ValueError, match="must divide"):
            ServingEngine(tp_model, tp=3, **KW)

    def test_tp2_token_exact_and_donated(self, model, tp_model):
        """ACCEPTANCE: the TP=2 engine (params + arenas sharded over the
        ``model`` mesh) emits the same tokens as the unsharded oracle,
        through ONE compiled decode signature whose per-shard arena
        slices pass the donation lint."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 (virtual) devices")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, 96, n).astype(np.int32)
                   for n in (5, 11, 20)]
        eng = ServingEngine(tp_model, tp=2, **KW)
        assert eng._mesh is not None and eng.tp == 2
        rids = [eng.submit(p, max_new_tokens=6, eos_token_id=5)
                for p in prompts]
        outs = eng.run()
        assert eng._decode_compiles == 1
        for p, r in zip(prompts, rids):
            np.testing.assert_array_equal(
                outs[r], _expect(model, p, 6, eos=5), err_msg=f"rid {r}")
        assert eng.lint_report is not None and eng.lint_report.ok
        eng.pool.check_leaks()


# -- depot KV-page streaming -------------------------------------------------

class TestKVFrames:
    def test_pack_unpack_roundtrip(self):
        frame = {"k": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
                 "v": np.ones((2, 3, 4), np.float32) * 0.5,
                 "ks": np.full((2, 3), 7, np.int8)}
        rt = unpack_kv_frame(pack_kv_frame(frame))
        assert sorted(rt) == sorted(frame)
        for k in frame:
            np.testing.assert_array_equal(rt[k], frame[k])
            assert rt[k].dtype == frame[k].dtype

    def test_truncated_payload_raises(self):
        data = pack_kv_frame({"k": np.ones((2, 2), np.float32)})
        with pytest.raises(ValueError):
            unpack_kv_frame(data[:-4])


class TestDepotKVStream:
    def test_put_commit_take_roundtrip(self, depot):
        payloads = [pack_kv_frame({"k": np.full((2, 2), i, np.float32)})
                    for i in range(3)]
        for i, p in enumerate(payloads):
            depot.kv_put("w0", 1, 7, i, p)
        depot.kv_commit("w0", 1, 7, {"rid": 7, "n_frames": 3})
        got = take_prefilled(depot, "w0", 1, 7)
        assert got is not None
        meta, frames = got
        assert meta["rid"] == 7 and len(frames) == 3
        np.testing.assert_array_equal(frames[2]["k"],
                                      np.full((2, 2), 2, np.float32))

    def test_take_is_one_shot(self, depot):
        depot.kv_put("w0", 1, 3, 0,
                     pack_kv_frame({"k": np.zeros((1,), np.float32)}))
        depot.kv_commit("w0", 1, 3, {"rid": 3, "n_frames": 1})
        assert depot.kv_take("w0", 1, 3) is not None
        assert depot.kv_take("w0", 1, 3) is None      # claim burned
        assert take_prefilled(depot, "w0", 1, 3) is None

    def test_commit_requires_every_frame(self, depot):
        depot.kv_put("w0", 1, 5, 0, b"\x00" * 8)
        with pytest.raises(OSError):
            depot.kv_commit("w0", 1, 5, {"rid": 5, "n_frames": 2})
        assert depot.kv_take("w0", 1, 5) is None      # nothing claimable

    def test_fence_mid_stream_refuses_zombie(self, depot):
        """ACCEPTANCE: a fence raised between a worker's puts makes every
        later put/commit of that epoch raise FencedEpoch — a SIGKILL'd
        worker's zombie can never complete a half-streamed rid."""
        depot.kv_put("w1", 1, 9, 0, b"\x01" * 8)
        depot.fence("w1", 2)                          # relaunch adopted 2
        with pytest.raises(FencedEpoch):
            depot.kv_put("w1", 1, 9, 1, b"\x02" * 8)
        with pytest.raises(FencedEpoch):
            depot.kv_commit("w1", 1, 9, {"rid": 9, "n_frames": 2})
        assert depot.kv_take("w1", 1, 9) is None
        depot.kv_put("w1", 2, 9, 0, b"\x03" * 8)      # new epoch streams
        depot.kv_commit("w1", 2, 9, {"rid": 9, "n_frames": 1})
        assert depot.kv_take("w1", 2, 9) is not None


# -- prefill tier e2e --------------------------------------------------------

class TestDisaggE2E:
    def test_prefill_tier_token_exact(self, model, depot):
        """ACCEPTANCE: a long prompt routed prefill-tier (export ->
        stream -> commit -> take -> import) and a short decode-direct one
        both finish token-exact vs the oracle; no pages leak on either
        engine."""
        rng = np.random.default_rng(0)
        long_p = np.asarray(rng.integers(1, 96, 23), np.int32)
        short_p = np.asarray(rng.integers(1, 96, 6), np.int32)
        pre = ServingEngine(model, **KW)
        dec = ServingEngine(model, **KW)
        w = PrefillWorker(pre, depot, name="pw0")
        coord = DisaggCoordinator(dec, [w], depot, min_prompt=12)
        r_long = coord.submit(long_p, max_new_tokens=5)
        r_short = coord.submit(short_p, max_new_tokens=5)
        outs = dec.run()
        np.testing.assert_array_equal(outs[r_long],
                                      _expect(model, long_p, 5))
        np.testing.assert_array_equal(outs[r_short],
                                      _expect(model, short_p, 5))
        assert coord.prefill_routed == 1 and coord.decode_direct == 1
        assert coord.fallbacks == 0
        assert w.prefills_total == 1
        dec.pool.check_leaks()
        pre.pool.check_leaks()

    @pytest.mark.parametrize("mode", ["error", "crash"])
    def test_worker_death_mid_stream_falls_back_exactly_once(
            self, model, depot, mode):
        """ACCEPTANCE (chaos): the worker dies mid-KV-stream (frame 1 of
        3).  The rid is uncommitted so nothing is claimable, the
        coordinator fences the incarnation and replays as a decode-local
        prefill — tokens exactly-once, equal to the oracle."""
        rng = np.random.default_rng(1)
        long_p = np.asarray(rng.integers(1, 96, 23), np.int32)
        pre = ServingEngine(model, **KW)
        dec = ServingEngine(model, **KW)
        w = PrefillWorker(pre, depot, name=f"pw_{mode}")
        epoch0 = w.epoch
        coord = DisaggCoordinator(dec, [w], depot, min_prompt=12)
        with faults.inject(op="disagg_stream", pattern="*frame1*",
                           mode=mode, times=1) as spec:
            rid = coord.submit(long_p, max_new_tokens=5)
        assert spec.fired == 1
        outs = dec.run()
        np.testing.assert_array_equal(outs[rid],
                                      _expect(model, long_p, 5))
        assert coord.fallbacks == 1 and coord.prefill_routed == 0
        assert w.epoch == epoch0 + 1        # incarnation fenced
        # the zombie's half-streamed rid is forever unclaimable
        assert depot.kv_take(w.name, epoch0, rid) is None
        dec.pool.check_leaks()
        pre.pool.check_leaks()

    @pytest.mark.parametrize("pre_tp,dec_tp", [(1, 2), (2, 1)])
    def test_frames_cross_arena_layouts(self, pre_tp, dec_tp):
        """One wire format: at tp=1 the arenas keep a token's 64-wide heads
        merged ``[N, P, kv * d]``, under the TP mesh the kv-head axis stays
        an axis (it is sharded).  Frames exported by an engine of one
        layout are imported by one of the other, token-exact."""
        import jax

        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 (virtual) devices")

        def build():    # TP shards the params in place: a model an engine
            paddle.seed(5)
            m = LlamaForCausalLM(llama_tiny(
                hidden_size=256, vocab_size=96, max_position_embeddings=128))
            m.eval()
            return m

        assert build().config.head_dim == 64
        pre = ServingEngine(build(), tp=pre_tp, **KW)
        dec = ServingEngine(build(), tp=dec_tp, **KW)
        assert pre._arenas["k"][0].ndim != dec._arenas["k"][0].ndim
        prompt = np.asarray(
            np.random.default_rng(2).integers(1, 96, 21), np.int32)
        first, frames = pre.prefill_export(prompt)
        assert frames[0]["k"].shape == (2, KW["page_tokens"], 2, 64)
        rid = dec.submit_prefilled(prompt, first, frames, max_new_tokens=5)
        outs = dec.run()
        np.testing.assert_array_equal(outs[rid],
                                      _expect(build(), prompt, 5))
        dec.pool.check_leaks()
        pre.pool.check_leaks()

    def test_short_prompts_never_pay_the_network_leg(self, model, depot):
        rng = np.random.default_rng(4)
        pre = ServingEngine(model, **KW)
        dec = ServingEngine(model, **KW)
        w = PrefillWorker(pre, depot, name="pw_short")
        coord = DisaggCoordinator(dec, [w], depot, min_prompt=64)
        p = np.asarray(rng.integers(1, 96, 10), np.int32)
        rid = coord.submit(p, max_new_tokens=4)
        outs = dec.run()
        np.testing.assert_array_equal(outs[rid], _expect(model, p, 4))
        assert coord.decode_direct == 1 and w.prefills_total == 0
        dec.pool.check_leaks()
        pre.pool.check_leaks()


# -- router tiers ------------------------------------------------------------

class TestRouterTier:
    def _fleet(self):
        return [ReplicaStatus(name="d0", capacity=4, queue_depth=2,
                              tier="decode"),
                ReplicaStatus(name="d1", capacity=4, queue_depth=0,
                              tier="decode"),
                ReplicaStatus(name="p0", capacity=4, queue_depth=3,
                              tier="prefill")]

    def test_tier_preference_beats_load(self):
        r = Router()
        # p0 is the most loaded replica, but a prefill-targeted pick
        # still lands there while the tier is routable
        assert r.pick(self._fleet(), tier="prefill").name == "p0"
        assert r.pick(self._fleet(), tier="decode").name == "d1"
        assert r.pick(self._fleet()).name == "d1"

    def test_empty_tier_falls_back_to_fleet(self):
        r = Router()
        fleet = [s for s in self._fleet() if s.tier != "prefill"]
        assert r.pick(fleet, tier="prefill").name == "d1"
        draining = self._fleet()
        draining[2].draining = True         # prefill tier all draining
        assert r.pick(draining, tier="prefill").name == "d1"

    def test_from_doc_default_tier_is_decode(self):
        st = ReplicaStatus.from_doc("r", {"capacity": 2})
        assert st.tier == "decode"
        st = ReplicaStatus.from_doc("p", {"tier": "prefill"})
        assert st.tier == "prefill"


# -- report CLI / rollup -----------------------------------------------------

class TestDisaggReport:
    def test_rollup_latest_disagg_doc_wins(self):
        from paddle_tpu.telemetry.aggregator import rollup
        newer = {"wall_time": 2.0, "disagg": {"prefix_hit_rate": 0.9}}
        older = {"wall_time": 1.0, "disagg": {"prefix_hit_rate": 0.1}}
        assert rollup({"a": older, "b": newer}
                      )["disagg"]["prefix_hit_rate"] == 0.9
        assert rollup({"a": newer, "z": older}
                      )["disagg"]["prefix_hit_rate"] == 0.9

    def test_report_smoke_renders_disagg_row(self, capsys):
        """ACCEPTANCE (satellite e): the report CLI shows the fleet
        prefix-hit-rate and per-tier occupancy, covered by --smoke."""
        from paddle_tpu.telemetry import report
        assert report.main(["--smoke"]) == 0
        out = capsys.readouterr().out
        assert "disagg: prefix_hit_rate=0.400" in out
        assert "tier_occupancy: decode=0.300 prefill=0.700" in out
        assert "prefill_routed=3" in out and "fallbacks=1" in out

    def test_frontend_publishes_disagg_doc(self, depot):
        from paddle_tpu.serving.fleet import ServingFrontend
        from paddle_tpu.telemetry.aggregator import rollup
        fe = ServingFrontend({}, depot, auto_attach=False)
        fe.meter.set_prefix_hit_rate(0.5)
        fe.meter.set_tier_occupancy("prefill", 0.8)
        fe.publish_disagg()
        agg = rollup(depot.metrics_pull())
        assert agg["disagg"]["prefix_hit_rate"] == 0.5
        assert agg["disagg"]["tier_occupancy"] == {"prefill": 0.8}


# -- fleet meter rows --------------------------------------------------------

class TestFleetMeterDisagg:
    def test_prefix_and_tier_rows_in_summary(self):
        m = FleetMeter()
        s = m.summary()
        assert s["prefix_hit_rate"] is None
        assert s["tier_occupancy"] == {}
        m.set_prefix_hit_rate(0.75)
        m.set_tier_occupancy("prefill", 0.5)
        m.set_tier_occupancy("decode", 0.25)
        m.prefill_route("p0", rid=1)
        m.prefill_fallback("p0", rid=2, reason="FencedEpoch")
        s = m.summary()
        assert s["prefix_hit_rate"] == 0.75
        assert s["tier_occupancy"] == {"prefill": 0.5, "decode": 0.25}
        assert s["prefill_routed"] == 1
        assert s["prefill_fallbacks"] == 1
