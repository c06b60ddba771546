"""Pallas decode-attention kernel vs the einsum/numpy oracle (interpret
mode on the CPU backend; the same kernel compiles on TPU), plus the varlen
flash forward and the kernel-fallback visibility counters.

Tier-1 ``serving`` lane: the kernel is the serving hot path — GQA, bf16,
ragged valid-lengths, and the aliased in-place cache append all get an
oracle here so regressions surface as numbers, not as an 8K bench cliff.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.ops.attention import sdpa_reference
from paddle_tpu.ops.pallas import (decode_attention,
                                   decode_attention_supported,
                                   flash_attention_varlen,
                                   flash_attention_varlen_supported)

pytestmark = pytest.mark.serving


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


def _oracle(q, k_new, v_new, ck, cv, pos, pad=None):
    """The grouped-einsum cached-attention path, verbatim semantics:
    append at ``pos``, attend cols [pad, pos]."""
    b, s, h, d = q.shape
    kv = k_new.shape[2]
    C = ck.shape[1]
    ck = jax.lax.dynamic_update_slice_in_dim(ck, k_new.astype(ck.dtype), pos, 1)
    cv = jax.lax.dynamic_update_slice_in_dim(cv, v_new.astype(cv.dtype), pos, 1)
    g = h // kv
    q5 = q.reshape(b, s, kv, g, d).astype(ck.dtype)
    scores = jnp.einsum("bskgd,bckd->bkgsc", q5, ck,
                        preferred_element_type=jnp.float32) / jnp.sqrt(float(d))
    col = jnp.arange(C)[None, None, None, None, :]
    allowed = col <= pos
    if pad is not None:
        allowed = allowed & (col >= pad[:, None, None, None, None])
    scores = jnp.where(allowed, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsc,bckd->bskgd", probs.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype), ck, cv


class TestDecodeAttention:
    # kv fills whole sublane tiles of the cache dtype (f32 8, bf16 16) —
    # the shapes the TPU lowering takes; MHA and GQA of each
    @pytest.mark.parametrize("h,kv,dtype", [
        (8, 8, jnp.float32), (16, 8, jnp.float32),
        (16, 16, jnp.bfloat16), (32, 16, jnp.bfloat16)])
    def test_matches_oracle(self, h, kv, dtype):
        b, d, C, blk, pos = 2, 32, 64, 32, 21
        q = _rand(0, (b, 1, h, d), dtype)
        kn = _rand(1, (b, 1, kv, d), dtype)
        vn = _rand(2, (b, 1, kv, d), dtype)
        ck = _rand(3, (b, C, kv, d), dtype)
        cv = _rand(4, (b, C, kv, d), dtype)
        assert decode_attention_supported(q.shape, ck.shape, block_k=blk,
                                          dtype=dtype)
        out, ck2, cv2 = decode_attention(q, kn, vn, ck, cv, pos,
                                         block_k=blk, interpret=True)
        ro, rck, rcv = _oracle(q, kn, vn, ck, cv, pos)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ro, np.float32),
            rtol=tol, atol=tol)
        # the appended row is the BIT-EXACT new k/v; untouched slots
        # identical to the input cache (the aliased in-place contract)
        np.testing.assert_array_equal(np.asarray(ck2, np.float32),
                                      np.asarray(rck, np.float32))
        np.testing.assert_array_equal(np.asarray(cv2, np.float32),
                                      np.asarray(rcv, np.float32))

    def test_ragged_valid_lengths(self):
        """Per-row left-padding: padded slots never contribute."""
        b, h, kv, d, C, blk, pos = 3, 16, 8, 16, 96, 32, 40
        pads = jnp.asarray([0, 7, 33], jnp.int32)
        q = _rand(5, (b, 1, h, d))
        kn = _rand(6, (b, 1, kv, d))
        vn = _rand(7, (b, 1, kv, d))
        ck = _rand(8, (b, C, kv, d))
        cv = _rand(9, (b, C, kv, d))
        out, _, _ = decode_attention(q, kn, vn, ck, cv, pos, pads,
                                     block_k=blk, interpret=True)
        ro, _, _ = _oracle(q, kn, vn, ck, cv, pos, pads)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                                   rtol=2e-5, atol=2e-5)

    def test_fully_padded_row_attends_only_new_token(self):
        """pad >= pos leaves a row NO valid cache cols — it must attend
        exactly its own new token (the einsum semantics), not go NaN."""
        b, h, kv, d, C, blk, pos = 2, 16, 8, 16, 64, 32, 8
        pads = jnp.asarray([0, pos], jnp.int32)   # row 1: cache fully masked
        q = _rand(13, (b, 1, h, d))
        kn = _rand(14, (b, 1, kv, d))
        vn = _rand(15, (b, 1, kv, d))
        ck = _rand(16, (b, C, kv, d))
        cv = _rand(17, (b, C, kv, d))
        out, _, _ = decode_attention(q, kn, vn, ck, cv, pos, pads,
                                     block_k=blk, interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        ro, _, _ = _oracle(q, kn, vn, ck, cv, pos, pads)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                                   rtol=2e-5, atol=2e-5)

    def test_traced_pos_under_scan(self):
        """The decode scan carries ``pos`` as a traced scalar; the cache
        threads through the aliased kernel step after step."""
        b, h, kv, d, C, blk = 1, 8, 8, 16, 32, 16
        q = _rand(10, (b, 1, h, d))
        kn = _rand(11, (b, 1, kv, d))
        vn = _rand(12, (b, 1, kv, d))
        ck = jnp.zeros((b, C, kv, d))
        cv = jnp.zeros((b, C, kv, d))

        def body(carry, pos):
            ck, cv = carry
            out, ck, cv = decode_attention(q, kn, vn, ck, cv, pos,
                                           block_k=blk, interpret=True)
            return (ck, cv), out

        (ck2, cv2), _ = jax.jit(lambda c: jax.lax.scan(
            body, c, jnp.arange(4, dtype=jnp.int32)))((ck, cv))
        for p in range(4):
            np.testing.assert_array_equal(np.asarray(ck2)[:, p],
                                          np.asarray(kn)[:, 0])
        assert not np.asarray(cv2)[:, 4:].any()  # untouched slots stay zero

    def test_gate_rejects_bad_shapes(self):
        f32 = dict(dtype=jnp.float32)
        assert decode_attention_supported((2, 1, 8, 32), (2, 64, 8, 32),
                                          block_k=32, **f32)
        assert not decode_attention_supported(
            (2, 1, 8, 32), (2, 64, 8, 32), **f32)  # default block 256 > C=64
        assert not decode_attention_supported((2, 2, 8, 32), (2, 64, 8, 32),
                                              block_k=32, **f32)  # s != 1
        assert not decode_attention_supported((2, 1, 8, 30), (2, 64, 8, 30),
                                              block_k=32, **f32)  # d % 8
        assert not decode_attention_supported((2, 1, 8, 32), (2, 60, 8, 32),
                                              block_k=32, **f32)  # C % block
        # kv must fill whole sublane tiles of the cache dtype
        assert not decode_attention_supported((2, 1, 4, 32), (2, 64, 2, 32),
                                              block_k=32, **f32)
        assert not decode_attention_supported((2, 1, 8, 32), (2, 64, 8, 32),
                                              block_k=32)  # bf16 wants 16
        # the streamed K/V blocks must fit scoped VMEM
        assert not decode_attention_supported(
            (1, 1, 64, 256), (1, 2048, 64, 256), block_k=1024)


class TestVarlenFlash:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2)])
    def test_matches_masked_reference(self, hq, hkv):
        b, s, d, blk = 2, 128, 32, 64
        q = _rand(20, (b, s, hq, d))
        k = _rand(21, (b, s, hkv, d))
        v = _rand(22, (b, s, hkv, d))
        pads = jnp.asarray([13, 49], jnp.int32)
        assert flash_attention_varlen_supported(q.shape, k.shape,
                                                block_q=blk, block_k=blk)
        out = flash_attention_varlen(q, k, v, pads, block_q=blk,
                                     block_k=blk, interpret=True)
        keep = (jnp.arange(s)[None, :] >= pads[:, None]).astype(jnp.float32)
        mask = (1.0 - keep)[:, None, None, :] * jnp.finfo(jnp.float32).min
        ref = sdpa_reference(q, k, v, mask=mask, is_causal=True)
        for ib in range(b):  # rows inside the padding are undefined
            p = int(pads[ib])
            np.testing.assert_allclose(np.asarray(out)[ib, p:],
                                       np.asarray(ref)[ib, p:],
                                       rtol=2e-5, atol=2e-5)

    def test_gate(self):
        assert not flash_attention_varlen_supported(
            (2, 64, 4, 32), (2, 128, 4, 32), block_q=64, block_k=64)  # sq!=sk
        assert not flash_attention_varlen_supported(
            (2, 100, 4, 32), (2, 100, 4, 32), block_q=64, block_k=64)


class TestKernelDispatchParity:
    """CPU-smoke acceptance: generate through the Pallas decode kernel
    (interpret mode) is TOKEN-EXACT vs the einsum path, padded and not."""

    @pytest.fixture(autouse=True)
    def _no_leftover_mesh(self):
        """A distributed test run earlier in the session can leave a live
        hybrid communicate group; pallas_mode would then dispatch 'mesh'
        and these tests would exercise (and assert on) the wrong path."""
        from paddle_tpu.distributed import topology as topo

        prior = topo.get_hybrid_communicate_group()
        topo._hcg = None
        yield
        topo._hcg = prior

    @pytest.fixture(scope="class")
    def model(self):
        from paddle_tpu.models import LlamaForCausalLM, llama_tiny

        paddle.seed(3)
        # f32 cache: 8 kv heads fill the sublane tile the kernel gate asks
        cfg = llama_tiny(num_hidden_layers=2, vocab_size=96,
                         hidden_size=128, num_attention_heads=8,
                         num_key_value_heads=8,
                         max_position_embeddings=128)
        m = LlamaForCausalLM(cfg)
        m.eval()
        return m

    def test_decode_parity_token_exact(self, model):
        rng = np.random.default_rng(0)
        ids = rng.integers(1, 96, (2, 11)).astype(np.int32)
        base, bs = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                                  eos_token_id=5, pad_token_id=0)
        prior = paddle.get_flags(["pallas_interpret"])
        paddle.set_flags({"pallas_interpret": True})
        try:
            kern, ks = model.generate(paddle.to_tensor(ids), max_new_tokens=8,
                                      eos_token_id=5, pad_token_id=0)
        finally:
            paddle.set_flags(prior)
        np.testing.assert_array_equal(base.numpy(), kern.numpy())
        np.testing.assert_allclose(bs.numpy(), ks.numpy(), atol=1e-5)

    def test_padded_decode_parity_token_exact(self, model):
        """Left-padded ragged batch: varlen-flash prefill + padded decode
        kernel vs the dense path."""
        rng = np.random.default_rng(1)
        ids = rng.integers(1, 96, (2, 16)).astype(np.int32)
        mask = np.ones((2, 16), np.int32)
        mask[0, :5] = 0
        base, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                                 eos_token_id=5, pad_token_id=0,
                                 attention_mask=mask)
        prior = paddle.get_flags(["pallas_interpret"])
        paddle.set_flags({"pallas_interpret": True})
        try:
            kern, _ = model.generate(paddle.to_tensor(ids), max_new_tokens=6,
                                     eos_token_id=5, pad_token_id=0,
                                     attention_mask=mask)
        finally:
            paddle.set_flags(prior)
        np.testing.assert_array_equal(base.numpy(), kern.numpy())

    def test_fallback_event_and_counter(self, model):
        """A gate rejection with the Pallas path enabled must narrate
        itself: flight-recorder event + counter naming the reason."""
        import paddle_tpu.telemetry as tel
        from paddle_tpu.generation import cached_attention

        tel.reset()
        prior = paddle.get_flags(["pallas_interpret"])
        paddle.set_flags({"pallas_interpret": True})
        try:
            # C=60 not tileable → decode kernel gate rejects → einsum path
            q = jnp.zeros((1, 1, 8, 30))
            kn = jnp.zeros((1, 1, 8, 30))
            out, _, _ = cached_attention(q, kn, kn, jnp.zeros((1, 60, 8, 30)),
                                         jnp.zeros((1, 60, 8, 30)), 3)
        finally:
            paddle.set_flags(prior)
        assert out.shape == (1, 1, 8, 30)
        counts = tel.counters()
        assert counts.get("kernel_fallback.decode_attention.shape", 0) >= 1
        events = [e for e in tel.get_flight_recorder().events()
                  if e["kind"] == "kernel_fallback"]
        assert any(e["name"] == "decode_attention"
                   and e.get("reason") == "shape" for e in events)
