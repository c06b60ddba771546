"""Pallas kernel numerics vs the XLA reference paths (interpret mode on the
CPU backend; the same kernels compile on TPU). Forward AND backward are
checked — the kernels carry custom VJPs."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.attention import sdpa_reference
from paddle_tpu.ops.pallas import flash_attention, fused_rms_norm, fused_rope


def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 2)])
    def test_forward_matches_reference(self, causal, hq, hkv):
        b, s, d = 2, 128, 64
        q = _rand(0, (b, s, hq, d))
        k = _rand(1, (b, s, hkv, d))
        v = _rand(2, (b, s, hkv, d))
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
        ref = sdpa_reference(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_reference(self, causal):
        b, s, hq, hkv, d = 1, 128, 4, 2, 32
        q = _rand(3, (b, s, hq, d))
        k = _rand(4, (b, s, hkv, d))
        v = _rand(5, (b, s, hkv, d))

        def loss_flash(q, k, v):
            o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                                interpret=True)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = sdpa_reference(q, k, v, is_causal=causal)
            return jnp.sum(o * o)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_rectangular_seq(self, causal):
        """sq < sk (chunked prefill); causal must be bottom-right aligned."""
        q = _rand(6, (1, 64, 2, 32))
        k = _rand(7, (1, 128, 2, 32))
        v = _rand(8, (1, 128, 2, 32))
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
        ref = sdpa_reference(q, k, v, is_causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_rectangular_causal_grads(self):
        q = _rand(12, (1, 64, 2, 32))
        k = _rand(13, (1, 128, 2, 32))
        v = _rand(14, (1, 128, 2, 32))

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

        gf = jax.grad(loss(lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=64, block_k=64, interpret=True)),
            argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(lambda q, k, v: sdpa_reference(
            q, k, v, is_causal=True)), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4)

    def test_bf16_tolerance(self):
        b, s, h, d = 1, 128, 2, 64
        q = _rand(9, (b, s, h, d), jnp.bfloat16)
        k = _rand(10, (b, s, h, d), jnp.bfloat16)
        v = _rand(11, (b, s, h, d), jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
        ref = sdpa_reference(q, k, v, is_causal=True)
        np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                                   np.asarray(ref, dtype=np.float32),
                                   rtol=2e-2, atol=2e-2)

    # (sq, sk, hq, hkv, block_q, block_k, causal): a grid step walks the
    # live chunks of its major tile and no others, whatever the tiles
    TILE_CASES = {
        "wide_q": (128, 128, 2, 2, 64, 32, True),
        "wide_k": (128, 128, 2, 2, 32, 64, True),
        # offset 64 is a multiple of neither tile: of query block 0's key
        # chunks 0 is whole, 1 and 2 straddle the diagonal, 3 is dead
        "offset_off_tile": (96, 160, 2, 2, 32, 40, True),
        "gqa_4_to_1": (128, 128, 8, 2, 64, 64, True),
        "gqa_offset": (64, 128, 4, 1, 32, 64, True),
        "open_wide_q": (128, 64, 2, 2, 64, 32, False),
        "open_wide_k": (64, 128, 4, 2, 32, 64, False),
    }

    @pytest.mark.parametrize("mode", ["forward", "grads"])
    @pytest.mark.parametrize("case", sorted(TILE_CASES))
    def test_tiles_match_reference(self, case, mode):
        sq, sk, hq, hkv, bq, bk, causal = self.TILE_CASES[case]
        q = _rand(20, (2, sq, hq, 32))
        k = _rand(21, (2, sk, hkv, 32))
        v = _rand(22, (2, sk, hkv, 32))

        def flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=bq,
                                   block_k=bk, interpret=True)

        def ref(q, k, v):
            return sdpa_reference(q, k, v, is_causal=causal)

        if mode == "forward":
            np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                                       np.asarray(ref(q, k, v)),
                                       rtol=2e-5, atol=2e-5)
            return
        w = _rand(23, (2, sq, hq, 32))
        gf = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
            q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(ref(*a) * w), argnums=(0, 1, 2))(
            q, k, v)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("major", [32, 64])
    @pytest.mark.parametrize("case", ["offset_off_tile", "gqa_4_to_1",
                                      "open_wide_k"])
    def test_several_major_tiles_a_row(self, monkeypatch, case, major):
        """With more rows than a grid step holds, the streamed operand takes
        several major tiles: the accumulators carry across them, and a dead
        step names the resident one (``major`` 32: a chunk a step)."""
        import importlib

        monkeypatch.setattr(importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention"), "_MAJOR", major)
        self.test_tiles_match_reference(case, "forward")
        self.test_tiles_match_reference(case, "grads")

    @pytest.mark.parametrize("bq,bk,sq,sk,n", [
        (8, 8, 32, 32, 1), (8, 16, 64, 64, 2), (16, 8, 64, 64, 4),
        (8, 8, 32, 40, 5), (8, 16, 56, 80, 1), (16, 8, 64, 88, 11),
        (24, 16, 72, 80, 5), (8, 24, 32, 72, 3), (8, 8, 64, 64, 8)])
    def test_live_chunks_against_the_elementwise_mask(self, bq, bk, sq, sk, n):
        """A block is live where the element-wise mask keeps anything.  For
        every resident block and every major tile of ``n`` chunks of the
        streamed operand (and of one chunk: a grid step a block), the chunks
        the kernels walk are the live ones, and the index-map clamps name
        the last live key tile of a query block and the first live query
        tile of a key block."""
        from paddle_tpu.ops.pallas.flash_attention import (
            _dead_q_chunks, _first_live_q, _last_live_k, _live_k_chunks)

        offset = sk - sq
        keep = np.arange(sq)[:, None] + offset >= np.arange(sk)[None, :]
        nq, nk = sq // bq, sk // bk
        live = np.array([[keep[iq * bq:(iq + 1) * bq,
                               ik * bk:(ik + 1) * bk].any()
                          for ik in range(nk)] for iq in range(nq)])
        assert live.any(axis=1).all() and not live.all()
        for iq in range(nq):
            assert int(_last_live_k(iq, bq, bk, offset)) \
                == np.flatnonzero(live[iq]).max()
            for n_k in {1, n} if nk % n == 0 else {1}:
                for ikm in range(nk // n_k):
                    count = int(_live_k_chunks(iq, ikm, bq, bk, n_k, offset))
                    assert (np.arange(n_k) < count).tolist() \
                        == live[iq, ikm * n_k:(ikm + 1) * n_k].tolist()
        for ik in range(nk):
            assert int(_first_live_q(ik, bq, bk, offset)) \
                == np.flatnonzero(live[:, ik]).min()
            for n_q in {1, n} if nq % n == 0 else {1}:
                for iqm in range(nq // n_q):
                    dead = int(_dead_q_chunks(ik, iqm, bq, bk, n_q, offset))
                    assert (np.arange(n_q) >= dead).tolist() \
                        == live[iqm * n_q:(iqm + 1) * n_q, ik].tolist()


class TestFusedRMSNorm:
    def _ref(self, x, w, eps=1e-6):
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(ms + eps) * w.astype(jnp.float32)).astype(x.dtype)

    def test_forward(self):
        x = _rand(0, (4, 96, 256))
        w = 1.0 + 0.1 * _rand(1, (256,))
        out = fused_rms_norm(x, w, 1e-6, True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(self._ref(x, w)),
                                   rtol=1e-5, atol=1e-5)

    def test_grads(self):
        x = _rand(2, (8, 128))
        w = 1.0 + 0.1 * _rand(3, (128,))

        gf = jax.grad(lambda x, w: jnp.sum(jnp.sin(
            fused_rms_norm(x, w, 1e-6, True))), argnums=(0, 1))(x, w)
        gr = jax.grad(lambda x, w: jnp.sum(jnp.sin(
            self._ref(x, w))), argnums=(0, 1))(x, w)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


class TestFusedAddLayerNorm:
    """SURVEY §7.8 tail (round-3 verdict #7): residual-add + LayerNorm in
    one kernel, fwd and bwd, including the cotangent flowing into the
    returned residual sum."""

    def _ref(self, x, r, w, b, eps=1e-5):
        s = (x + r).astype(jnp.float32)
        mu = jnp.mean(s, axis=-1, keepdims=True)
        var = jnp.var(s, axis=-1, keepdims=True)
        out = (s - mu) * jax.lax.rsqrt(var + eps) * w + b
        return out.astype(x.dtype), s.astype(x.dtype)

    def test_forward(self):
        from paddle_tpu.ops.pallas.fused_ln_swiglu import fused_add_layer_norm

        x = _rand(0, (4, 24, 256))
        r = _rand(1, (4, 24, 256))
        w = 1.0 + 0.1 * _rand(2, (256,))
        b = 0.1 * _rand(3, (256,))
        out, s = fused_add_layer_norm(x, r, w, b, 1e-5, True)
        ro, rs = self._ref(x, r, w, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ro),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs),
                                   rtol=1e-6, atol=1e-6)

    def test_grads_both_outputs(self):
        from paddle_tpu.ops.pallas.fused_ln_swiglu import fused_add_layer_norm

        x = _rand(4, (6, 128))
        r = _rand(5, (6, 128))
        w = 1.0 + 0.1 * _rand(6, (128,))
        b = 0.1 * _rand(7, (128,))

        def loss_k(x, r, w, b):  # uses BOTH outputs (normed and the sum)
            out, s = fused_add_layer_norm(x, r, w, b, 1e-5, True)
            return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.cos(s) * 0.3)

        def loss_r(x, r, w, b):
            out, s = self._ref(x, r, w, b)
            return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.cos(s) * 0.3)

        gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(x, r, w, b)
        gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(x, r, w, b)
        for a, e in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       rtol=1e-4, atol=1e-5)

    def test_incubate_surface_dispatches(self):
        import paddle_tpu as paddle
        from paddle_tpu.incubate.nn.functional import fused_layer_norm

        paddle.set_flags({"pallas_interpret": True,
                          "use_fused_layernorm": True})
        try:
            x = paddle.to_tensor(np.asarray(_rand(8, (2, 8, 128))))
            r = paddle.to_tensor(np.asarray(_rand(9, (2, 8, 128))))
            w = paddle.ones([128])
            b = paddle.zeros([128])
            out, pre = fused_layer_norm(x, w, b, residual=r)
            ro, rs = self._ref(x._value, r._value, w._value, b._value)
            np.testing.assert_allclose(out.numpy(), np.asarray(ro),
                                       rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(pre.numpy(), np.asarray(rs),
                                       rtol=1e-6, atol=1e-6)
        finally:
            paddle.set_flags({"pallas_interpret": False,
                              "use_fused_layernorm": False})


class TestFusedSwiglu:
    def _ref(self, g, u):
        return jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)

    def test_forward_and_grads(self):
        from paddle_tpu.ops.pallas.fused_ln_swiglu import fused_swiglu

        g = _rand(10, (4, 16, 256))
        u = _rand(11, (4, 16, 256))
        out = fused_swiglu(g, u, True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(g, u)),
                                   rtol=1e-5, atol=1e-5)
        gk = jax.grad(lambda a, b: jnp.sum(jnp.sin(fused_swiglu(a, b, True))),
                      argnums=(0, 1))(g, u)
        gr = jax.grad(lambda a, b: jnp.sum(jnp.sin(
            jax.nn.silu(a) * b)), argnums=(0, 1))(g, u)
        for a, e in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       rtol=1e-4, atol=1e-5)

    def test_f_swiglu_dispatch_matches_jnp(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F

        g = np.asarray(_rand(12, (2, 8, 128)))
        u = np.asarray(_rand(13, (2, 8, 128)))
        plain = F.swiglu(paddle.to_tensor(g), paddle.to_tensor(u)).numpy()
        paddle.set_flags({"pallas_interpret": True, "use_fused_swiglu": True})
        try:
            fused = F.swiglu(paddle.to_tensor(g), paddle.to_tensor(u)).numpy()
        finally:
            paddle.set_flags({"pallas_interpret": False,
                              "use_fused_swiglu": False})
        np.testing.assert_allclose(fused, plain, rtol=1e-5, atol=1e-5)


class TestFusedAdamW:
    def test_matches_update_rule(self):
        from paddle_tpu.ops.pallas.fused_ln_swiglu import fused_adamw

        p = _rand(14, (256, 128))
        g = 0.1 * _rand(15, (256, 128))
        m = 0.01 * _rand(16, (256, 128))
        v = jnp.abs(0.01 * _rand(17, (256, 128)))
        lr, t, b1, b2, eps, wd = 1e-3, 7, 0.9, 0.999, 1e-8, 0.01
        new_p, new_m, new_v = fused_adamw(p, g, m, v, lr, t, b1, b2, eps,
                                          wd, True, interpret=True)
        rm = b1 * m + (1 - b1) * g
        rv = b2 * v + (1 - b2) * jnp.square(g)
        mhat = rm / (1 - b1 ** t)
        vhat = rv / (1 - b2 ** t)
        rp = p - lr * mhat / (jnp.sqrt(vhat) + eps) - lr * wd * p
        np.testing.assert_allclose(np.asarray(new_p), np.asarray(rp),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(new_m), np.asarray(rm),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(new_v), np.asarray(rv),
                                   rtol=1e-6, atol=1e-7)

    def test_optimizer_flag_path_matches_dense(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn as nn

        def run(flag):
            paddle.seed(0)
            m = nn.Linear(128, 128, bias_attr=False)
            opt = paddle.optimizer.AdamW(1e-2, parameters=m.parameters(),
                                         weight_decay=0.01)
            paddle.set_flags({"use_fused_adamw": flag,
                              "pallas_interpret": flag})
            try:
                for _ in range(3):
                    loss = (m(paddle.ones([4, 128])) ** 2).sum()
                    loss.backward()
                    opt.step()
                    opt.clear_grad()
            finally:
                paddle.set_flags({"use_fused_adamw": False,
                                  "pallas_interpret": False})
            return m.weight.numpy()

        np.testing.assert_allclose(run(True), run(False), rtol=1e-5,
                                   atol=1e-6)


class TestFusedRope:
    def _tables(self, s, d):
        from paddle_tpu.models.llama import _rope_tables

        cos, sin = _rope_tables(d, s, 10000.0)
        return cos, sin

    def _ref(self, x, cos, sin):
        half = x.shape[-1] // 2
        rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
        c = cos[None, :, None, :].astype(jnp.float32)
        s = sin[None, :, None, :].astype(jnp.float32)
        return (x.astype(jnp.float32) * c + rot.astype(jnp.float32) * s).astype(x.dtype)

    def test_forward(self):
        b, s, hq, hk, d = 2, 64, 4, 2, 64
        cos, sin = self._tables(s, d)
        q, k = _rand(0, (b, s, hq, d)), _rand(1, (b, s, hk, d))
        oq, ok = fused_rope(q, k, cos, sin, True)
        np.testing.assert_allclose(np.asarray(oq), np.asarray(self._ref(q, cos, sin)),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(ok), np.asarray(self._ref(k, cos, sin)),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_orthogonal_backward(self):
        b, s, h, d = 1, 32, 2, 32
        cos, sin = self._tables(s, d)
        q, k = _rand(2, (b, s, h, d)), _rand(3, (b, s, h, d))

        def loss_fused(q, k):
            oq, ok = fused_rope(q, k, cos, sin, True)
            return jnp.sum(oq * oq) + jnp.sum(jnp.cos(ok))

        def loss_ref(q, k):
            return (jnp.sum(self._ref(q, cos, sin) ** 2) +
                    jnp.sum(jnp.cos(self._ref(k, cos, sin))))

        gf = jax.grad(loss_fused, argnums=(0, 1))(q, k)
        gr = jax.grad(loss_ref, argnums=(0, 1))(q, k)
        for a, b_ in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-5)
