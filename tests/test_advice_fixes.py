"""Regression tests for advisor findings. Round 1: batch_norm
eager gradients, pool ceil_mode/return_mask, AmpScaler.minimize contract,
interpolate align_corners, AdamW lr_ratio. Round 3: rpc frame auth, ASP
masks registered after TrainStep compilation, DataLoader unpicklable custom
collate, deepcopy of an O2-decorated model."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


class TestBatchNormEagerGrad:
    def test_eager_grad_differentiates_batch_stats(self):
        """Training-mode BN grads must include the terms through batch
        mean/var (advisor found them dropped: eager treated stats as
        constants)."""
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        xv = rng.standard_normal((8, 4, 5, 5)).astype("float32")

        x = paddle.to_tensor(xv, stop_gradient=False)
        rm = paddle.zeros([4])
        rv = paddle.ones([4])
        out = F.batch_norm(x, rm, rv, training=True)
        (out * out).sum().backward()
        got = x.grad.numpy()

        def ref(v):
            mean = jnp.mean(v, axis=(0, 2, 3), keepdims=True)
            var = jnp.var(v, axis=(0, 2, 3), keepdims=True)
            o = (v - mean) * jax.lax.rsqrt(var + 1e-5)
            return jnp.sum(o * o)

        want = np.asarray(jax.grad(ref)(jnp.asarray(xv)))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_running_stats_still_update(self):
        x = paddle.to_tensor(np.random.default_rng(1)
                             .standard_normal((16, 3)).astype("float32"))
        rm = paddle.zeros([3])
        rv = paddle.ones([3])
        F.batch_norm(x, rm, rv, training=True, momentum=0.9)
        assert not np.allclose(rm.numpy(), 0.0)


class TestPoolModes:
    def test_return_mask_raises(self):
        x = paddle.rand([1, 2, 8, 8])
        with pytest.raises(NotImplementedError):
            F.max_pool2d(x, 2, return_mask=True)

    def test_ceil_mode_shape_and_values(self):
        import torch

        xv = np.random.default_rng(2).standard_normal((1, 1, 8, 8)).astype("float32")
        got = F.max_pool2d(paddle.to_tensor(xv), 3, stride=2, padding=0,
                           ceil_mode=True).numpy()
        want = torch.nn.functional.max_pool2d(
            torch.from_numpy(xv), 3, stride=2, padding=0, ceil_mode=True).numpy()
        assert got.shape == want.shape == (1, 1, 4, 4)
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_ceil_mode_drops_window_entirely_in_padding(self):
        """(out-1)*stride >= n + pad_lo must drop the last window (torch/
        paddle rule); a naive ceil extension yields a -inf element."""
        import torch

        xv = np.array([[[1.0, 2.0, 3.0]]], dtype="float32")
        got = F.max_pool1d(paddle.to_tensor(xv), 2, stride=2, padding=1,
                           ceil_mode=True).numpy()
        want = torch.nn.functional.max_pool1d(
            torch.from_numpy(xv), 2, stride=2, padding=1, ceil_mode=True).numpy()
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want)

    def test_avg_inclusive_count_ceil_mode(self):
        """count_include_pad counts real padding but never the ceil
        extension."""
        import torch

        xv = np.ones((1, 1, 5), dtype="float32")
        got = F.avg_pool1d(paddle.to_tensor(xv), 2, stride=2, padding=0,
                           exclusive=False, ceil_mode=True).numpy()
        want = torch.nn.functional.avg_pool1d(
            torch.from_numpy(xv), 2, stride=2, padding=0,
            count_include_pad=True, ceil_mode=True).numpy()
        np.testing.assert_allclose(got, want)

    def test_layer_wrappers_forward_ceil_and_mask(self):
        x = paddle.rand([1, 1, 8, 8])
        out = nn.MaxPool2D(3, stride=2, ceil_mode=True)(x)
        assert tuple(out.shape) == (1, 1, 4, 4)
        with pytest.raises(NotImplementedError):
            nn.MaxPool2D(2, return_mask=True)(x)

    def test_avg_ceil_mode_matches_torch(self):
        import torch

        xv = np.random.default_rng(3).standard_normal((1, 2, 7, 7)).astype("float32")
        got = F.avg_pool2d(paddle.to_tensor(xv), 2, stride=2,
                           ceil_mode=True).numpy()
        # paddle exclusive=True counts only real elements, = torch
        # count_include_pad=False
        want = torch.nn.functional.avg_pool2d(
            torch.from_numpy(xv), 2, stride=2, ceil_mode=True,
            count_include_pad=False).numpy()
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


class TestInterpolateAlignment:
    def test_bilinear_align_corners_matches_torch(self):
        import torch

        xv = np.random.default_rng(4).standard_normal((2, 3, 5, 7)).astype("float32")
        got = F.interpolate(paddle.to_tensor(xv), size=(10, 13), mode="bilinear",
                            align_corners=True).numpy()
        want = torch.nn.functional.interpolate(
            torch.from_numpy(xv), size=(10, 13), mode="bilinear",
            align_corners=True).numpy()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_area_mode_is_true_area_pool(self):
        import torch

        xv = np.random.default_rng(5).standard_normal((1, 2, 8, 8)).astype("float32")
        got = F.interpolate(paddle.to_tensor(xv), size=(4, 4), mode="area").numpy()
        want = torch.nn.functional.interpolate(
            torch.from_numpy(xv), size=(4, 4), mode="area").numpy()
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_unsupported_align_corners_raises(self):
        x = paddle.rand([1, 1, 4, 4])
        with pytest.raises(NotImplementedError):
            F.interpolate(x, size=(8, 8), mode="bicubic", align_corners=True)


class TestAdamWLrRatio:
    def test_lr_ratio_scales_updates(self):
        paddle.seed(0)
        m = nn.Linear(4, 4)
        w0 = m.weight.numpy().copy()
        b0 = m.bias.numpy().copy()
        # ratio 0 for the 2-D weight, 1 for bias → weight must not move
        opt = paddle.optimizer.AdamW(
            0.1, parameters=m.parameters(), weight_decay=0.0,
            lr_ratio=lambda p: 0.0 if p.ndim == 2 else 1.0)
        loss = (m(paddle.rand([2, 4])) ** 2).sum()
        loss.backward()
        opt.step()
        np.testing.assert_allclose(m.weight.numpy(), w0)
        assert not np.allclose(m.bias.numpy(), b0)


class TestRpcFrameAuth:
    def test_hmac_roundtrip_and_tamper_rejection(self):
        import socket
        import threading

        from paddle_tpu.distributed.rpc import _recv_blob, _send_blob

        secret = b"s3cret"
        srv = socket.socket()
        srv.bind(("127.0.0.1", 0))
        srv.listen(1)
        port = srv.getsockname()[1]
        got = {}

        def receiver(expect_secret):
            conn, _ = srv.accept()
            with conn:
                try:
                    got["blob"] = _recv_blob(conn, expect_secret)
                except PermissionError as e:
                    got["err"] = e

        # 1) same secret → payload arrives
        t = threading.Thread(target=receiver, args=(secret,))
        t.start()
        with socket.create_connection(("127.0.0.1", port)) as c:
            _send_blob(c, b"payload", secret)
        t.join()
        assert got.pop("blob") == b"payload"

        # 2) wrong secret (tampered/foreign frame) → rejected BEFORE pickle
        t = threading.Thread(target=receiver, args=(secret,))
        t.start()
        with socket.create_connection(("127.0.0.1", port)) as c:
            _send_blob(c, b"payload", b"wrong-secret")
        t.join()
        srv.close()
        assert isinstance(got.get("err"), PermissionError)

    def test_local_ip_resolves_routable_interface(self):
        from paddle_tpu.distributed.rpc import _local_ip

        ip = _local_ip("127.0.0.1:12345")
        assert ip.startswith("127.")
        import os

        os.environ["PADDLE_LOCAL_IP"] = "10.1.2.3"
        try:
            assert _local_ip("127.0.0.1:1") == "10.1.2.3"
        finally:
            del os.environ["PADDLE_LOCAL_IP"]


class TestAspLateMask:
    def test_prune_after_trainstep_compilation_raises(self):
        from paddle_tpu.incubate import asp

        asp.ASPHelper.reset()
        paddle.seed(0)
        m = nn.Linear(8, 8)
        opt = paddle.optimizer.SGD(0.1, parameters=m.parameters())
        step = paddle.jit.TrainStep(m, lambda mm, x, y: ((mm(x) - y) ** 2).mean(),
                                    opt)
        x = paddle.rand([4, 8])
        y = paddle.rand([4, 8])
        float(step(x, y).numpy())  # dense step works
        asp.prune_model(m)  # masks registered AFTER compilation
        try:
            with pytest.raises(RuntimeError, match="ASP mask.*changed"):
                step(x, y)
        finally:
            asp.ASPHelper.reset()

    def test_prune_before_trainstep_still_masks(self):
        from paddle_tpu.incubate import asp

        asp.ASPHelper.reset()
        paddle.seed(1)
        m = nn.Linear(8, 8)
        asp.prune_model(m)
        opt = paddle.optimizer.SGD(0.1, parameters=m.parameters())
        step = paddle.jit.TrainStep(m, lambda mm, x, y: ((mm(x) - y) ** 2).mean(),
                                    opt)
        float(step(paddle.rand([4, 8]), paddle.rand([4, 8])).numpy())
        w = m.weight.numpy()
        # 2:4 sparsity held through the fused update
        assert asp.check_mask_1d(w.T) or asp.check_mask_1d(w)
        asp.ASPHelper.reset()


class TestDataLoaderPicklingFallback:
    def test_unpicklable_custom_collate_falls_back_to_threads(self, caplog):
        import logging

        from paddle_tpu.io import DataLoader, Dataset

        class DS(Dataset):
            def __getitem__(self, i):
                return np.float32(i)

            def __len__(self):
                return 8

        def collate(batch):  # output closes over a lambda → unpicklable
            return {"value": np.stack(batch), "fn": lambda: None}

        dl = DataLoader(DS(), batch_size=2, num_workers=2, collate_fn=collate)
        with caplog.at_level(logging.WARNING, logger="paddle_tpu.io"):
            out = list(dl)
        assert len(out) == 4 and callable(out[0]["fn"])
        assert any("not picklable" in r.message or "falling back" in r.message
                   for r in caplog.records)


class TestAmpO2Deepcopy:
    def test_deepcopy_rebinds_forward_to_the_copy(self):
        import copy

        paddle.seed(0)
        m = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        opt = paddle.optimizer.SGD(0.1, parameters=m.parameters())
        m, opt = paddle.amp.decorate(m, opt, level="O2", dtype="bfloat16")
        x = paddle.rand([2, 4])
        before = m(x).numpy()

        m2 = copy.deepcopy(m)
        np.testing.assert_allclose(m2(x).numpy(), before, rtol=1e-3)
        # zero the ORIGINAL's weights: the copy must be unaffected (the old
        # bug kept the copy's forward bound to the original's parameters)
        for p in m.parameters():
            p.set_value(np.zeros(p.shape, dtype="float32"))
        assert np.allclose(m(x).numpy(), 0.0)
        np.testing.assert_allclose(m2(x).numpy(), before, rtol=1e-3)
        # the copy's params are its own objects
        assert {id(p) for p in m.parameters()}.isdisjoint(
            {id(p) for p in m2.parameters()})


class TestAmpScalerContract:
    def test_minimize_does_not_clear_grads_or_backward(self):
        paddle.seed(0)
        m = nn.Linear(4, 4)
        opt = paddle.optimizer.SGD(0.1, parameters=m.parameters())
        scaler = paddle.amp.AmpScaler(init_loss_scaling=8.0)
        loss = (m(paddle.rand([2, 4])) ** 2).sum()
        scaled = scaler.scale(loss)
        scaled.backward()  # caller's responsibility (reference contract)
        g_before = m.weight.grad.numpy().copy()
        scaler.minimize(opt)
        # grads unscaled in place but NOT cleared
        assert m.weight.grad is not None
        np.testing.assert_allclose(m.weight.grad.numpy(), g_before / 8.0,
                                   rtol=1e-6)

    def test_scaler_defaults_match_reference(self):
        s = paddle.amp.AmpScaler()
        assert s.get_loss_scaling() == 2.0 ** 15
        assert s._incr_every_n_steps == 1000
        g = paddle.amp.GradScaler()
        assert g.get_loss_scaling() == 2.0 ** 16
        assert g._incr_every_n_steps == 2000
