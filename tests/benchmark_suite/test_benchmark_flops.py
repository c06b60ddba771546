"""The benchmark's own operation and byte counts against hand-worked values
for both published configurations, and the peaks table."""

import json
import os

import pytest

from benchmark.lib import flops, peaks

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def test_mistral_parameters_by_hand():
    cfg = _config("mistral-7b-v0.3")
    # q, o: 4096*4096 each; k, v: 4096*1024 each; mlp 3*4096*14336
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert layer == 218_103_808
    s = flops.shape_of(cfg)
    assert s["per_layer"] == layer and s["layers"] == 16
    total = 16 * (layer + 2 * 4096) + 4096 + 2 * 32768 * 4096
    assert flops.param_count(cfg) == total == 3_758_231_552
    # the published depth is the 7.25 B model
    assert flops.param_count(dict(cfg, num_hidden_layers=32)) == 7_248_023_552


def test_cerebras_parameters_by_hand():
    cfg = _config("cerebras-gpt-1.3b")
    layer_w = 4 * 2048 * 2048 + 2 * 2048 * 8192
    layer_small = (3 * 2048 + 2048 + 8192 + 2048) + 4 * 2048
    total = 12 * (layer_w + layer_small) + 2 * 2048 \
        + (50304 + 2048) * 2048
    assert flops.param_count(cfg) == total == 711_520_256
    published = dict(cfg, n_layer=24, vocab_size=50257)
    assert flops.param_count(published) == 1_315_723_264   # "1.3B"


def test_train_flops_per_token_by_hand():
    cfg = _config("cerebras-gpt-1.3b")
    matmul = 2 * (12 * (4 * 2048 * 2048 + 2 * 2048 * 8192) + 50304 * 2048)
    attn = 12 * 2 * 2048 * 16 * 128
    assert flops.forward_flops_per_token(cfg, 2048) == matmul + attn
    assert flops.train_flops_per_token(cfg, 2048) == 3 * (matmul + attn) \
        == pytest.approx(4.543e9, rel=1e-3)
    m = dict(_config("mistral-7b-v0.3"), num_hidden_layers=8)
    fwd = 2 * (8 * 218_103_808 + 32768 * 4096) + 8 * 2 * 4096 * 32 * 128
    assert flops.train_flops_per_token(m, 4096) == 3 * fwd \
        == pytest.approx(12.08e9, rel=1e-3)


def test_flash_forward_ops_bytes_and_roofline():
    ops, nbytes = flops.flash_fwd_ops_bytes(4, 2048, 16, 16, 128)
    assert ops == 4 * 16 * 2048 * 2048 * 128 * 4 / 2
    assert nbytes == 4 * 4 * 2048 * 16 * 128 * 2 + 4 * 16 * 2048 * 4
    peak = peaks.lookup("TPU v5 lite")
    t, bound = flops.roofline_s(ops, nbytes, peak)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)
    t, bound = flops.roofline_s(1e6, 819e9, peak)
    assert bound == "memory" and t == pytest.approx(1.0)
    # grouped-query attention reads fewer k/v bytes, does the same work
    ops8, bytes8 = flops.flash_fwd_ops_bytes(1, 4096, 32, 8, 128)
    ops32, bytes32 = flops.flash_fwd_ops_bytes(1, 4096, 32, 32, 128)
    assert ops8 == ops32 and bytes8 < bytes32


def test_unknown_device_kind_is_an_error():
    assert peaks.lookup("TPU v5e") is peaks.lookup("TPU v5 lite")
    with pytest.raises(KeyError, match="not in benchmark/lib/peaks.py"):
        peaks.lookup("cpu")
