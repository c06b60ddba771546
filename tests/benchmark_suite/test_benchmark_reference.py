"""Both plain references against the repo's own models at a tiny size, in
float32 on the CPU, through each builder's weight adapter."""

import numpy as np
import pytest

from benchmark.builders import gpt_train, llama_serve
from benchmark.lib import checks
from benchmark.reference import gpt2_like, llama_like

LLAMA = {"hidden_size": 64, "intermediate_size": 160, "num_hidden_layers": 3,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 300, "max_position_embeddings": 1024,
         "rms_norm_eps": 1e-5, "rope_theta": 1000000.0,
         "tie_word_embeddings": False, "initializer_range": 0.05}
GPT = {"n_embd": 64, "n_layer": 3, "n_head": 4, "n_inner": 256,
       "n_positions": 1024, "vocab_size": 300, "layer_norm_epsilon": 1e-5,
       "resid_pdrop": 0.0, "initializer_range": 0.05}


def _ids(n, vocab, seed=0):
    return np.random.default_rng(seed).integers(1, vocab, n).astype(np.int32)


def test_llama_like_matches_the_repos_llama():
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaForCausalLM

    paddle.seed(3)
    model = LlamaForCausalLM(llama_serve.llama_config(LLAMA))
    model.eval()
    ids = _ids(700, 300)        # longer than one query block of the reference
    with paddle.no_grad():
        got = np.asarray(model(paddle.to_tensor(ids[None])).value[0])
    weights = llama_serve.reference_weights(model)
    ref = np.asarray(llama_like.logits(weights, LLAMA, ids))
    assert checks.row_errors(got, ref).max() < 1e-4
    picked = np.asarray(llama_like.logits(weights, LLAMA, ids, [5, 699]))
    np.testing.assert_allclose(picked, ref[[5, 699]], rtol=1e-5, atol=1e-5)
    labels = np.roll(ids, -1)
    with paddle.no_grad():
        loss = float(model(paddle.to_tensor(ids[None]),
                           labels=paddle.to_tensor(labels[None]))[0])
    assert llama_like.loss_of(ref, labels) == \
        pytest.approx(loss, abs=1e-4)


def test_gpt2_like_matches_the_repos_gpt():
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM

    paddle.seed(4)
    model = GPTForCausalLM(gpt_train.gpt_config(GPT, recompute=False))
    model.eval()
    # biases and norm offsets start at 0: move them so that they count
    rng = np.random.default_rng(1)
    for name, p in model.named_parameters():
        if name.endswith("bias"):
            p.set_value(rng.normal(0, 0.1, p.shape).astype("float32"))
    ids = _ids(600, 300, seed=2)
    labels = np.roll(ids, -1)
    with paddle.no_grad():
        loss, logits = model(paddle.to_tensor(ids[None]),
                             labels=paddle.to_tensor(labels[None]))
    weights = gpt_train.reference_weights(model)
    ref = np.asarray(gpt2_like.logits(weights, GPT, ids))
    assert checks.row_errors(np.asarray(logits.value[0]), ref).max() < 1e-4
    assert gpt2_like.loss_of(ref, labels) == \
        pytest.approx(float(loss), abs=1e-4)


def test_the_limits_catch_a_coarser_precision():
    """What decides ``correct``, at the limits the two configuration files
    state: noise of the size bfloat16 gave on the chip passes, noise of the
    size int8 or fp8 KV pages gave does not, and neither does one corrupted
    row among good ones."""
    import json
    import os

    from benchmark.lib import registry

    def tol(name):
        with open(os.path.join(registry.ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            return json.load(f)["check"]["logit_rms_tol"]

    rng = np.random.default_rng(0)
    ref = rng.normal(0, 1.3, (24, 4096)).astype(np.float32)

    def noisy(rel):
        return ref + rng.normal(0, 1.3 * rel, ref.shape)

    m = tol("mistral-7b-v0.3")
    assert checks.logits_agree(checks.row_errors(noisy(0.036), ref), m)["ok"]
    assert not checks.logits_agree(checks.row_errors(noisy(0.067), ref),
                                   m)["ok"]
    assert not checks.logits_agree(checks.row_errors(noisy(0.22), ref),
                                   m)["ok"]
    one_bad = noisy(0.036)
    one_bad[7] = noisy(0.3)[7]
    v = checks.logits_agree(checks.row_errors(one_bad, ref), m)
    assert v["logits_rms_rel_err_median"] < m and not v["ok"]
    g = tol("cerebras-gpt-1.3b")
    assert checks.logits_agree(checks.row_errors(noisy(0.010), ref), g)["ok"]
    assert not checks.logits_agree(checks.row_errors(noisy(0.03), ref),
                                   g)["ok"]
    chosen = ref.argmax(-1)
    assert checks.short_of_best(ref, chosen) == 0.0
    second = np.argsort(ref, -1)[:, -2]
    gap = ref.max(-1) - ref[np.arange(24), second]
    assert checks.short_of_best(ref, second) == pytest.approx(gap.max())
