"""The Granite-4.0-H hybrid (Mamba-2 + NoPE GQA) on the normal path: the
sequence ops against the token recurrence, the model's full forward against
the benchmark's plain reference (``lax.scan`` over tokens, float32), and the
decode state-update kernel against its ``jax.numpy`` twin."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.builders import granite_hybrid_serve as builder
from benchmark.reference import granite_hybrid as reference
from paddle_tpu.models import (GraniteHybridConfig, GraniteHybridForCausalLM,
                               granite_hybrid_tiny)
from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas.ssm_state_update import (
    ssm_state_update, ssm_state_update_refusal)

H, P, N = 4, 8, 128


def as_config_dict(cfg: GraniteHybridConfig) -> dict:
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "attention_multiplier", "embedding_multiplier",
            "residual_multiplier", "logits_scaling", "rms_norm_eps",
            "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
            "mamba_d_state", "mamba_d_conv", "mamba_expand", "layer_types")
    return {k: getattr(cfg, k) for k in keys}


@pytest.fixture(scope="module")
def tiny():
    paddle.seed(7)
    model = GraniteHybridForCausalLM(granite_hybrid_tiny())
    model.eval()
    return model


def _recurrence(x, dt, A, B, C, D, state, n_valid):
    ys = []
    for t in range(x.shape[1]):
        y, state = ssm.ssm_step(state, t < n_valid, x[:, t], dt[:, t], A,
                                B[:, t], C[:, t], D)
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.fixture(scope="module")
def sequence():
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    b, T = 2, 150
    return dict(
        x=jax.random.normal(k[0], (b, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(k[1], (b, T, H)) - 2.0),
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        B=jax.random.normal(k[3], (b, T, 1, N)),
        C=jax.random.normal(k[4], (b, T, 1, N)), D=jnp.ones((H,)),
        state=jax.random.normal(k[5], (b, H, P, N)),
        n_valid=jnp.array([150, 101]))


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_ssd_chunked_is_the_token_recurrence(sequence, chunk):
    """A carried-in state, a ragged ``n_valid``, a length no chunk divides:
    the chunk length changes no value, and tokens past ``n_valid`` leave
    the state as it was."""
    s = sequence
    want_y, want_state = _recurrence(**s)
    y, state = ssm.ssd_chunked(s["x"], s["dt"], s["A"], s["B"], s["C"],
                               s["D"], s["state"], s["n_valid"], chunk)
    real = (jnp.arange(150)[None] < s["n_valid"][:, None])[..., None, None]
    np.testing.assert_allclose(np.where(real, y, 0),
                               np.where(real, want_y, 0), atol=2e-4)
    np.testing.assert_allclose(state, want_state, atol=2e-5)


def test_causal_conv_carries_its_tail():
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    b, T, C, K = 2, 11, 6, 4
    x = jax.random.normal(k[0], (b, T, C))
    w, bias = jax.random.normal(k[1], (C, K)), jax.random.normal(k[2], (C,))
    zero = jnp.zeros((b, K - 1, C))
    whole, tail = ssm.causal_conv1d(x, w, bias, zero, jnp.array([T, T]))
    # in two pieces, the second ending in padding for row 1
    y1, t1 = ssm.causal_conv1d(x[:, :5], w, bias, zero, jnp.array([5, 5]))
    y2, t2 = ssm.causal_conv1d(x[:, 5:], w, bias, t1, jnp.array([6, 2]))
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), whole, atol=1e-6)
    np.testing.assert_allclose(t2[0], x[0, -3:], atol=0)
    np.testing.assert_allclose(t2[1], x[1, 4:7], atol=0)
    np.testing.assert_allclose(tail, x[:, -3:], atol=0)
    # no valid token: the tail comes back as it went in
    _, t3 = ssm.causal_conv1d(x[:, 5:], w, bias, t1, jnp.array([0, 0]))
    np.testing.assert_array_equal(t3, t1)
    # by the definition: y_t = b + sum_k w[:, k] x_{t - (K-1) + k}
    xp = np.concatenate([np.zeros((b, K - 1, C)), np.asarray(x)], 1)
    want = sum(xp[:, j:j + T] * np.asarray(w)[:, j] for j in range(K)) \
        + np.asarray(bias)
    np.testing.assert_allclose(whole, want, atol=1e-5)


@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0], [0, 0, 0, 0, 0],
                                  [1, 1, 1, 1, 1]],
                         ids=["some", "none", "all"])
def test_ssm_state_update_kernel_touches_live_rows_only(live):
    """Interpret mode against the ``jax.numpy`` twin; an idle row's arena
    slot is bit-unchanged and its output zero."""
    k = jax.random.split(jax.random.PRNGKey(11), 7)
    R, heads = 5, 16                    # two head blocks at this size
    state = jax.random.normal(k[0], (R, heads, P, N))
    live = jnp.array(live, bool)
    x = jax.random.normal(k[1], (R, heads, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[2], (R, heads)))
    A = -jnp.exp(jax.random.normal(k[3], (heads,)))
    B = jax.random.normal(k[4], (R, 1, N)).astype(jnp.bfloat16)
    C = jax.random.normal(k[5], (R, 1, N)).astype(jnp.bfloat16)
    D = jax.random.normal(k[6], (heads,))
    assert ssm_state_update_refusal(state.shape, state.dtype, B.shape) is None
    want_y, want = ssm.ssm_step(state, live, x, dt, A, B, C, D)
    import sys

    mod = sys.modules[ssm_state_update_refusal.__module__]
    old, mod._BLOCK_BYTES = mod._BLOCK_BYTES, 8 * P * N * 4
    try:
        y, new = ssm_state_update(state, live, x, dt, A, B, C, D,
                                  interpret=True)
    finally:
        mod._BLOCK_BYTES = old
    assert y.dtype == x.dtype
    np.testing.assert_allclose(new, want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want_y, np.float32), atol=0.06)
    idle = np.flatnonzero(~np.asarray(live))
    np.testing.assert_array_equal(np.asarray(new)[idle],
                                  np.asarray(state)[idle])
    assert not np.asarray(y, np.float32)[idle].any()


@pytest.mark.parametrize("shape,dtype,groups,reason", [
    ((4, 8, 16, 128), "float32", 1, None),
    ((4, 8, 16, 128), "bfloat16", 1, "state_dtype"),
    ((4, 8, 16, 128), "float32", 2, None),
    ((4, 8, 16, 128), "float32", 8, None),
    ((4, 8, 16, 128), "float32", 3, "head_groups"),
    ((4, 8, 16, 96), "float32", 1, "state_tile"),
    ((4, 8, 16), "float32", 1, "rank")])
def test_ssm_state_update_gate(shape, dtype, groups, reason):
    assert ssm_state_update_refusal(shape, dtype, (4, groups, 128)) == reason


def test_forward_agrees_with_the_plain_reference(tiny):
    """A sequence that no chunk divides, against the token recurrence."""
    ids = np.random.default_rng(0).integers(1, 256, 45).astype(np.int32)
    got = tiny(paddle.to_tensor(ids[None])).numpy()[0]
    want = np.asarray(reference.logits(
        builder.reference_weights(tiny), as_config_dict(tiny.config), ids))
    assert got.shape == want.shape == (45, 256)
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def test_forward_trains(tiny):
    """The mixer's custom op records a tape node: the loss falls."""
    paddle.seed(1)
    model = GraniteHybridForCausalLM(granite_hybrid_tiny(num_hidden_layers=2,
                                     layer_types=("mamba", "attention")))
    opt = paddle.optimizer.AdamW(1e-2, parameters=model.parameters())
    ids = paddle.to_tensor(
        np.random.default_rng(1).integers(1, 256, (2, 40)).astype(np.int32))
    losses = []
    for _ in range(4):
        loss, _ = model(ids, labels=ids)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    assert model.model.layers[0].mamba.A_log.grad is None  # cleared


@pytest.mark.parametrize("change,error", [
    (dict(num_local_experts=4), NotImplementedError),
    (dict(position_embedding_type="rope"), NotImplementedError),
    (dict(tie_word_embeddings=False), NotImplementedError),
    (dict(layer_types=("mamba",)), ValueError),
    (dict(layer_types=("mamba", "conv", "mamba", "mamba")), ValueError)])
def test_config_refuses_what_is_not_built(change, error):
    with pytest.raises(error):
        granite_hybrid_tiny(**change)


def test_published_defaults_are_the_micro_model():
    cfg = GraniteHybridConfig()
    assert cfg.layer_types.count("attention") == 4
    assert [i for i, k in enumerate(cfg.layer_types)
            if k == "attention"] == [5, 15, 25, 35]
    assert (cfg.head_dim, cfg.mamba_d_inner, cfg.mamba_conv_dim) == \
        (64, 4096, 4352)
