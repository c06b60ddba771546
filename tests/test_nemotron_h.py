"""Nemotron-H (one part a block: Mamba-2 with grouped B / C and a grouped
gated norm, NoPE GQA, sigmoid-routed two-matrix ``relu ** 2`` experts plus a
shared one) on the normal path: the model's full forward against the
benchmark's plain reference (``lax.scan`` over tokens, a loop over experts,
float32), the parts the shares of an expert-parallel deployment compute
against the uncut layer, the decode state-update kernel at several groups
against its ``jax.numpy`` twin, and the two-matrix expert form against a
loop over experts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.builders import nemotron_h_serve as builder
from benchmark.reference import nemotron_h as reference
from paddle_tpu.models import (NemotronHConfig, NemotronHForCausalLM,
                               nemotron_h_tiny)
from paddle_tpu.nn.layer.moe import ACTIVATIONS, RoutedExperts, \
    held_experts_mlp
from paddle_tpu.ops import ssm
from paddle_tpu.ops.pallas.ssm_state_update import (
    ssm_state_update, ssm_state_update_refusal)


def as_config_dict(cfg: NemotronHConfig) -> dict:
    keys = ("hybrid_override_pattern", "num_attention_heads",
            "num_key_value_heads", "head_dim", "hidden_size",
            "mamba_num_heads", "mamba_head_dim", "n_groups",
            "ssm_state_size", "conv_kernel", "layer_norm_epsilon",
            "num_experts_per_tok", "n_group", "topk_group",
            "norm_topk_prob", "routed_scaling_factor")
    first, count = cfg.experts_held or (0, cfg.n_routed_experts)
    return dict({k: getattr(cfg, k) for k in keys},
                experts_held=[first, count])


def tiny_model(seed=7, **kw):
    paddle.seed(seed)
    model = NemotronHForCausalLM(nemotron_h_tiny(**kw))
    model.eval()
    return model


@pytest.fixture(scope="module")
def tiny():
    return tiny_model()


@pytest.mark.parametrize("held", [None, (2, 4), (6, 2)],
                         ids=["whole", "share-2-5", "share-6-7"])
def test_forward_agrees_with_the_plain_reference(held):
    """A sequence no chunk divides, every kind of block, the whole layer
    and two shares of it: the chunked scan against the token recurrence,
    the sorted grouped matmul against the loop over experts."""
    model = tiny_model(experts_held=held)
    ids = np.random.default_rng(0).integers(1, 256, 45).astype(np.int32)
    got = model(paddle.to_tensor(ids[None])).numpy()[0]
    own = []
    want, states = reference.logits_and_states(
        builder.reference_weights(model), as_config_dict(model.config), ids,
        choices=own)
    assert got.shape == want.shape == (45, 256)
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())
    # two Mamba-2 blocks' states, two expert blocks' choices
    assert states.shape == (2, 8, 16, 128)
    assert [c.shape for c in own] == [(45, 2)] * 2


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """An expert layer spread 8 ways: what the 8 shares' held experts give,
    plus the shared expert counted ONCE, is the uncut reference's layer."""
    cfg = dict(n_routed_experts=16, num_experts_per_tok=4)
    whole = tiny_model(**cfg)
    moe = whole.backbone.layers[1].mixer
    x = paddle.to_tensor(np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (2, 19, 64)), np.float32))
    parts = []
    for i in range(8):
        share = tiny_model(experts_held=(2 * i, 2), **cfg) \
            .backbone.layers[1].mixer.experts
        assert share.up_proj.shape == [2, 64, 32] and share.gate_proj is None
        share.gate_weight.set_value(moe.experts.gate_weight.value)
        share.e_score_correction_bias.set_value(
            moe.experts.e_score_correction_bias.value)
        share.up_proj.set_value(moe.experts.up_proj.value[2 * i:2 * i + 2])
        share.down_proj.set_value(
            moe.experts.down_proj.value[2 * i:2 * i + 2])
        parts.append(share(x).numpy())
        # a token's 4 experts fall on this share or not: never dropped
        assert int(share.last_load.sum()) == int(
            ((share.last_choice >= 2 * i)
             & (share.last_choice < 2 * i + 2)).sum())
    total = sum(parts) + moe.shared_experts(x).numpy()
    w = {k: jnp.asarray(v, jnp.float32) for k, v in
         builder.reference_weights(whole)["layer"](1).items()}
    with jax.default_matmul_precision("highest"):
        want, _, _ = reference.expert_layer(
            jnp.asarray(x.numpy().reshape(-1, 64)), w,
            as_config_dict(whole.config), 0)
    np.testing.assert_allclose(total.reshape(-1, 64), want,
                               atol=2e-5 * np.abs(want).max() + 1e-6)
    # and it is the whole layer's own forward
    np.testing.assert_allclose(total, moe(x).numpy(), atol=1e-5)


def _update_args(R, H, P, N, G, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        state=jax.random.normal(k[0], (R, H, P, N)),
        live=jnp.arange(R) % 3 != 1,
        x=jax.random.normal(k[1], (R, H, P), jnp.bfloat16),
        dt=jax.nn.softplus(jax.random.normal(k[2], (R, H)) - 2.0),
        A=-jnp.exp(jax.random.normal(k[3], (H,))),
        B=jax.random.normal(k[4], (R, G, N), jnp.bfloat16),
        C=jax.random.normal(k[5], (R, G, N), jnp.bfloat16),
        D=jnp.ones((H,)))


@pytest.mark.parametrize("G", [1, 2, 8])
@pytest.mark.parametrize("shape", [(5, 8, 16, 128), (3, 64, 64, 128)],
                         ids=["tiny", "published-heads"])
def test_ssm_state_update_at_any_number_of_groups(shape, G):
    """The kernel (interpreted) against ``ops/ssm.py``'s ``jax.numpy`` step:
    head ``h`` reads group ``h // (H / G)``; at the published 64 heads a
    grid step moves 32 of them, 4 groups of 8 at once."""
    a = _update_args(*shape, G)
    assert ssm_state_update_refusal(shape, jnp.float32, a["B"].shape) is None
    want_y, want = ssm.ssm_step(**a)
    y, new = ssm_state_update(**a, interpret=True)
    np.testing.assert_allclose(new, want, atol=2e-6)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(want_y, np.float32), atol=0.06)
    idle = np.flatnonzero(~np.asarray(a["live"]))
    np.testing.assert_array_equal(np.asarray(new)[idle],
                                  np.asarray(a["state"])[idle])


@pytest.mark.parametrize("G", [2, 8])
def test_one_group_is_bit_for_bit_what_repeated_groups_give(G):
    """G = 1 is the program Granite-4.0-H had: the same B / C rows handed
    over as ``G`` groups give every bit of it, state and output."""
    a = _update_args(5, 8, 16, 128, 1)
    y1, s1 = ssm_state_update(**a, interpret=True)
    a["B"], a["C"] = (jnp.repeat(a[k], G, axis=1) for k in "BC")
    yg, sg = ssm_state_update(**a, interpret=True)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(sg))
    np.testing.assert_array_equal(np.asarray(y1, np.float32),
                                  np.asarray(yg, np.float32))
    want_y, want = ssm.ssm_step(**a)
    np.testing.assert_allclose(s1, want, atol=2e-6)


@pytest.mark.parametrize("kernel", [None, "interpret"])
@pytest.mark.parametrize("d,h", [(64, 32), (128, 96)],
                         ids=["aligned", "width-no-lane-register-divides"])
def test_two_matrix_experts_against_a_loop_over_experts(kernel, d, h):
    """``held_experts_mlp`` with no gate matrix: two grouped matmuls
    (``ragged_dot``, and the Pallas kernel interpreted — at 128 x 96 through
    its transposed-weights view) against ``sum_e w_e W_down,e relu(W_up,e
    x) ** 2`` over the held experts 3..6 of 8."""
    k = jax.random.split(jax.random.PRNGKey(2), 5)
    n, top, first, count = 32, 4, 3, 4
    x = jax.random.normal(k[0], (n, d))
    up = jax.random.normal(k[1], (count, d, h)) * 0.1
    down = jax.random.normal(k[2], (count, h, d)) * 0.1
    idx = jnp.argsort(jax.random.uniform(k[3], (n, 8)), -1)[:, :top] \
        .astype(jnp.int32)
    idx = idx.at[-3:].set(-1)           # padding: routed nowhere
    wt = jax.random.uniform(k[4], (n, top))
    y, load = held_experts_mlp(x, idx, wt, None, up, down, first,
                               kernel=kernel, activation="relu2")
    want = jnp.zeros((n, d))
    for e in range(count):
        mine = jnp.sum(jnp.where(idx == first + e, wt, 0.0), axis=1)
        want = want + mine[:, None] * (
            jnp.square(jax.nn.relu(x @ up[e])) @ down[e])
    np.testing.assert_allclose(y, want, atol=2e-5 * float(jnp.abs(want).max()))
    assert load.tolist() == [int((idx == first + e).sum())
                             for e in range(count)]


def test_the_gated_form_is_untouched_and_the_forms_are_named():
    """SwiGLU stays the default; the two-matrix form keeps no gate matrix;
    an unknown activation is refused by name."""
    swiglu = RoutedExperts(16, 8, 4, 2)
    assert swiglu.activation == "silu" and swiglu.gate_proj.shape == [4, 16, 8]
    bare = RoutedExperts(16, 8, 4, 2, activation="relu2", gated=False)
    assert bare.gate_proj is None
    assert [n for n, _ in bare.named_parameters()] == [
        "gate_weight", "e_score_correction_bias", "up_proj", "down_proj"]
    assert sorted(ACTIVATIONS) == ["relu2", "silu"]
    with pytest.raises(ValueError, match="gelu"):
        RoutedExperts(16, 8, 4, 2, activation="gelu")


@pytest.mark.parametrize("change,error", [
    (dict(hybrid_override_pattern="MEM-E"), NotImplementedError),
    (dict(hybrid_override_pattern="MEMXE"), ValueError),
    (dict(hybrid_override_pattern="ME*"), ValueError),
    (dict(mlp_hidden_act="silu"), NotImplementedError),
    (dict(tie_word_embeddings=True), NotImplementedError),
    (dict(n_groups=3), ValueError)])
def test_config_refuses_what_is_not_built(change, error):
    with pytest.raises(error):
        nemotron_h_tiny(**change)


def test_published_defaults_are_nemotron_3_nano():
    cfg = NemotronHConfig()
    pattern = cfg.hybrid_override_pattern
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*"),
            len(pattern)) == (23, 23, 6, 52)
    dims = cfg.mamba_dims
    assert (dims.d_inner, dims.conv_dim, dims.norm_groups, dims.chunk) == \
        (4096, 6144, 8, 128)
    # in_proj: z, xBC and dt
    assert dims.d_inner + dims.conv_dim + dims.n_heads == 10304


def test_the_output_projection_of_a_mamba_block_is_rescaled(tiny):
    """``rescale_prenorm_residual``: ``out_proj`` is drawn sqrt(blocks)
    narrower than the other matrices."""
    blk = tiny.backbone.layers[0]
    assert blk.kind == "M"
    wide = float(np.std(blk.mixer.in_proj.weight.numpy()))
    narrow = float(np.std(blk.mixer.out_proj.weight.numpy()))
    assert narrow == pytest.approx(wide / np.sqrt(5), rel=0.1)
