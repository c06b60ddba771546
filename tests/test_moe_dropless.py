"""The dropless expert layer: group-limited sigmoid routing with the
selection bias, sort-based dispatch over a grouped matmul, a layer that is
told which experts it holds.  The share test ties the cut to the model: the
parts all shares compute, with the shared expert counted once, add up to the
uncut reference layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.reference import deepseek_v3 as reference
from paddle_tpu.nn.layer.moe import (RoutedExperts, group_limited_topk,
                                     held_experts_mlp)
from paddle_tpu.ops.pallas.grouped_matmul import (KERNEL_NAME,
                                                  grouped_matmul,
                                                  grouped_matmul_refusal)

ROUTING = dict(top_k=4, n_group=4, topk_group=2, norm_topk_prob=True,
               routed_scaling_factor=2.5)
CFG = dict(n_group=4, topk_group=2, num_experts_per_tok=4,
           norm_topk_prob=True, routed_scaling_factor=2.5)


def test_the_group_limit_binds():
    """Groups 0 and 1 hold the two largest single scores, but a group is
    judged by the sum of its two largest: groups 2 and 3 stay, and nothing
    is chosen outside them."""
    s = np.full((1, 16), 0.1, np.float32)
    s[0, [0, 4]] = 0.9, 0.8                 # lone peaks in groups 0 and 1
    s[0, [8, 9, 10]] = 0.7, 0.6, 0.5        # group 2: top-2 sum 1.3
    s[0, [12, 13, 14]] = 0.65, 0.6, 0.55    # group 3: top-2 sum 1.25
    idx, w = group_limited_topk(jnp.asarray(s), jnp.zeros(16), **ROUTING)
    assert sorted(idx[0].tolist()) == [8, 9, 12, 13]
    np.testing.assert_allclose(float(w.sum()), 2.5, rtol=1e-6)


def test_the_bias_moves_the_choice_and_not_the_weight():
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.uniform(0.2, 0.8, (6, 16)), jnp.float32)
    bias = jnp.zeros(16).at[5].set(1.0)     # expert 5 always wins a slot
    plain_idx, _ = group_limited_topk(s, jnp.zeros(16), **ROUTING)
    idx, w = group_limited_topk(s, bias, **ROUTING)
    assert (idx == 5).any(axis=1).all()
    assert not (plain_idx == 5).any(axis=1).all()
    # the weights are s over the chosen, without the bias, and sum to 2.5
    chosen = np.take_along_axis(np.asarray(s), np.asarray(idx), 1)
    np.testing.assert_allclose(
        w, 2.5 * chosen / chosen.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(1), 2.5, rtol=1e-6)


def test_routing_agrees_with_the_reference():
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=(40, 32)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.05, 0.05, 16), jnp.float32)
    want_idx, want_w = reference.route(y, wr, bias, CFG)
    idx, w = group_limited_topk(jax.nn.sigmoid(y @ wr), bias, **ROUTING)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    np.testing.assert_allclose(w, want_w, rtol=1e-5)


@pytest.mark.parametrize("sizes,m", [([3, 0, 9, 5], 32), ([130, 0, 100], 256),
                                     ([0, 0, 0, 0], 32), ([128, 128], 256)],
                         ids=["ragged", "across-tiles", "empty", "full"])
def test_grouped_matmul_kernel_agrees_with_ragged_dot(sizes, m):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(m, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(len(sizes), 16, 24)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    assert grouped_matmul_refusal(x.shape, w.shape, x.dtype,
                                  interpret=True) is None
    got = grouped_matmul(x, w, gs, interpret=True)
    np.testing.assert_allclose(got, jax.lax.ragged_dot(x, w, gs), atol=1e-4)
    assert not np.asarray(got[sum(sizes):]).any()


@pytest.mark.parametrize("m", [272, 200])
def test_pairs_that_are_no_whole_row_tiles_still_take_the_kernel(m):
    """A serving launch's pairs (a prompt's tokens and the decode rows that
    ride beside them, times the experts a token) need not fill whole row
    tiles: the layer's ``grouped_matmul`` pads them past every group, and
    its gate asks the kernel about the padded count."""
    from paddle_tpu import telemetry
    from paddle_tpu.distributed import topology
    from paddle_tpu.nn.layer import moe

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(m, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 16, 24)), jnp.float32)
    sizes = jnp.asarray([70, 0, m - 90], jnp.int32)     # 20 rows of no group
    assert grouped_matmul_refusal(x.shape, w.shape, x.dtype,
                                  interpret=True) == "tiling"
    flags = ["pallas_interpret", "use_decode_attention"]
    prior, prior_hcg = paddle.get_flags(flags), topology._hcg
    topology._hcg = None          # an earlier distributed test's mesh
    paddle.set_flags(dict.fromkeys(flags, True))
    try:
        before = dict(telemetry.counters())
        assert moe.grouped_matmul_kernel(m, w.shape, (3, 24, 16),
                                         x.dtype) == "interpret"
        assert {k: v for k, v in telemetry.counters().items()
                if k.startswith("kernel_fallback.")} == \
            {k: v for k, v in before.items()
             if k.startswith("kernel_fallback.")}
    finally:
        paddle.set_flags(prior)
        topology._hcg = prior_hcg
    got = moe.grouped_matmul(x, w, sizes, "interpret")
    assert got.shape == (m, 24)
    np.testing.assert_allclose(got, jax.lax.ragged_dot(x, w, sizes),
                               atol=1e-4)


def test_grouped_matmul_gate_names_its_reason():
    bf16 = jnp.bfloat16
    assert KERNEL_NAME == "moe_grouped_matmul"
    assert grouped_matmul_refusal((1024, 7168), (16, 7168, 2048), bf16) is None
    assert grouped_matmul_refusal((1024, 2048), (16, 2048, 7168), bf16) is None
    assert grouped_matmul_refusal((1024,), (16, 8, 8), bf16) == "rank"
    assert grouped_matmul_refusal((64, 8), (2, 16, 8), bf16) == "shape"
    assert grouped_matmul_refusal((12, 128), (2, 128, 128), bf16) == "tiling"
    assert grouped_matmul_refusal((64, 100), (2, 100, 128), bf16) == "tiling"


def layer_of(held, seed=3):
    paddle.seed(seed)
    return RoutedExperts(32, 24, 16, 4, n_group=4, topk_group=2,
                         routed_scaling_factor=2.5, experts_held=held,
                         weight_attr=paddle.nn.initializer.Normal(0.0, 0.3),
                         bias_attr=paddle.nn.initializer.Uniform(-0.05, 0.05))


def reference_layer(whole, x):
    """The uncut reference layer's routed part (its shared expert zero)."""
    w = {"w_router": whole.gate_weight.value,
         "router_bias": whole.e_score_correction_bias.value,
         "e_gate": whole.gate_proj.value, "e_up": whole.up_proj.value,
         "e_down": whole.down_proj.value}
    zero = jnp.zeros((32, 24)), jnp.zeros((32, 24)), jnp.zeros((24, 32))
    w.update(zip(("s_gate", "s_up", "s_down"), zero))
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda y, w: reference.expert_layer(y, w, CFG, 0)[0])(
            jnp.asarray(x), w)


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips: each share routes over all 16 and computes
    its 4; the four routed parts add up to the whole layer's, which is the
    uncut reference's (the shared expert, which every chip computes alike,
    is counted once: it is outside ``RoutedExperts``)."""
    whole = layer_of(None)
    x = np.random.default_rng(4).normal(size=(2, 9, 32)).astype(np.float32)
    total = np.zeros_like(x)
    for first in range(0, 16, 4):
        share = layer_of((first, 4))
        share.gate_weight.set_value(whole.gate_weight.value)
        share.e_score_correction_bias.set_value(
            whole.e_score_correction_bias.value)
        for name in ("gate_proj", "up_proj", "down_proj"):
            getattr(share, name).set_value(
                getattr(whole, name).value[first:first + 4])
        total += np.asarray(share(paddle.to_tensor(x)).value)
    np.testing.assert_allclose(total, whole(paddle.to_tensor(x)).value,
                               atol=1e-5)
    np.testing.assert_allclose(
        total.reshape(18, 32), reference_layer(whole, x.reshape(18, 32)),
        atol=1e-5)


@pytest.mark.parametrize("kernels", [False, True], ids=["ragged_dot",
                                                        "kernel"])
def test_no_token_is_dropped_when_all_choose_one_expert(kernels):
    """64 tokens whose four choices all include expert 2: the expert
    computes every one of its 64 pairs (a capacity of 64 * 4 / 16 = 16
    would have dropped 48)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    idx = jnp.asarray(np.stack([np.full(64, 2), rng.integers(4, 8, 64),
                                rng.integers(8, 12, 64),
                                rng.integers(12, 16, 64)], 1), jnp.int32)
    wt = jnp.asarray(rng.uniform(0.1, 1.0, (64, 4)), jnp.float32)
    wg, wu = (jnp.asarray(rng.normal(size=(4, 16, 8)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.normal(size=(4, 8, 16)), jnp.float32)
    y, load = held_experts_mlp(x, idx, wt, wg, wu, wd, 0,
                               kernel="interpret" if kernels else None)
    assert load.tolist() == [0, 0, 64, 0]
    want = wt[:, :1] * ((jax.nn.silu(x @ wg[2]) * (x @ wu[2])) @ wd[2])
    np.testing.assert_allclose(y, want, atol=1e-4)


def test_the_tie_width_of_a_choice():
    """What the benchmark's check holds a program's expert choices to: 0
    for the reference's own choice, no more than a small multiple of the
    noise for the choice of noisy scores, and large for a choice that no
    rounding explains."""
    rng = np.random.default_rng(9)
    scores = jnp.asarray(rng.uniform(0, 1, (400, 16)), jnp.float32)
    own = reference._select(scores, CFG)
    assert float(reference.tie_width(scores, own, CFG).max()) == 0.0
    noisy = reference._select(scores + jnp.asarray(
        rng.uniform(-0.01, 0.01, scores.shape), jnp.float32), CFG)
    flipped = (np.sort(own, -1) != np.sort(noisy, -1)).any(-1)
    width = np.asarray(reference.tie_width(scores, noisy, CFG))
    assert flipped.any() and (width[flipped] > 0).all() \
        and not width[~flipped].any() and width.max() <= 0.02
    worst = reference._select(-scores, CFG)     # the least likely experts
    assert float(reference.tie_width(scores, worst, CFG).min()) > 0.1


def test_the_reference_follows_forced_choices():
    """``forced`` rows that are not negative replace the layer's own
    choice, the weights stay its own scores over them; a row of -1 keeps
    the layer's choice."""
    rng = np.random.default_rng(10)
    y = jnp.asarray(rng.normal(size=(6, 32)), jnp.float32)
    w = {"w_router": jnp.asarray(rng.normal(size=(32, 16)), jnp.float32),
         "router_bias": jnp.zeros(16),
         **{k: jnp.asarray(rng.normal(size=sh) * 0.1, jnp.float32)
            for k, sh in (("e_gate", (16, 32, 8)), ("e_up", (16, 32, 8)),
                          ("e_down", (16, 8, 32)), ("s_gate", (32, 8)),
                          ("s_up", (32, 8)), ("s_down", (8, 32)))}}
    free, own, width = reference.expert_layer(y, w, CFG, 0)
    assert not np.asarray(width).any()
    forced = np.asarray(own).copy()
    forced[3:] = -1
    forced[0] = np.asarray(reference._select(
        -jax.nn.sigmoid(y @ w["w_router"]), CFG))[0]
    out, own2, width = reference.expert_layer(y, w, CFG, 0,
                                              jnp.asarray(forced))
    np.testing.assert_array_equal(own, own2)
    assert float(width[0]) > 0 and not np.asarray(width[1:]).any()
    np.testing.assert_allclose(out[1:], free[1:], atol=1e-6)
    assert np.abs(np.asarray(out[0] - free[0])).max() > 1e-3


def test_the_kernel_differentiates_through_ragged_dot():
    """The Pallas grouped matmul has no backward of its own; the layer's
    ``grouped_matmul`` gives it ``ragged_dot``'s, so one forward serves a
    program with a tape or a VJP over it and one without."""
    from paddle_tpu.nn.layer import moe

    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 128, 128)), jnp.float32)
    sizes = jnp.asarray([40, 0, 50, 20], jnp.int32)    # 18 rows of no group

    def loss(kernel):
        return lambda x, w: jnp.sum(
            moe.grouped_matmul(x, w, sizes, kernel) ** 2)

    assert grouped_matmul_refusal(x.shape, w.shape, x.dtype,
                                  interpret=True) is None
    for got, want in zip(jax.grad(loss("interpret"), (0, 1))(x, w),
                         jax.grad(loss(None), (0, 1))(x, w)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_tokens_that_are_not_valid_are_routed_nowhere():
    layer = layer_of((0, 16))
    x = paddle.to_tensor(np.random.default_rng(6).normal(
        size=(1, 6, 32)).astype(np.float32))
    valid = jnp.asarray([[True, True, False, True, False, False]])
    y = layer(x, valid=valid)
    assert int(layer.last_load.sum()) == 3 * 4
    assert not np.asarray(y.value)[0, [2, 4, 5]].any()
    np.testing.assert_allclose(np.asarray(y.value)[0, [0, 1, 3]],
                               np.asarray(layer(x).value)[0, [0, 1, 3]],
                               atol=1e-6)
