"""Ouro (a looped LM): the model's plain forward against the benchmark's
float32 reference on seeded random weights, the exit rule at and below the
published threshold, and each mechanism control of the reference refused
by the comparison's limit."""

import dataclasses

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.builders import ouro_serve as builder
from benchmark.lib import checks
from benchmark.reference import ouro as reference
from paddle_tpu.models import OuroForCausalLM, ouro_tiny
from paddle_tpu.models.ouro import exit_pass

# the comparison's limit here: float32 on both sides agrees to ~1e-6; the
# mildest control (K/V rounded to float8) reads 4 % and more
LIMIT = 1e-3


@pytest.fixture(scope="module")
def model():
    paddle.seed(11)
    m = OuroForCausalLM(ouro_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def cfg(model):
    return dataclasses.asdict(model.config)


@pytest.fixture(scope="module")
def weights(model):
    return builder.reference_weights(model)


def ids(n, seed=0):
    return np.random.default_rng(seed + n).integers(1, 256, n).astype(
        np.int32)


def forward(model, p):
    return np.asarray(model(paddle.to_tensor(p[None]))._value)[0]


def rel(got, want):
    return float(np.median(checks.row_errors(got, want)))


@pytest.mark.parametrize("n", [1, 7, 40])
def test_forward_agrees_with_the_reference(model, cfg, weights, n):
    p = ids(n)
    got = forward(model, p)
    want = np.asarray(reference.logits(weights, cfg, p))
    assert got.shape == (n, cfg["vocab_size"])
    assert rel(got, want) < LIMIT


@pytest.mark.parametrize("name", sorted(reference.CONTROLS))
def test_every_control_is_refused(model, cfg, weights, name):
    """Each departure from the published model (one pass fewer, the last
    pass's K/V shared by every pass, no inter-pass norm, no post-sublayer
    norms, float8 K/V) reads far above the limit the model meets."""
    p = ids(40)
    got = forward(model, p)
    bad = np.asarray(reference.logits(
        weights, cfg, p, **reference.control_kwargs(cfg, name)))
    assert rel(got, bad) > 10 * LIMIT


def test_the_exit_rule_at_and_below_the_threshold(model, cfg, weights):
    """At threshold 1.0 every token exits at the last pass; below it the
    first pass whose cumulative exit probability reaches it, and the
    model's forward follows the reference's choice token by token."""
    g = np.array([[0.0, 2.0, -3.0], [0.0, 0.0, 0.0], [5.0, 5.0, 5.0]])
    # cumulative exit probability by token (a column) after passes 0, 1:
    # [.5, .75], [.88, .94], [.047, .52]
    assert exit_pass(g, 1.0).tolist() == [2, 2, 2]
    assert exit_pass(g, 0.5).tolist() == [0, 0, 1]
    assert exit_pass(g, 0.9).tolist() == [2, 1, 2]
    for rule in (exit_pass, reference.exit_pass):
        assert np.asarray(rule(g, 0.9)).tolist() == [2, 1, 2]
    p = ids(40, 3)
    _, last = reference.logits(weights, cfg, p, return_exit=True)
    assert (np.asarray(last) == cfg["total_ut_steps"] - 1).all()
    low = dataclasses.replace(model.config, early_exit_threshold=0.5)
    _, early = reference.logits(weights, dataclasses.asdict(low), p,
                                return_exit=True)
    early = np.asarray(early)
    assert (early < cfg["total_ut_steps"] - 1).any()
    model.config = low
    try:
        got = forward(model, p)
    finally:
        model.config = ouro_tiny()
    want = np.asarray(reference.logits(weights, dataclasses.asdict(low), p))
    assert rel(got, want) < LIMIT
    assert rel(got, forward(model, p)) > 10 * LIMIT


def test_the_served_path_takes_only_the_published_threshold(model):
    model.config = dataclasses.replace(model.config,
                                       early_exit_threshold=0.9)
    try:
        with pytest.raises(NotImplementedError, match="threshold 1.0"):
            model.serve_passes()
    finally:
        model.config = ouro_tiny()
    assert model.serve_passes() == 3


def test_parameters_of_a_layer_and_the_gate(model):
    """Four norms a layer and a gate with a bias, beside Llama's matrices."""
    names = {n for n, _ in model.named_parameters()}
    for norm in ("input_layernorm", "input_layernorm_2",
                 "post_attention_layernorm", "post_attention_layernorm_2"):
        assert f"ouro.layers.1.{norm}.weight" in names
    assert {"ouro.early_exit_gate.weight", "ouro.early_exit_gate.bias",
            "ouro.norm.weight", "lm_head.weight"} <= names
    c = model.config
    layer = 4 * c.hidden_size ** 2 + 3 * c.hidden_size \
        * c.intermediate_size + 4 * c.hidden_size
    assert model.num_params() == c.num_hidden_layers * layer \
        + 2 * c.vocab_size * c.hidden_size + 2 * c.hidden_size + 1


def test_the_reference_rounded_to_bf16(model, cfg, weights):
    """Kept in bfloat16 between its operations, the reference departs from
    its float32 self by rounding alone: more than the limit of two float32
    paths, less than K/V rounded to float8."""
    p = ids(40, 5)
    f32 = np.asarray(reference.logits(weights, cfg, p))
    bf16 = np.asarray(reference.logits(weights, cfg, p, dtype="bfloat16"))
    fp8 = np.asarray(reference.logits(weights, cfg, p, kv_dtype="fp8"))
    assert bf16.dtype == np.float32
    assert LIMIT < rel(bf16, f32) < rel(fp8, f32)
