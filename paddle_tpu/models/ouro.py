"""Ouro (ByteDance's looped language model, "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741): ONE stack of decoder layers that
a forward pass runs ``total_ut_steps`` times, the same weights each time.

A layer is Llama's multi-head attention (rotate-half RoPE over the whole
head) and SwiGLU MLP with a norm on both sides of each sublayer, as the
published ``modeling_ouro.py`` has it::

    h = h + input_layernorm_2(attn(input_layernorm(h)))
    h = h + post_attention_layernorm_2(mlp(post_attention_layernorm(h)))

After every pass the one shared final norm is applied, and the normalised
state is what the next pass starts from.  ``early_exit_gate`` (a Linear to
one logit, with a bias) scores each pass's output; the exit rule turns the
passes' scores into an exit distribution and a token leaves at the first
pass whose cumulative probability reaches ``early_exit_threshold``.  At the
published threshold 1.0 that is the last pass, which is what the serving
path computes; :meth:`OuroForCausalLM.forward` applies the rule in full.

Served through ``ServingEngine`` as a looped walk (``serve_passes``,
``serve_pass_end``: ``models/serve_protocol.py``): the programs hold each
layer once, and each (pass, layer) keeps K/V in pages of its own."""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..tensor.manipulation import reshape
from ..tensor.tensor import Tensor
from .llama import (LlamaAttention, LlamaConfig, LlamaMLP, _rope_tables,
                    rope_rows, rotate_half_apply)
from .serve_protocol import AttentionLayer

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "ouro_tiny",
           "exit_pass"]


@dataclass
class OuroConfig:
    """The published ``config.json`` keys the model reads, under their own
    names (Ouro-2.6B's values)."""
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0

    def llama_config(self) -> LlamaConfig:
        """Llama's attention and MLP take their shapes from a LlamaConfig,
        whose head is ``hidden_size / num_attention_heads`` wide."""
        if self.head_dim * self.num_attention_heads != self.hidden_size:
            raise ValueError(
                f"head_dim {self.head_dim} x {self.num_attention_heads} "
                f"heads must be hidden_size {self.hidden_size}")
        return LlamaConfig(
            vocab_size=self.vocab_size, hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            num_hidden_layers=self.num_hidden_layers,
            num_attention_heads=self.num_attention_heads,
            num_key_value_heads=self.num_key_value_heads,
            max_position_embeddings=self.max_position_embeddings,
            rms_norm_eps=self.rms_norm_eps, rope_theta=self.rope_theta,
            initializer_range=self.initializer_range)


def ouro_tiny(**kw) -> OuroConfig:
    """Test scale: 2 layers run 3 times."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=4, head_dim=16,
                max_position_embeddings=512, total_ut_steps=3)
    base.update(kw)
    return OuroConfig(**base)


def exit_pass(gate_logits, threshold: float):
    """The pass each token exits at, from the passes' gate logits
    ``[passes, ...]``: pass ``t`` takes ``sigmoid(g_t)`` of what the earlier
    passes left, the last pass all that is left, and a token exits at the
    first pass whose cumulative probability reaches ``threshold`` (the last
    one where none does before).  Returns int32 ``[...]``."""
    lam = jax.nn.sigmoid(jnp.asarray(gate_logits, jnp.float32))
    left, cdf, reached = 1.0, 0.0, []
    for t in range(lam.shape[0] - 1):
        cdf = cdf + lam[t] * left
        left = left * (1.0 - lam[t])
        reached.append(cdf >= threshold)
    reached.append(jnp.ones_like(lam[0], bool))
    return jnp.argmax(jnp.stack(reached), axis=0).astype(jnp.int32)


class OuroDecoderLayer(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        lc = config.llama_config()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.self_attn = LlamaAttention(lc)
        self.mlp = LlamaMLP(lc)
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.input_layernorm_2 = nn.RMSNorm(h, eps)
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.post_attention_layernorm_2 = nn.RMSNorm(h, eps)

    def forward(self, x, cos, sin):
        x = x + self.input_layernorm_2(
            self.self_attn(self.input_layernorm(x), cos, sin))
        return x + self.post_attention_layernorm_2(
            self.mlp(self.post_attention_layernorm(x)))


class OuroModel(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=init)
        self.layers = nn.LayerList([OuroDecoderLayer(config)
                                    for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.early_exit_gate = nn.Linear(config.hidden_size, 1,
                                         weight_attr=init)
        cos, sin = _rope_tables(config.head_dim,
                                config.max_position_embeddings,
                                config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)


class OuroForCausalLM(nn.Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        if config.tie_word_embeddings:
            raise ValueError("Ouro's head is untied (tie_word_embeddings "
                             "false in the published config)")
        self.config = config
        self.ouro = OuroModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 weight_attr=nn.initializer.Normal(
                                     0.0, config.initializer_range),
                                 bias_attr=False)

    def forward(self, input_ids):
        """``input_ids`` [b, s] -> logits [b, s, V]: every pass over the
        whole prompt, then each token's state at the pass the exit rule
        picks (:func:`exit_pass`) through the head."""
        base, cfg = self.ouro, self.config
        cos, sin = base.rope_cos._value, base.rope_sin._value
        x = base.embed_tokens(input_ids)
        states, gates = [], []
        for _ in range(cfg.total_ut_steps):
            for layer in base.layers:
                x = layer(x, cos, sin)
            x = base.norm(x)
            states.append(x._value)
            gates.append(base.early_exit_gate(x)._value[..., 0])
        pick = exit_pass(jnp.stack(gates), cfg.early_exit_threshold)
        h = jnp.take_along_axis(jnp.stack(states), pick[None, ..., None],
                                axis=0)[0]
        return self.lm_head(Tensor(h))

    # -- what ServingEngine asks of a model (serve_protocol.py) ------------
    def serve_layers(self):
        cfg = self.config
        return [AttentionLayer(cfg.num_attention_heads,
                               cfg.num_key_value_heads, cfg.head_dim)
                for _ in self.ouro.layers]

    def serve_passes(self) -> int:
        """The walk runs ``total_ut_steps`` times.  Served tokens leave at
        the last pass, which is the exit rule at threshold 1.0 only."""
        if self.config.early_exit_threshold < 1.0:
            raise NotImplementedError(
                f"early_exit_threshold {self.config.early_exit_threshold}: "
                f"the engine serves every token through every pass (the "
                f"exit rule at threshold 1.0); a token that leaves early "
                f"would leave later tokens without its K/V of the passes "
                f"it skipped")
        return self.config.total_ut_steps

    def serve_begin(self, tokens, positions):
        """``tokens`` [R, s] ids, ``positions`` [R] each row's first
        token's position, or [R, s] every token's: the embeddings, and the
        rotary tables that every layer of every pass shares."""
        base = self.ouro
        shared = rope_rows(base.rope_cos._value, base.rope_sin._value,
                           positions, tokens.shape[1])
        return base.embed_tokens(tokens), shared

    def serve_layer(self, i, x, shared, io):
        layer = self.ouro.layers[i]
        cfg = self.config
        h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        R, s = x.shape[0], x.shape[1]
        xin = layer.input_layernorm(x)
        q = reshape(layer.self_attn.q_proj(xin), [R, s, h, d])
        k = reshape(layer.self_attn.k_proj(xin), [R, s, kvh, d])
        v = reshape(layer.self_attn.v_proj(xin), [R, s, kvh, d])
        qv, kv_ = rotate_half_apply(q._value, k._value, *shared)
        out_v = io.attend(qv, kv_, v._value)
        attn = layer.self_attn.o_proj(Tensor(out_v.reshape(R, s, h * d)))
        x = x + layer.input_layernorm_2(attn)
        return x + layer.post_attention_layernorm_2(
            layer.mlp(layer.post_attention_layernorm(x)))

    def serve_pass_end(self, x):
        """The shared final norm, after every pass: the next pass starts
        from the normalised state, and the last one's goes to the head."""
        return self.ouro.norm(x)

    def serve_end(self, x):
        return self.lm_head(x)

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())
