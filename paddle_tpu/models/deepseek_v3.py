"""DeepSeek-V3 (``deepseek_v3``): multi-head latent attention, 256
sigmoid-routed experts with a shared expert, YaRN rotary frequencies
(reference capability: deepseek-ai/DeepSeek-V3 ``config.json``; DeepSeek-V3
Technical Report, arXiv:2412.19437; MLA: DeepSeek-V2, arXiv:2405.04434).

A layer is ``h = h + MLA(RMSNorm(h))`` then ``h = h + FFN(RMSNorm(h))``; the
first ``first_k_dense_replace`` layers' FFN is a dense SwiGLU, the others'
the expert layer.

- **MLA.** ``c_q = RMSNorm(W_DQ x)``, per head ``[q_nope | q_rope] = W_UQ
  c_q``; ``[c_kv | k_rope] = W_DKV x``, ``c_kv = RMSNorm(c_kv)``, ONE
  ``k_rope`` for all heads; ``q_rope`` and ``k_rope`` are rotated (YaRN
  frequencies); ``k_nope_i = W_UK,i c_kv``, ``v_i = W_UV,i c_kv``; the scores
  are scaled by ``(nope + rope) ** -0.5 * m ** 2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``.  ``forward`` attends in this expanded
  form.  Under :class:`~paddle_tpu.serving.ServingEngine` what a layer
  caches is ``c_kv`` and the rotated ``k_rope`` — one latent row a token
  (``serve_protocol.LatentAttentionLayer``) — and the engine attends in the
  absorbed form in decode.
- **Expert layer.** :class:`~paddle_tpu.nn.layer.moe.RoutedExperts` (sigmoid
  scores, group-limited top-k with the selection bias, dropless, told which
  experts it holds) plus the shared expert(s), one SwiGLU of
  ``n_shared_experts * moe_intermediate_size``.

``experts_held = (first, count)`` and ``vocab_size`` make the model one
chip's share of an expert-parallel deployment: the router keeps
``n_routed_experts`` outputs, the layer keeps ``count`` experts' weights and
computes their part of the result, embedding and head keep ``vocab_size``
rows.  The multi-token-prediction block (``num_nextn_predict_layers``) is
not built: the report runs the main model without it.

The rotary pairs are the halves of the rope part, ``(i, i + rope / 2)``: the
published weights pair neighbours ``(2i, 2i + 1)`` and the published code
de-interleaves them first, so the two differ by a fixed permutation of
``W_UQ``'s and ``W_DKV``'s rope columns.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import functional as F
from ..nn.layer.moe import RoutedExperts
from ..tensor.manipulation import reshape
from ..tensor.tensor import Tensor, apply_op
from .llama import rotate_half_apply
from .serve_protocol import LatentAttentionLayer

__all__ = ["DeepseekV3Config", "DeepseekV3Model", "DeepseekV3ForCausalLM",
           "deepseek_v3_tiny", "yarn_inv_freq", "yarn_mscale"]


def _yarn_defaults() -> dict:
    return {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 1, "mscale_all_dim": 1,
            "original_max_position_embeddings": 4096}


@dataclass
class DeepseekV3Config:
    """The published keys of a ``deepseek_v3`` ``config.json`` (defaults:
    DeepSeek-V3), plus ``experts_held``: which routed experts this model
    holds, ``(first, count)``; None is all of them."""
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    moe_layer_freq: int = 1
    num_attention_heads: int = 128
    num_key_value_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    attention_bias: bool = False
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = field(default_factory=_yarn_defaults)
    max_position_embeddings: int = 163840
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    initializer_range: float = 0.02
    router_bias_range: float = 0.0
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)
        for key, only in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"),
                          ("hidden_act", "silu"), ("moe_layer_freq", 1),
                          ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("num_nextn_predict_layers", 0)):
            if getattr(self, key) != only:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only {only!r} is built")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("MLA keeps one latent for every head: "
                             "num_key_value_heads must equal "
                             "num_attention_heads")
        if self.rope_scaling is not None \
                and self.rope_scaling.get("type") != "yarn":
            raise NotImplementedError(
                f"rope_scaling {self.rope_scaling.get('type')!r}: only "
                f"'yarn' (or none) is built")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = 1.0 if self.rope_scaling is None else yarn_mscale(
            self.rope_scaling["factor"], self.rope_scaling["mscale_all_dim"])
        return self.qk_head_dim ** -0.5 * m * m


def deepseek_v3_tiny(**kw) -> DeepseekV3Config:
    """Test-scale config: a dense layer and two expert layers, 16 experts
    in 4 groups of which 2 stay, 4 experts a token."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                first_k_dense_replace=1, num_attention_heads=4,
                num_key_value_heads=4, q_lora_rank=48, kv_lora_rank=32,
                qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                n_routed_experts=16, num_experts_per_tok=4, n_group=4,
                topk_group=2, max_position_embeddings=512,
                rope_scaling=dict(_yarn_defaults(), factor=4,
                                  original_max_position_embeddings=128),
                router_bias_range=0.05)
    base.update(kw)
    return DeepseekV3Config(**base)


# -- YaRN (Peng et al., arXiv:2309.00071; the published modeling code) -----
def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float,
                  scaling: Optional[dict]) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies: ``theta ** (-2i / dim)``, and
    under YaRN those divided by ``factor`` where a frequency turns fewer
    than ``beta_slow`` times over the original context, kept where it turns
    more than ``beta_fast`` times, and a linear ramp between."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if scaling is None:
        return extra.astype(np.float32)
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / scaling["factor"] * ramp
            + extra * (1.0 - ramp)).astype(np.float32)


def rope_cos_sin(config: DeepseekV3Config, pos_ids):
    """cos / sin [..., rope] (both halves alike) at integer ``pos_ids``, in
    float32, times ``mscale / mscale_all_dim`` as published."""
    inv = jnp.asarray(yarn_inv_freq(config.qk_rope_head_dim,
                                    config.rope_theta, config.rope_scaling))
    ang = pos_ids.astype(jnp.float32)[..., None] * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    sc = config.rope_scaling
    m = 1.0 if sc is None else yarn_mscale(sc["factor"], sc["mscale"]) \
        / yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return jnp.cos(ang) * m, jnp.sin(ang) * m


class DeepseekV3Attention(nn.Layer):
    """Multi-head latent attention (module docstring)."""

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        init = nn.initializer.Normal(0.0, config.initializer_range)
        hs, h = config.hidden_size, config.num_attention_heads
        self.q_a_proj = nn.Linear(hs, config.q_lora_rank, weight_attr=init,
                                  bias_attr=False)
        self.q_a_layernorm = nn.RMSNorm(config.q_lora_rank,
                                        config.rms_norm_eps)
        self.q_b_proj = nn.Linear(config.q_lora_rank,
                                  h * config.qk_head_dim, weight_attr=init,
                                  bias_attr=False)
        self.kv_a_proj_with_mqa = nn.Linear(
            hs, config.kv_lora_rank + config.qk_rope_head_dim,
            weight_attr=init, bias_attr=False)
        self.kv_a_layernorm = nn.RMSNorm(config.kv_lora_rank,
                                         config.rms_norm_eps)
        self.kv_b_proj = nn.Linear(
            config.kv_lora_rank,
            h * (config.qk_nope_head_dim + config.v_head_dim),
            weight_attr=init, bias_attr=False)
        self.o_proj = nn.Linear(h * config.v_head_dim, hs, weight_attr=init,
                                bias_attr=False)

    def _down_projections(self, x):
        """``x`` [b, s, hidden] to the heads' queries [b, s, h, nope + rope]
        and the token's ``[c_kv | k_rope]`` before its norm and rotation."""
        cfg = self.config
        q = reshape(self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x))),
                    [x.shape[0], x.shape[1], cfg.num_attention_heads,
                     cfg.qk_head_dim])
        return q, self.kv_a_proj_with_mqa(x)

    def latent_qkv(self, x, cos, sin):
        """``x`` [b, s, hidden] to what the cache and the scores need, as
        arrays: ``q_nope`` [b, s, h, nope], the rotated ``q_rope`` [b, s, h,
        rope], the normalised ``c_kv`` [b, s, latent] and the rotated
        ``k_rope`` [b, s, rope].  ``cos`` / ``sin`` [b, s, rope]."""
        cfg = self.config
        nope = cfg.qk_nope_head_dim
        q, ckv = self._down_projections(x)
        c_kv = self.kv_a_layernorm(ckv[..., :cfg.kv_lora_rank])
        q_rope, k_rope = rotate_half_apply(
            q._value[..., nope:], ckv._value[:, :, None, cfg.kv_lora_rank:],
            cos[:, :, None, :], sin[:, :, None, :])
        return q._value[..., :nope], q_rope, c_kv._value, k_rope[:, :, 0]

    def up_projections(self):
        """``W_UK`` [latent, h, nope] and ``W_UV`` [latent, h, v]: the two
        halves of ``kv_b_proj``, as arrays."""
        cfg = self.config
        w = self.kv_b_proj.weight._value.reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads,
            cfg.qk_nope_head_dim + cfg.v_head_dim)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def forward(self, x, cos, sin):
        """Expanded attention over the whole sequence, causal."""
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        h, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim)
        q, ckv = self._down_projections(x)
        kv = reshape(self.kv_b_proj(self.kv_a_layernorm(
            ckv[..., :cfg.kv_lora_rank])), [b, s, h, nope + cfg.v_head_dim])
        k_rope = ckv[..., cfg.kv_lora_rank:]
        scale = cfg.softmax_scale

        def fn(qv, kvv, krv):
            q_rope, k_r = rotate_half_apply(
                qv[..., nope:], krv[:, :, None, :], cos[:, :, None, :],
                sin[:, :, None, :])
            qq = jnp.concatenate([qv[..., :nope], q_rope], axis=-1)
            kk = jnp.concatenate(
                [kvv[..., :nope], jnp.broadcast_to(k_r, (b, s, h, rope))],
                axis=-1)
            scores = jnp.einsum("bqhd,bkhd->bhqk", qq, kk,
                                preferred_element_type=jnp.float32) * scale
            keep = jnp.tril(jnp.ones((s, s), bool))
            probs = jax.nn.softmax(jnp.where(
                keep, scores, jnp.finfo(jnp.float32).min), axis=-1)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(kvv.dtype),
                             kvv[..., nope:],
                             preferred_element_type=jnp.float32)
            return out.astype(qv.dtype).reshape(b, s, h * cfg.v_head_dim)

        return self.o_proj(apply_op("mla_attention", fn, (q, kv, k_rope)))


class DeepseekV3MLP(nn.Layer):
    def __init__(self, config: DeepseekV3Config, width: int):
        super().__init__()
        init = nn.initializer.Normal(0.0, config.initializer_range)
        hs = config.hidden_size
        self.gate_proj = nn.Linear(hs, width, weight_attr=init,
                                   bias_attr=False)
        self.up_proj = nn.Linear(hs, width, weight_attr=init,
                                 bias_attr=False)
        self.down_proj = nn.Linear(width, hs, weight_attr=init,
                                   bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class DeepseekV3MoE(nn.Layer):
    """The expert layer: the held routed experts' part plus the shared
    expert, which every chip computes alike."""

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.experts = RoutedExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            n_group=config.n_group, topk_group=config.topk_group,
            norm_topk_prob=config.norm_topk_prob,
            routed_scaling_factor=config.routed_scaling_factor,
            experts_held=config.experts_held,
            weight_attr=nn.initializer.Normal(0.0,
                                              config.initializer_range),
            bias_attr=nn.initializer.Uniform(-config.router_bias_range,
                                             config.router_bias_range))
        self.shared_experts = DeepseekV3MLP(
            config, config.n_shared_experts * config.moe_intermediate_size)

    def forward(self, x, valid=None):
        return self.experts(x, valid=valid) + self.shared_experts(x)


class DeepseekV3DecoderLayer(nn.Layer):
    def __init__(self, config: DeepseekV3Config, index: int):
        super().__init__()
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.is_moe = index >= config.first_k_dense_replace
        self.mlp = DeepseekV3MoE(config) if self.is_moe else \
            DeepseekV3MLP(config, config.intermediate_size)

    def forward(self, x, cos, sin):
        x = x + self.self_attn(self.input_layernorm(x), cos, sin)
        return x + self.mlp(self.post_attention_layernorm(x))


class DeepseekV3Model(nn.Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.initializer.Normal(0.0, config.initializer_range))
        self.layers = nn.LayerList([
            DeepseekV3DecoderLayer(config, i)
            for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        b, s = input_ids.shape[0], input_ids.shape[1]
        cos, sin = rope_cos_sin(
            self.config, jnp.broadcast_to(jnp.arange(s)[None], (b, s)))
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, cos, sin)
        return self.norm(x)


class DeepseekV3ForCausalLM(nn.Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.model = DeepseekV3Model(config)
        self.lm_head = nn.Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=nn.initializer.Normal(0.0, config.initializer_range))

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.model(input_ids))
        if labels is None:
            return logits
        loss = F.cross_entropy(
            reshape(logits, [-1, self.config.vocab_size]),
            reshape(labels, [-1]))
        return loss, logits

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())

    # -- what ServingEngine asks of a model (serve_protocol.py) ------------
    def serve_layers(self):
        cfg = self.config
        return [LatentAttentionLayer(
            cfg.num_attention_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim,
            cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.softmax_scale)
            for _ in self.model.layers]

    def serve_begin(self, tokens, positions):
        """``tokens`` [R, s] ids, ``positions`` [R] the absolute position of
        each row's first token, or [R, s] every token's: the embeddings,
        and the rows' rotary cos / sin [R, s, rope] that every layer
        shares."""
        if positions.ndim == 1:
            positions = positions[:, None] + jnp.arange(tokens.shape[1])
        return self.model.embed_tokens(tokens), \
            rope_cos_sin(self.config, positions)

    def serve_layer(self, i, x, shared, io):
        layer = self.model.layers[i]
        attn, cfg = layer.self_attn, self.config
        R, s = x.shape[0], x.shape[1]
        out = io.attend_latent(
            *attn.latent_qkv(layer.input_layernorm(x), *shared),
            *attn.up_projections())
        x = x + attn.o_proj(Tensor(out.reshape(
            R, s, cfg.num_attention_heads * cfg.v_head_dim)))
        xin = layer.post_attention_layernorm(x)
        if not layer.is_moe:
            return x + layer.mlp(xin)
        # idle rows and a launch's padding are routed nowhere
        y = layer.mlp(xin, valid=io.valid)
        # pairs computed on the held experts, how many of them got one,
        # and the fullest; every token's chosen experts stay on the device
        # for whoever holds the routing to a reference
        load = layer.mlp.experts.last_load
        io.keep("moe_choice", layer.mlp.experts.last_choice)
        io.note("moe_pairs", load.sum())
        io.note("moe_experts_hit", (load > 0).sum())
        io.note("moe_max_load", load.max(), reduce="max")
        return x + y

    def serve_end(self, x):
        return self.lm_head(self.model.norm(x))
