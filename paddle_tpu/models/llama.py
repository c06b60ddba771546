"""Llama model family — the flagship pretrain target (BASELINE.md configs
#4/#5; reference capability: PaddleNLP llama on the reference's fused kernel
set `incubate/nn/functional/fused_rms_norm.py`, `fused_rotary_position_embedding.py`,
`nn/functional/flash_attention.py`).

TPU-first choices:
- weights created in bf16-friendly fp32 and castable via amp.decorate O2
- attention in flash layout [batch, seq, heads, head_dim] through
  F.scaled_dot_product_attention (Pallas flash kernel on TPU)
- rotary embeddings precomputed once per max_seq and sliced (static shapes)
- GQA: num_key_value_heads < num_attention_heads
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..generation import GenerationMixin
from ..nn import functional as F
from ..tensor.manipulation import reshape
from ..tensor.tensor import Tensor, apply_op
from .serve_protocol import AttentionLayer

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM", "llama_tiny", "llama2_7b",
           "llama2_13b", "llama2_70b", "llama_moe_tiny", "mixtral_8x7b"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    recompute: bool = False  # rematerialize each decoder layer (jax.checkpoint)
    # MoE (reference capability: incubate/distributed/models/moe): replace the
    # dense MLP with an ExpertParallelMLP in every `moe_every`-th layer
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_every: int = 1
    moe_expert_axes: tuple = None  # mesh axes to shard the expert dim over
    # >0: compute the LM loss via the chunked fused linear+CE (never
    # materializes the full [tokens, vocab] logits; see
    # F.fused_linear_cross_entropy) — the HBM lever for big-vocab heads
    fused_ce_chunk: int = 0

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


def llama_tiny(**kw) -> LlamaConfig:
    """Test-scale config (shapes stay MXU-aligned: multiples of 128 where it
    matters is waived at this scale)."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                max_position_embeddings=128)
    base.update(kw)
    return LlamaConfig(**base)


def llama2_7b(**kw) -> LlamaConfig:
    base = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
                num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=32,
                max_position_embeddings=4096)
    base.update(kw)
    return LlamaConfig(**base)


def llama2_13b(**kw) -> LlamaConfig:
    base = dict(hidden_size=5120, intermediate_size=13824, num_hidden_layers=40,
                num_attention_heads=40, num_key_value_heads=40)
    base.update(kw)
    return LlamaConfig(**base)


def llama_moe_tiny(**kw) -> LlamaConfig:
    """Test-scale MoE config: 4 experts, top-2, every layer."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                max_position_embeddings=128, moe_num_experts=4, moe_top_k=2)
    base.update(kw)
    return LlamaConfig(**base)


def mixtral_8x7b(**kw) -> LlamaConfig:
    """Mixtral-8x7B-shaped MoE ladder rung (8 experts, top-2; the MoE
    analogue of BASELINE.md's llama2 ladder)."""
    base = dict(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                num_hidden_layers=32, num_attention_heads=32,
                num_key_value_heads=8, max_position_embeddings=4096,
                moe_num_experts=8, moe_top_k=2)
    base.update(kw)
    return LlamaConfig(**base)


def llama2_70b(**kw) -> LlamaConfig:
    base = dict(hidden_size=8192, intermediate_size=28672, num_hidden_layers=80,
                num_attention_heads=64, num_key_value_heads=8)
    base.update(kw)
    return LlamaConfig(**base)


def _normalize_mask(attn_mask):
    """bool/int keep-mask ([b, s] or broadcastable) → additive float mask;
    float masks pass through (assumed already additive)."""
    if attn_mask is None:
        return None
    m = attn_mask._value if isinstance(attn_mask, Tensor) else jnp.asarray(attn_mask)
    if jnp.issubdtype(m.dtype, jnp.bool_) or jnp.issubdtype(m.dtype, jnp.integer):
        keep = m.astype(jnp.float32)
        if keep.ndim == 2:  # [b, s] padding mask → [b, 1, 1, s]
            keep = keep[:, None, None, :]
        return Tensor((1.0 - keep) * jnp.finfo(jnp.float32).min)
    return attn_mask if isinstance(attn_mask, Tensor) else Tensor(m)


def _rope_tables(head_dim: int, max_pos: int, theta: float):
    inv_freq = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))
    t = np.arange(max_pos, dtype=np.float32)
    freqs = np.outer(t, inv_freq)                      # [max_pos, head_dim/2]
    emb = np.concatenate([freqs, freqs], axis=-1)      # [max_pos, head_dim]
    return jnp.asarray(np.cos(emb)), jnp.asarray(np.sin(emb))


def apply_rotary_pos_emb(q: Tensor, k: Tensor, cos, sin, position_offset: int = 0):
    """q/k: [b, s, h, d]; cos/sin: [max_pos, d] jax arrays (fused path:
    ops/pallas/rope.py; reference `fused_rotary_position_embedding.py`)."""
    from ..ops import pallas_mode

    s = q.shape[1]
    mode = pallas_mode("use_fused_rope")
    if mode is not None and q.shape[-1] % 2 == 0 and s % 8 == 0 \
            and isinstance(position_offset, int):  # decode offsets are traced
        kind, mesh, interp = mode
        from ..ops.pallas import fused_rope
        from ..ops.sharded import mesh_rope, mesh_rope_supported

        table_c = cos[position_offset:position_offset + s]
        table_s = sin[position_offset:position_offset + s]
        if kind == "mesh":
            if mesh_rope_supported(mesh, q.shape, k.shape):
                return apply_op(
                    "fused_rope",
                    lambda qv, kv: mesh_rope(qv, kv, table_c, table_s, mesh,
                                             interpret=interp),
                    (q, k), multi_out=True)
        else:
            return apply_op("fused_rope",
                            lambda qv, kv: fused_rope(qv, kv, table_c, table_s,
                                                      interpret=interp),
                            (q, k), multi_out=True)

    # dynamic_slice accepts both static ints and traced scalars (the
    # jit-compiled decode step carries position_offset as a traced int32)
    cos_s = jax.lax.dynamic_slice_in_dim(cos, position_offset, s, 0)[None, :, None, :]
    sin_s = jax.lax.dynamic_slice_in_dim(sin, position_offset, s, 0)[None, :, None, :]

    def fn(qv, kv):
        return rotate_half_apply(qv, kv, cos_s, sin_s)

    return apply_op("rope", fn, (q, k), multi_out=True)


def rope_rows(cos, sin, positions, s: int):
    """The rotary tables ``cos`` / ``sin`` [max_pos, d] at ``positions``
    ([R] the first token of each row of ``s`` tokens, or [R, s] every
    token's), shaped [R, s, 1, d] for :func:`rotate_half_apply`."""
    if positions.ndim == 1:
        positions = positions[:, None] + jnp.arange(s)[None, :]
    pos_ids = jnp.clip(positions, 0, cos.shape[0] - 1)      # [R, s]
    return (jnp.take(cos, pos_ids, axis=0)[:, :, None, :],
            jnp.take(sin, pos_ids, axis=0)[:, :, None, :])


def rotate_half_apply(qv, kv, cos_s, sin_s):
    """The rotate-half rope application in fp32 (shared by the training
    path above and the per-row decode path in generation/): q/k [b,s,h,d],
    cos_s/sin_s broadcastable to them."""

    def rot(v):
        half = v.shape[-1] // 2
        return jnp.concatenate([-v[..., half:], v[..., :half]], axis=-1)

    c = cos_s.astype(jnp.float32)
    si = sin_s.astype(jnp.float32)
    qf, kf = qv.astype(jnp.float32), kv.astype(jnp.float32)
    return ((qf * c + rot(qf) * si).astype(qv.dtype),
            (kf * c + rot(kf) * si).astype(kv.dtype))


class LlamaAttention(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        h, kv, d = config.num_attention_heads, config.num_key_value_heads, config.head_dim
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.q_proj = nn.Linear(config.hidden_size, h * d, weight_attr=init, bias_attr=False)
        self.k_proj = nn.Linear(config.hidden_size, kv * d, weight_attr=init, bias_attr=False)
        self.v_proj = nn.Linear(config.hidden_size, kv * d, weight_attr=init, bias_attr=False)
        self.o_proj = nn.Linear(h * d, config.hidden_size, weight_attr=init, bias_attr=False)

    def forward(self, x, cos, sin, attn_mask=None, position_offset: int = 0,
                kv_cache=None, pad_lens=None):
        b, s = x.shape[0], x.shape[1]
        cfg = self.config
        q = reshape(self.q_proj(x), [b, s, cfg.num_attention_heads, cfg.head_dim])
        k = reshape(self.k_proj(x), [b, s, cfg.num_key_value_heads, cfg.head_dim])
        v = reshape(self.v_proj(x), [b, s, cfg.num_key_value_heads, cfg.head_dim])
        if kv_cache is not None:
            # decode path (generation/__init__.py): write k/v into the
            # static cache at position_offset, attend over the prefix; no
            # grads flow here, so raw-value math is fine. pad_lens carries
            # per-row LEFT padding (rope positions shift, pad slots masked)
            if attn_mask is not None:
                raise NotImplementedError(
                    "attn_mask with kv_cache is not supported — ragged "
                    "batched prompts go through generate(attention_mask=...) "
                    "/ the pad_lens argument")
            from ..generation import cached_attention, rope_with_row_offsets

            if pad_lens is not None:
                qv, kv_ = rope_with_row_offsets(q._value, k._value, cos, sin,
                                                position_offset, pad_lens)
            else:
                q, k = apply_rotary_pos_emb(q, k, cos, sin, position_offset)
                qv, kv_ = q._value, k._value
            out_v, ck, cv = cached_attention(
                qv, kv_, v._value, kv_cache[0], kv_cache[1],
                position_offset, pad_lens)
            out = self.o_proj(Tensor(out_v.reshape(
                b, s, cfg.num_attention_heads * cfg.head_dim)))
            return out, (ck, cv)
        q, k = apply_rotary_pos_emb(q, k, cos, sin, position_offset)
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=attn_mask, is_causal=True)
        return self.o_proj(reshape(out, [b, s, cfg.num_attention_heads * cfg.head_dim]))


class LlamaMLP(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.gate_proj = nn.Linear(config.hidden_size, config.intermediate_size,
                                   weight_attr=init, bias_attr=False)
        self.up_proj = nn.Linear(config.hidden_size, config.intermediate_size,
                                 weight_attr=init, bias_attr=False)
        self.down_proj = nn.Linear(config.intermediate_size, config.hidden_size,
                                   weight_attr=init, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaDecoderLayer(nn.Layer):
    def __init__(self, config: LlamaConfig, use_moe: bool = False):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        if use_moe:
            from ..incubate.distributed.models.moe import ExpertParallelMLP

            self.mlp = ExpertParallelMLP(
                config.hidden_size, config.intermediate_size,
                num_experts=config.moe_num_experts, top_k=config.moe_top_k,
                capacity_factor=config.moe_capacity_factor,
                activation="swiglu", expert_axes=config.moe_expert_axes)
        else:
            self.mlp = LlamaMLP(config)
        self.input_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, x, cos, sin, attn_mask=None, position_offset: int = 0,
                kv_cache=None, pad_lens=None):
        if kv_cache is not None:
            attn, new_cache = self.self_attn(self.input_layernorm(x), cos, sin,
                                             attn_mask, position_offset,
                                             kv_cache, pad_lens)
            x = x + attn
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_cache
        x = x + self.self_attn(self.input_layernorm(x), cos, sin, attn_mask, position_offset)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(nn.Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.initializer.Normal(0.0, config.initializer_range))
        self.layers = nn.LayerList([
            LlamaDecoderLayer(config,
                              use_moe=(config.moe_num_experts > 0 and
                                       i % config.moe_every == 0))
            for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        cos, sin = _rope_tables(config.head_dim, config.max_position_embeddings,
                                config.rope_theta)
        self.register_buffer("rope_cos", Tensor(cos), persistable=False)
        self.register_buffer("rope_sin", Tensor(sin), persistable=False)

    def forward(self, input_ids, attn_mask=None, position_offset: int = 0,
                kv_cache=None, pad_lens=None):
        """``attn_mask``: either an additive float mask (0 to keep, large
        negative to drop) or a bool/int keep-mask (True/1 = attend), which is
        converted to additive form; causal masking is always applied.
        ``kv_cache``: list of per-layer (k, v) static-shape cache arrays —
        the decode path; returns (hidden, new_cache).  ``pad_lens`` [b]:
        per-row LEFT-padding count for batched ragged prompts (decode
        path only)."""
        if isinstance(position_offset, int) and \
                input_ids.shape[1] + position_offset > self.config.max_position_embeddings:
            raise ValueError(
                f"sequence length {input_ids.shape[1]} (+offset {position_offset}) exceeds "
                f"max_position_embeddings {self.config.max_position_embeddings}")
        attn_mask = _normalize_mask(attn_mask)
        x = self.embed_tokens(input_ids)
        cos, sin = self.rope_cos._value, self.rope_sin._value
        if kv_cache is not None:
            new_caches = []
            for layer, lc in zip(self.layers, kv_cache):
                x, nc = layer(x, cos, sin, attn_mask, position_offset,
                              kv_cache=lc, pad_lens=pad_lens)
                new_caches.append(nc)
            return self.norm(x), new_caches
        if self.config.recompute:
            from ..distributed.fleet_utils import recompute

            for layer in self.layers:
                if getattr(layer.mlp, "l_aux", "absent") != "absent":
                    # MoE layers run un-checkpointed: the router's l_aux
                    # side-channel cannot escape a jax.checkpoint region
                    # (dense layers still rematerialize — they hold the
                    # bulk of the activation memory)
                    x = layer(x, cos, sin, attn_mask, position_offset)
                else:
                    x = recompute(layer, x, cos, sin, attn_mask, position_offset)
        else:
            for layer in self.layers:
                x = layer(x, cos, sin, attn_mask, position_offset)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                     weight_attr=nn.initializer.Normal(
                                         0.0, config.initializer_range),
                                     bias_attr=False)

    def forward(self, input_ids, labels=None, attn_mask=None, kv_cache=None,
                position_offset: int = 0, pad_lens=None):
        if kv_cache is not None:  # decode path: (logits, new_cache)
            hidden, new_cache = self.llama(input_ids, attn_mask,
                                           position_offset, kv_cache=kv_cache,
                                           pad_lens=pad_lens)
            if self.lm_head is not None:
                logits = self.lm_head(hidden)
            else:
                logits = F.linear(hidden, self.llama.embed_tokens.weight.T)
            return logits, new_cache
        hidden = self.llama(input_ids, attn_mask)
        if labels is not None and self.config.fused_ce_chunk > 0:
            # chunked fused linear+CE: the full [tokens, vocab] logits are
            # NEVER materialized (so no logits to return — paddle-style
            # training loops read only the loss here)
            flat_h = reshape(hidden, [-1, self.config.hidden_size])
            head_w = self.lm_head.weight if self.lm_head is not None \
                else self.llama.embed_tokens.weight.T  # tied embeddings
            loss = F.fused_linear_cross_entropy(
                flat_h, head_w, reshape(labels, [-1]),
                chunk_size=self.config.fused_ce_chunk)
            if self.config.moe_num_experts > 0:
                loss = loss + 0.01 * self.moe_aux_loss()
            return loss, None
        if self.lm_head is not None:
            logits = self.lm_head(hidden)
        else:
            logits = F.linear(hidden, self.llama.embed_tokens.weight.T)
        if labels is not None:
            loss = F.cross_entropy(
                reshape(logits, [-1, self.config.vocab_size]),
                reshape(labels, [-1]))
            if self.config.moe_num_experts > 0:
                loss = loss + 0.01 * self.moe_aux_loss()
            return loss, logits
        return logits

    # -- what ServingEngine asks of a model (serve_protocol.py) ------------
    def serve_layers(self):
        cfg = self.config
        return [AttentionLayer(cfg.num_attention_heads,
                               cfg.num_key_value_heads, cfg.head_dim)
                for _ in self.llama.layers]

    def serve_begin(self, tokens, positions):
        """``tokens`` [R, s] ids, ``positions`` [R] the absolute position of
        each row's first token, or [R, s] every token's: the embeddings,
        and the rows' rotary tables that every layer shares."""
        base = self.llama
        shared = rope_rows(base.rope_cos._value, base.rope_sin._value,
                           positions, tokens.shape[1])
        return base.embed_tokens(tokens), shared

    def serve_layer(self, i, x, shared, io):
        layer = self.llama.layers[i]
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
        x = x + layer.self_attn.o_proj(Tensor(out_v.reshape(R, s, h * d)))
        return x + layer.mlp(layer.post_attention_layernorm(x))

    def serve_end(self, x):
        hidden = self.llama.norm(x)
        if self.lm_head is not None:
            return self.lm_head(hidden)
        return F.linear(hidden, self.llama.embed_tokens.weight.T)

    def moe_aux_loss(self):
        """Sum of the routers' load-balance losses from the last forward
        (GShard aux loss; weighted 0.01 into the training loss)."""
        aux = None
        for layer in self.llama.layers:
            la = getattr(layer.mlp, "l_aux", None)
            if la is not None:
                aux = la if aux is None else aux + la
        if aux is None:
            raise RuntimeError("moe_aux_loss: no MoE layers or no forward yet")
        return aux

    def num_params(self) -> int:
        return sum(p.size for p in self.parameters())
