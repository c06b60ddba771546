"""Nemotron-H (``nemotron_h``): a stack of blocks each of which is ONE part
— a Mamba-2 mixer, a grouped-query attention layer, or an expert layer —
with no positional encoding and no multipliers (reference capability:
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json`` and the family's
published modeling code; Mamba-2: Dao & Gu 2024).

``hybrid_override_pattern`` names each block by a letter, and a block is
``x = x + part(RMSNorm(x))`` with the residual in the compute dtype:

- ``M``, Mamba-2: ``[z | xBC | dt] = W_in u`` (``d_inner = mamba_num_heads *
  mamba_head_dim``; ``expand`` is carried by the config and read by nothing);
  a causal depthwise convolution and silu over ``xBC``; the SSD recurrence
  (:mod:`paddle_tpu.ops.ssm`) over ``x [T, H, P]`` with ``B, C [T, G, N]``,
  ``G = n_groups`` groups of ``H / G`` consecutive heads; the gated norm
  ``w * RMSNorm(y * silu(z))`` taken over each group's ``d_inner / G``
  channels APART (gate first, then the norm); ``W_out``.  The mixer is
  Granite-4.0-H's (:class:`~paddle_tpu.models.granite_hybrid.Mamba2Mixer`)
  at other sizes.
- ``*``, attention: q/k/v/o projections, GQA, scores over ``sqrt(head_dim)``
  and **no rotary** (the published modeling code applies none; ``rope_theta``
  and ``partial_rotary_factor`` are carried and read by nothing).
- ``E``, experts: :class:`~paddle_tpu.nn.layer.moe.RoutedExperts` (sigmoid
  scores in float32, the selection bias, ``n_group`` 1, normalised top-k
  times ``routed_scaling_factor``) whose experts are two matrices with no
  gate, ``W_down relu(W_up x) ** 2`` (``mlp_hidden_act`` ``relu2``), plus one
  shared expert of the same form, ``moe_shared_expert_intermediate_size``
  wide.
- ``-``, a dense MLP, is in the family and in no published pattern this
  file was written for: not built.

After the last block ``norm_f``, then the untied head.

``experts_held = (first, count)`` and ``vocab_size`` make the model one
chip's share of an expert-parallel deployment, as in
:mod:`~paddle_tpu.models.deepseek_v3`: the router keeps ``n_routed_experts``
outputs, an expert layer keeps ``count`` experts' weights and computes their
part of the result, embedding and head keep ``vocab_size`` rows.

Under :class:`~paddle_tpu.serving.ServingEngine` a block describes what it
keeps per request (``serve_layers``): an ``M`` block a convolution tail and
the recurrent state, a ``*`` block K/V pages, an ``E`` block nothing
(``serve_protocol.StatelessLayer``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn.layer.moe import RoutedExperts
from ..tensor.manipulation import reshape
from ..tensor.tensor import Tensor
from .granite_hybrid import Mamba2Dims, Mamba2Mixer
from .serve_protocol import AttentionLayer, StateLayer, StatelessLayer

__all__ = ["NemotronHConfig", "NemotronHModel", "NemotronHForCausalLM",
           "nemotron_h_tiny"]

_NANO_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass
class NemotronHConfig:
    """The published keys of a ``nemotron_h`` ``config.json`` (defaults:
    NVIDIA-Nemotron-3-Nano-30B-A3B), plus ``experts_held``: which routed
    experts this model holds, ``(first, count)``; None is all of them."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = _NANO_PATTERN
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    attention_bias: bool = False
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2                         # carried; read by nothing
    use_conv_bias: bool = True
    use_bias: bool = False
    mamba_proj_bias: bool = False
    mamba_hidden_act: str = "silu"
    mlp_hidden_act: str = "relu2"
    mlp_bias: bool = False
    intermediate_size: int = 1856
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0             # carried; read by nothing
    partial_rotary_factor: float = 1.0      # carried; read by nothing
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    rescale_prenorm_residual: bool = True
    residual_in_fp32: bool = False
    initializer_range: float = 0.02
    router_bias_range: float = 0.0
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)
        if len(self.hybrid_override_pattern) != self.num_hidden_layers:
            raise ValueError(
                f"hybrid_override_pattern names "
                f"{len(self.hybrid_override_pattern)} blocks, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.hybrid_override_pattern) - set("ME*-")
        if unknown:
            raise ValueError(f"unknown block letters {sorted(unknown)}")
        if "-" in self.hybrid_override_pattern:
            raise NotImplementedError(
                "a dense MLP block ('-') is not built: only M (Mamba-2), "
                "E (experts) and * (attention) are")
        for key, only in (("mamba_hidden_act", "silu"),
                          ("mlp_hidden_act", "relu2"), ("mlp_bias", False),
                          ("attention_bias", False), ("use_bias", False),
                          ("n_shared_experts", 1),
                          ("tie_word_embeddings", False),
                          ("residual_in_fp32", False)):
            if getattr(self, key) != only:
                raise NotImplementedError(
                    f"{key}={getattr(self, key)!r}: only {only!r} is built")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("n_groups must divide mamba_num_heads")

    @property
    def mamba_dims(self) -> Mamba2Dims:
        return Mamba2Dims(
            self.hidden_size, self.mamba_num_heads, self.mamba_head_dim,
            self.ssm_state_size, self.conv_kernel, self.n_groups,
            self.chunk_size, self.use_conv_bias, self.mamba_proj_bias,
            self.layer_norm_epsilon, norm_groups=self.n_groups)


def nemotron_h_tiny(**kw) -> NemotronHConfig:
    """Test-scale config: every kind of block, two B/C groups, 8 experts of
    which a token takes 2."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                hybrid_override_pattern="MEM*E", num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
                mamba_head_dim=16, n_groups=2, ssm_state_size=128,
                chunk_size=32, intermediate_size=32,
                moe_intermediate_size=32,
                moe_shared_expert_intermediate_size=64, n_routed_experts=8,
                num_experts_per_tok=2, max_position_embeddings=512,
                router_bias_range=0.05)
    base.update(kw)
    return NemotronHConfig(**base)


def _normal(config: NemotronHConfig, rescaled: bool = False):
    """``rescale_prenorm_residual``: the published initialisation divides
    the parameters named ``out_proj.weight`` — the Mamba-2 mixers' — by
    ``sqrt(num_hidden_layers)``."""
    std = config.initializer_range
    if rescaled and config.rescale_prenorm_residual:
        std /= math.sqrt(config.num_hidden_layers)
    return nn.initializer.Normal(0.0, std)


class NemotronHAttention(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        h, kv, d = (config.num_attention_heads, config.num_key_value_heads,
                    config.head_dim)
        init, hs = _normal(config), config.hidden_size
        self.q_proj = nn.Linear(hs, h * d, weight_attr=init, bias_attr=False)
        self.k_proj = nn.Linear(hs, kv * d, weight_attr=init,
                                bias_attr=False)
        self.v_proj = nn.Linear(hs, kv * d, weight_attr=init,
                                bias_attr=False)
        self.o_proj = nn.Linear(h * d, hs, weight_attr=init, bias_attr=False)

    def qkv(self, x):
        cfg = self.config
        b, s = x.shape[0], x.shape[1]
        h, kv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
        return (reshape(self.q_proj(x), [b, s, h, d]),
                reshape(self.k_proj(x), [b, s, kv, d]),
                reshape(self.v_proj(x), [b, s, kv, d]))

    def forward(self, x):
        cfg = self.config
        q, k, v = self.qkv(x)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(reshape(out, [
            x.shape[0], x.shape[1],
            cfg.num_attention_heads * cfg.head_dim]))


class NemotronHMLP(nn.Layer):
    """Two matrices, no gate: ``W_down relu(W_up x) ** 2``."""

    def __init__(self, config: NemotronHConfig, width: int):
        super().__init__()
        init = _normal(config)
        self.up_proj = nn.Linear(config.hidden_size, width,
                                 weight_attr=init, bias_attr=False)
        self.down_proj = nn.Linear(width, config.hidden_size,
                                   weight_attr=init, bias_attr=False)

    def forward(self, x):
        h = F.relu(self.up_proj(x))
        return self.down_proj(h * h)


class NemotronHMoE(nn.Layer):
    """The expert layer: the held routed experts' part plus the shared
    expert, which every chip computes alike."""

    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.experts = RoutedExperts(
            config.hidden_size, config.moe_intermediate_size,
            config.n_routed_experts, config.num_experts_per_tok,
            n_group=config.n_group, topk_group=config.topk_group,
            norm_topk_prob=config.norm_topk_prob,
            routed_scaling_factor=config.routed_scaling_factor,
            experts_held=config.experts_held,
            activation=config.mlp_hidden_act, gated=False,
            weight_attr=_normal(config),
            bias_attr=nn.initializer.Uniform(-config.router_bias_range,
                                             config.router_bias_range))
        self.shared_experts = NemotronHMLP(
            config, config.moe_shared_expert_intermediate_size)

    def forward(self, x, valid=None):
        return self.experts(x, valid=valid) + self.shared_experts(x)


class NemotronHBlock(nn.Layer):
    """``x + mixer(norm(x))``; ``kind`` is the block's letter."""

    def __init__(self, config: NemotronHConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(config.hidden_size, config.layer_norm_epsilon)
        if kind == "M":
            self.mixer = Mamba2Mixer(config.mamba_dims, _normal(config),
                                     _normal(config, rescaled=True))
        elif kind == "*":
            self.mixer = NemotronHAttention(config)
        else:
            self.mixer = NemotronHMoE(config)

    def forward(self, x):
        return x + self.mixer(self.norm(x))


class NemotronHModel(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.embeddings = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=_normal(config))
        self.layers = nn.LayerList([
            NemotronHBlock(config, kind)
            for kind in config.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(config.hidden_size,
                                 config.layer_norm_epsilon)

    def forward(self, input_ids):
        x = self.embeddings(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm_f(x)


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        self.backbone = NemotronHModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False, weight_attr=_normal(config))

    def forward(self, input_ids, labels=None):
        logits = self.lm_head(self.backbone(input_ids))
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
        dtype = self.backbone.embeddings.weight._value.dtype
        attention = AttentionLayer(cfg.num_attention_heads,
                                   cfg.num_key_value_heads, cfg.head_dim)
        return [StateLayer.of(**blk.mixer.state_shapes(dtype))
                if blk.kind == "M" else attention if blk.kind == "*"
                else StatelessLayer() for blk in self.backbone.layers]

    def serve_begin(self, tokens, positions):
        # no positional encoding: nothing is shared between the blocks
        return self.backbone.embeddings(tokens), None

    def serve_layer(self, i, x, shared, io):
        blk = self.backbone.layers[i]
        xin = blk.norm(x)
        R, s = x.shape[0], x.shape[1]
        if blk.kind == "*":
            cfg = self.config
            q, k, v = blk.mixer.qkv(xin)
            out = io.attend(q._value, k._value, v._value)
            return x + blk.mixer.o_proj(Tensor(out.reshape(
                R, s, cfg.num_attention_heads * cfg.head_dim)))
        if blk.kind == "M":
            # one token a row is the decode step: the in-place state
            # update; a prefill launch scans in chunks of the published size
            mixed, tail, state = blk.mixer(
                xin, io.read_state("conv"), io.read_state("ssm"),
                io.n_valid, chunk=min(s, self.config.chunk_size),
                live=io.live if s == 1 else None)
            io.write_state("conv", tail)
            io.write_state("ssm", state)
            return x + mixed
        # idle rows and a launch's padding are routed nowhere
        y = blk.mixer(xin, valid=jnp.arange(s)[None, :] < io.n_valid[:, None])
        # pairs computed on the held experts, how many of them got one, and
        # the fullest; every token's chosen experts stay on the device for
        # whoever holds the routing to a reference
        load = blk.mixer.experts.last_load
        io.keep("moe_choice", blk.mixer.experts.last_choice)
        io.note("moe_pairs", load.sum())
        io.note("moe_experts_hit", (load > 0).sum())
        io.note("moe_max_load", load.max(), reduce="max")
        return x + y

    def serve_end(self, x):
        return self.lm_head(self.backbone.norm_f(x))
