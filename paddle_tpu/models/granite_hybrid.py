"""Granite-4.0-H family (``granitemoehybrid``): Mamba-2 state-space layers
with a grouped-query attention layer every so often, no positional
encoding, and muP-style multipliers (reference capability:
ibm-granite/granite-4.0-h-micro ``config.json``; Mamba-2: Dao & Gu 2024).

A layer is ``h = h + r * Mixer(RMSNorm(h))`` then ``h = h + r *
W_o(silu(g) * v)`` with ``[g | v] = W_i RMSNorm(h)`` and ``r =
residual_multiplier``; ``layer_types`` says which mixer each layer has:

- ``"attention"``: q/k/v/o projections, GQA, **no rotary** (``nope``),
  scores times ``attention_multiplier`` (not ``1 / sqrt(head_dim)``).
- ``"mamba"``: ``[z | xBC | dt] = W_in u``; a causal depthwise convolution
  and silu over ``xBC``; the SSD recurrence (:mod:`paddle_tpu.ops.ssm`) over
  ``x [T, H, P]`` with ``B, C [T, G, N]``; ``RMSNorm(y * silu(z))`` over
  the whole inner width; ``W_out``.

Embeddings are multiplied by ``embedding_multiplier``, logits divided by
``logits_scaling``; the head is tied.  The dense models of the family have
no routed experts (``num_local_experts`` 0): only those are built here.

The full-sequence ``forward`` scans in chunks of ``mamba_chunk_size``; under
:class:`~paddle_tpu.serving.ServingEngine` the same mixer runs a page at a
time from the request's carried state (``serve_*`` below), and one token at
a time in decode, where the state update is the ``ssm_state_update`` kernel.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..nn import functional as F
from ..nn import initializer as I
from ..tensor.manipulation import reshape
from ..tensor.tensor import Tensor, apply_op
from .serve_protocol import AttentionLayer, StateLayer

__all__ = ["GraniteHybridConfig", "GraniteHybridModel",
           "GraniteHybridForCausalLM", "granite_hybrid_tiny", "Mamba2Dims",
           "Mamba2Mixer"]

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


@dataclass(frozen=True)
class Mamba2Dims:
    """One Mamba-2 mixer's sizes, whatever keys a family publishes them
    under.  ``norm_groups``: the gated norm's groups — 1 norms ``y *
    silu(z)`` over the whole inner width (Granite-4.0-H), ``n_groups`` norms
    each B/C group's channels apart (Nemotron-H)."""
    hidden: int
    n_heads: int
    d_head: int
    d_state: int
    d_conv: int
    n_groups: int
    chunk: int
    conv_bias: bool = True
    proj_bias: bool = False
    eps: float = 1e-5
    norm_groups: int = 1

    @property
    def d_inner(self) -> int:
        return self.n_heads * self.d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


@dataclass
class GraniteHybridConfig:
    """The published keys of a ``granitemoehybrid`` ``config.json``
    (defaults: granite-4.0-h-micro)."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Tuple[str, ...] = _PERIOD * 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_bias: bool = False
    attention_multiplier: float = 0.015625
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    position_embedding_type: str = "nope"
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        unknown = set(self.layer_types) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if self.num_local_experts:
            raise NotImplementedError(
                "routed experts (num_local_experts > 0) are not built: only "
                "the family's dense models are")
        if self.position_embedding_type != "nope":
            raise NotImplementedError(
                f"position_embedding_type "
                f"{self.position_embedding_type!r}: only 'nope' (no "
                f"positional encoding) is built")
        if not self.tie_word_embeddings:
            raise NotImplementedError("an untied head is not built")
        if self.mamba_n_heads * self.mamba_d_head != self.mamba_d_inner:
            raise ValueError("mamba_n_heads * mamba_d_head must equal "
                             "mamba_expand * hidden_size")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def mamba_conv_dim(self) -> int:
        return self.mamba_d_inner + 2 * self.mamba_n_groups \
            * self.mamba_d_state

    @property
    def mamba_dims(self) -> Mamba2Dims:
        return Mamba2Dims(
            self.hidden_size, self.mamba_n_heads, self.mamba_d_head,
            self.mamba_d_state, self.mamba_d_conv, self.mamba_n_groups,
            self.mamba_chunk_size, self.mamba_conv_bias,
            self.mamba_proj_bias, self.rms_norm_eps)


def granite_hybrid_tiny(**kw) -> GraniteHybridConfig:
    """Test-scale config: one short period with both kinds of layer."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                shared_intermediate_size=128, num_hidden_layers=4,
                layer_types=("mamba", "attention", "mamba", "mamba"),
                num_attention_heads=4, num_key_value_heads=2,
                attention_multiplier=0.0625, mamba_n_heads=8,
                mamba_d_head=16, mamba_d_state=128, mamba_chunk_size=32,
                max_position_embeddings=512)
    base.update(kw)
    return GraniteHybridConfig(**base)


class _LogUniformExp(I.Initializer):
    """``log(U[lo, hi])``: Mamba-2's ``A_log`` (``A = -exp(A_log)`` lies in
    ``[-hi, -lo]``)."""

    def __init__(self, lo: float, hi: float):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype, key):
        u = jax.random.uniform(key, shape, jnp.float32, self.lo, self.hi)
        return jnp.log(u).astype(dtype)


class _InverseSoftplusLogUniform(I.Initializer):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    ``[lo, hi]`` (Mamba-2's ``dt_min`` / ``dt_max``)."""

    def __init__(self, lo: float, hi: float):
        self.lo, self.hi = lo, hi

    def __call__(self, shape, dtype, key):
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (math.log(self.hi) - math.log(self.lo))
                     + math.log(self.lo))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


@functools.partial(jax.jit, static_argnames=("dims", "chunk"))
def _mamba_mix(dims, zxbcdt, conv_w, conv_b, A_log, dt_bias, D, norm_w,
               tail0, state0, n_valid, chunk, live):
    """Everything between the two projections of a Mamba-2 mixer, on arrays.
    ``dims``: the mixer's ``(H, P, G, N, d_inner, conv_dim, eps, norm
    groups)``;
    ``zxbcdt [b, T, 2 * d_inner + 2 * G * N + H]``; ``tail0 [b, K - 1,
    conv_dim]``, ``state0 [b, H, P, N]`` carried in; ``n_valid [b]``.  With
    ``live`` ([b] bool: the decode step, T == 1) the recurrence is one
    token through :func:`~paddle_tpu.ops.ssm.ssm_decode_update`, else the
    chunked scan.  Returns ``(gated y [b, T, d_inner], tail, state)``.

    A ``jit`` of its own: inside a program's trace the layers after the
    first reuse the first one's trace and its lowering (one function, called
    36 times), which a program compiled at several widths pays for each."""
    from ..ops import ssm

    b, T, _ = zxbcdt.shape
    H, P, G, N, di, conv_dim, eps, norm_groups = dims
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + conv_dim]
    dt = zxbcdt[..., di + conv_dim:]
    xBC, tail = ssm.causal_conv1d(xBC, conv_w, conv_b, tail0, n_valid)
    xBC = jax.nn.silu(xBC.astype(jnp.float32)).astype(xBC.dtype)
    x = xBC[..., :di].reshape(b, T, H, P)
    B = xBC[..., di:di + G * N].reshape(b, T, G, N)
    C = xBC[..., di + G * N:].reshape(b, T, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + dt_bias.astype(jnp.float32))
    A = -jnp.exp(A_log.astype(jnp.float32))
    if live is not None:
        y, state = ssm.ssm_decode_update(state0, live, x[:, 0], dt[:, 0], A,
                                         B[:, 0], C[:, 0], D)
        y = y[:, None]
    else:
        y, state = ssm.ssd_chunked(x, dt, A, B, C, D, state0, n_valid,
                                   chunk)
    # the gated norm: RMSNorm(y * silu(z)) over the whole inner width, or
    # over each of ``norm_groups`` runs of channels apart
    g = y.reshape(b, T, di).astype(jnp.float32) \
        * jax.nn.silu(z.astype(jnp.float32))
    if norm_groups > 1:
        g = g.reshape(b, T, norm_groups, di // norm_groups)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    g = g.reshape(b, T, di) * norm_w.astype(jnp.float32)
    return g.astype(zxbcdt.dtype), tail, state


class Mamba2Mixer(nn.Layer):
    """``in_proj``, the convolution, the recurrence, the gated norm and
    ``out_proj`` of one Mamba-2 layer (module docstring).  ``out_init``:
    the output projection's initializer where it is not ``init``."""

    def __init__(self, dims: Mamba2Dims, init, out_init=None):
        super().__init__()
        self.dims = dims
        H, K = dims.n_heads, dims.d_conv
        di, cd = dims.d_inner, dims.conv_dim
        bias = None if dims.proj_bias else False
        self.in_proj = nn.Linear(dims.hidden, di + cd + H,
                                 weight_attr=init, bias_attr=bias)
        # depthwise taps [conv_dim, K]; torch's conv1d default range
        self.conv_weight = self.create_parameter(
            [cd, K], default_initializer=I.Uniform(-K ** -0.5, K ** -0.5))
        self.conv_bias = self.create_parameter(
            [cd], is_bias=True) if dims.conv_bias else None
        self.A_log = self.create_parameter(
            [H], default_initializer=_LogUniformExp(1.0, 16.0))
        self.dt_bias = self.create_parameter(
            [H], default_initializer=_InverseSoftplusLogUniform(1e-3, 1e-1))
        self.D = self.create_parameter(
            [H], default_initializer=I.Constant(1.0))
        self.norm_weight = self.create_parameter(
            [di], default_initializer=I.Constant(1.0))
        self.out_proj = nn.Linear(di, dims.hidden,
                                  weight_attr=out_init or init,
                                  bias_attr=bias)

    def state_shapes(self, dtype):
        """One request's carried arrays: ``(shape, dtype)`` by name.  The
        recurrent state is float32 whatever the compute dtype."""
        d = self.dims
        return {"conv": ((d.d_conv - 1, d.conv_dim), dtype),
                "ssm": ((d.n_heads, d.d_head, d.d_state), "float32")}

    def forward(self, u, tail0=None, state0=None, n_valid=None,
                chunk: Optional[int] = None, live=None):
        """``u [b, T, hidden]``.  Alone it is the full-sequence mixer (zero
        state in, states dropped); with ``tail0`` / ``state0`` (arrays) it
        returns ``(out, tail, state)``."""
        d = self.dims
        b, T = u.shape[0], u.shape[1]
        carried = tail0 is not None
        zx = self.in_proj(u)
        if not carried:
            shapes = self.state_shapes(zx._value.dtype)
            tail0 = jnp.zeros((b, *shapes["conv"][0]), zx._value.dtype)
            state0 = jnp.zeros((b, *shapes["ssm"][0]), jnp.float32)
        if n_valid is None:
            n_valid = jnp.full((b,), T, jnp.int32)
        chunk = chunk or d.chunk
        params = [self.conv_weight, self.A_log, self.dt_bias, self.D,
                  self.norm_weight]
        if self.conv_bias is not None:
            params.append(self.conv_bias)

        dims = (d.n_heads, d.d_head, d.n_groups, d.d_state, d.d_inner,
                d.conv_dim, d.eps, d.norm_groups)

        def fn(zx_v, w, a_log, dt_b, d_skip, nw, cb=None):
            return _mamba_mix(dims, zx_v, w, cb, a_log, dt_b, d_skip, nw,
                              tail0, state0, n_valid, chunk, live)

        y, tail, state = apply_op("mamba2_mix", fn, (zx, *params),
                                  multi_out=True)
        out = self.out_proj(y)
        return (out, tail._value, state._value) if carried else out


class GraniteAttention(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        h, kv, d = (config.num_attention_heads, config.num_key_value_heads,
                    config.head_dim)
        init = nn.initializer.Normal(0.0, config.initializer_range)
        bias = None if config.attention_bias else False
        hs = config.hidden_size
        self.q_proj = nn.Linear(hs, h * d, weight_attr=init, bias_attr=bias)
        self.k_proj = nn.Linear(hs, kv * d, weight_attr=init, bias_attr=bias)
        self.v_proj = nn.Linear(hs, kv * d, weight_attr=init, bias_attr=bias)
        self.o_proj = nn.Linear(h * d, hs, weight_attr=init, bias_attr=bias)

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
        b, s = x.shape[0], x.shape[1]
        q, k, v = self.qkv(x)
        # F.scaled_dot_product_attention divides by sqrt(d): fold the
        # model's own multiplier into q
        q = q * (cfg.attention_multiplier * math.sqrt(cfg.head_dim))
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return self.o_proj(reshape(
            out, [b, s, cfg.num_attention_heads * cfg.head_dim]))


class GraniteMLP(nn.Layer):
    """The shared SwiGLU MLP: one input matrix for gate and value."""

    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        init = nn.initializer.Normal(0.0, config.initializer_range)
        self.width = config.shared_intermediate_size
        self.input_linear = nn.Linear(config.hidden_size, 2 * self.width,
                                      weight_attr=init, bias_attr=False)
        self.output_linear = nn.Linear(self.width, config.hidden_size,
                                       weight_attr=init, bias_attr=False)

    def forward(self, x):
        gv = self.input_linear(x)
        return self.output_linear(
            F.swiglu(gv[..., :self.width], gv[..., self.width:]))


class GraniteHybridLayer(nn.Layer):
    def __init__(self, config: GraniteHybridConfig, kind: str):
        super().__init__()
        self.kind = kind
        self.residual_multiplier = config.residual_multiplier
        self.input_layernorm = nn.RMSNorm(config.hidden_size,
                                          config.rms_norm_eps)
        if kind == "mamba":
            self.mamba = Mamba2Mixer(
                config.mamba_dims,
                nn.initializer.Normal(0.0, config.initializer_range))
        else:
            self.self_attn = GraniteAttention(config)
        self.post_attention_layernorm = nn.RMSNorm(config.hidden_size,
                                                   config.rms_norm_eps)
        self.shared_mlp = GraniteMLP(config)

    def add_mixer(self, x, mixed):
        """The block after its mixer: both residual adds and the MLP."""
        r = self.residual_multiplier
        x = x + mixed * r
        return x + self.shared_mlp(self.post_attention_layernorm(x)) * r

    def forward(self, x):
        xin = self.input_layernorm(x)
        mixer = self.mamba if self.kind == "mamba" else self.self_attn
        return self.add_mixer(x, mixer(xin))


class GraniteHybridModel(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=nn.initializer.Normal(0.0, config.initializer_range))
        self.layers = nn.LayerList([GraniteHybridLayer(config, kind)
                                    for kind in config.layer_types])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def embed(self, input_ids):
        return self.embed_tokens(input_ids) * self.config.embedding_multiplier

    def forward(self, input_ids):
        x = self.embed(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class GraniteHybridForCausalLM(nn.Layer):
    def __init__(self, config: GraniteHybridConfig):
        super().__init__()
        self.config = config
        self.model = GraniteHybridModel(config)

    def head(self, hidden):
        logits = F.linear(hidden, self.model.embed_tokens.weight.T)
        return logits * (1.0 / self.config.logits_scaling)

    def forward(self, input_ids, labels=None):
        logits = self.head(self.model(input_ids))
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
        dtype = self.model.embed_tokens.weight._value.dtype
        return [StateLayer.of(**layer.mamba.state_shapes(dtype))
                if layer.kind == "mamba" else
                AttentionLayer(cfg.num_attention_heads,
                               cfg.num_key_value_heads, cfg.head_dim,
                               scale=cfg.attention_multiplier)
                for layer in self.model.layers]

    def serve_begin(self, tokens, positions):
        # no positional encoding: nothing is shared between the layers
        return self.model.embed(tokens), None

    def serve_layer(self, i, x, shared, io):
        layer = self.model.layers[i]
        xin = layer.input_layernorm(x)
        if layer.kind == "attention":
            cfg = self.config
            R, s = x.shape[0], x.shape[1]
            q, k, v = layer.self_attn.qkv(xin)
            out = io.attend(q._value, k._value, v._value)
            mixed = layer.self_attn.o_proj(Tensor(out.reshape(
                R, s, cfg.num_attention_heads * cfg.head_dim)))
        else:
            # one token a row is the decode step: the in-place state
            # update; a prefill launch of several pages scans in chunks of
            # the published size, so the intra-chunk work stays what it is
            mixed, tail, state = layer.mamba(
                xin, io.read_state("conv"), io.read_state("ssm"),
                io.n_valid,
                chunk=min(x.shape[1], self.config.mamba_chunk_size),
                live=io.live if x.shape[1] == 1 else None)
            # a row with no valid token got its tail and state back as
            # they were
            io.write_state("conv", tail)
            io.write_state("ssm", state)
        return layer.add_mixer(x, mixed)

    def serve_end(self, x):
        return self.head(self.model.norm(x))
