"""Mixture-of-Experts with expert parallelism, TPU-native.

Parity target: ``python/paddle/incubate/distributed/models/moe/``
(``moe_layer.py:263`` MoELayer, ``gate/naive_gate.py``,
``gate/gshard_gate.py:31``, ``gate/switch_gate.py``, dispatch utils
``distributed/utils/moe_utils.py:20`` global_scatter/global_gather).

The reference routes tokens with index scatter + NCCL all-to-all between
ranks. The TPU-native formulation is GShard's: routing is two dense einsums
against a one-hot *dispatch* mask [tokens, experts, capacity] — no dynamic
shapes, so the whole layer jits, and when the expert dimension of the
[E, C, d] buffer is sharded over a mesh axis, XLA lowers the
dispatch/combine einsums to exactly the all-to-alls the reference issues by
hand. Capacity makes the compute static: overflow tokens are dropped
(contribute zero), underflow slots are zero-padded — the standard
GShard/Switch semantics.

Two layer classes:

- :class:`MoELayer` — API-parity with the reference: arbitrary per-expert
  ``nn.LayerList`` experts, gate configurable by dict or Gate instance. The
  expert loop is unrolled (E static sub-graphs); fine for eager parity +
  moderate E.
- :class:`ExpertParallelMLP` — the GShard path: stacked expert weights
  ``[E, d, h]`` applied with one batched einsum, expert axis shardable over
  mesh axes (``expert_axes``) under the engine/pjit.  ``LlamaForCausalLM``'s
  MoE variants train through it.

Which layer a transformer should use now: a model whose experts are routed
without a capacity (no token dropped), whose router is sigmoid / group-limited,
or which holds only its chip's share of the experts takes
:class:`paddle_tpu.nn.layer.moe.RoutedExperts` — sort-based dropless dispatch
over a grouped matmul, told ``experts_held`` (``DeepseekV3ForCausalLM`` does).
The two classes here keep GShard's capacity semantics (overflow tokens are
dropped) and their ``[N, E, C]`` dispatch tensors; they are not given a third
dispatch.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from .....nn import functional as F
from .....nn import initializer as I
from .....nn.layer.layers import Layer
from .....tensor.tensor import Tensor, apply_op
from .....tensor._op_utils import ensure_tensor

__all__ = ["MoELayer", "ExpertParallelMLP", "NaiveGate", "GShardGate", "SwitchGate"]


# ---------------------------------------------------------------------------
# routing math (pure jnp; shared by both layers and both gates)
# ---------------------------------------------------------------------------

def _topk_routing(logits: jax.Array, k: int, capacity: int,
                  normalize_weights: bool = True):
    """From router logits [N, E] build GShard-style routing tensors.

    Returns (dispatch [N, E, C] float 0/1, combine [N, E, C], l_aux scalar).
    Position assignment is priority-ordered exactly as GShard: all tokens'
    1st choices claim slots before any 2nd choice (cumsum per choice round).
    """
    n, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(probs, k)                       # [N, k]
    if normalize_weights:
        topv = topv / jnp.clip(jnp.sum(topv, axis=-1, keepdims=True), 1e-9)

    # auxiliary load-balance loss (GShard eq.4 / Switch eq.4):
    # E * sum_e mean_prob_e * frac_top1_tokens_e
    me = jnp.mean(probs, axis=0)                               # [E]
    ce = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    l_aux = jnp.sum(me * ce) * e

    counts = jnp.zeros((e,), jnp.int32)
    dispatch = jnp.zeros((n, e, capacity), jnp.float32)
    combine = jnp.zeros((n, e, capacity), jnp.float32)
    for j in range(k):                                          # k is tiny (1 or 2)
        choice = jax.nn.one_hot(topi[:, j], e, dtype=jnp.int32)          # [N, E]
        pos = jnp.cumsum(choice, axis=0) - 1 + counts[None, :]           # [N, E]
        counts = counts + jnp.sum(choice, axis=0)
        pos_j = jnp.sum(pos * choice, axis=-1)                           # [N]
        keep = (pos_j < capacity).astype(jnp.float32)
        slot = jax.nn.one_hot(pos_j, capacity, dtype=jnp.float32)        # [N, C]
        mask = choice.astype(jnp.float32)[:, :, None] * slot[:, None, :] \
            * keep[:, None, None]
        dispatch = dispatch + mask
        combine = combine + mask * topv[:, j][:, None, None]
    return dispatch, combine, l_aux


def _capacity(num_tokens: int, num_experts: int, k: int, capacity_factor: float) -> int:
    cap = int(math.ceil(capacity_factor * k * num_tokens / num_experts))
    return max(8, -(-cap // 8) * 8)  # round up to a lane-friendly multiple of 8


# ---------------------------------------------------------------------------
# gates
# ---------------------------------------------------------------------------

class NaiveGate(Layer):
    """Plain learned top-k router (reference ``gate/naive_gate.py``): linear
    scores, top-k softmax weights, no capacity pressure beyond the layer's."""

    top_k = 2

    def __init__(self, d_model: int, num_expert: int, world_size: int = 1,
                 topk: int = 2):
        super().__init__()
        # reference keeps num_expert per rank × world_size; TPU sees the
        # global expert count directly
        self.num_expert_global = num_expert * world_size
        self.d_model = d_model
        self.top_k = topk
        w = self.create_parameter([d_model, self.num_expert_global],
                                  default_initializer=I.XavierUniform())
        self.add_parameter("gate_weight", w)
        self.loss: Optional[Tensor] = None

    def gate_logits(self, x: Tensor) -> Tensor:
        return F.linear(x, self.gate_weight)

    def forward(self, x: Tensor) -> Tuple[Tensor, Tensor]:
        logits = self.gate_logits(x)
        val, idx = apply_op(
            "topk_gate",
            lambda lg: jax.lax.top_k(jax.nn.softmax(lg.astype(jnp.float32), -1),
                                     self.top_k),
            (logits,), multi_out=True)
        self.loss = None
        return val, idx


class GShardGate(NaiveGate):
    """Top-2 gate with the GShard load-balancing loss
    (reference ``gate/gshard_gate.py:31``; capacity enforced by the layer)."""

    def __init__(self, d_model: int, num_expert: int, world_size: int = 1,
                 topk: int = 2, capacity: Tuple[float, float] = (1.2, 2.4),
                 random_routing: bool = True, group=None):
        super().__init__(d_model, num_expert, world_size, topk=topk)
        self.capacity_factor = capacity

    def forward(self, x: Tensor) -> Tuple[Tensor, Tensor]:
        logits = self.gate_logits(x)
        e = self.num_expert_global

        def fn(lg):
            probs = jax.nn.softmax(lg.astype(jnp.float32), -1)
            topv, topi = jax.lax.top_k(probs, self.top_k)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
            return topv, topi, jnp.sum(me * ce) * e

        val, idx, loss = apply_op("gshard_gate", fn, (logits,), multi_out=True)
        self.loss = loss
        return val, idx

    def get_loss(self, clear: bool = True) -> Optional[Tensor]:
        loss = self.loss
        if clear:
            self.loss = None
        return loss


class SwitchGate(NaiveGate):
    """Top-1 Switch-Transformer gate (reference ``gate/switch_gate.py``)."""

    def __init__(self, d_model: int, num_expert: int, world_size: int = 1,
                 topk: int = 1, switch_eps: float = 0.1, capacity: Tuple = (1.2, 2.4),
                 group=None):
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.switch_eps = switch_eps

    def forward(self, x: Tensor) -> Tuple[Tensor, Tensor]:
        logits = self.gate_logits(x)
        e = self.num_expert_global
        eps = self.switch_eps
        noise_key = None
        if self.training and eps > 0:
            from .....framework.random import next_key
            noise_key = next_key()

        def fn(lg):
            lgf = lg.astype(jnp.float32)
            if noise_key is not None:  # multiplicative jitter, as the reference
                noise = jax.random.uniform(noise_key, lgf.shape,
                                           minval=1.0 - eps, maxval=1.0 + eps)
                lgf = lgf * noise
            probs = jax.nn.softmax(lgf, -1)
            topv, topi = jax.lax.top_k(probs, 1)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
            return topv, topi, jnp.sum(me * ce) * e

        val, idx, loss = apply_op("switch_gate", fn, (logits,), multi_out=True)
        self.loss = loss
        return val, idx

    get_loss = GShardGate.get_loss


def _make_gate(gate, d_model: int, num_expert: int) -> NaiveGate:
    if isinstance(gate, NaiveGate):
        return gate
    cfg = dict(gate) if isinstance(gate, dict) else {}
    kind = cfg.get("type", "gshard")
    topk = cfg.get("top_k", 2)
    if kind == "naive" or kind is None:
        return NaiveGate(d_model, num_expert, topk=topk)
    if kind == "gshard":
        return GShardGate(d_model, num_expert, topk=topk)
    if kind == "switch":
        return SwitchGate(d_model, num_expert)
    raise ValueError(f"unknown gate type {kind!r} (naive|gshard|switch)")


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

class MoELayer(Layer):
    """API-parity MoE layer (reference ``moe_layer.py:263``).

    ``experts`` is an ``nn.LayerList`` of arbitrary expert networks mapping
    [tokens, d_model] → [tokens, d_model]. Routing follows the gate's top-k;
    token→expert transport is the dispatch-einsum formulation (module
    docstring) instead of the reference's global_scatter/global_gather, so
    the layer works identically in eager, under ``jit.to_static`` and under
    the distributed engine (where sharding the [E, C, d] buffer over mesh
    axes turns the einsums into all-to-alls)."""

    def __init__(self, d_model: int, experts=None, gate=None, moe_group=None,
                 mp_group=None, recompute_interval: int = 0, recompute_ctx=None,
                 capacity_factor: float = 2.0):
        super().__init__()
        if experts is None or len(experts) == 0:
            raise ValueError("MoELayer requires a non-empty experts LayerList")
        self.d_model = d_model
        self.experts = experts if isinstance(experts, Layer) else None
        if self.experts is None:
            from .....nn.layer.container import LayerList
            self.experts = LayerList(list(experts))
        self.num_expert = len(self.experts)
        self.gate = _make_gate(gate, d_model, self.num_expert)
        self.top_k = self.gate.top_k
        self.capacity_factor = capacity_factor
        self.recompute_interval = recompute_interval
        self.l_aux: Optional[Tensor] = None

    def forward(self, inp: Tensor) -> Tensor:
        inp = ensure_tensor(inp)
        orig_shape = tuple(inp.shape)
        d = orig_shape[-1]
        tokens = inp.reshape([-1, d])
        n = tokens.shape[0]
        cap = _capacity(n, self.num_expert, self.top_k, self.capacity_factor)

        logits = self.gate.gate_logits(tokens)
        dispatch, combine, l_aux = apply_op(
            "moe_routing",
            lambda lg: _topk_routing(lg, self.top_k, cap),
            (logits,), multi_out=True)
        self.l_aux = l_aux
        self.gate.loss = l_aux

        # [N, d] → [E, C, d]
        expert_in = apply_op("moe_dispatch",
                             lambda disp, t: jnp.einsum("nec,nd->ecd", disp, t,
                                                        preferred_element_type=jnp.float32
                                                        ).astype(t.dtype),
                             (dispatch, tokens))
        outs = []
        for e in range(self.num_expert):
            outs.append(self.experts[e](expert_in[e]))
        from .....tensor.manipulation import stack
        expert_out = stack(outs, axis=0)                       # [E, C, d]
        out = apply_op("moe_combine",
                       lambda comb, eo: jnp.einsum("nec,ecd->nd", comb,
                                                   eo.astype(jnp.float32)
                                                   ).astype(eo.dtype),
                       (combine, expert_out))
        return out.reshape(list(orig_shape))


class ExpertParallelMLP(Layer):
    """Stacked-expert MoE FFN — the TPU flagship path.

    Expert weights live as ``w1 [E, d, h]`` / ``w2 [E, h, d]`` (gated variant
    adds ``w_gate``), applied with one batched einsum over the expert dim.
    Under the distributed engine, ``expert_axes`` shards dim 0 of the weights
    and of the [E, C, d] activation buffers (GSPMD then emits all-to-all for
    dispatch/combine — expert parallelism without explicit collectives).

    ``gate_type``: "gshard" (top-2) or "switch" (top-1). ``activation``:
    "swiglu" (llama-style gated) or any name in incubate fused_bias_act."""

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int = 2, capacity_factor: float = 2.0,
                 activation: str = "swiglu", expert_axes: Union[str, Sequence[str], None] = None,
                 param_dtype="float32"):
        super().__init__(dtype=param_dtype)
        self.d_model, self.d_hidden, self.num_experts = d_model, d_hidden, num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.expert_axes = (expert_axes,) if isinstance(expert_axes, str) else \
            tuple(expert_axes) if expert_axes else None
        mk = lambda shape: self.create_parameter(shape, default_initializer=I.XavierUniform())
        self.add_parameter("gate_weight", mk([d_model, num_experts]))
        self.add_parameter("w1", mk([num_experts, d_model, d_hidden]))
        if activation == "swiglu":
            self.add_parameter("w_gate", mk([num_experts, d_model, d_hidden]))
        self.add_parameter("w2", mk([num_experts, d_hidden, d_model]))
        self.l_aux: Optional[Tensor] = None

    def _constrain(self, x: jax.Array) -> jax.Array:
        if self.expert_axes is None:
            return x
        try:
            from jax.sharding import PartitionSpec as P
            spec = P(self.expert_axes, *([None] * (x.ndim - 1)))
            return jax.lax.with_sharding_constraint(x, spec)
        except Exception:  # no mesh context (pure eager single-device)
            return x

    def forward(self, inp: Tensor) -> Tensor:
        inp = ensure_tensor(inp)
        orig_shape = tuple(inp.shape)
        d = orig_shape[-1]
        tokens = inp.reshape([-1, d])
        n = tokens.shape[0]
        cap = _capacity(n, self.num_experts, self.top_k, self.capacity_factor)
        k, act, constrain = self.top_k, self.activation, self._constrain

        def fn(t, gw, *ws):
            logits = t.astype(jnp.float32) @ gw.astype(jnp.float32)
            dispatch, combine, l_aux = _topk_routing(logits, k, cap)
            xe = jnp.einsum("nec,nd->ecd", dispatch.astype(t.dtype), t)
            xe = constrain(xe)
            if act == "swiglu":
                w1, wg, w2 = ws
                h = jax.nn.silu(jnp.einsum("ecd,edh->ech", xe, w1)) * \
                    jnp.einsum("ecd,edh->ech", xe, wg)
            else:
                w1, w2 = ws
                h = _ACT_FNS[act](jnp.einsum("ecd,edh->ech", xe, w1))
            ye = jnp.einsum("ech,ehd->ecd", h, w2)
            ye = constrain(ye)
            out = jnp.einsum("nec,ecd->nd", combine.astype(ye.dtype), ye)
            return out, l_aux

        params = (tokens, self.gate_weight) + ((self.w1, self.w_gate, self.w2)
                                               if act == "swiglu" else (self.w1, self.w2))
        out, l_aux = apply_op("expert_parallel_mlp", fn, params, multi_out=True)
        self.l_aux = l_aux
        return out.reshape(list(orig_shape))


_ACT_FNS = {"gelu": jax.nn.gelu, "relu": jax.nn.relu, "silu": jax.nn.silu}
