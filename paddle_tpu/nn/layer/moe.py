"""A dropless mixture-of-experts layer that is told which experts it holds.

Routing is the published one of DeepSeek-V3 (arXiv:2412.19437, and the
``config.json`` keys ``scoring_func`` sigmoid, ``topk_method`` noaux_tc): in
float32 ``s = sigmoid(W_g x)`` over ALL ``n_routed_experts``; the selection
looks at ``s + b`` (``b``: ``e_score_correction_bias``): a group's score is
the sum of its two largest, the ``topk_group`` best of ``n_group`` groups of
consecutive experts stay, and the ``top_k`` largest inside them are chosen;
the weights use ``s`` without ``b``: ``w_e = routed_scaling_factor * s_e /
sum_chosen s``.

Dispatch is sort-based and dropless: the token-expert pairs are sorted by
expert (a stable sort, so a token's order is kept), every expert's rows are
contiguous, and one grouped matmul a projection runs over them
(:func:`grouped_matmul`: the Pallas kernel ``moe_grouped_matmul`` where
kernels run and its gate takes the shapes, ``jax.lax.ragged_dot``
elsewhere; the kernel's backward is ``ragged_dot``'s).  There is no capacity
and no dropped token.

``experts_held = (first, count)``: the layer keeps the weights of experts
``first .. first + count - 1`` only (one chip's share under expert
parallelism), routes over all of them all the same, and returns the held
experts' weighted part of the result.  What the other experts would add is
the other chips' to compute; on one chip the layer runs without the
exchange.  ``(0, n_routed_experts)`` is the whole layer.

An expert is a SwiGLU of three matrices, ``W_down (silu(W_gate x) * W_up
x)`` (``gated=True``, ``activation="silu"``: DeepSeek-V3), or of two with no
gate matrix, ``W_down act(W_up x)`` (``gated=False``): Nemotron-H's experts
are ``W_down relu(W_up x) ** 2`` (``activation="relu2"``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import initializer as I
from ...tensor.tensor import apply_op
from .layers import Layer

__all__ = ["RoutedExperts", "group_limited_topk", "grouped_matmul",
           "held_experts_mlp", "ACTIVATIONS"]

# an expert's activation by its published name, in float32
ACTIVATIONS = {"silu": jax.nn.silu,
               "relu2": lambda v: jnp.square(jax.nn.relu(v))}


def group_limited_topk(scores, bias, *, top_k: int, n_group: int,
                       topk_group: int, norm_topk_prob: bool = True,
                       routed_scaling_factor: float = 1.0):
    """``scores`` [N, E] float32 (``sigmoid`` of the router logits), ``bias``
    [E]: the chosen experts [N, top_k] int32 and their weights [N, top_k]
    float32 (module docstring).  Ties go to the lower expert index."""
    n, e = scores.shape
    choice = scores + bias.astype(jnp.float32)[None, :]
    groups = choice.reshape(n, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, topk_group)[1]           # [N, kept]
    in_kept = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None],
                      axis=1)                                  # [N, n_group]
    masked = jnp.where(jnp.repeat(in_kept, e // n_group, axis=1), choice,
                       -jnp.inf)
    idx = jax.lax.top_k(masked, top_k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * routed_scaling_factor


@functools.partial(jax.jit, static_argnames=(
    "top_k", "n_group", "topk_group", "norm_topk_prob",
    "routed_scaling_factor"))
def _route(x, gate_weight, bias, **routing):
    """``x`` [N, D] to its experts and weights: the router in float32."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), gate_weight.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    return group_limited_topk(scores, bias, **routing)


def grouped_matmul_kernel(rows: int, w_up_shape, w_down_shape, dtype):
    """How an expert layer multiplies ``rows`` sorted pairs by their
    experts, decided at trace time like the decode kernels
    (``pallas_mode("use_decode_attention")``): ``"mosaic"`` or
    ``"interpret"`` where ``moe_grouped_matmul`` runs and its gate takes
    both projections' shapes, else None (``jax.lax.ragged_dot``) with a
    counted ``kernel_fallback`` wherever a kernel could have run."""
    from ...ops import pallas_mode
    from ...ops.pallas.grouped_matmul import (KERNEL_NAME,
                                              grouped_matmul_refusal)

    mode = pallas_mode("use_decode_attention")
    if mode is None:
        return None
    kind, _, interpret = mode
    rows = _whole_tiles(rows)
    reason = "hybrid_mesh" if kind != "local" else (
        grouped_matmul_refusal((rows, w_up_shape[1]), w_up_shape, dtype,
                               interpret=interpret)
        or grouped_matmul_refusal((rows, w_down_shape[1]), w_down_shape,
                                  dtype, interpret=interpret))
    if reason is None:
        return "interpret" if interpret else "mosaic"
    from ...telemetry import kernel_fallback

    kernel_fallback(KERNEL_NAME, reason, rows=rows)
    return None


def _whole_tiles(rows: int) -> int:
    """``rows`` pairs as the kernel takes them: more than one row tile's
    worth padded to whole tiles (a serving launch's pairs need not be: a
    prompt's tokens and the decode rows riding beside them).  The padding
    lies past every group and is computed by no tile."""
    from ...ops.pallas.grouped_matmul import TILE_M

    return rows if rows <= TILE_M else -(-rows // TILE_M) * TILE_M


def _ragged_dot(x, w, group_sizes):
    return jax.lax.ragged_dot(x, w.astype(x.dtype), group_sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernel_matmul(x, w, group_sizes, interpret: bool):
    from ...ops.pallas.grouped_matmul import grouped_matmul as pallas_gmm

    return pallas_gmm(x, w, group_sizes, interpret=interpret)


def _kernel_matmul_fwd(x, w, group_sizes, interpret):
    return _kernel_matmul(x, w, group_sizes, interpret), (x, w, group_sizes)


def _kernel_matmul_bwd(interpret, saved, dy):
    # the kernel has no backward of its own: the same product through
    # ``ragged_dot`` has
    x, w, group_sizes = saved
    dx, dw = jax.vjp(lambda a, b: _ragged_dot(a, b, group_sizes), x, w)[1](dy)
    return dx, dw, None


_kernel_matmul.defvjp(_kernel_matmul_fwd, _kernel_matmul_bwd)


def grouped_matmul(x, w, group_sizes, kernel: Optional[str] = None):
    """``x`` [M, K], rows sorted by group, times ``w`` [G, K, N] a group:
    [M, N], rows past ``sum(group_sizes)`` zero.  ``kernel``
    (:func:`grouped_matmul_kernel`): the Pallas kernel (differentiated
    through ``ragged_dot``), else ``jax.lax.ragged_dot``."""
    if kernel is None:
        return _ragged_dot(x, w, group_sizes)
    m = x.shape[0]
    pad = _whole_tiles(m) - m
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    y = _kernel_matmul(x, w, group_sizes, kernel == "interpret")
    return y[:m] if pad else y


# jitted: the expert layers of a program share one trace, and an eager call
# is one dispatch, not one an operation
@functools.partial(jax.jit, static_argnames=("kernel", "activation"))
def held_experts_mlp(x, idx, weight, w_gate, w_up, w_down, first,
                     *, kernel: Optional[str] = None,
                     activation: str = "silu"):
    """The held experts' part of ``sum_chosen w_e * E_e(x)``.  ``x`` [N, D];
    ``idx`` / ``weight`` [N, k]: every token's experts and weights;
    ``w_gate`` / ``w_up`` [G, D, H], ``w_down`` [G, H, D]: experts ``first ..
    first + G - 1``, each ``W_down (act(W_gate x) * W_up x)``, or with
    ``w_gate`` None ``W_down act(W_up x)``: two grouped matmuls, not three.
    Returns ``(y [N, D], group_sizes [G])``: a pair whose expert is not
    held adds nothing, and no pair whose expert is held is dropped."""
    n, k = idx.shape
    act = ACTIVATIONS[activation]
    count = w_up.shape[0]
    local = idx - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)            # [N * k]
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    xs = x[order // k]                                         # [N * k, D]
    h = act(grouped_matmul(xs, w_up if w_gate is None else w_gate, sizes,
                           kernel).astype(jnp.float32)).astype(x.dtype)
    if w_gate is not None:
        h = h * grouped_matmul(xs, w_up, sizes, kernel)
    y = grouped_matmul(h, w_down, sizes, kernel)               # sorted rows
    # back to (token, choice) order; rows of experts not held are zero
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(n * k, dtype=order.dtype), unique_indices=True)
    y = y[back].astype(jnp.float32) \
        * jnp.where(held, weight, 0.0).reshape(-1)[:, None]
    return y.reshape(n, k, -1).sum(axis=1).astype(x.dtype), sizes


class RoutedExperts(Layer):
    """The router and the held routed experts of one expert layer (module
    docstring).  ``forward(x)`` returns the held experts' part of the
    result for ``x`` [..., hidden]; ``last_load`` then holds the pairs each
    held expert computed, [count] int32, and ``last_choice`` every token's
    chosen experts, [..., top_k] int32 (-1: a token that is not valid)."""

    def __init__(self, hidden_size: int, intermediate_size: int,
                 n_routed_experts: int, top_k: int, *, n_group: int = 1,
                 topk_group: int = 1, norm_topk_prob: bool = True,
                 routed_scaling_factor: float = 1.0,
                 experts_held: Optional[Tuple[int, int]] = None,
                 activation: str = "silu", gated: bool = True,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        first, count = experts_held or (0, n_routed_experts)
        if not (0 <= first and count >= 1
                and first + count <= n_routed_experts):
            raise ValueError(f"experts_held {experts_held} lies outside "
                             f"the {n_routed_experts} routed experts")
        if n_routed_experts % n_group or not 1 <= topk_group <= n_group \
                or top_k > topk_group * (n_routed_experts // n_group):
            raise ValueError("n_group must divide n_routed_experts and the "
                             "kept groups must hold top_k experts")
        if activation not in ACTIVATIONS:
            raise ValueError(f"activation {activation!r}: one of "
                             f"{sorted(ACTIVATIONS)}")
        self.experts_held = (int(first), int(count))
        self.activation = activation
        self.routing = dict(top_k=top_k, n_group=n_group,
                            topk_group=topk_group,
                            norm_topk_prob=norm_topk_prob,
                            routed_scaling_factor=routed_scaling_factor)
        d, h = hidden_size, intermediate_size
        # the router keeps its published width whatever is held here
        self.gate_weight = self.create_parameter(
            [d, n_routed_experts], attr=weight_attr)
        self.e_score_correction_bias = self.create_parameter(
            [n_routed_experts], attr=bias_attr,
            default_initializer=I.Constant(0.0))
        # no gate matrix: the expert is W_down act(W_up x)
        self.gate_proj = self.create_parameter(
            [count, d, h], attr=weight_attr) if gated else None
        self.up_proj = self.create_parameter([count, d, h], attr=weight_attr)
        self.down_proj = self.create_parameter([count, h, d],
                                               attr=weight_attr)
        self.last_load = self.last_choice = None

    def forward(self, x, valid=None):
        """``valid`` (an array like ``x`` without its last axis, bool):
        tokens that are real; the others (a serving program's idle rows and
        padding) are routed nowhere and cost no expert a row."""
        first = self.experts_held[0]
        routing = self.routing
        activation = self.activation
        kernel = grouped_matmul_kernel(
            x.size // x.shape[-1] * routing["top_k"], self.up_proj.shape,
            self.down_proj.shape, x._value.dtype)

        def fn(xv, wg, b, *experts):
            # (gate,) up, down
            w_gate = experts[0] if len(experts) == 3 else None
            w_up, w_down = experts[-2:]
            flat = xv.reshape(-1, xv.shape[-1])
            idx, w = _route(flat, wg, b, **routing)
            if valid is not None:
                idx = jnp.where(valid.reshape(-1, 1), idx, -1)
            y, load = held_experts_mlp(flat, idx, w, w_gate, w_up, w_down,
                                       first, kernel=kernel,
                                       activation=activation)
            return y.reshape(xv.shape), load, \
                idx.reshape(xv.shape[:-1] + idx.shape[-1:])

        experts = [w for w in (self.gate_proj, self.up_proj, self.down_proj)
                   if w is not None]
        y, load, choice = apply_op("routed_experts", fn, (
            x, self.gate_weight, self.e_score_correction_bias, *experts),
            multi_out=True)
        self.last_load, self.last_choice = load._value, choice._value
        return y
