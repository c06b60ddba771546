"""Plain reference of a GPT-2-shaped decoder (Cerebras-GPT is one,
arXiv:2304.03208): learned positions, pre-LayerNorm with bias, multi-head
causal attention, exact (erf) GELU MLP, head tied to the token embedding.
Straightforward float32 ``jax.numpy`` at the highest matmul precision.

``weights`` is ``{"wte": [V, H], "wpe": [P, H], "layer": i -> dict,
"ln_f": (w, b)}``; a layer's dict holds ``wq wk wv wo w_in w_out`` ([in,
out]) with biases ``bq bk bv bo b_in b_out`` and ``ln_1 ln_2`` as (w, b)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .llama_like import causal_attention

F32 = jnp.float32


def _layer_norm(x, wb, eps):
    w, b = wb
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _layer(x, w, heads, eps):
    s, hid = x.shape
    d = w["wq"].shape[1] // heads
    y = _layer_norm(x, w["ln_1"], eps)
    q, k, v = ((y @ w["w" + n] + w["b" + n]).reshape(s, heads, d)
               for n in "qkv")
    x = x + causal_attention(q, k, v).reshape(s, heads * d) @ w["wo"] + w["bo"]
    y = _layer_norm(x, w["ln_2"], eps)
    y = jax.nn.gelu(y @ w["w_in"] + w["b_in"], approximate=False)
    return x + y @ w["w_out"] + w["b_out"]


def logits(weights: dict, cfg: dict, ids, positions=None):
    """ids [s] -> float32 logits [len(positions) or s, V]."""
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids)
        wte = jnp.asarray(weights["wte"], F32)
        x = jnp.take(wte, ids, axis=0) \
            + jnp.asarray(weights["wpe"], F32)[:ids.shape[0]]
        eps = cfg["layer_norm_epsilon"]
        step = jax.jit(lambda x, w: _layer(x, w, cfg["n_head"], eps))
        for i in range(cfg["n_layer"]):
            w = jax.tree_util.tree_map(lambda a: jnp.asarray(a, F32),
                                       weights["layer"](i))
            x = step(x, w)
            del w
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _layer_norm(x, tuple(jnp.asarray(a, F32)
                                 for a in weights["ln_f"]), eps)
        return x @ wte.T


def loss_of(logits_, labels) -> float:
    """Mean cross-entropy of reference logits [s, V] against the labels."""
    lp = jax.nn.log_softmax(jnp.asarray(logits_, F32), -1)
    return float(-jnp.mean(jnp.take_along_axis(
        lp, jnp.asarray(labels)[:, None], axis=1)))
