"""Plain reference of a Llama-shaped decoder (Mistral-7B-v0.3 is one):
RMSNorm, rotate-half RoPE, grouped-query causal attention, SwiGLU, untied
head.  Straightforward float32 ``jax.numpy`` at the highest matmul
precision: no kernels, no cache, no batching tricks.  It follows the
published description (Mistral 7B, arXiv:2310.06825; v0.3 has no sliding
window); nothing departs from it.

``weights`` is ``{"embed": [V, H], "layer": i -> dict, "norm": [H],
"head": [H, V]}``; a layer's dict holds ``wq wk wv wo`` ([in, out]),
``w_gate w_up w_down``, ``ln_attn ln_mlp``.  ``layer`` is a function so that
one layer's weights are fetched and cast at a time: the reference then fits
beside the model under test."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [s, heads, d] at positions 0..s-1, rotate-half convention."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def causal_attention(q, k, v):
    """q [s, h, d], k/v [s, kv, d] -> [s, h, d]; queries in blocks so that
    the score matrix of a long sequence stays small."""
    s, h, d = q.shape
    kv = k.shape[1]
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    cols = jnp.arange(s)[None, None, :]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(F32(d))
        rows = (lo + jnp.arange(qb.shape[0]))[None, :, None]
        sc = jnp.where(cols <= rows, sc, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    return jnp.concatenate(out, 0)


def _layer(x, w, cfg):
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // h
    s = x.shape[0]
    y = _rms_norm(x, w["ln_attn"], cfg["rms_norm_eps"])
    q = _rope((y @ w["wq"]).reshape(s, h, d), cfg["rope_theta"])
    k = _rope((y @ w["wk"]).reshape(s, kv, d), cfg["rope_theta"])
    v = (y @ w["wv"]).reshape(s, kv, d)
    x = x + causal_attention(q, k, v).reshape(s, h * d) @ w["wo"]
    y = _rms_norm(x, w["ln_mlp"], cfg["rms_norm_eps"])
    return x + (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]


def logits(weights: dict, cfg: dict, ids, positions=None):
    """ids [s] -> float32 logits [len(positions) or s, V]."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(jnp.asarray(weights["embed"], F32),
                     jnp.asarray(ids), axis=0)
        step = jax.jit(lambda x, w: _layer(x, w, cfg))
        for i in range(cfg["num_hidden_layers"]):
            w = {k: jnp.asarray(a, F32) for k, a in weights["layer"](i).items()}
            x = step(x, w)
            del w
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _rms_norm(x, jnp.asarray(weights["norm"], F32),
                      cfg["rms_norm_eps"])
        return x @ jnp.asarray(weights["head"], F32)


def loss_of(logits_, labels) -> float:
    """Mean cross-entropy of reference logits [s, V] against the labels."""
    lp = jax.nn.log_softmax(jnp.asarray(logits_, F32), -1)
    return float(-jnp.mean(jnp.take_along_axis(
        lp, jnp.asarray(labels)[:, None], axis=1)))
