"""Plain reference of a Granite-4.0-H decoder (``granitemoehybrid``, dense):
Mamba-2 layers with a grouped-query attention layer every so often, no
positional encoding, muP-style multipliers, a shared SwiGLU MLP in every
layer, a tied head.  Straightforward float32 ``jax.numpy`` at the highest
matmul precision: no kernels, no cache, no batching, and the state-space
recurrence AS WRITTEN, one token at a time by ``lax.scan`` — not the chunked
algorithm the program under test uses.

It follows the published ``config.json`` of ibm-granite/granite-4.0-h-micro
and the Mamba-2 paper (Dao & Gu 2024, section 7: the layer; the recurrence
``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``).
Departures: none known; what the config does not state (the initialisation,
the state's float32) is the configuration file's ``assumed``.

``logits_and_states`` also returns every Mamba-2 layer's recurrent state
after a given number of tokens: the builder holds the engine's own state to
it, because a state kept in too few bits hides in the logits.

``weights`` is ``{"embed": [V, H], "layer": i -> dict, "norm": [H]}``; a
layer's dict holds ``ln_in ln_mlp w_i w_o`` and either ``wq wk wv wo``
([in, out]) or ``w_in conv_w conv_b A_log dt_bias D norm_w w_out``
(``conv_w`` [conv_dim, K]: tap ``k`` multiplies the input ``K-1-k`` steps
back).  ``layer`` is a function so that one layer's weights are fetched and
cast at a time: the reference then fits beside the model under test."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_attention(q, k, v, scale):
    """q [s, h, d], k/v [s, kv, d] -> [s, h, d]; scores times ``scale``;
    queries in blocks so that a long sequence's scores stay small."""
    s, h, _ = q.shape
    kv = k.shape[1]
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    cols = jnp.arange(s)[None, None, :]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = (lo + jnp.arange(qb.shape[0]))[None, :, None]
        sc = jnp.where(cols <= rows, sc, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    return jnp.concatenate(out, 0)


def _attention(y, w, cfg):
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    s = y.shape[0]
    q = (y @ w["wq"]).reshape(s, h, d)
    k = (y @ w["wk"]).reshape(s, kv, d)
    v = (y @ w["wv"]).reshape(s, kv, d)
    out = causal_attention(q, k, v, cfg["attention_multiplier"])
    return out.reshape(s, h * d) @ w["wo"]


def _mamba(u, w, cfg, state_after):
    """u [T, hidden] -> [T, hidden] and the recurrent state [H, P, N] after
    ``state_after`` tokens: one Mamba-2 mixer from a zero state."""
    T = u.shape[0]
    H, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    G, N, K = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    di = cfg["mamba_expand"] * cfg["hidden_size"]
    cd = di + 2 * G * N
    zx = u @ w["w_in"]
    z, xBC, dt = zx[:, :di], zx[:, di:di + cd], zx[:, di + cd:]
    xp = jnp.pad(xBC, ((K - 1, 0), (0, 0)))
    conv = sum(xp[k:k + T] * w["conv_w"][:, k] for k in range(K))
    xBC = jax.nn.silu(conv + w["conv_b"])
    x = xBC[:, :di].reshape(T, H, P)
    B = jnp.repeat(xBC[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    C = jnp.repeat(xBC[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + w["dt_bias"])             # [T, H]
    A = -jnp.exp(w["A_log"])                            # [H]

    def token(S, inp):
        x_t, B_t, C_t, dt_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", S, C_t) + w["D"][:, None] * x_t
        return S, y

    seq = (x, B, C, dt)
    S, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32),
                        tuple(a[:state_after] for a in seq))
    _, rest = jax.lax.scan(token, S, tuple(a[state_after:] for a in seq))
    g = jnp.concatenate([y, rest]).reshape(T, di) * jax.nn.silu(z)
    return _rms_norm(g, w["norm_w"], cfg["rms_norm_eps"]) @ w["w_out"], S


def _layer(x, w, cfg, kind, state_after):
    """-> the layer's output and, of a Mamba-2 layer, its state (else
    None)."""
    r = cfg["residual_multiplier"]
    y = _rms_norm(x, w["ln_in"], cfg["rms_norm_eps"])
    mixed, state = _mamba(y, w, cfg, state_after) if kind == "mamba" \
        else (_attention(y, w, cfg), None)
    x = x + r * mixed
    y = _rms_norm(x, w["ln_mlp"], cfg["rms_norm_eps"])
    gv = y @ w["w_i"]
    half = gv.shape[-1] // 2
    return x + r * ((jax.nn.silu(gv[:, :half]) * gv[:, half:]) @ w["w_o"]), \
        state


def logits_and_states(weights: dict, cfg: dict, ids, positions=None,
                      state_after=None):
    """ids [s] -> float32 logits [len(positions) or s, V] and every Mamba-2
    layer's recurrent state after the first ``state_after`` tokens (None:
    all of them), float32 [mamba layers, H, P, N]."""
    state_after = len(ids) if state_after is None else int(state_after)
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(weights["embed"], F32)
        x = jnp.take(embed, jnp.asarray(ids), axis=0) \
            * cfg["embedding_multiplier"]
        steps = {kind: jax.jit(lambda x, w, kind=kind: _layer(
            x, w, cfg, kind, state_after))
                 for kind in set(cfg["layer_types"])}
        states = []
        for i, kind in enumerate(cfg["layer_types"]):
            w = {k: jnp.asarray(a, F32) for k, a in weights["layer"](i).items()}
            x, state = steps[kind](x, w)
            if state is not None:
                states.append(state)
            del w
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _rms_norm(x, jnp.asarray(weights["norm"], F32),
                      cfg["rms_norm_eps"])
        return (x @ embed.T) / cfg["logits_scaling"], jnp.stack(states)


def logits(weights: dict, cfg: dict, ids, positions=None):
    """ids [s] -> float32 logits [len(positions) or s, V]."""
    return logits_and_states(weights, cfg, ids, positions)[0]
