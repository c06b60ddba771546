"""Plain reference of Ouro (a looped LM, arXiv:2510.25741; Ouro-2.6B's
published ``config.json`` and ``modeling_ouro.py``): ONE stack of
``num_hidden_layers`` decoder layers run ``total_ut_steps`` times over the
whole sequence with the same weights.  A layer is RMSNorm, rotate-half RoPE
over the whole head, causal multi-head attention, a second RMSNorm on the
attention's output before the residual add; then the same around a SwiGLU
MLP.  After every pass the shared final RMSNorm, whose output the next pass
starts from.  The exit rule: ``early_exit_gate`` (one logit and a bias)
scores each pass's normalised state, ``sigmoid`` of it is the share of what
the earlier passes left that exits there (the last pass takes the rest),
and a token exits at the first pass whose cumulative share reaches
``early_exit_threshold``; its logits are the head of that pass's state.

Straightforward float32 ``jax.numpy`` at the highest matmul precision, the
whole sequence at every pass: no kernels, no cache, no batching.  Each
pass's attention is recomputed over that pass's own K/V of every earlier
token, which is what the published cache (indexed ``pass * layers +
layer``) holds.

``weights`` is ``{"embed": [V, H], "layer": i -> dict, "norm": [H],
"gate_w": [H, 1], "gate_b": [1], "head": [H, V]}``; a layer's dict holds
``wq wk wv wo`` ([in, out]), ``w_gate w_up w_down`` and the four norms
``ln_attn ln_attn_2 ln_mlp ln_mlp_2``.  ``layer`` is a function so that
one layer's weights are fetched and cast at a time.

The keyword arguments of :func:`logits` other than ``positions`` are the
mechanism controls, each a departure from the published model that a check
must refuse: ``passes`` (fewer passes), ``kv_from_pass`` (every pass
attends over ONE pass's K/V, the paper's decode-time cache sharing),
``inter_pass_norm=False`` (the final norm only after the last pass),
``post_norms=False`` (no norm on the sublayers' outputs) and
``kv_dtype="fp8"`` (K/V rounded to float8 e4m3 as pages of that type
would keep them, at the engine's default scale of 1).

``dtype`` (float32 by default) is the type the weights, the residual state
and each pass's K/V are kept in between the layer's operations:
``"bfloat16"`` is the reference rounded as the served model's bf16 weights,
activations and pages are, which says how far bf16 rounding alone carries
a row from the float32 reference."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.llama_like import _rms_norm, _rope, causal_attention

F32 = jnp.float32
FP8_MAX = 448.0


def exit_pass(gate_logits, threshold: float):
    """``gate_logits`` [passes, n] -> int32 [n]: the pass each token exits
    at under the published rule (the last where no earlier pass's
    cumulative exit probability reaches ``threshold``)."""
    lam = jax.nn.sigmoid(jnp.asarray(gate_logits, F32))
    n_pass = lam.shape[0]
    left = jnp.ones_like(lam[0])
    cdf = jnp.zeros_like(lam[0])
    pick = jnp.full(lam.shape[1:], n_pass - 1, jnp.int32)
    for t in range(n_pass - 1):
        cdf = cdf + lam[t] * left
        left = left * (1.0 - lam[t])
        pick = jnp.where((cdf >= threshold) & (pick == n_pass - 1), t, pick)
    return pick


def _fp8(x):
    return jnp.clip(x, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn) \
        .astype(F32)


def _layer(x, w, kv, cfg, post_norms, kv_dtype, dt):
    """One layer over the whole sequence; ``kv``: the (K, V) to attend over
    in place of this pass's own (None: its own).  Returns the new state and
    this pass's K, V."""
    h, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    s = x.shape[0]
    y = _rms_norm(x, w["ln_attn"], eps)
    q = _rope((y @ w["wq"]).reshape(s, h, d), cfg["rope_theta"])
    k = _rope((y @ w["wk"]).reshape(s, kvh, d), cfg["rope_theta"])
    v = (y @ w["wv"]).reshape(s, kvh, d)
    k, v = k.astype(dt), v.astype(dt)
    if kv_dtype == "fp8":
        k, v = _fp8(k), _fp8(v)
    kk, vv = (k, v) if kv is None else kv
    a = causal_attention(q, kk, vv).reshape(s, h * d).astype(dt) @ w["wo"]
    if post_norms:
        a = _rms_norm(a, w["ln_attn_2"], eps)
    x = (x + a).astype(dt)
    y = _rms_norm(x, w["ln_mlp"], eps)
    m = (jax.nn.silu(y @ w["w_gate"]) * (y @ w["w_up"])) @ w["w_down"]
    if post_norms:
        m = _rms_norm(m, w["ln_mlp_2"], eps)
    return (x + m).astype(dt), k, v


def _forward(weights, cfg, ids, positions, passes, threshold,
             inter_pass_norm, post_norms, kv_dtype, dt, shared=None,
             capture=None):
    step = jax.jit(lambda x, w, kv: _layer(x, w, kv, cfg, post_norms,
                                           kv_dtype, dt))
    norm = jnp.asarray(weights["norm"], dt)
    gate_w = jnp.asarray(weights["gate_w"], dt)
    gate_b = jnp.asarray(weights["gate_b"], dt)
    x = jnp.take(jnp.asarray(weights["embed"], dt), jnp.asarray(ids),
                 axis=0)
    rows = jnp.arange(len(ids)) if positions is None \
        else jnp.asarray(positions)
    states, gates = [], []
    for t in range(passes):
        for i in range(cfg["num_hidden_layers"]):
            w = {k: jnp.asarray(a, dt)
                 for k, a in weights["layer"](i).items()}
            x, k, v = step(x, w, None if shared is None else shared[i])
            if capture is not None and t == capture[0]:
                capture[1][i] = (k, v)
            del w
        if inter_pass_norm or t == passes - 1:
            x = _rms_norm(x, norm, cfg["rms_norm_eps"]).astype(dt)
        states.append(x[rows])
        gates.append((x[rows] @ gate_w + gate_b)[:, 0])
    pick = exit_pass(jnp.stack(gates), threshold)
    h = jnp.take_along_axis(jnp.stack(states), pick[None, :, None],
                            axis=0)[0]
    return (h @ jnp.asarray(weights["head"], dt)).astype(F32), pick


def logits(weights: dict, cfg: dict, ids, positions=None, *, passes=None,
           threshold=None, inter_pass_norm=True, post_norms=True,
           kv_from_pass=None, kv_dtype=None, dtype=F32, return_exit=False):
    """ids [s] -> float32 logits [len(positions) or s, V] (and, with
    ``return_exit``, the pass each of those rows exited at)."""
    passes = cfg["total_ut_steps"] if passes is None else passes
    threshold = cfg["early_exit_threshold"] if threshold is None \
        else threshold
    args = (passes, threshold, inter_pass_norm, post_norms, kv_dtype,
            jnp.dtype(dtype))
    with jax.default_matmul_precision("highest"):
        shared = None
        if kv_from_pass is not None:
            shared = {}
            _forward(weights, cfg, ids, [0], *args,
                     capture=(kv_from_pass, shared))
        out, pick = _forward(weights, cfg, ids, positions, *args,
                             shared=shared)
    return (out, pick) if return_exit else out


# the controls a check refuses, by name, as keyword arguments of logits()
CONTROLS = {
    "one_pass_fewer": dict(passes=-1),
    "last_pass_kv_shared": dict(kv_from_pass=-1),
    "no_inter_pass_norm": dict(inter_pass_norm=False),
    "no_post_norms": dict(post_norms=False),
    "fp8_kv": dict(kv_dtype="fp8"),
}


def control_kwargs(cfg: dict, name: str) -> dict:
    """The keyword arguments of control ``name`` for this configuration:
    ``one_pass_fewer`` runs one pass fewer than published, and the shared
    K/V is the last pass's."""
    kw = dict(CONTROLS[name])
    if kw.get("passes") == -1:
        kw["passes"] = cfg["total_ut_steps"] - 1
    if kw.get("kv_from_pass") == -1:
        kw["kv_from_pass"] = cfg["total_ut_steps"] - 1
    return kw
