"""Plain reference of a Nemotron-H decoder (``nemotron_h``): a stack of
blocks each of which is ONE part — a Mamba-2 mixer with grouped B / C and a
grouped gated norm, a grouped-query attention layer with no positional
encoding, or an expert layer of sigmoid-routed two-matrix ``relu ** 2``
experts plus a shared one — then ``norm_f`` and an untied head.
Straightforward float32 ``jax.numpy`` at the highest matmul precision: no
kernels, no cache, no batching, the state-space recurrence AS WRITTEN, one
token at a time by ``lax.scan`` (not the chunked algorithm the program under
test uses), and a plain loop over the held experts with a mask (no sorting,
no grouped matmul).

It follows the published ``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, the family's published modeling
code, and the Mamba-2 paper (Dao & Gu 2024, section 7).  Per block ``x = x +
part(RMSNorm(x; layer_norm_epsilon))``:

- ``M``: ``[z | xBC | dt] = W_in u`` with ``d_inner = mamba_num_heads *
  mamba_head_dim``; ``xBC = silu(causal depthwise conv + bias)``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t = exp(dt A) S_{t-1} +
  (dt x_t) (outer) B_{g(h),t}``, ``y_t = S_t C_{g(h),t} + D x_t`` with ``g(h)
  = h // (H / n_groups)``; ``w * RMSNorm(y * silu(z))`` over each group's
  ``d_inner / n_groups`` channels apart; ``W_out``.
- ``*``: causal ``softmax(q k^T / sqrt(head_dim)) v``, no rotary.
- ``E``: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias`` (``n_group`` 1: no group limit); weights
  ``routed_scaling_factor * s / sum_chosen s``; ``sum_held w_e W_down,e
  relu(W_up,e x) ** 2`` plus the shared expert of the same form.

Departures from the published code: weights are random, from the run's seed;
``e_score_correction_bias`` is drawn from the seed and not zero, so that
which of ``s`` and ``s + b`` selects and which weighs shows in the result
(both the configuration's ``assumed``).  ``expand``, ``rope_theta`` and
``partial_rotary_factor`` are read by nothing, as in the published code.

One chip's share: ``cfg["experts_held"] = [first, count]`` names the routed
experts whose weights ``weights`` holds; the router scores all
``router_experts`` (``weights``' router width) and what the other experts
would add is left out, here as in the program.  ``vocab_size`` is the slice
of the vocabulary held.

``logits_and_states`` also returns every Mamba-2 block's recurrent state
after a given number of tokens (a state kept in too few bits hides in the
logits), and like :mod:`benchmark.reference.deepseek_v3` it can follow the
expert choices of the program under test (``forced``) while reporting its
own (``choices``) and how near a tie the forced ones are (``tie_widths``).

``weights`` is ``{"embed": [V, H], "layer": i -> dict, "norm": [H], "head":
[H, V]}``; a block's dict holds ``ln`` and ``w_in conv_w conv_b A_log dt_bias
D norm_w w_out`` (``conv_w`` [conv_dim, K]: tap ``k`` multiplies the input
``K-1-k`` steps back), or ``wq wk wv wo`` ([in, out]), or ``w_router [H, E]
router_bias [E] e_up [G, H, W] e_down [G, W, H] s_up s_down``.  ``layer`` is
a function so that one block's weights are fetched at a time and cast to
float32 where they are used: the reference then fits beside the model under
test."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.deepseek_v3 import tie_width
from benchmark.reference.granite_hybrid import causal_attention

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2_mlp(y, up, down):
    return jnp.square(jax.nn.relu(y @ up)) @ down


def mamba(u, w, cfg, state_after):
    """u [T, hidden] -> [T, hidden] and the recurrent state [H, P, N] after
    ``state_after`` tokens: one Mamba-2 mixer from a zero state."""
    T = u.shape[0]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    di = H * P
    cd = di + 2 * G * N
    zx = u @ w["w_in"]
    z, xBC, dt = zx[:, :di], zx[:, di:di + cd], zx[:, di + cd:]
    xp = jnp.pad(xBC, ((K - 1, 0), (0, 0)))
    conv = sum(xp[k:k + T] * w["conv_w"][:, k] for k in range(K))
    xBC = jax.nn.silu(conv + w["conv_b"])
    x = xBC[:, :di].reshape(T, H, P)
    # head h reads group h // (H / G)
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
    # gate first, then the norm over each group's channels apart
    g = (jnp.concatenate([y, rest]).reshape(T, di) * jax.nn.silu(z)) \
        .reshape(T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return (g.reshape(T, di) * w["norm_w"]) @ w["w_out"], S


def attention(y, w, cfg):
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    s = y.shape[0]
    out = causal_attention((y @ w["wq"]).reshape(s, h, d),
                           (y @ w["wk"]).reshape(s, kv, d),
                           (y @ w["wv"]).reshape(s, kv, d), d ** -0.5)
    return out.reshape(s, h * d) @ w["wo"]


def _select(choice, cfg):
    """The experts [s, k] with the largest selection scores ``choice``
    [s, E]; a tie goes to the lower index."""
    return jnp.argsort(-choice, axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]


def expert_layer(y, w, cfg, first: int, forced=None):
    """Shared expert plus the held experts' weighted part.  ``forced`` [s, k]
    int32: the experts a token takes where its row is not negative (the
    weights are still this layer's own ``s`` over them).  Returns the
    layer's output, the experts this layer chooses by itself, and how near
    a tie the forced ones are to them (0 where none are)."""
    s_ = jax.nn.sigmoid(y @ w["w_router"])
    choice = s_ + w["router_bias"][None, :]
    own = idx = _select(choice, cfg)
    width = jnp.zeros((y.shape[0],), F32)
    if forced is not None:
        told = forced[:, 0] >= 0
        idx = jnp.where(told[:, None], forced, own)
        width = jnp.where(told, tie_width(choice, idx, cfg), 0.0)
    wt = jnp.take_along_axis(s_, idx, axis=1)
    if cfg["norm_topk_prob"]:
        wt = wt / wt.sum(-1, keepdims=True)
    wt = wt * cfg["routed_scaling_factor"]
    out = _relu2_mlp(y, w["s_up"], w["s_down"])
    for j in range(w["e_up"].shape[0]):
        mine = jnp.sum(jnp.where(idx == first + j, wt, 0.0), axis=1)
        out = out + mine[:, None] * _relu2_mlp(y, w["e_up"][j],
                                               w["e_down"][j])
    return out, own, width


def _block(x, w, cfg, kind, first, state_after, forced):
    """-> the block's output, a Mamba-2 block's state, an expert block's
    own choices and tie widths (None where the kind has none)."""
    y = _rms_norm(x, w["ln"], cfg["layer_norm_epsilon"])
    if kind == "M":
        out, state = mamba(y, w, cfg, state_after)
        return x + out, state, None, None
    if kind == "*":
        return x + attention(y, w, cfg), None, None, None
    out, own, width = expert_layer(y, w, cfg, first, forced)
    return x + out, None, own, width


def logits_and_states(weights: dict, cfg: dict, ids, positions=None,
                      state_after=None, choices=None, forced=None,
                      tie_widths=None):
    """ids [s] -> float32 logits [len(positions) or s, V] and every Mamba-2
    block's recurrent state after the first ``state_after`` tokens (None:
    all of them), float32 [Mamba-2 blocks, H, P, N].  ``choices``, a list,
    receives each expert block's own chosen experts [s, k]; ``forced``
    [expert blocks, s, k] int32: the experts the tokens take instead, where
    a row is not negative; ``tie_widths``, a list, then receives each
    expert block's [s]."""
    if cfg.get("n_group", 1) != 1:
        raise NotImplementedError("group-limited selection: n_group is 1 "
                                  "in every published nemotron_h config")
    state_after = len(ids) if state_after is None else int(state_after)
    first = (cfg.get("experts_held") or [0])[0]
    pattern = cfg["hybrid_override_pattern"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(jnp.asarray(weights["embed"], F32),
                     jnp.asarray(ids), axis=0)
        # a block's weights are cast inside its program, so that the
        # float32 copy of one expert at a time is alive, not of all held
        steps = {kind: jax.jit(lambda x, w, forced, kind=kind: _block(
            x, {k: a.astype(F32) for k, a in w.items()}, cfg, kind, first,
            state_after, forced)) for kind in set(pattern)}
        states, expert_blocks = [], 0
        for i, kind in enumerate(pattern):
            w = {k: jnp.asarray(a) for k, a in weights["layer"](i).items()}
            told = None
            if kind == "E" and forced is not None:
                told = jnp.asarray(forced[expert_blocks], jnp.int32)
            x, state, own, width = steps[kind](x, w, told)
            del w
            if state is not None:
                states.append(state)
            if own is not None:
                expert_blocks += 1
                if choices is not None:
                    choices.append(np.asarray(own))
                if tie_widths is not None:
                    tie_widths.append(np.asarray(width))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _rms_norm(x, jnp.asarray(weights["norm"], F32),
                      cfg["layer_norm_epsilon"])
        return x @ jnp.asarray(weights["head"], F32), jnp.stack(states)


def logits(weights: dict, cfg: dict, ids, positions=None, **routing):
    """ids [s] -> float32 logits [len(positions) or s, V]."""
    return logits_and_states(weights, cfg, ids, positions, **routing)[0]
