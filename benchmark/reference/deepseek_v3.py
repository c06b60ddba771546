"""Plain reference of a DeepSeek-V3 decoder (``deepseek_v3``): multi-head
latent attention in its EXPANDED form over the whole sequence, sigmoid
scores with group-limited top-k routing and the selection bias, routed
experts and a shared expert (each a SwiGLU), YaRN rotary frequencies, an
untied head.  Straightforward float32 ``jax.numpy`` at the highest matmul
precision: no kernels, no cache, no absorption (per-head ``k_nope`` and
``v`` are made from ``c_kv`` at every position), no sorting or grouped
matmul (a plain loop over the held experts with a mask).

It follows the published ``config.json`` of deepseek-ai/DeepSeek-V3, the
DeepSeek-V3 Technical Report (arXiv:2412.19437, sections 2.1.1 and 2.1.2)
and DeepSeek-V2 (arXiv:2405.04434) for MLA.  Departures from the published
code:

- weights are random, from the run's seed (the configuration's ``assumed``);
- the rotary pairs are the two halves of the 64 rope lanes, ``(i, i + 32)``;
  the published weights pair neighbours and the published code
  de-interleaves them before rotating, a fixed permutation of columns that
  random weights do not see;
- ``e_score_correction_bias`` is drawn from the seed and not zero, so that
  which of ``s`` and ``s + b`` selects and which weighs shows in the result;
- out of the kept groups an expert is never chosen (the published inference
  code masks with ``-inf``; the ``transformers`` port masks with 0.0, which
  differs only where fewer than ``top_k`` candidates are positive);
- the multi-token-prediction block is left out (the report: the main model
  runs without it).

One chip's share: ``cfg["experts_held"] = [first, count]`` names the routed
experts whose weights ``weights`` holds.  The router scores all
``router_experts`` of them; the held experts' weighted outputs and the shared
expert's are summed and what the other experts would add is left out, here
as in the program.  ``vocab_size`` is the slice of the vocabulary held.

``weights`` is ``{"embed": [V, H], "layer": i -> dict, "norm": [H], "head":
[H, V]}``; a layer's dict holds ``ln_attn ln_mlp w_dq ln_q w_uq w_dkv ln_kv
w_ukv wo`` ([in, out]) and either ``w_gate w_up w_down`` (dense) or
``w_router [H, E] router_bias [E] e_gate e_up [G, H, W] e_down [G, W, H]
s_gate s_up s_down``.  ``layer`` is a function so that one layer's weights
are fetched at a time and cast to float32 where they are used: the reference
then fits beside the model under test."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def inv_freq(cfg) -> np.ndarray:
    """YaRN (arXiv:2309.00071) over the rope lanes: a frequency that turns
    more than ``beta_fast`` times over the original context is kept, one
    that turns fewer than ``beta_slow`` times is divided by ``factor``, and
    between the two a linear ramp over the frequency's index."""
    d, theta, sc = cfg["qk_rope_head_dim"], cfg["rope_theta"], \
        cfg.get("rope_scaling")
    base = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not sc:
        return base.astype(np.float32)
    orig = sc["original_max_position_embeddings"]

    def index_of(turns):    # the index whose frequency turns so often
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(index_of(sc["beta_fast"])), 0)
    hi = min(math.ceil(index_of(sc["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (base * (1 - ramp) + base / sc["factor"] * ramp).astype(
        np.float32)


def softmax_scale(cfg) -> float:
    sc = cfg.get("rope_scaling")
    m = _mscale(sc["factor"], sc["mscale_all_dim"]) if sc else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, cfg):
    """x [s, heads, rope] at positions 0..s-1, rotate-half convention."""
    s, _, d = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv_freq(cfg))
    sc = cfg.get("rope_scaling")
    m = _mscale(sc["factor"], sc["mscale"]) \
        / _mscale(sc["factor"], sc["mscale_all_dim"]) if sc else 1.0
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * m
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * m
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def mla(y, w, cfg):
    """Expanded multi-head latent attention of ``y`` [s, H], causal."""
    s = y.shape[0]
    h, nope, rope, vd, rank = (
        cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps = cfg["rms_norm_eps"]
    q = (_rms_norm(y @ w["w_dq"], w["ln_q"], eps) @ w["w_uq"]) \
        .reshape(s, h, nope + rope)
    ckv = y @ w["w_dkv"]
    c_kv = _rms_norm(ckv[:, :rank], w["ln_kv"], eps)
    k_rope = _rope(ckv[:, None, rank:], cfg)                  # [s, 1, rope]
    kv = (c_kv @ w["w_ukv"]).reshape(s, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cfg)], -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (s, h, rope))], -1)
    v = kv[..., nope:]
    cols = jnp.arange(s)[None, None, :]
    out = []
    for lo in range(0, s, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * softmax_scale(cfg)
        rows = (lo + jnp.arange(qb.shape[0]))[None, :, None]
        sc = jnp.where(cols <= rows, sc, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    return jnp.concatenate(out, 0).reshape(s, h * vd) @ w["wo"]


def _select(choice, cfg):
    """The experts [s, k] that selection scores ``choice`` [s, E] choose:
    the groups with the largest sums of their two best, then the best
    inside them."""
    n, e, g = choice.shape[0], choice.shape[1], cfg["n_group"]
    top2 = jnp.sort(choice.reshape(-1, g, e // g), axis=-1)[..., -2:]
    group_rank = jnp.argsort(-top2.sum(-1), axis=-1, stable=True)
    kept = jnp.zeros((n, g), bool).at[
        jnp.arange(n)[:, None], group_rank[:, :cfg["topk_group"]]].set(True)
    choice = jnp.where(jnp.repeat(kept, e // g, axis=1), choice, -jnp.inf)
    return jnp.argsort(-choice, axis=-1, stable=True)[
        :, :cfg["num_experts_per_tok"]]


def _weights(s_, idx, cfg):
    wt = jnp.take_along_axis(s_, idx, axis=1)
    if cfg["norm_topk_prob"]:
        wt = wt / wt.sum(-1, keepdims=True)
    return wt * cfg["routed_scaling_factor"]


def route(y, w_router, bias, cfg):
    """The experts every token chooses [s, k] and their weights [s, k]."""
    s_ = jax.nn.sigmoid(y @ w_router)                         # [s, E]
    idx = _select(s_ + bias[None, :], cfg)
    return idx, _weights(s_, idx, cfg)


def tie_width(choice, idx, cfg, halvings: int = 16):
    """How near a tie the experts ``idx`` [s, k] are to what selection
    scores ``choice`` [s, E] choose: the least ``eps`` [s] at which raising
    the scores of ``idx`` and lowering all others by ``eps`` makes ``idx``
    the selection, found by bisection (0 where it is the selection
    already).  Scores that differ from ``choice`` by at most ``d`` choose
    experts no wider than a small multiple of ``d`` (inside a kept group
    the move is the most favourable one; a group's sum of its two best may
    lose by it, so it is an upper bound of the least move of all)."""
    n, e = choice.shape
    mine = jnp.zeros((n, e), bool).at[jnp.arange(n)[:, None], idx].set(True)
    want = jnp.sort(idx, axis=-1)

    def chosen(eps):        # [s] bool
        got = _select(choice + eps[:, None] * jnp.where(mine, 1.0, -1.0), cfg)
        return jnp.all(jnp.sort(got, axis=-1) == want, axis=-1)

    def halve(_, span):     # one trace of the selection for all halvings
        lo, hi = span
        mid = (lo + hi) / 2
        ok = chosen(mid)
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    zero = jnp.zeros((n,), F32)
    _, hi = jax.lax.fori_loop(0, halvings, halve,
                              (zero, jnp.full((n,), 2.0, F32)))
    return jnp.where(chosen(zero), 0.0, hi)


def _swiglu(y, gate, up, down):
    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def expert_layer(y, w, cfg, first: int, forced=None):
    """Shared expert plus the held experts' weighted part.  ``forced`` [s, k]
    int32: the experts a token takes where its row is not negative (the
    weights are still this layer's own ``s`` over them), for a reference
    that follows the choices of the program under test.  Returns the
    layer's output, the experts this layer chooses by itself, and how near
    a tie the forced ones are to them (:func:`tie_width`; 0 where none
    are)."""
    s_ = jax.nn.sigmoid(y @ w["w_router"])
    choice = s_ + w["router_bias"][None, :]
    own = idx = _select(choice, cfg)
    width = jnp.zeros((y.shape[0],), F32)
    if forced is not None:
        told = forced[:, 0] >= 0
        idx = jnp.where(told[:, None], forced, own)
        width = jnp.where(told, tie_width(choice, idx, cfg), 0.0)
    wt = _weights(s_, idx, cfg)
    out = _swiglu(y, w["s_gate"], w["s_up"], w["s_down"])
    for j in range(w["e_gate"].shape[0]):
        mine = jnp.sum(jnp.where(idx == first + j, wt, 0.0), axis=1)
        out = out + mine[:, None] * _swiglu(y, w["e_gate"][j], w["e_up"][j],
                                            w["e_down"][j])
    return out, own, width


def _layer(x, w, cfg, first, forced=None):
    eps = cfg["rms_norm_eps"]
    x = x + mla(_rms_norm(x, w["ln_attn"], eps), w, cfg)
    y = _rms_norm(x, w["ln_mlp"], eps)
    if "w_router" in w:
        out, own, width = expert_layer(y, w, cfg, first, forced)
        return x + out, own, width
    return x + _swiglu(y, w["w_gate"], w["w_up"], w["w_down"]), None, None


def logits(weights: dict, cfg: dict, ids, positions=None, choices=None,
           forced=None, tie_widths=None):
    """ids [s] -> float32 logits [len(positions) or s, V].  ``choices``, a
    list, receives each expert layer's own chosen experts [s, k].
    ``forced`` [expert layers, s, k] int32: the experts the tokens take
    instead, where a row is not negative (:func:`expert_layer`);
    ``tie_widths``, a list, then receives each expert layer's [s]."""
    first = (cfg.get("experts_held") or [0])[0]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(jnp.asarray(weights["embed"], F32),
                     jnp.asarray(ids), axis=0)
        # a layer's weights are cast inside its program, so that the
        # float32 copy of one expert at a time is alive, not of all 16
        step = jax.jit(lambda x, w, forced: _layer(
            x, {k: a.astype(F32) for k, a in w.items()}, cfg, first, forced))
        expert_layers = 0
        for i in range(cfg["num_hidden_layers"]):
            w = {k: jnp.asarray(a) for k, a in weights["layer"](i).items()}
            told = None
            if forced is not None and "w_router" in w:
                told = jnp.asarray(forced[expert_layers], jnp.int32)
            x, own, width = step(x, w, told)
            if own is not None:
                expert_layers += 1
                if choices is not None:
                    choices.append(np.asarray(own))
                if tie_widths is not None:
                    tie_widths.append(np.asarray(width))
        if positions is not None:
            x = x[jnp.asarray(positions)]
        x = _rms_norm(x, jnp.asarray(weights["norm"], F32),
                      cfg["rms_norm_eps"])
        return x @ jnp.asarray(weights["head"], F32)
