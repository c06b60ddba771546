"""Speculative decoding: draft k tokens cheaply, verify them with ONE
target-model forward, emit every token the target agrees with.

A decode step is bandwidth-bound — it reads every weight byte to emit one
token.  Speculation amortizes that
weight read: a cheap drafter proposes ``k`` tokens, the target model runs
ONCE over ``[t_last, d_1..d_k]`` (positions ``p..p+k``), and the longest
prefix of drafts matching the target's own greedy argmax is accepted plus
one bonus/correction token — between 1 and ``k+1`` tokens per weight read.

**Token-exact by construction** (greedy): the verify logits at slot ``i``
condition on exactly ``prefix + d_1..d_i``; a draft is only consumed when
it EQUALS the target's argmax at the previous slot, so every emitted token
is the same argmax the serial decode would have produced.  Drafter quality
changes the speed, never the tokens.  ``do_sample=True`` switches the
acceptance test to rejection sampling (accept ``d`` w.p. ``min(1,
p(d)/q(d))``, else sample the residual ``max(p-q, 0)``), which preserves
the target distribution exactly — distribution-exact, not bit-exact
(different RNG stream than ``generate``).

Stale-KV safety: a rejected draft's k/v stays in the cache at positions
``> p+m`` (m = tokens emitted), but every future query at position ``x``
attends only cols ``<= x``, and the cache slot at ``x`` is rewritten by
the step that queries it — stale slots are always overwritten before they
become attendable.  The same argument makes the paged serving composition
(:class:`~paddle_tpu.serving.ServingEngine` with ``speculative=``) safe
across eviction replay.

Drafters (all host-side state; proposals can be wrong, never harmful):

- :class:`NGramDrafter` — suffix-match over the request's own context
  (prompt + generated); free, surprisingly strong on looping/repetitive
  continuations.  The default.
- :class:`ShallowExitDrafter` — self-drafting: the target model's FIRST
  ``draft_layers`` layers + final norm + lm_head as the proposal model
  (no second model to deploy; one compiled single-token program).
- :class:`DraftModelDrafter` — a separate (smaller) causal LM drafts with
  its own compiled incremental decode; supplies real proposal
  distributions for rejection sampling.

``speculative_generate`` is the standalone loop (contiguous static cache,
one compiled verify program per ``(k, capacity)`` signature, caches
donated).  Batched rows run sequentially per row — per-row positions
diverge as acceptance differs, and the batched composition with per-row
position vectors is exactly what the serving engine's paged decode
provides.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np

__all__ = ["SpecConfig", "NGramDrafter", "ShallowExitDrafter",
           "DraftModelDrafter", "speculative_generate",
           "rejection_sample_step", "AdaptiveK"]


# --------------------------------------------------------------------------
# config / adaptation
# --------------------------------------------------------------------------
@dataclass
class SpecConfig:
    """Speculation knobs shared by the standalone loop and the serving
    engine.  ``k`` is the MAX draft length; with ``adaptive=True`` the
    EMA of the measured acceptance rate shrinks the per-step draft length
    (the verify program keeps its compiled ``k+1`` width — only the
    dynamic valid-token count changes, nothing recompiles).  ``drafter``
    is ``"ngram"`` or a zero-arg factory returning a fresh drafter."""

    k: int = 4
    adaptive: bool = True
    drafter: Union[str, Callable[[], object]] = "ngram"
    ngram_max: int = 4
    ema_decay: float = 0.7

    def make_drafter(self):
        if callable(self.drafter):
            return self.drafter()
        if self.drafter == "ngram":
            return NGramDrafter(max_ngram=self.ngram_max)
        raise ValueError(f"unknown drafter {self.drafter!r}")


class AdaptiveK:
    """EMA acceptance-rate → draft-length controller.  Optimistic start
    (full k); a cold streak decays toward 1-token drafts, recovery grows
    back — all host-side, the compiled verify width never changes."""

    def __init__(self, k_max: int, adaptive: bool = True,
                 decay: float = 0.7):
        self.k_max = max(int(k_max), 1)
        self.adaptive = bool(adaptive)
        self.decay = float(decay)
        self.ema = 1.0

    def k(self) -> int:
        if not self.adaptive:
            return self.k_max
        return max(1, min(self.k_max, int(round(self.ema * self.k_max))))

    def update(self, accepted: int, proposed: int) -> None:
        if proposed <= 0:
            return
        rate = accepted / proposed
        self.ema = self.decay * self.ema + (1.0 - self.decay) * rate


# --------------------------------------------------------------------------
# drafters
# --------------------------------------------------------------------------
class NGramDrafter:
    """Propose the continuation that followed the most recent earlier
    occurrence of the context's longest matching suffix (up to
    ``max_ngram`` tokens).  Pure host-side list matching — zero model
    cost, and greedy decodes of looping continuations accept at ~1.0."""

    def __init__(self, max_ngram: int = 4):
        self.max_ngram = max(int(max_ngram), 1)
        self._ctx: List[int] = []
        self.probs: Optional[List[Optional[np.ndarray]]] = None

    def begin(self, context: Sequence[int]) -> None:
        self._ctx = [int(t) for t in context]

    def observe(self, tokens: Sequence[int]) -> None:
        self._ctx.extend(int(t) for t in tokens)

    def propose(self, k: int, temperature: float = 0.0,
                rng=None) -> List[int]:
        self.probs = None
        ctx, n = self._ctx, len(self._ctx)
        if k <= 0 or n < 2:
            return []
        for L in range(min(self.max_ngram, n - 1), 0, -1):
            suffix = ctx[n - L:]
            for start in range(n - L - 1, -1, -1):
                if ctx[start:start + L] == suffix:
                    cont = ctx[start + L:start + L + k]
                    if cont:
                        return list(cont)
        return []


class _ModelDrafterBase:
    """Shared machinery for model-backed drafters: a single-row compiled
    incremental decode (``_step(tok, pos) → logits``) over a donated
    contiguous cache.  ``propose`` rolls draft steps through the SAME
    cache; the stale draft k/v it leaves behind is overwritten by the
    next ``observe``/``propose`` writes before any query can attend it
    (col ``<= pos`` masking) — the standard speculative-cache argument."""

    def __init__(self):
        self._caches = None
        self._pos = 0
        self._last: Optional[np.ndarray] = None
        self.probs: Optional[List[Optional[np.ndarray]]] = None

    # subclasses: self._capacity, _fresh_caches(), _step(tok, pos)
    def begin(self, context: Sequence[int]) -> None:
        self._caches = self._fresh_caches()
        self._pos = 0
        self._last = None
        self.observe(context)

    def observe(self, tokens: Sequence[int]) -> None:
        for t in tokens:
            if self._pos >= self._capacity:
                self._last = None
                return
            self._last = self._step(int(t), self._pos)
            self._pos += 1

    def propose(self, k: int, temperature: float = 0.0,
                rng=None) -> List[int]:
        self.probs = None
        if k <= 0 or self._last is None:
            return []
        toks: List[int] = []
        probs: List[Optional[np.ndarray]] = []
        logits, pos = self._last, self._pos
        for i in range(k):
            lg = np.asarray(logits, np.float32)
            if temperature > 0.0 and rng is not None:
                p = _softmax_np(lg / max(temperature, 1e-6))
                t = int(rng.choice(p.shape[-1], p=p))
                probs.append(p)
            else:
                t = int(np.argmax(lg))
                probs.append(None)
            toks.append(t)
            if i < k - 1:
                if pos >= self._capacity:
                    break
                logits = self._step(t, pos)     # scratch write; see class doc
                pos += 1
        self.probs = probs
        return toks


class DraftModelDrafter(_ModelDrafterBase):
    """External draft model: any causal LM with the ``kv_cache`` /
    ``position_offset`` forward contract.  One compiled single-token
    program per cache capacity (cached on the draft model), caches
    donated so the incremental decode never copies them."""

    def __init__(self, draft_model, capacity: int):
        super().__init__()
        self.model = draft_model
        self._capacity = -(-int(capacity) // 8) * 8   # sublane-aligned

    def _fresh_caches(self):
        import jax.numpy as jnp

        n_layers, kv_heads, head_dim = self.model._kv_cache_spec()
        cdt = next((p._value.dtype for _, p in self.model.named_parameters()
                    if jnp.issubdtype(p._value.dtype, jnp.floating)),
                   jnp.float32)
        return [(jnp.zeros((1, self._capacity, kv_heads, head_dim), cdt),
                 jnp.zeros((1, self._capacity, kv_heads, head_dim), cdt))
                for _ in range(n_layers)]

    def _step(self, tok: int, pos: int) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        from ..autograd import no_grad
        from ..jit import _StateSwap
        from ..tensor.tensor import Tensor

        model = self.model
        params = [p for _, p in model.named_parameters()]
        buffers = [b for _, b in model.named_buffers()]

        def build():
            def fn(pa, ba, caches, tok, pos):
                with _StateSwap(params, pa), _StateSwap(buffers, ba), \
                        no_grad():
                    logits, caches = model(Tensor(tok[None, None]),
                                           kv_cache=caches,
                                           position_offset=pos)
                    return logits._value[0, -1], caches
            return jax.jit(fn, donate_argnums=(2,))

        prog = model._cached_program(("spec_draft_step", self._capacity),
                                     build)
        logits, self._caches = prog(
            [p._value for p in params], [b._value for b in buffers],
            self._caches, jnp.asarray(tok, jnp.int32),
            jnp.asarray(pos, jnp.int32))
        return np.asarray(logits)


class ShallowExitDrafter(_ModelDrafterBase):
    """Self-drafting via early exit: the TARGET model's first
    ``draft_layers`` transformer layers + final norm + lm_head propose;
    no second model.  Llama-family structure required (same contract as
    the serving engine).  The shallow stack shares the target's weights,
    so its compiled program caches on the target model itself."""

    def __init__(self, model, capacity: int, draft_layers: int = 1):
        super().__init__()
        base = getattr(model, "llama", None)
        if base is None or not hasattr(base, "layers"):
            raise TypeError("ShallowExitDrafter needs a llama-family model "
                            "(model.llama.layers); got "
                            + type(model).__name__)
        self.model = model
        self.draft_layers = max(1, min(int(draft_layers), len(base.layers)))
        self._capacity = -(-int(capacity) // 8) * 8

    def _fresh_caches(self):
        import jax.numpy as jnp

        _, kv_heads, head_dim = self.model._kv_cache_spec()
        cdt = next((p._value.dtype for _, p in self.model.named_parameters()
                    if jnp.issubdtype(p._value.dtype, jnp.floating)),
                   jnp.float32)
        return [(jnp.zeros((1, self._capacity, kv_heads, head_dim), cdt),
                 jnp.zeros((1, self._capacity, kv_heads, head_dim), cdt))
                for _ in range(self.draft_layers)]

    def _step(self, tok: int, pos: int) -> np.ndarray:
        import jax
        import jax.numpy as jnp

        from ..autograd import no_grad
        from ..jit import _StateSwap
        from ..models.llama import rotate_half_apply
        from ..nn import functional as F
        from ..tensor.manipulation import reshape
        from ..tensor.tensor import Tensor
        from . import cached_attention

        model = self.model
        n = self.draft_layers
        params = [p for _, p in model.named_parameters()]
        buffers = [b for _, b in model.named_buffers()]

        def build():
            def fn(pa, ba, caches, tok, pos):
                with _StateSwap(params, pa), _StateSwap(buffers, ba), \
                        no_grad():
                    base = model.llama
                    cfg = model.config
                    h, kvh, d = (cfg.num_attention_heads,
                                 cfg.num_key_value_heads, cfg.head_dim)
                    cos = base.rope_cos._value
                    sin = base.rope_sin._value
                    pid = jnp.clip(pos, 0, cos.shape[0] - 1)
                    cos_s = jax.lax.dynamic_slice_in_dim(
                        cos, pid, 1)[None, :, None, :]
                    sin_s = jax.lax.dynamic_slice_in_dim(
                        sin, pid, 1)[None, :, None, :]
                    x = base.embed_tokens(Tensor(tok[None, None]))
                    new_caches = []
                    for li, layer in enumerate(base.layers[:n]):
                        xin = layer.input_layernorm(x)
                        q = reshape(layer.self_attn.q_proj(xin),
                                    [1, 1, h, d])
                        k = reshape(layer.self_attn.k_proj(xin),
                                    [1, 1, kvh, d])
                        v = reshape(layer.self_attn.v_proj(xin),
                                    [1, 1, kvh, d])
                        qv, kv_ = rotate_half_apply(q._value, k._value,
                                                    cos_s, sin_s)
                        out_v, ck, cv = cached_attention(
                            qv, kv_, v._value, caches[li][0],
                            caches[li][1], pos)
                        new_caches.append((ck, cv))
                        x = x + layer.self_attn.o_proj(
                            Tensor(out_v.reshape(1, 1, h * d)))
                        x = x + layer.mlp(layer.post_attention_layernorm(x))
                    hidden = base.norm(x)
                    if model.lm_head is not None:
                        logits = model.lm_head(hidden)
                    else:
                        logits = F.linear(hidden,
                                          base.embed_tokens.weight.T)
                    return logits._value[0, -1], new_caches
            return jax.jit(fn, donate_argnums=(2,))

        prog = model._cached_program(
            ("spec_shallow_step", n, self._capacity), build)
        logits, self._caches = prog(
            [p._value for p in params], [b._value for b in buffers],
            self._caches, jnp.asarray(tok, jnp.int32),
            jnp.asarray(pos, jnp.int32))
        return np.asarray(logits)


# --------------------------------------------------------------------------
# rejection sampling (temperature > 0)
# --------------------------------------------------------------------------
def _softmax_np(logits: np.ndarray) -> np.ndarray:
    z = np.asarray(logits, np.float64)
    z = z - np.max(z)
    e = np.exp(z)
    return e / e.sum()


def rejection_sample_step(p: np.ndarray, q: Optional[np.ndarray],
                          draft_token: int, rng) -> Tuple[bool, int]:
    """One speculative-sampling acceptance test.  ``p`` is the target
    distribution at this slot, ``q`` the drafter's proposal distribution
    (``None`` = deterministic drafter = one-hot at ``draft_token``).
    Returns ``(accepted, token)``; the emitted token is distributed
    EXACTLY as ``p`` regardless of ``q`` (Leviathan et al. correctness:
    accept w.p. min(1, p/q), else sample the normalized residual
    ``max(p-q, 0)``)."""
    d = int(draft_token)
    p = np.asarray(p, np.float64)
    if q is None:
        qd = 1.0
        accept_p = min(1.0, float(p[d]) / qd)
        if rng.random() < accept_p:
            return True, d
        resid = p.copy()
        resid[d] = max(p[d] - 1.0, 0.0)
    else:
        q = np.asarray(q, np.float64)
        qd = max(float(q[d]), 1e-20)
        if rng.random() < min(1.0, float(p[d]) / qd):
            return True, d
        resid = np.maximum(p - q, 0.0)
    tot = resid.sum()
    if tot <= 0.0:                      # q covers p exactly: sample p
        resid, tot = p, p.sum()
    resid = resid / tot
    return False, int(rng.choice(resid.shape[0], p=resid))


# --------------------------------------------------------------------------
# standalone loop
# --------------------------------------------------------------------------
def speculative_generate(model, input_ids, max_new_tokens: int = 64, *,
                         drafter: Union[str, object, Callable] = "ngram",
                         k: int = 4, adaptive: bool = True,
                         eos_token_id: Optional[int] = None,
                         pad_token_id: Optional[int] = None,
                         do_sample: bool = False, temperature: float = 1.0,
                         seed: int = 0):
    """Speculative decoding over a contiguous static cache.  Greedy
    (``do_sample=False``) output is token-exact vs ``model.generate``;
    sampling is distribution-exact via rejection sampling.

    Returns ``(ids, stats)``: ``ids`` a Tensor ``[batch, max_new_tokens]``
    (eos-latched rows padded with ``pad_token_id``, default eos), and
    ``stats`` with ``proposed`` / ``accepted`` / ``acceptance_rate`` /
    ``verify_steps`` / ``effective_tokens_per_step``."""
    import jax
    import jax.numpy as jnp

    from ..autograd import no_grad
    from ..jit import _StateSwap
    from ..tensor.tensor import Tensor

    ids = input_ids._value if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    if ids.ndim != 2:
        raise ValueError(f"input_ids must be [batch, seq], got {ids.shape}")
    b, prompt = int(ids.shape[0]), int(ids.shape[1])
    max_new = int(max_new_tokens)
    if max_new < 1:
        raise ValueError("max_new_tokens must be >= 1")
    k = max(1, int(k))
    # the verify program writes its FULL k+1 window every call (padded
    # slots included — dynamic_update_slice would CLAMP an overhanging
    # start index and corrupt earlier cache slots), so the cache must
    # always hold pos + k + 1 slots and every queried position must stay
    # inside the rope table
    max_pos = model.config.max_position_embeddings
    spare = max_pos - (prompt + max_new)
    if spare < 1:
        raise ValueError(
            f"speculative decoding needs prompt + max_new_tokens + 1 <= "
            f"max_position_embeddings ({max_pos}) for the draft overhang; "
            f"got {prompt} + {max_new}")
    k = min(k, spare)
    total = -(-(prompt + max_new + k) // 8) * 8   # rounded slots past
    # max_pos are never written: pos + k <= prompt + max_new + k - 2 + 1
    eos = None if eos_token_id is None else int(eos_token_id)
    pad = eos if pad_token_id is None else int(pad_token_id)
    if pad is None:
        pad = 0
    params = [p for _, p in model.named_parameters()]
    buffers = [bf for _, bf in model.named_buffers()]
    pa = [p._value for p in params]
    ba = [bf._value for bf in buffers]
    S = k + 1

    def build_prefill():
        n_layers, kv_heads, head_dim = model._kv_cache_spec()

        def fn(pa, ba, row_ids):
            with _StateSwap(params, pa), _StateSwap(buffers, ba), \
                    no_grad():
                cdt = next((a.dtype for a in pa
                            if jnp.issubdtype(a.dtype, jnp.floating)),
                           jnp.float32)
                caches = [(jnp.zeros((1, total, kv_heads, head_dim), cdt),
                           jnp.zeros((1, total, kv_heads, head_dim), cdt))
                          for _ in range(n_layers)]
                logits, caches = model(Tensor(row_ids), kv_cache=caches,
                                       position_offset=0)
                return logits._value[0, -1], caches
        return jax.jit(fn)

    def build_verify():
        def fn(pa, ba, caches, tokens, pos):
            with _StateSwap(params, pa), _StateSwap(buffers, ba), \
                    no_grad():
                logits, caches = model(Tensor(tokens), kv_cache=caches,
                                       position_offset=pos)
                return logits._value[0], caches
        return jax.jit(fn, donate_argnums=(2,))

    prefill = model._cached_program(("spec_prefill", prompt, total),
                                    build_prefill)
    verify = model._cached_program(("spec_verify", S, total), build_verify)

    def _make_drafter():
        if callable(drafter) and not hasattr(drafter, "propose"):
            return drafter()
        if isinstance(drafter, str):
            return SpecConfig(drafter=drafter).make_drafter()
        return drafter                  # single instance, re-begun per row

    rng = np.random.default_rng(seed)
    out = np.full((b, max_new), pad, np.int32)
    stats = {"proposed": 0, "accepted": 0, "verify_steps": 0, "tokens": 0,
             "rows": []}
    temp = float(temperature) if do_sample else 0.0

    for row in range(b):
        dr = _make_drafter()
        ctrl = AdaptiveK(k, adaptive)
        row_prompt = [int(t) for t in np.asarray(ids[row])]
        dr.begin(row_prompt)
        last_logits, caches = prefill(pa, ba, ids[row][None])
        lg0 = np.asarray(last_logits, np.float32)
        if do_sample:
            p0 = _softmax_np(lg0 / max(temp, 1e-6))
            t0 = int(rng.choice(p0.shape[0], p=p0))
        else:
            t0 = int(np.argmax(lg0))
        generated = [t0]
        dr.observe([t0])
        r_prop = r_acc = r_steps = 0
        while len(generated) < max_new and not (eos is not None
                                                and generated[-1] == eos):
            pos = prompt + len(generated) - 1
            k_r = max(min(ctrl.k(), max_new - len(generated) - 1), 0)
            drafts = list(dr.propose(k_r, temperature=temp, rng=rng))[:k_r]
            q_probs = list(getattr(dr, "probs", None) or [])
            tokens = np.zeros((1, S), np.int32)
            tokens[0, 0] = generated[-1]
            tokens[0, 1:1 + len(drafts)] = drafts
            logits, caches = verify(pa, ba, caches, jnp.asarray(tokens),
                                    jnp.asarray(pos, jnp.int32))
            logits = np.asarray(logits, np.float32)    # [S, V]
            n_valid = 1 + len(drafts)
            emitted: List[int] = []
            for i in range(n_valid):
                if do_sample:
                    p = _softmax_np(logits[i] / max(temp, 1e-6))
                    if i < len(drafts):
                        q = q_probs[i] if i < len(q_probs) else None
                        ok, tok = rejection_sample_step(p, q, drafts[i],
                                                        rng)
                    else:
                        ok, tok = False, int(rng.choice(p.shape[0], p=p))
                else:
                    tok = int(np.argmax(logits[i]))
                    ok = i < len(drafts) and tok == drafts[i]
                emitted.append(tok)
                full = len(generated) + len(emitted) >= max_new
                if (eos is not None and tok == eos) or full or not ok:
                    break
            generated.extend(emitted)
            dr.observe(emitted)
            acc = max(len(emitted) - 1, 0)
            ctrl.update(acc, len(drafts))
            r_prop += len(drafts)
            r_acc += acc
            r_steps += 1
        out[row, :len(generated)] = generated[:max_new]
        stats["proposed"] += r_prop
        stats["accepted"] += r_acc
        stats["verify_steps"] += r_steps
        stats["tokens"] += len(generated)
        stats["rows"].append({
            "tokens": len(generated), "proposed": r_prop,
            "accepted": r_acc, "verify_steps": r_steps})
    stats["acceptance_rate"] = (stats["accepted"] / stats["proposed"]
                                if stats["proposed"] else None)
    total_steps = stats["verify_steps"] + b     # + per-row prefill token
    stats["effective_tokens_per_step"] = stats["tokens"] / max(total_steps,
                                                               1)
    return Tensor(jnp.asarray(out, jnp.int32)), stats
