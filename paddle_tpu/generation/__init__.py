"""Autoregressive decoding: static-shape KV cache + ``GenerationMixin``.

Reference capability: the serving attention stack —
`/root/reference/paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu:1`
(single-token cached attention), `block_multi_head_attention_kernel.cu:1`
(paged cache), and the python surface
`/root/reference/python/paddle/incubate/nn/functional/fused_transformer.py:976`
(``fused_multi_transformer`` with ``cache_kvs``).  There decode is a ring of
fused CUDA kernels driven from python; the TPU-native translation compiles
the ENTIRE generation — prefill, every decode step, cache updates, sampling,
the eos latch — into ONE XLA program (``lax.scan`` over the decode steps),
so there is no per-token dispatch at all.

Design (TPU-first):
- the cache is a list of per-layer ``(k, v)`` arrays of STATIC shape
  ``[batch, prompt+max_new, kv_heads, head_dim]``; the write position is a
  traced scalar (``lax.dynamic_update_slice``), so shapes never change and
  there is exactly one compile per (batch, prompt_len, max_new, sampling
  config) signature.
- decode attends over the full static cache with an additive position mask
  (``col <= pos``) — the XLA fusion of (cache write + masked attention) is
  the analogue of the reference's masked_multihead_attention kernel.
- greedy / temperature / top-k / top-p sampling run inside the same
  program via ``jax.random``; finished rows are latched on eos and emit
  ``pad_token_id`` while the others continue (static shapes).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..autograd import no_grad
from ..tensor.tensor import Tensor

from .speculative import (AdaptiveK, DraftModelDrafter,  # noqa: F401
                          NGramDrafter, ShallowExitDrafter, SpecConfig,
                          rejection_sample_step, speculative_generate)

__all__ = ["GenerationMixin", "cached_attention",
           "SpecConfig", "AdaptiveK", "NGramDrafter", "DraftModelDrafter",
           "ShallowExitDrafter", "rejection_sample_step",
           "speculative_generate"]


def cached_attention(q, k_new, v_new, cache_k, cache_v, pos, pad_lens=None):
    """Write ``k_new``/``v_new`` into the static cache at ``pos`` and attend
    ``q`` over the cache prefix (absolute-position causal mask).

    q: [b, s, h, d]; k_new/v_new: [b, s, kv, d]; cache_k/v: [b, C, kv, d];
    ``pos``: traced or static int scalar — absolute position of q's first
    token.  ``pad_lens`` [b] (optional): per-row count of LEFT padding —
    those cache slots are masked out of attention forever.
    Returns (out [b, s, h, d], new_cache_k, new_cache_v).

    Match: masked_multihead_attention_kernel.cu:1 (the decode s=1 case) —
    one fused cache-update + attention, no [C, C] matrix, no dynamic shape.
    """
    from ..ops import pallas_mode

    b, s, h, d = q.shape
    kv = k_new.shape[2]
    C = cache_k.shape[1]
    if s == 1:
        # DECODE fast path: the fused Pallas kernel appends k/v via an
        # input_output-ALIASED single-block write, so the compiled scan
        # keeps the cache in place instead of copying all C slots every
        # step (not measured on the current tree; see PERF.md).
        mode = pallas_mode("use_decode_attention")
        if mode is not None:
            kind, _mesh, interp = mode
            from ..framework.flags import get_flags
            from ..ops.pallas import (decode_attention,
                                      decode_attention_supported)
            from ..ops.sharded import _auto_block
            from ..telemetry import kernel_fallback

            blk = _auto_block(
                C, int(get_flags("decode_block_k")["decode_block_k"]))
            if kind != "local":
                # multi-chip decode composes through the sharded einsum
                # path; the shard-local kernel wrapper is future work
                kernel_fallback("decode_attention", "mesh", cache_len=C)
            elif blk is not None and decode_attention_supported(
                    q.shape, cache_k.shape, block_k=blk,
                    dtype=cache_k.dtype):
                return decode_attention(q, k_new, v_new, cache_k, cache_v,
                                        pos, pad_lens, block_k=blk,
                                        interpret=interp)
            else:
                kernel_fallback("decode_attention", "shape",
                                q_shape=list(q.shape), cache_len=C)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), pos, 1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), pos, 1)
    if s > 1 and pad_lens is None and isinstance(pos, int) and pos == 0:
        # PREFILL fast path: the prefix being attended IS q's own window,
        # so this is plain causal self-attention — route it through the
        # flash kernel instead of materializing the [s, C] score matrix
        # (at an 8K prompt that matrix is the exact blow-up the reference
        # built masked_multihead/flash kernels to avoid).  The dense
        # masked path below stays for decode steps (s small, prefix
        # large).
        from ..nn.functional import scaled_dot_product_attention
        from ..tensor.tensor import Tensor as _T

        out = scaled_dot_product_attention(_T(q), _T(k_new), _T(v_new),
                                           is_causal=True, training=False)
        return out._value.astype(q.dtype), cache_k, cache_v
    if s > 1 and pad_lens is not None and isinstance(pos, int) and pos == 0:
        # LEFT-PADDED bucketed prefill: the varlen flash kernel carries the
        # per-row valid-length mask in its online-softmax loop, so ragged
        # serving prefill no longer falls back to the dense [s, C] einsum
        mode = pallas_mode("use_flash_attention")
        if mode is not None:
            kind, _mesh, interp = mode
            from ..framework.flags import get_flags
            from ..ops.pallas import (flash_attention_varlen,
                                      flash_attention_varlen_supported)
            from ..ops.sharded import _auto_block
            from ..telemetry import kernel_fallback

            bq = _auto_block(s, int(get_flags("flash_block_q")["flash_block_q"]))
            bk = _auto_block(s, int(get_flags("flash_block_k")["flash_block_k"]))
            if kind == "local" and bq is not None and bk is not None and \
                    flash_attention_varlen_supported(
                        q.shape, k_new.shape, block_q=bq, block_k=bk):
                out = flash_attention_varlen(q, k_new, v_new, pad_lens,
                                             causal=True, block_q=bq,
                                             block_k=bk, interpret=interp)
                return out.astype(q.dtype), cache_k, cache_v
            kernel_fallback("flash_attention_varlen",
                            "mesh" if kind != "local" else "shape",
                            q_shape=list(q.shape))
    # decode attention as a grouped-head einsum in the CACHE dtype with
    # fp32 ACCUMULATION (preferred_element_type), never casting the cache:
    # an .astype(f32) materializes a second full-cache copy — measured on
    # v5e at 8K context that halves the achieved bandwidth (0.51 → 0.98
    # of peak on the isolated einsum).  GQA likewise indexes the grouped
    # q against the raw [b, C, kv, d] cache instead of jnp.repeat-ing it
    # (a repeat would multiply cache traffic by h/kv).
    g = h // kv
    q5 = q.reshape(b, s, kv, g, d).astype(cache_k.dtype)
    scores = jnp.einsum("bskgd,bckd->bkgsc", q5, cache_k,
                        preferred_element_type=jnp.float32) \
        / jnp.sqrt(float(d))
    col = jnp.arange(C)[None, None, None, None, :]
    row = pos + jnp.arange(s)[None, None, None, :, None]
    allowed = col <= row
    if pad_lens is not None:
        allowed = allowed & (col >= pad_lens[:, None, None, None, None])
    scores = jnp.where(allowed, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgsc,bckd->bskgd", probs.astype(cache_v.dtype),
                     cache_v, preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype), cache_k, cache_v


def rope_with_row_offsets(q, k, cos, sin, pos, pad_lens):
    """Rotary embedding with PER-ROW positions for left-padded decode:
    row i's token at cache slot ``pos + j`` sits at logical position
    ``pos + j - pad_lens[i]`` (clipped at 0 for the pad slots themselves,
    whose k is masked out of attention anyway).  q/k: [b, s, h, d]; cos/sin:
    [max_pos, d] tables."""
    from ..models.llama import rotate_half_apply

    s = q.shape[1]
    pos_ids = pos + jnp.arange(s)[None, :] - pad_lens[:, None]  # [b, s]
    pos_ids = jnp.clip(pos_ids, 0, cos.shape[0] - 1)
    cos_s = jnp.take(cos, pos_ids, axis=0)[:, :, None, :]
    sin_s = jnp.take(sin, pos_ids, axis=0)[:, :, None, :]
    return rotate_half_apply(q, k, cos_s, sin_s)


class GenerationMixin:
    """``model.generate(input_ids, max_new_tokens=...)`` for causal-LM
    Layers whose forward accepts ``kv_cache``/``position_offset`` and then
    returns ``(logits, new_cache)`` (LlamaForCausalLM, GPTForCausalLM).

    Returns the paddle/PaddleNLP-shaped pair ``(ids, scores)``: generated
    token ids ``[batch, <=max_new_tokens]`` (prompt NOT included) and the
    per-token log-probability of each chosen token."""

    def _kv_cache_spec(self) -> Tuple[int, int, int]:
        """(num_layers, kv_heads, head_dim) — override per model family."""
        cfg = self.config
        kv = getattr(cfg, "num_key_value_heads", cfg.num_attention_heads)
        return cfg.num_hidden_layers, kv, cfg.head_dim

    @staticmethod
    def _kernel_flags_key():
        """Kernel dispatch state that changes what a generate program
        TRACES: it must be part of the compile-cache key, or flipping a
        flag after the first compile silently reuses the stale program
        (and a kernel-vs-einsum parity test compares a program to
        itself)."""
        from ..framework.flags import get_flags

        names = ("use_decode_attention", "decode_block_k",
                 "use_flash_attention", "flash_block_q", "flash_block_k",
                 "pallas_interpret")
        f = get_flags(list(names))
        return tuple(f[n] for n in names)

    def _cached_program(self, sig, build):
        """LRU-bounded compile cache (``generate_cache_size`` flag): every
        distinct signature compiles one program; a serving process must not
        retain them forever.  ``self._generate_compiles`` counts builds so
        serving tests can assert bucketing keeps the program count at the
        bucket count."""
        from collections import OrderedDict

        from ..framework.flags import get_flags

        cache = self.__dict__.setdefault("_generate_cache", OrderedDict())
        sig = sig + (self._kernel_flags_key(),)
        if sig in cache:
            cache.move_to_end(sig)
            return cache[sig]
        prog = build()
        self._generate_compiles = getattr(self, "_generate_compiles", 0) + 1
        cache[sig] = prog
        cap = max(1, int(get_flags("generate_cache_size")
                         ["generate_cache_size"]))
        while len(cache) > cap:
            cache.popitem(last=False)
        return prog

    # -- public API --------------------------------------------------------
    @no_grad()
    def generate(self, input_ids, max_new_tokens: int = 64,
                 do_sample: bool = False, top_k: int = 0, top_p: float = 1.0,
                 temperature: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 pad_token_id: Optional[int] = None, seed: int = 0,
                 min_new_tokens: int = 0, repetition_penalty: float = 1.0,
                 attention_mask=None, num_beams: int = 1,
                 length_penalty: float = 1.0, early_stopping: bool = False,
                 num_return_sequences: int = 1, bucket: Optional[str] = None):
        """Greedy (``do_sample=False``), sampled, or — with ``num_beams>1``
        — beam-search decoding with a static KV cache, fully jit-compiled
        (prefill + scan over decode steps).

        Beam search (reference `nn/decode.py:153,994` capability; HF/
        PaddleNLP knobs): ``num_beams`` beams per row, hypotheses scored
        ``cum_logprob / len**length_penalty``; ``early_stopping=True``
        stops a row once ``num_beams`` hypotheses exist, False keeps
        searching while a running beam could still win.  Returns the best
        ``num_return_sequences`` hypotheses per row as
        ``[batch*num_return_sequences, max_new_tokens]`` ids and their
        final scores (one per sequence — not per token as in sampling).
        ``do_sample=True`` is incompatible with ``num_beams>1``.

        ``bucket="pow2"`` left-pads the prompt to the next power-of-two
        length (≥16, capped by the position budget) so ragged serving
        prompts share compiled programs instead of compiling one per
        length (the reference absorbs ragged prompts in its paged
        block_multi_head_attention cache; here the static-cache program
        is reused via the left-pad machinery).  Mask semantics make the
        bucketed decode TOKEN-equivalent to the unbucketed one, but not
        bit-identical on accelerators: padding changes which prefill
        kernel the gate picks (a bucketed prompt can take the dense
        masked einsum where the unbucketed one takes flash) and with it
        the accumulation order, so logits agree only to numerical
        tolerance — argmax ties at float precision can in principle
        resolve differently.  Exactness tests compare greedy TOKENS on
        CPU (where both paths share one kernel) and logits to tolerance
        elsewhere.

        ``input_ids``: int Tensor/array [batch, prompt_len].  Batched
        ragged prompts use LEFT padding + ``attention_mask`` ([batch,
        prompt_len], 1 = real token): pad slots are excluded from
        attention forever and positions are shifted per row, so every
        row decodes as if unpadded.  Rows that emit ``eos_token_id`` are
        latched and emit ``pad_token_id`` (default: eos) afterwards.
        ``min_new_tokens`` suppresses eos until that many tokens emitted;
        ``repetition_penalty`` > 1 down-weights tokens already generated
        or in the prompt (CTRL-style: positive logits divided, negative
        multiplied — PaddleNLP generation parity)."""
        import numpy as np

        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        if ids.ndim != 2:
            raise ValueError(f"input_ids must be [batch, seq], got {ids.shape}")
        if bucket is not None:
            if bucket != "pow2":
                raise ValueError(f"bucket={bucket!r}: only 'pow2' supported")
            cur = int(ids.shape[1])
            cap = self.config.max_position_embeddings - int(max_new_tokens)
            tgt = max(16, 1 << (cur - 1).bit_length())
            tgt = max(min(tgt, cap), cur)
            if tgt > cur:
                extra = tgt - cur
                nb = int(ids.shape[0])
                filler = jnp.zeros((nb, extra), ids.dtype)  # masked out below
                ids = jnp.concatenate([filler, ids], axis=1)
                m = (np.ones((nb, cur), np.int32) if attention_mask is None
                     else np.asarray(
                         attention_mask.numpy()
                         if isinstance(attention_mask, Tensor)
                         else attention_mask).astype(np.int32))
                attention_mask = np.concatenate(
                    [np.zeros((nb, extra), np.int32), m], axis=1)
        pad_lens = None
        if attention_mask is not None:
            m = np.asarray(attention_mask.numpy()
                           if isinstance(attention_mask, Tensor)
                           else attention_mask).astype(np.int32)
            if m.shape != tuple(ids.shape):
                raise ValueError(
                    f"attention_mask shape {m.shape} != input_ids "
                    f"{tuple(ids.shape)}")
            if not np.isin(m, (0, 1)).all():
                raise ValueError(
                    "attention_mask must be binary 0/1 keep-mask (additive "
                    "float masks are not accepted here)")
            if not (np.diff(m, axis=1) >= 0).all():
                raise ValueError(
                    "attention_mask must be LEFT-padded (0s then 1s per row)")
            if (m.sum(axis=1) == 0).any():
                raise ValueError("attention_mask has an all-pad row")
            pad_lens = jnp.asarray(m.shape[1] - m.sum(axis=1), jnp.int32)
        b, prompt = int(ids.shape[0]), int(ids.shape[1])
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = prompt + max_new
        max_pos = self.config.max_position_embeddings
        if total > max_pos:
            raise ValueError(
                f"prompt ({prompt}) + max_new_tokens ({max_new}) = {total} "
                f"exceeds max_position_embeddings {max_pos}")
        eos = -1 if eos_token_id is None else int(eos_token_id)
        pad = eos if pad_token_id is None else int(pad_token_id)
        if not 0 <= int(min_new_tokens) <= max_new:
            raise ValueError("min_new_tokens must be in [0, max_new_tokens]")
        if repetition_penalty <= 0:
            raise ValueError("repetition_penalty must be > 0")
        if num_beams > 1:
            if do_sample:
                raise ValueError("num_beams > 1 requires do_sample=False "
                                 "(beam-sample is not supported)")
            if repetition_penalty != 1.0:
                raise NotImplementedError(
                    "repetition_penalty with beam search is not supported")
            if not 1 <= int(num_return_sequences) <= num_beams:
                raise ValueError("num_return_sequences must be in "
                                 "[1, num_beams]")
            sig = ("beam", b, prompt, max_new, int(num_beams), eos, pad,
                   int(min_new_tokens), float(length_penalty),
                   bool(early_stopping), pad_lens is not None)
            prog = self._cached_program(
                sig, lambda: self._build_generate_beam(*sig[1:]))
            params = [p for _, p in self.named_parameters()]
            buffers = [bf for _, bf in self.named_buffers()]
            if pad_lens is None:
                pad_lens = jnp.zeros((b,), jnp.int32)
            all_ids, all_scores = prog(
                [p._value for p in params], [bf._value for bf in buffers],
                ids.astype(jnp.int32), pad_lens)
            nrs = int(num_return_sequences)
            out = all_ids[:, :nrs, :].reshape(b * nrs, max_new)
            sc = all_scores[:, :nrs].reshape(b * nrs)
            return Tensor(out), Tensor(sc)
        if num_return_sequences != 1:
            if not do_sample:
                raise ValueError(
                    "num_return_sequences > 1 requires num_beams > 1 or "
                    "do_sample=True")
            if int(num_return_sequences) < 1:
                raise ValueError("num_return_sequences must be >= 1")
            # sampling path: expand each row num_return_sequences times —
            # categorical draws independent noise per batch row, so the
            # copies decode to distinct samples (PaddleNLP convention:
            # returns [batch*num_return_sequences, ...])
            nrs = int(num_return_sequences)
            ids = jnp.repeat(ids, nrs, axis=0)
            if pad_lens is not None:
                pad_lens = jnp.repeat(pad_lens, nrs, axis=0)
            b = b * nrs
        sig = (b, prompt, max_new, bool(do_sample), int(top_k),
               float(top_p), float(temperature), eos, pad,
               int(min_new_tokens), float(repetition_penalty),
               pad_lens is not None)
        prog = self._cached_program(sig, lambda: self._build_generate(*sig))
        params = [p for _, p in self.named_parameters()]
        buffers = [bf for _, bf in self.named_buffers()]
        if pad_lens is None:
            pad_lens = jnp.zeros((b,), jnp.int32)  # shape-stable jit arg
        out_ids, scores = prog(
            [p._value for p in params], [bf._value for bf in buffers],
            ids.astype(jnp.int32), pad_lens, jax.random.PRNGKey(seed))
        return Tensor(out_ids), Tensor(scores)

    # -- compiled program --------------------------------------------------
    def _build_generate(self, b, prompt, max_new, do_sample, top_k, top_p,
                        temperature, eos, pad, min_new=0, rep_penalty=1.0,
                        padded=False):
        from ..jit import _StateSwap

        params = [p for _, p in self.named_parameters()]
        buffers = [bf for _, bf in self.named_buffers()]
        n_layers, kv_heads, head_dim = self._kv_cache_spec()
        # cache capacity rounds up to a sublane multiple so the Pallas
        # decode kernel tiles it for ANY (prompt, max_new); the extra
        # slots stay masked (col <= pos) and contribute exact zeros
        total = -(-(prompt + max_new) // 8) * 8
        model = self

        def sample_tok(logits, key, seen=None, step=0):
            logits = logits.astype(jnp.float32)
            if rep_penalty != 1.0 and seen is not None:
                # CTRL repetition penalty over prompt + generated tokens
                penal = jnp.where(logits > 0, logits / rep_penalty,
                                  logits * rep_penalty)
                logits = jnp.where(seen, penal, logits)
            if eos >= 0 and min_new > 0:
                # suppress eos until min_new tokens have been emitted
                suppress = jnp.asarray(step, jnp.int32) < min_new
                eos_col = jnp.arange(logits.shape[-1]) == eos
                logits = jnp.where(suppress & eos_col[None, :],
                                   jnp.finfo(jnp.float32).min, logits)
            if not do_sample:
                logprobs_full = jax.nn.log_softmax(logits, axis=-1)
                tok = jnp.argmax(logits, axis=-1)
            else:
                scaled = logits / max(temperature, 1e-6)
                if top_k and top_k > 0:
                    k_eff = min(int(top_k), scaled.shape[-1])
                    kth = jnp.sort(scaled, axis=-1)[:, -k_eff][:, None]
                    scaled = jnp.where(scaled < kth,
                                       jnp.finfo(jnp.float32).min, scaled)
                if top_p < 1.0:
                    srt = jnp.sort(scaled, axis=-1)[:, ::-1]
                    cdf = jnp.cumsum(jax.nn.softmax(srt, axis=-1), axis=-1)
                    # smallest set with cumulative prob >= top_p (the
                    # chosen token itself always survives)
                    cutoff_idx = jnp.sum(cdf < top_p, axis=-1)
                    kth = jnp.take_along_axis(srt, cutoff_idx[:, None],
                                              axis=-1)
                    scaled = jnp.where(scaled < kth,
                                       jnp.finfo(jnp.float32).min, scaled)
                tok = jax.random.categorical(key, scaled, axis=-1)
                # scores reflect the distribution actually SAMPLED from
                # (post temperature/top-k/top-p), matching the reference
                # generation convention (advisor round 4)
                logprobs_full = jax.nn.log_softmax(scaled, axis=-1)
            logp = jnp.take_along_axis(logprobs_full, tok[:, None],
                                       axis=-1)[:, 0]
            return tok.astype(jnp.int32), logp

        def step_model(ids_slice, caches, offset, pad_lens):
            logits, caches = model(Tensor(ids_slice), kv_cache=caches,
                                   position_offset=offset,
                                   pad_lens=pad_lens if padded else None)
            return logits._value, caches

        def fn(param_arrays, buffer_arrays, ids, pad_lens, key):
            with _StateSwap(params, param_arrays), \
                    _StateSwap(buffers, buffer_arrays), no_grad():
                cdt = next((a.dtype for a in param_arrays
                            if jnp.issubdtype(a.dtype, jnp.floating)),
                           jnp.float32)
                caches = [(jnp.zeros((b, total, kv_heads, head_dim), cdt),
                           jnp.zeros((b, total, kv_heads, head_dim), cdt))
                          for _ in range(n_layers)]
                logits, caches = step_model(ids, caches, 0, pad_lens)  # prefill
                vocab = logits.shape[-1]
                rows = jnp.arange(b)
                if rep_penalty != 1.0:
                    seen = jnp.zeros((b, vocab), bool)
                    # pad filler ids must NOT count as seen, or a padded
                    # row penalizes the filler token and diverges from its
                    # unpadded decode
                    real = jnp.arange(prompt)[None, :] >= pad_lens[:, None]
                    seen = seen.at[rows[:, None], ids].max(real)
                else:
                    seen = None
                key, sub = jax.random.split(key)
                tok, logp = sample_tok(logits[:, -1, :], sub, seen, 0)
                done = tok == eos
                tok = jnp.where(done & (eos >= 0), eos, tok)
                if seen is not None:
                    seen = seen.at[rows, tok].set(True)

                def body(carry, _):
                    prev, caches, offset, key, done, seen, t = carry
                    logits, caches = step_model(prev[:, None], caches, offset,
                                                pad_lens)
                    key, sub = jax.random.split(key)
                    nxt, logp = sample_tok(logits[:, -1, :], sub, seen, t)
                    nxt = jnp.where(done, jnp.asarray(pad, jnp.int32), nxt)
                    logp = jnp.where(done, 0.0, logp)
                    done = done | (nxt == eos)
                    if seen is not None:
                        seen = seen.at[rows, nxt].set(True)
                    return (nxt, caches, offset + 1, key, done, seen,
                            t + 1), (nxt, logp)

                carry0 = (tok, caches, jnp.asarray(prompt, jnp.int32), key,
                          done, seen, jnp.asarray(1, jnp.int32))
                if max_new > 1:
                    _, (rest, rest_logp) = jax.lax.scan(
                        body, carry0, None, length=max_new - 1)
                    out = jnp.concatenate([tok[:, None], rest.T], axis=1)
                    scores = jnp.concatenate([logp[:, None], rest_logp.T],
                                             axis=1)
                else:
                    out, scores = tok[:, None], logp[:, None]
            return out, scores

        return jax.jit(fn)

    def _build_generate_beam(self, b, prompt, max_new, num_beams, eos, pad,
                             min_new=0, length_penalty=1.0,
                             early_stopping=False, padded=False):
        """Compile beam search: prefill (batch b) + K-fold cache tiling +
        the ``beam_search_loop`` scan, all in ONE XLA program."""
        from ..jit import _StateSwap
        from .beam_search import beam_search_loop

        params = [p for _, p in self.named_parameters()]
        buffers = [bf for _, bf in self.named_buffers()]
        n_layers, kv_heads, head_dim = self._kv_cache_spec()
        total = -(-(prompt + max_new) // 8) * 8  # sublane-aligned capacity
        K = int(num_beams)
        model = self

        def step_model(ids_slice, caches, offset, pad_lens):
            logits, caches = model(Tensor(ids_slice), kv_cache=caches,
                                   position_offset=offset,
                                   pad_lens=pad_lens if padded else None)
            return logits._value, caches

        def fn(param_arrays, buffer_arrays, ids, pad_lens):
            with _StateSwap(params, param_arrays), \
                    _StateSwap(buffers, buffer_arrays), no_grad():
                cdt = next((a.dtype for a in param_arrays
                            if jnp.issubdtype(a.dtype, jnp.floating)),
                           jnp.float32)
                caches = [(jnp.zeros((b, total, kv_heads, head_dim), cdt),
                           jnp.zeros((b, total, kv_heads, head_dim), cdt))
                          for _ in range(n_layers)]
                logits, caches = step_model(ids, caches, 0, pad_lens)
                caches = jax.tree_util.tree_map(
                    lambda a: jnp.repeat(a, K, axis=0), caches)
                beam_pad_lens = jnp.repeat(pad_lens, K, axis=0)

                def beam_step(tok, caches, offset, pl):
                    return step_model(tok, caches, offset, pl)

                return beam_search_loop(
                    beam_step, caches, logits[:, -1, :],
                    num_beams=K, max_new=max_new, eos=eos, pad=pad,
                    length_penalty=length_penalty,
                    early_stopping=early_stopping, min_new=min_new,
                    prompt_len=prompt,
                    pad_lens=beam_pad_lens if padded else None)

        return jax.jit(fn)
