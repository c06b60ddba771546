"""Flash attention as a Pallas TPU kernel (forward + backward).

Blockwise online-softmax attention: never materializes the [b, h, sq, sk]
logits, streams K/V blocks through VMEM, accumulates output and logsumexp in
f32 scratch. GQA reads the shared KV head via the BlockSpec index map — no
`jnp.repeat` of K/V. Causal blocks above the diagonal are skipped with
`pl.when`.

Capability parity target: the reference's FA2 path
(`paddle/phi/kernels/gpu/flash_attn_kernel.cu`, python surface
`nn/functional/flash_attention.py:147`) in the paddle flash-attn layout
[batch, seq, heads, head_dim] (transposed to [b, h, s, d] internally — the
Mosaic-friendly layout where the (seq, head_dim) block is lane-aligned).

Backward follows the FA2 two-kernel split: one kernel accumulates dQ over KV
blocks, one accumulates dK/dV over Q blocks (and over the GQA head group),
both re-computing probabilities from the saved logsumexp. The dQ kernel also
computes the row statistic delta = rowsum(dO * O) once per Q block and
exports it for the dK/dV kernel (per-row scalars are stored broadcast along
a 128-lane minor dim, the TPU-native layout).

Causal masking is bottom-right aligned (q row i sees k cols <= i + sk - sq),
matching `sdpa_reference`'s tril(k=sk-sq) and the FA2 convention for
rectangular shapes (chunked prefill against a KV cache).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import sds_like
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128

# default tile sizes; sq/sk must be divisible by these for the kernel path
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256


def flash_attention_supported(q_shape, k_shape, *, has_mask: bool,
                              dropout_p: float, causal: bool = False,
                              block_q: int = DEFAULT_BLOCK_Q,
                              block_k: int = DEFAULT_BLOCK_K) -> bool:
    """Shapes/features the tiled kernel handles; callers fall back to the XLA
    reference path otherwise. Causal requires sq <= sk (bottom-right aligned;
    rows with zero valid keys are undefined in any flash implementation)."""
    b, sq, hq, d = q_shape
    _, sk, hkv, _ = k_shape
    return (not has_mask and dropout_p == 0.0 and sq % block_q == 0
            and sk % block_k == 0 and d % 8 == 0 and d <= 256 and hq % hkv == 0
            and (not causal or sq <= sk))


def _bcast_lanes(col):
    """(Bq, 1) f32 → (Bq, 128) broadcast along the lane dim."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES))


def flash_attention_varlen_supported(q_shape, k_shape, *,
                                     block_q: int = DEFAULT_BLOCK_Q,
                                     block_k: int = DEFAULT_BLOCK_K) -> bool:
    """Gate for the left-padded (per-row valid-length) forward: the varlen
    path is causal square prefill over a left-padded batch — sq == sk, both
    tile-divisible.  Backward is not implemented (serving prefill runs under
    ``no_grad``), so training callers must not route masked calls here."""
    b, sq, hq, d = q_shape
    _, sk, hkv, _ = k_shape
    return (sq == sk and sq % block_q == 0 and sk % block_k == 0
            and d % 8 == 0 and d <= 256 and hq % hkv == 0)


# Causal masking uses bottom-right alignment (FA2 convention, matching
# `sdpa_reference`'s tril(k=sk-sq)): q row i attends to k cols <= i + sk - sq.
def _causal_live(iq, ik, block_q, block_k, offset):
    """Whether block (iq, ik) contains any unmasked element."""
    return ik * block_k <= iq * block_q + block_q - 1 + offset


def _causal_mask(s, iq, ik, block_q, block_k, offset):
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos + offset >= k_pos, s, _NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, scale: float, causal: bool,
                block_q: int, block_k: int, offset: int, padded: bool):
    # with ``padded`` the per-row left-pad lengths ride scalar prefetch
    # ahead of the tensor operands (varlen serving prefill): the whole [b]
    # vector sits in SMEM and the kernel indexes its own batch row
    if padded:
        (pad_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
         acc_ref, m_ref, l_ref) = refs
        pad = pad_ref[pl.program_id(0)]
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         acc_ref, m_ref, l_ref) = refs
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    live = _causal_live(iq, ik, block_q, block_k, offset) if causal else True
    if padded:
        # blocks entirely left of the row's first valid key are dead
        live = jnp.logical_and(live, (ik + 1) * block_k > pad)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]                            # (Bq, d)
        k = k_ref[0, 0]                            # (Bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, offset)
        if padded:
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos >= pad, s, _NEG_INF)
        m_prev = m_ref[:, :1]                      # (Bq, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # a row with every score masked so far keeps m == -inf, and
        # exp(-inf - -inf) is NaN — NaN that later poisons VALID rows
        # downstream (0 * NaN in the next layer's dot).  Happens for query
        # rows inside the left-padding (padded) and empty causal rows
        # (sq > sk); a finite reference point collapses p/alpha to exact
        # zeros so the row finalizes through the l == 0 guard to zeros.
        m_ok = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_ok)                      # (Bq, Bk) f32
        alpha = jnp.exp(m_prev - m_ok)
        l_ref[:] = _bcast_lanes(l_prev * alpha + jnp.sum(p, axis=1, keepdims=True))
        m_ref[:] = _bcast_lanes(m_new)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        # causal with sq > sk could leave empty rows; guard the divide
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = _bcast_lanes(m_ref[:, :1] + jnp.log(l))


def _fwd(q, k, v, *, scale, causal, block_q, block_k, interpret,
         pad_lens=None):
    """q [b, hq, sq, d]; k/v [b, hkv, sk, d] → out [b, hq, sq, d],
    lse [b, hq, sq, 128] (value broadcast along the minor dim).
    ``pad_lens`` [b] int32: per-row LEFT-padding — keys below it masked."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    rep = hq // hkv
    grid = (b, hq, sq // block_q, sk // block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               offset=sk - sq, padded=pad_lens is not None)
    # index maps take the scalar-prefetch ref (if any) after the grid ids
    pad_args = [] if pad_lens is None else [
        jnp.asarray(pad_lens, jnp.int32).reshape(b)]
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pad_args),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda ib, ih, iq, ik, *_: (ib, ih, iq, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, iq, ik, *_: (ib, ih // rep, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, iq, ik, *_: (ib, ih // rep, ik, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d),
                             lambda ib, ih, iq, ik, *_: (ib, ih, iq, 0)),
                pl.BlockSpec((1, 1, block_q, _LANES),
                             lambda ib, ih, iq, ik, *_: (ib, ih, iq, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            sds_like((b, hq, sq, d), q.dtype, q),
            sds_like((b, hq, sq, _LANES), jnp.float32, q),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * hq * sq * sk * d // (2 if causal else 1),
            bytes_accessed=(b * sq * hq * d + 2 * b * sk * hkv * d) * q.dtype.itemsize,
            transcendentals=b * hq * sq * sk),
        name="flash_fwd",
        interpret=interpret,
    )(*pad_args, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                   dq_ref, delta_out_ref, acc_ref, delta_ref, *, scale: float,
                   causal: bool, block_q: int, block_k: int, offset: int):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # delta_i = rowsum(dO_i * O_i); computed once per Q block and exported
        # for the dK/dV kernel (FA2 precompute)
        delta = _bcast_lanes(jnp.sum(
            do_ref[0, 0].astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
            axis=1, keepdims=True))
        delta_ref[:] = delta
        delta_out_ref[0, 0] = delta

    live = _causal_live(iq, ik, block_q, block_k, offset) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]                 # (Bq, 1)
        delta = delta_ref[:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, offset)
        p = jnp.exp(s - lse)                       # (Bq, Bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        acc_ref[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal: bool, block_q: int, block_k: int, offset: int):
    # grid (b, hkv, nk, rep, nq): innermost two dims accumulate over the GQA
    # head group and the Q blocks while the K/V block stays resident
    ik, irep, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    nrep, nq = pl.num_programs(3), pl.num_programs(4)

    @pl.when(jnp.logical_and(irep == 0, iq == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = _causal_live(iq, ik, block_q, block_k, offset) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, iq, ik, block_q, block_k, offset)
        p = jnp.exp(s - lse)                       # (Bq, Bk)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale              # (Bq, Bk)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(irep == nrep - 1, iq == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res                        # internal [b, h, s, d] layout
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    rep = hq // hkv

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                  block_q=block_q, block_k=block_k, offset=sk - sq)
    dq, delta = pl.pallas_call(
        dq_kernel,
        grid=(b, hq, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ih, iq, ik: (ib, ih // rep, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        ],
        out_shape=[
            sds_like((b, hq, sq, d), q.dtype, q),
            sds_like((b, hq, sq, _LANES), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, out, do, lse)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                   block_q=block_q, block_k=block_k, offset=sk - sq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, hkv, sk // block_k, rep, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ihkv, ik, ir, iq: (ib, ihkv * rep + ir, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ihkv, ik, ir, iq: (ib, ihkv, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ihkv, ik, ir, iq: (ib, ihkv, ik, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda ib, ihkv, ik, ir, iq: (ib, ihkv * rep + ir, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda ib, ihkv, ik, ir, iq: (ib, ihkv * rep + ir, iq, 0)),
            pl.BlockSpec((1, 1, block_q, _LANES),
                         lambda ib, ihkv, ik, ir, iq: (ib, ihkv * rep + ir, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ihkv, ik, ir, iq: (ib, ihkv, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda ib, ihkv, ik, ir, iq: (ib, ihkv, ik, 0)),
        ],
        out_shape=[
            sds_like((b, hkv, sk, d), k.dtype, k),
            sds_like((b, hkv, sk, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry — paddle flash-attn layout [b, s, h, d]
# ---------------------------------------------------------------------------
def _to_internal(x):
    return jnp.transpose(x, (0, 2, 1, 3))          # [b,s,h,d] → [b,h,s,d]


def _from_internal(x):
    return jnp.transpose(x, (0, 2, 1, 3))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, scale: Optional[float] = None, causal: bool = False,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False):
    """q [b, sq, hq, d]; k/v [b, sk, hkv, d] (GQA: hkv | hq) → [b, sq, hq, d]."""
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def flash_attention_varlen(q, k, v, pad_lens, scale: Optional[float] = None,
                           causal: bool = True,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = False):
    """Left-padded prefill attention: row ``b`` attends keys in
    ``[pad_lens[b], i]`` (causal, bottom-right aligned).  q [b, s, hq, d];
    k/v [b, s, hkv, d]; ``pad_lens`` [b] int32 counts LEFT padding per row.
    Rows whose query position lies inside the padding have no valid keys
    and produce zeros (their outputs are never consumed — their own keys
    are masked for every later query).  FORWARD ONLY (``no_grad`` serving
    prefill); the trainable path keeps the unmasked ``flash_attention``."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    out, _ = _fwd(_to_internal(q), _to_internal(k), _to_internal(v),
                  scale=s, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=interpret, pad_lens=pad_lens)
    return _from_internal(out)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qi, ki, vi = _to_internal(q), _to_internal(k), _to_internal(v)
    out, lse = _fwd(qi, ki, vi, scale=s, causal=causal,
                    block_q=block_q, block_k=block_k, interpret=interpret)
    return _from_internal(out), (qi, ki, vi, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    d = res[0].shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    dq, dk, dv = _bwd(s, causal, block_q, block_k, interpret, res,
                      _to_internal(g))
    return _from_internal(dq), _from_internal(dk), _from_internal(dv)


flash_attention.defvjp(_flash_fwd, _flash_bwd)
