"""Flash attention as a Pallas TPU kernel (forward + backward).

Blockwise online-softmax attention: never materializes the [b, h, sq, sk]
logits, streams K/V blocks through VMEM, accumulates output and logsumexp in
f32 scratch. GQA reads the shared KV head via the BlockSpec index map — no
`jnp.repeat` of K/V.

A grid step holds one tile of the resident operand (``block_q`` query rows;
in the dK/dV kernel ``block_k`` keys) and up to ``_MAJOR`` rows of the
streamed one, which it walks in chunks of the other block size inside the
kernel. Under a causal mask the walk stops at the diagonal: chunks above it
are not visited, and a grid step with nothing to visit names the block
already resident, so nothing is copied for it. (A grid step costs about
0.3 us on a v5e whatever it does, and a dead one that names a new block
fetches it: with one 512 x 512 tile a step that was a quarter to a third of
each kernel. Masking only the chunks that straddle the diagonal yields
nothing: the loops wait on the MXU. Both measured: PERF.md, PR 35.)

Capability parity target: the reference's FA2 path
(`paddle/phi/kernels/gpu/flash_attn_kernel.cu`, python surface
`nn/functional/flash_attention.py:147`) in the paddle flash-attn layout
[batch, seq, heads, head_dim] (transposed to [b, h, s, d] internally — the
Mosaic-friendly layout where the (seq, head_dim) block is lane-aligned).

Backward follows the FA2 two-kernel split: one kernel accumulates dQ over KV
blocks, one accumulates dK/dV over Q blocks (and over the GQA head group),
both re-computing probabilities from the saved logsumexp. The dQ kernel also
computes the row statistic delta = rowsum(dO * O) once per Q block and
exports it for the dK/dV kernel.

Per-row scalars (running max, sum, lse, delta) live broadcast along a
128-lane minor dim and are used as they lie: a `[rows, 128]` array meets a
`[rows, block_k]` tile by repetition along lanes, which moves nothing, where
a `[:, :1]` column costs a lane permute a vreg on the XLU (the forward
step's schedule was twice as long for it). The dK/dV kernel holds its
scores transposed, `[block_k, block_q]`, so that no operand of its four
products is transposed and lse / delta meet the tile as ROWS; the dQ kernel
writes them so, `[b, h, sq / block_q, 8, block_q]` (eight sublanes alike:
a whole tile, whatever ``block_q``).

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
# rows of the streamed operand (K/V; Q/dO in the dK/dV kernel) a grid step
# holds: 2048 x 128 bf16 is 512 KB an operand a buffer
_MAJOR = 2048

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


def _lanes(x, n: int):
    """x [rows, 128], every lane alike → [rows, n] by repetition: no data
    moves when ``n`` is whole lane registers."""
    if n == _LANES:
        return x
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    if n < _LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _major(s: int, block: int) -> int:
    """Rows of the streamed operand a grid step holds: the most chunks of
    ``block`` rows that divide ``s`` and fit ``_MAJOR``."""
    n = s // block
    m = max(1, min(n, _MAJOR // block))
    while n % m:
        m -= 1
    return block * m


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
# A block above the diagonal holds nothing unmasked: it is not visited.
def _last_live_k(iq, block_q, block_k, offset):
    """The last key block query block ``iq`` sees any of."""
    return jnp.maximum(iq * block_q + block_q - 1 + offset, 0) // block_k


def _first_live_q(ik, block_q, block_k, offset):
    """The first query block that sees any of key block ``ik``."""
    return jnp.maximum(ik * block_k - offset, 0) // block_q


def _live_k_chunks(iq, ikm, block_q, block_k, n, offset):
    """For query block ``iq``: how many of the ``n`` key chunks of major
    tile ``ikm`` are live (they are the first ones)."""
    return jnp.clip(_last_live_k(iq, block_q, block_k, offset) + 1 - ikm * n,
                    0, n)


def _dead_q_chunks(ik, iqm, block_q, block_k, n, offset):
    """For key block ``ik``: how many of the ``n`` query chunks of major
    tile ``iqm`` are dead (they are the first ones)."""
    return jnp.clip(_first_live_q(ik, block_q, block_k, offset) - iqm * n,
                    0, n)


def _causal_mask(s, q0, k0, offset, transposed=False):
    """Scores of queries from ``q0`` and keys from ``k0`` (``[q, k]``, or
    ``[k, q]`` when ``transposed``) with the masked ones at -inf."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, int(transposed))
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                          int(not transposed))
    return jnp.where(q_pos + offset >= k_pos, s, _NEG_INF)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a [m, d] . b [n, d] -> [m, n]
_NN = ((1,), (0,))      # a [m, n] . b [n, d] -> [m, d]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, n_chunks: int, offset: int, padded: bool):
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
    iq, ikm = pl.program_id(2), pl.program_id(3)
    d = q_ref.shape[-1]

    @pl.when(ikm == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, 0, rows, :]                           # (Bk, d)
        v = v_ref[0, 0, rows, :]
        s = _dot(q_ref[0, 0], k, _NT) * scale              # (Bq, Bk) f32
        k0 = (ikm * n_chunks + j) * block_k
        if causal:
            s = _causal_mask(s, iq * block_q, k0, offset)
        if padded:
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(k_pos >= pad, s, _NEG_INF)
        m_prev = m_ref[:]                          # (Bq, 128), lanes alike
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_ok = m_new
        if padded or (causal and offset < 0):
            # a row with every score masked so far keeps m == -inf, and
            # exp(-inf - -inf) is NaN — NaN that later poisons VALID rows
            # downstream (0 * NaN in the next layer's dot).  Happens for
            # query rows inside the left-padding (padded) and empty causal
            # rows (sq > sk); a finite reference point collapses p/alpha to
            # exact zeros so the row finalizes through the l == 0 guard to
            # zeros.  Elsewhere key 0 is visible to every row from the
            # first chunk on.
            m_ok = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - _lanes(m_ok, block_k))
        alpha = jnp.exp(m_prev - m_ok)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * _lanes(alpha, d) + _dot(
            p.astype(v.dtype), v, _NN)
        return carry

    # chunks above the diagonal, and with ``padded`` those wholly left of
    # the row's first valid key, are dead
    first = jnp.clip(pad // block_k - ikm * n_chunks, 0, n_chunks) \
        if padded else 0
    live = _live_k_chunks(iq, ikm, block_q, block_k, n_chunks, offset) \
        if causal else n_chunks
    jax.lax.fori_loop(first, live, _chunk, None)

    @pl.when(ikm == pl.num_programs(3) - 1)
    def _finalize():
        l = l_ref[:]
        # causal with sq > sk could leave empty rows; guard the divide
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[:] / _lanes(l, d)).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:] + jnp.log(l)


def _kv_specs(rep, causal, block_q, block_k_major, d, offset):
    """K and V as the forward and the dQ kernel stream them: a grid step
    above the diagonal names the last live tile, which is resident."""
    def index(ib, ih, iq, ikm, *_):
        if causal:
            ikm = jnp.minimum(ikm, _last_live_k(iq, block_q, block_k_major,
                                                offset))
        return (ib, ih // rep, ikm, 0)

    return [pl.BlockSpec((1, 1, block_k_major, d), index)] * 2


# jitted, so that a model's layers, alike in shapes, share ONE trace of the
# kernels and one lowering: traced a layer, the three kernels were 2–3 s of
# the train cell's set-up in Python alone (PERF.md, PR 35)
@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret"))
def _fwd(q, k, v, *, scale, causal, block_q, block_k, interpret,
         pad_lens=None):
    """q [b, hq, sq, d]; k/v [b, hkv, sk, d] → out [b, hq, sq, d],
    lse [b, hq, sq, 128] (value broadcast along the minor dim).
    ``pad_lens`` [b] int32: per-row LEFT-padding — keys below it masked."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    major_k = _major(sk, block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               n_chunks=major_k // block_k, offset=sk - sq,
                               padded=pad_lens is not None)
    # index maps take the scalar-prefetch ref (if any) after the grid ids
    pad_args = [] if pad_lens is None else [
        jnp.asarray(pad_lens, jnp.int32).reshape(b)]

    def q_index(ib, ih, iq, ikm, *_):
        return (ib, ih, iq, 0)

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(pad_args),
            grid=(b, hq, sq // block_q, sk // major_k),
            in_specs=[pl.BlockSpec((1, 1, block_q, d), q_index)]
            + _kv_specs(hq // hkv, causal, block_q, major_k, d, sk - sq),
            out_specs=[
                pl.BlockSpec((1, 1, block_q, d), q_index),
                pl.BlockSpec((1, 1, block_q, _LANES), q_index),
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
                   dq_ref, lse_row_ref, delta_row_ref, acc_ref, delta_ref, *,
                   scale: float, causal: bool, block_q: int, block_k: int,
                   n_chunks: int, offset: int):
    iq, ikm = pl.program_id(2), pl.program_id(3)

    @pl.when(ikm == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        # delta_i = rowsum(dO_i * O_i); computed once per Q block (FA2
        # precompute) and exported with lse AS ROWS for the dK/dV kernel
        delta_ref[:] = jnp.broadcast_to(jnp.sum(
            do_ref[0, 0].astype(jnp.float32) * o_ref[0, 0].astype(jnp.float32),
            axis=1, keepdims=True), delta_ref.shape)
        lse_row_ref[0, 0, 0] = jnp.transpose(lse_ref[0, 0])[:8]
        delta_row_ref[0, 0, 0] = jnp.transpose(delta_ref[:])[:8]

    def _chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
        k = k_ref[0, 0, rows, :]
        s = _dot(q_ref[0, 0], k, _NT) * scale              # (Bq, Bk)
        if causal:
            s = _causal_mask(s, iq * block_q,
                             (ikm * n_chunks + j) * block_k, offset)
        p = jnp.exp(s - _lanes(lse_ref[0, 0], block_k))
        dp = _dot(do_ref[0, 0], v_ref[0, 0, rows, :], _NT)
        ds = p * (dp - _lanes(delta_ref[:], block_k)) * scale
        acc_ref[:] += _dot(ds.astype(k.dtype), k, _NN)
        return carry

    live = _live_k_chunks(iq, ikm, block_q, block_k, n_chunks, offset) \
        if causal else n_chunks
    jax.lax.fori_loop(0, live, _chunk, None)

    @pl.when(ikm == pl.num_programs(3) - 1)
    def _finalize():
        dq_ref[0, 0] = acc_ref[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal: bool, block_q: int, block_k: int, n_chunks: int,
                    offset: int):
    # grid (b, hkv, nk, rep, nq_major): the innermost two dims accumulate
    # over the GQA head group and the Q tiles while the K/V block stays
    # resident.  Scores are held transposed, (Bk, Bq): lse and delta meet
    # them as rows, and no operand of the four products is transposed.
    ik, irep, iqm = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    nrep, nqm = pl.num_programs(3), pl.num_programs(4)

    @pl.when(jnp.logical_and(irep == 0, iqm == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _chunk(j, carry):
        rows = pl.ds(pl.multiple_of(j * block_q, block_q), block_q)
        q = q_ref[0, 0, rows, :]
        do = do_ref[0, 0, rows, :]
        st = _dot(k_ref[0, 0], q, _NT) * scale             # (Bk, Bq)
        if causal:
            st = _causal_mask(st, (iqm * n_chunks + j) * block_q,
                              ik * block_k, offset, transposed=True)
        pt = jnp.exp(st - lse_ref[0, 0, j][:1])
        dv_acc[:] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v_ref[0, 0], do, _NT)
        dst = pt * (dpt - delta_ref[0, 0, j][:1]) * scale
        dk_acc[:] += _dot(dst.astype(q.dtype), q, _NN)
        return carry

    dead = _dead_q_chunks(ik, iqm, block_q, block_k, n_chunks, offset) \
        if causal else 0
    jax.lax.fori_loop(dead, n_chunks, _chunk, None)

    @pl.when(jnp.logical_and(irep == nrep - 1, iqm == nqm - 1))
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _bwd(scale, causal, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res                        # internal [b, h, s, d] layout
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    rep = hq // hkv
    nq = sq // block_q
    major_q, major_k = _major(sq, block_q), _major(sk, block_k)
    tiles = dict(scale=scale, causal=causal, block_q=block_q,
                 block_k=block_k, offset=sk - sq)

    def q_index(ib, ih, iq, ikm):
        return (ib, ih, iq, 0)

    rows = sds_like((b, hq, nq, 8, block_q), jnp.float32, q)
    dq, lse_rows, delta_rows = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_chunks=major_k // block_k,
                          **tiles),
        grid=(b, hq, nq, sk // major_k),
        in_specs=[pl.BlockSpec((1, 1, block_q, d), q_index)]
        + _kv_specs(rep, causal, block_q, major_k, d, sk - sq)
        + [pl.BlockSpec((1, 1, block_q, d), q_index)] * 2
        + [pl.BlockSpec((1, 1, block_q, _LANES), q_index)],
        out_specs=[pl.BlockSpec((1, 1, block_q, d), q_index)]
        + [pl.BlockSpec((1, 1, 1, 8, block_q),
                        lambda ib, ih, iq, ikm: (ib, ih, iq, 0, 0))] * 2,
        out_shape=[sds_like((b, hq, sq, d), q.dtype, q), rows, rows],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_bwd_dq",
        interpret=interpret,
    )(q, k, v, out, do, lse)

    # Q, dO and the two rows are streamed: a grid step before the first
    # live query tile names it, so nothing is copied for it
    def streamed(*tail):
        def index(ib, ihkv, ik, ir, iqm):
            if causal:
                iqm = jnp.maximum(iqm, _first_live_q(ik, major_q, block_k,
                                                     sk - sq))
            return (ib, ihkv * rep + ir, iqm) + tail
        return index

    def kv_index(ib, ihkv, ik, ir, iqm):
        return (ib, ihkv, ik, 0)

    n_q = major_q // block_q
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_chunks=n_q, **tiles),
        grid=(b, hkv, sk // block_k, rep, sq // major_q),
        in_specs=[
            pl.BlockSpec((1, 1, major_q, d), streamed(0)),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, block_k, d), kv_index),
            pl.BlockSpec((1, 1, major_q, d), streamed(0)),
            pl.BlockSpec((1, 1, n_q, 8, block_q), streamed(0, 0)),
            pl.BlockSpec((1, 1, n_q, 8, block_q), streamed(0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, block_k, d), kv_index)] * 2,
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
    )(q, k, v, do, lse_rows, delta_rows)
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
