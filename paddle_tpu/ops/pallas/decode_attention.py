"""Decode attention as a Pallas TPU kernel: single query per sequence against
the static KV cache, with the cache append done *in place*.

Capability parity target: the reference's serving hot kernel
`paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu:1` — one
fused (cache write + masked single-token attention) per decode step.  The
XLA einsum path (`generation.cached_attention`) is numerically fine but its
`dynamic_update_slice` inside the decode scan materializes a full copy of
the cache every step (its cost is not measured on the current tree; see
PERF.md).  Here the cache arrays are passed through
``input_output_aliases``: the kernel writes exactly the new token's rows
back and the rest of the aliased HBM buffer is never touched, so the
compiled scan keeps the cache resident in place.

Shape contract (paddle flash-attn layout):

- q        [b, 1, h, d]      — the single decode-step query
- k_new/v_new [b, 1, kv, d]  — this step's key/value (GQA: kv | h)
- cache_k/cache_v [b, C, kv, d] — static cache; C % block_k == 0
- pos      scalar int32 (traced ok) — absolute write position; the query
  attends cols ``[pad_lens[b], pos]`` (its own new token included)
- pad_lens [b] int32 or None — LEFT-padding per row; those slots are
  masked out of attention forever

Returns ``(out [b, 1, h, d], new_cache_k, new_cache_v)`` where the new
caches alias the inputs.

Kernel structure (``decode_attention``): the cache is viewed as
``[b, C*kv, d]`` — rows ordered (col, kv head), a free reshape because a
whole number of ``(kv, d)`` tiles sits under every col — and streamed in
``(block_k*kv, d)`` blocks on a ``(b, C // block_k)`` grid.  Mosaic cannot
block a single head out of the second-minor ``kv`` axis (the block's last
two dims must be tile-divisible or whole), so every block carries ALL kv
heads and the per-head structure is a mask, not a slice: one
``[h, d] x [d, block_k*kv]`` matmul scores every query head against every
(col, kv head) row and an additive group bias keeps only the rows of the
head's own kv group.  The online-softmax loop runs in f32 scratch, folds
the NEW token's score in at the last block (the cache content at ``pos``
is stale and masked with ``col < pos``), and the append writes just the
new token's ``(kv, d)`` row group through the aliased output.
``pos``/``pad_lens`` ride scalar prefetch so the output block index map can
target the append rows dynamically.

No VJP: decode runs under ``no_grad`` by construction.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_LANES = 128
_MIN_SUBLANES = 8

DEFAULT_BLOCK_K = 256

# K and V blocks, double-buffered, plus the f32 score panel and the group
# bias must fit the 16 MiB scoped-VMEM default with room for the compiler
_VMEM_BUDGET = 12 * 1024 * 1024


def _decode_shape_ok(q_shape, cache_shape, block_k: int) -> bool:
    """Shape algebra every decode variant shares (single query, matching
    head dims, GQA divisibility, cache tiled by ``block_k``)."""
    if len(q_shape) != 4 or len(cache_shape) != 4:
        return False
    b, s, h, d = q_shape
    _, C, kv, dc = cache_shape
    return (s == 1 and d == dc and d % 8 == 0 and d <= 256
            and kv >= 1 and h % kv == 0
            and C >= block_k and C % block_k == 0)


def _sublane_rows(dtype) -> int:
    """Rows of one native VMEM tile for ``dtype`` (f32 8, bf16 16, 8-bit
    32): narrower types pack more rows per sublane."""
    return _MIN_SUBLANES * max(1, 4 // jnp.dtype(dtype).itemsize)


def decode_attention_supported(q_shape, cache_shape, *,
                               block_k: int = DEFAULT_BLOCK_K,
                               dtype=jnp.bfloat16) -> bool:
    """Shapes the decode kernel handles; callers fall back to the XLA
    grouped-einsum path (``generation.cached_attention``) otherwise.
    Beyond the shared shape algebra the TPU lowering needs ``kv`` to fill
    whole sublane tiles of the cache ``dtype`` (the ``[b, C*kv, d]`` view
    is then a bitcast and the append block ``(kv, d)`` is tile-aligned),
    and the streamed blocks must fit scoped VMEM."""
    if not _decode_shape_ok(q_shape, cache_shape, block_k):
        return False
    _, _, h, d = q_shape
    kv = cache_shape[2]
    if kv % _sublane_rows(dtype) != 0:
        return False
    n = block_k * kv
    hp = max(h, _MIN_SUBLANES)
    vmem = 4 * n * d * jnp.dtype(dtype).itemsize + 3 * hp * n * 4
    return vmem <= _VMEM_BUDGET


def _decode_kernel(pos_ref, pad_ref, q_ref, knh_ref, vnh_ref, kn_ref, vn_ref,
                   bias_ref, col_ref, ck_ref, cv_ref,
                   o_ref, cko_ref, cvo_ref, acc_ref, m_ref, l_ref, *,
                   scale: float, block_k: int):
    ib, ik = pl.program_id(0), pl.program_id(1)
    nk = pl.num_programs(1)
    pos = pos_ref[0]
    pad = pad_ref[ib]

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _bcast(col):
        return jnp.broadcast_to(col, (col.shape[0], _LANES))

    def _online(s_col, pv_of):
        """Fold a masked score panel ``s_col`` (hp, n) into the running
        (m, l, acc) online-softmax state; ``pv_of(p)`` is the panel's
        (hp, d) value contribution for probabilities ``p``."""
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s_col, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # an all-masked panel (pad >= pos: the row's only valid col is the
        # new token, folded in _finalize) keeps m == -inf and
        # exp(-inf - -inf) would poison the row with NaN; a finite
        # reference point collapses p/alpha to exact zeros instead
        m_ok = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s_col - m_ok)
        alpha = jnp.exp(m_prev - m_ok)
        l_ref[:] = _bcast(l_prev * alpha + jnp.sum(p, axis=1, keepdims=True))
        m_ref[:] = _bcast(m_new)
        acc_ref[:] = acc_ref[:] * alpha + pv_of(p)

    # cache cols live in this block iff any col satisfies pad <= col < pos
    @pl.when((ik * block_k < pos) & ((ik + 1) * block_k > pad))
    def _attend():
        k = ck_ref[0]                                  # (block_k*kv, d)
        v = cv_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = ik * block_k + col_ref[...]              # (1, block_k*kv)
        # bias is 0 on the head's own kv group and -inf elsewhere
        s = jnp.where((col < pos) & (col >= pad), s + bias_ref[...],
                      _NEG_INF)
        _online(s, lambda p: jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))

    # the NEW token (always valid: it is being written at ``pos``) folds in
    # at the last block, then the output row finalizes and the append
    # writes the token's (kv, d) row group through the aliased buffer
    @pl.when(ik == nk - 1)
    def _finalize():
        # knh/vnh are k_new/v_new expanded to one row per QUERY head, so
        # the single-column fold is elementwise (no kv-wide matmul)
        s_new = jnp.sum(q_ref[0].astype(jnp.float32)
                        * knh_ref[0].astype(jnp.float32),
                        axis=1, keepdims=True) * scale  # (hp, 1)
        vnh = vnh_ref[0].astype(jnp.float32)
        _online(s_new, lambda p: p * vnh)
        o_ref[0] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)
        cko_ref[0] = kn_ref[0].astype(cko_ref.dtype)
        cvo_ref[0] = vn_ref[0].astype(cvo_ref.dtype)


def _per_head(x, g: int, hp: int):
    """[b, 1, kv, d] → [b, hp, d]: each kv row repeated for the ``g``
    query heads of its group, zero-padded to ``hp`` rows."""
    b, _, kv, d = x.shape
    xh = jnp.repeat(x[:, 0], g, axis=1)
    if hp != kv * g:
        xh = jnp.concatenate(
            [xh, jnp.zeros((b, hp - kv * g, d), xh.dtype)], axis=1)
    return xh


def decode_attention(q, k_new, v_new, cache_k, cache_v, pos,
                     pad_lens=None, *, scale: Optional[float] = None,
                     block_k: int = DEFAULT_BLOCK_K, interpret: bool = False):
    """Fused decode step: append ``k_new/v_new`` at ``pos`` (in place via
    buffer aliasing) and attend ``q`` over cols ``[pad_lens, pos]``."""
    b, s, h, d = q.shape
    _, C, kv, _ = cache_k.shape
    assert s == 1, "decode kernel is single-query (s == 1)"
    g = h // kv
    hp = max(h, _MIN_SUBLANES)
    n = block_k * kv
    sc = scale if scale is not None else 1.0 / (d ** 0.5)
    cdt = cache_k.dtype

    # query heads as rows, in the cache dtype (the einsum path casts too);
    # rows past h are zero and sliced away
    q3 = q[:, 0].astype(cdt)
    if hp != h:
        q3 = jnp.concatenate([q3, jnp.zeros((b, hp - h, d), cdt)], axis=1)
    knh, vnh = _per_head(k_new, g, hp), _per_head(v_new, g, hp)
    kn3, vn3 = k_new[:, 0], v_new[:, 0]                # [b, kv, d]
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    pad_arr = (jnp.zeros((b,), jnp.int32) if pad_lens is None
               else jnp.asarray(pad_lens, jnp.int32).reshape(b))
    # block row r is (col r // kv, kv head r % kv); query head i belongs
    # to kv group i // g (padded rows reuse the last group: finite, unused)
    rows = np.arange(n)
    group = np.minimum(np.arange(hp) // g, kv - 1)
    bias = jnp.asarray(np.where(group[:, None] == rows[None, :] % kv,
                                0.0, _NEG_INF), jnp.float32)
    col = jnp.asarray((rows // kv)[None, :], jnp.int32)
    ck2 = cache_k.reshape(b, C * kv, d)
    cv2 = cache_v.reshape(b, C * kv, d)

    nk = C // block_k
    kernel = functools.partial(_decode_kernel, scale=sc, block_k=block_k)

    def per_row(ib, ik, pos_r, pad_r):
        return (ib, 0, 0)

    def const(ib, ik, pos_r, pad_r):
        return (0, 0)

    def stream(ib, ik, pos_r, pad_r):
        return (ib, ik, 0)

    def append(ib, ik, pos_r, pad_r):
        # block size kv over the C*kv row axis: block index == col index.
        # CONSTANT over the inner grid dim, so the revolving out buffer
        # writes back once per batch row — (kv, d) of HBM write per step
        return (ib, pos_r[0], 0)

    out, ck_out, cv_out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nk),
            in_specs=[
                pl.BlockSpec((1, hp, d), per_row),     # q
                pl.BlockSpec((1, hp, d), per_row),     # k_new per q head
                pl.BlockSpec((1, hp, d), per_row),     # v_new per q head
                pl.BlockSpec((1, kv, d), per_row),     # k_new
                pl.BlockSpec((1, kv, d), per_row),     # v_new
                pl.BlockSpec((hp, n), const),          # group bias
                pl.BlockSpec((1, n), const),           # col of each row
                pl.BlockSpec((1, n, d), stream),       # cache_k
                pl.BlockSpec((1, n, d), stream),       # cache_v
            ],
            out_specs=[
                pl.BlockSpec((1, hp, d), per_row),
                pl.BlockSpec((1, kv, d), append),
                pl.BlockSpec((1, kv, d), append),
            ],
            scratch_shapes=[
                pltpu.VMEM((hp, d), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hp, d), q.dtype),
            jax.ShapeDtypeStruct(ck2.shape, cdt),
            jax.ShapeDtypeStruct(cv2.shape, cache_v.dtype),
        ],
        # operand indices count the scalar-prefetch args: pos=0, pad=1,
        # q=2, knh=3, vnh=4, k_new=5, v_new=6, bias=7, col=8,
        # cache_k=9, cache_v=10
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            # every head scores every kv group's rows (masked after)
            flops=4 * b * hp * C * kv * d,
            bytes_accessed=(2 * b * C * kv * d * cdt.itemsize
                            + 2 * b * kv * d * cdt.itemsize
                            + b * h * d * q.dtype.itemsize),
            transcendentals=b * hp * C * kv),
        name="decode_attention",
        interpret=interpret,
    )(pos_arr, pad_arr, q3, knh, vnh, kn3, vn3, bias, col, ck2, cv2)

    return (out[:, :h].reshape(b, 1, h, d),
            ck_out.reshape(cache_k.shape), cv_out.reshape(cache_v.shape))
