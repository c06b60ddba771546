"""Absorbed multi-head latent attention (MLA) over a paged latent cache as a
Pallas TPU kernel: every row walks its LIVE latent pages, and ONE copy of a
page serves both the score and the value product.

An MLA layer (DeepSeek-V2, arXiv:2405.04434) caches one latent row a token,
``[c_kv | k_rope]``, shared by every head; head ``i``'s key is
``[W_UK,i c_kv | k_rope]`` and its value ``W_UV,i c_kv``.  Decode absorbs the
two up-projections into the query and the output::

    score_ij = (W_UK,i^T q_nope_i) . c_kv_j + q_rope_i . k_rope_j
    o_i      = W_UV,i (sum_j p_ij c_kv_j)

so all ``h`` query heads attend over the same ``[tokens, width]`` page whose
first ``latent`` lanes are also the value: per page one ``[S*h, width] x
[width, P]`` matmul and one ``[S*h, P] x [P, latent]``.  The caller brings
the absorbed query ``[W_UK^T q_nope | q_rope]`` padded to the page's width,
and applies ``W_UV`` to what comes back.

Shape contract:

- q         [R, S, h, width] — S query tokens a row, absorbed (above)
- pages     [N, P, width]    — ONE layer's latent arena, this step's rows
  already scattered in; lanes past ``latent + rope`` are zero padding
- tables    [R, MP] int32, positions [R] int32, n_tok [R] int32: as
  :mod:`paged_decode_attention` has them

Query ``i`` of a row attends columns ``<= positions + i``.  Returns
``[R, S, h, latent]``; rows with ``n_tok == 0`` come back zero.

The grid runs over rows, so a row's query and output blocks ride Pallas's
own pipeline (128 rows x 128 heads do not fit VMEM whole); the pages come by
the manual multi-buffered DMA of :mod:`paged_decode_attention` over the same
work list, which keeps prefetching across row boundaries.  Scores, softmax
and accumulation are float32; the cache dtype multiplies.

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

from .decode_attention import (_LANES, _MIN_SUBLANES, _NEG_INF, _VMEM_BUDGET,
                               _sublane_rows)
from .paged_decode_attention import _MAX_WORK, N_BUF, _query_rows, _work_list

KERNEL_NAME = "mla_paged_decode_attention"


def mla_paged_decode_attention_refusal(q_shape, arena_shape, tables_shape,
                                       dtype, latent: int, *,
                                       interpret: bool = False
                                       ) -> Optional[str]:
    """None when the kernel takes the call, else the reason it does not
    (the caller's ``kernel_fallback`` reason)."""
    if len(q_shape) != 4 or len(arena_shape) != 3 or len(tables_shape) != 2:
        return "rank"
    R, s, h, width = q_shape
    _, P, wc = arena_shape
    if width != wc or not 0 < latent <= width or tables_shape[0] != R:
        return "shape"
    # a latent row is whole lane registers on the chip, and the value is a
    # lane-aligned slice of it; the interpreter (CPU tests) takes any
    # multiple of a sublane
    lane = _MIN_SUBLANES if interpret else _LANES
    if width % lane or latent % lane:
        return "latent_width"
    if P % _sublane_rows(dtype) != 0:
        return "page_rows"
    if R * tables_shape[1] > _MAX_WORK:
        return "table_size"
    hp = _query_rows(s, h)
    item = jnp.dtype(dtype).itemsize
    vmem = (N_BUF * P * width * item            # pages in flight
            + 2 * hp * (width + latent) * item  # a row's q and out, twice
            + hp * latent * 4 + 3 * hp * P * 4)  # accumulator, score panels
    if vmem > _VMEM_BUDGET:
        return "vmem"
    return None


def _mla_kernel(nw_ref, start_ref, page_ref, pos_ref, live_ref,
                q_ref, qoff_ref, pages_hbm, o_ref,
                buf, sem, acc_ref, m_ref, l_ref, *,
                scale: float, page_tokens: int, latent: int):
    r = pl.program_id(0)
    nw, first, n = nw_ref[0], start_ref[r], live_ref[r]

    def copy(w, slot):
        return pltpu.make_async_copy(pages_hbm.at[page_ref[w]], buf.at[slot],
                                     sem.at[slot])

    @pl.when(r == 0)
    def _prime():
        for i in range(N_BUF - 1):
            @pl.when(i < nw)
            def _start(i=i):
                copy(i, i).start()

    acc_ref[:] = jnp.zeros_like(acc_ref)
    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    q = q_ref[0]                                       # (hp, width)

    def _bcast(col):
        return jnp.broadcast_to(col, (col.shape[0], _LANES))

    def body(j, carry):
        w = first + j
        slot = jax.lax.rem(w, N_BUF)
        ahead = w + (N_BUF - 1)

        # the slot refilled here is the one item w - 1 was scored from; the
        # item may be a later row's: the queue stays full across rows
        @pl.when(ahead < nw)
        def _prefetch():
            copy(ahead, jax.lax.rem(ahead, N_BUF)).start()

        copy(w, slot).wait()
        page = buf[slot]                               # (P, width)
        s = jax.lax.dot_general(q, page, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = j * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(col <= pos_ref[r] + qoff_ref[...], s, _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a panel with nothing to see (a speculative row's earlier query on
        # its last page) keeps m where it was; while m is still -inf a
        # finite reference point collapses p / alpha to exact zeros
        m_ok = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_ok)
        alpha = jnp.exp(m_prev - m_ok)
        l_ref[:] = _bcast(l_prev * alpha + jnp.sum(p, axis=1, keepdims=True))
        m_ref[:] = _bcast(m_new)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(page.dtype), page[:, :latent], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, n, body, 0)
    # an idle row walked nothing: zero, not 0 / 0
    l = l_ref[:, :1]
    o_ref[0] = jnp.where(l > 0.0, acc_ref[:] / jnp.where(l > 0.0, l, 1.0),
                         0.0).astype(o_ref.dtype)


# jitted: the layers of a program share one trace and one Mosaic lowering
@functools.partial(jax.jit,
                   static_argnames=("latent", "scale", "interpret"))
def mla_paged_decode_attention(q, pages, tables, positions, n_tok, *,
                               latent: int, scale: float,
                               interpret: bool = False):
    """Attend each row of the absorbed query ``q`` over its live pages of
    one layer's latent arena (module docstring)."""
    R, S, h, width = q.shape
    N, P, _ = pages.shape
    hp = _query_rows(S, h)
    cdt = pages.dtype

    # (query, head) pairs as rows, in the cache dtype; rows past S * h are
    # zero and sliced away
    q3 = q.reshape(R, S * h, width).astype(cdt)
    if hp != S * h:
        q3 = jnp.concatenate(
            [q3, jnp.zeros((R, hp - S * h, width), cdt)], axis=1)
    qrow = np.minimum(np.arange(hp), S * h - 1)
    qoff = jnp.asarray((qrow // h)[:, None], jnp.int32)

    tables = tables.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    n_work, _, _, page, live = _work_list(tables, positions, n_tok, P)
    start = (jnp.cumsum(live) - live).astype(jnp.int32)

    out = pl.pallas_call(
        functools.partial(_mla_kernel, scale=float(scale), page_tokens=P,
                          latent=latent),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(R,),
            in_specs=[
                pl.BlockSpec((1, hp, width), lambda r, *_: (r, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hp, latent), lambda r, *_: (r, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((N_BUF, P, width), cdt),
                pltpu.SemaphoreType.DMA((N_BUF,)),
                pltpu.VMEM((hp, latent), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, hp, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(n_work, start, page, positions, live, q3, qoff, pages)
    return out[:, :S * h].reshape(R, S, h, latent)
