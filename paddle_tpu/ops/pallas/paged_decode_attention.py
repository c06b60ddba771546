"""Decode attention over a paged KV pool as a Pallas TPU kernel: every row
walks its LIVE pages straight out of one layer's page arenas.

The serving engine's decode program used to copy each row's whole padded
page table out of the pool (``kp[tables]``: ``R x MP`` pages written and
read back, every layer) and then ran a dense softmax over all
``MP x page_tokens`` columns however short the row was.  Here nothing is
gathered: the arenas stay in HBM (``pl.ANY``), and one kernel call loops
over the step's *work list* — one item per live (row, page) pair, rows in
order, a row's pages in order — bringing each page into VMEM by a manual
multi-buffered DMA while the previous one is scored.  The work list is
built inside the program from ``positions`` and ``n_tok`` (live pages of a
row: ``ceil((pos + n_tok) / P)``, none for an idle row), so there are no
dead grid steps, ``TRASH_PAGE`` slots are never visited and the DMA queue
stays full across row boundaries.

Shape contract:

- q         [R, S, h, d]   — S query tokens a row (1; 1 + k speculative)
- k, v      [N, P, kv, d]  — ONE layer's arenas, as the serving engine lays
  them out, this step's tokens already scattered in (the scatter stays in
  XLA); or ``[N, P, kv*d]``, a token's heads merged into one row, which is
  how the engine keeps heads narrower than a lane register
- tables    [R, MP] int32  — physical page of each logical page
- positions [R] int32      — absolute position of the row's first query
- n_tok     [R] int32      — valid queries of the row (0: idle row)
- scale     static float   — multiplies the scores (None: ``d ** -0.5``)

Query ``i`` of a row attends columns ``<= positions + i``.  Returns
``out [R, S, h, d]``; rows with ``n_tok == 0`` come back zero.

A 4-D page is used through the ``[N, P*kv, d]`` view (rows ordered slot, kv
head): Mosaic cannot block one head out of the second-minor ``kv`` axis, so
as in :mod:`decode_attention` every query head is scored against every
row of the page in one ``[S*h, d] x [d, P*kv]`` matmul and an additive
group bias keeps the rows of the head's own kv group.  Scores, softmax and
accumulation are f32; the cache dtype multiplies, as the einsum has it.

A merged page ``[P, kv*d]`` is used as it lies, one row a token: splitting
its heads apart outside the kernel re-lays the whole pool out.  Each query
head is widened to ``kv*d`` lanes that are zero outside its own kv head's
``d`` (the other heads' lanes add exact zeros to its score, so the group
bias is all zeros), the same kernel body runs with one "kv head" of width
``kv*d``, and the head's own ``d`` lanes are taken from the wide output.

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

KERNEL_NAME = "paged_decode_attention"

# pages in flight: one being scored, the rest on their way in
N_BUF = 3

# three int32 work-list arrays of R * MP entries ride scalar prefetch (SMEM)
_MAX_WORK = 16384


def _query_rows(s: int, h: int) -> int:
    return -(-(s * h) // _MIN_SUBLANES) * _MIN_SUBLANES


def paged_decode_attention_refusal(q_shape, arena_shape, tables_shape, dtype,
                                   *, interpret: bool = False
                                   ) -> Optional[str]:
    """None when the kernel takes the call, else the reason it does not
    (the caller's ``kernel_fallback`` reason)."""
    if len(q_shape) != 4 or len(arena_shape) not in (3, 4) or \
            len(tables_shape) != 2:
        return "rank"
    R, s, h, d = q_shape
    P, width = arena_shape[1], arena_shape[-1]
    if len(arena_shape) == 3:       # a token's heads merged into one row
        if width % d != 0:
            return "shape"
        kv, n = width // d, P
    else:
        kv, n = arena_shape[2], P * arena_shape[2]
        if width != d:
            return "shape"
    if kv < 1 or h % kv != 0 or tables_shape[0] != R:
        return "shape"
    # the kernel's page is n rows of ``width`` lanes: whole lane registers
    # on the chip; the interpreter (CPU tests) takes any multiple of a
    # sublane
    if width % (_MIN_SUBLANES if interpret else _LANES) != 0:
        return "head_dim"
    if n % _sublane_rows(dtype) != 0:
        return "page_rows"
    if R * tables_shape[1] > _MAX_WORK:
        return "table_size"
    hp = _query_rows(s, h)
    item = jnp.dtype(dtype).itemsize
    vmem = (2 * N_BUF * n * width * item   # K and V pages in flight
            + 2 * R * hp * width * item    # q and out, whole
            + hp * width * 4               # the accumulator
            + 4 * hp * n * 4)              # bias, scores, probabilities
    if vmem > _VMEM_BUDGET:
        return "vmem"
    return None


def _work_list(tables, positions, n_tok, page_tokens: int):
    """The step's (row, page index, physical page) triples, rows in order
    and a row's pages in order, padded to ``R * MP``; their count; and the
    live pages of each row (``ceil((pos + n_tok) / P)``, none when idle)."""
    R, MP = tables.shape
    live = jnp.where(
        n_tok > 0,
        jnp.clip((positions + n_tok + page_tokens - 1) // page_tokens, 0, MP),
        0).astype(jnp.int32)
    ends = jnp.cumsum(live)
    w = jnp.arange(R * MP, dtype=jnp.int32)
    row = jnp.minimum(jnp.sum(w[:, None] >= ends[None, :], axis=1), R - 1) \
        .astype(jnp.int32)
    j = jnp.clip(w - (ends - live)[row], 0, MP - 1).astype(jnp.int32)
    return ends[-1:].astype(jnp.int32), row, j, tables[row, j], live


def _paged_kernel(nw_ref, row_ref, j_ref, page_ref, pos_ref, live_ref,
                  q_ref, bias_ref, col_ref, qoff_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sem, acc_ref, m_ref, l_ref, *,
                  scale: float, page_tokens: int):
    nw = nw_ref[0]
    # idle rows are never visited: their output is zero, not stale VMEM
    o_ref[...] = jnp.zeros_like(o_ref)

    def copies(w, slot):
        page = page_ref[w]
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[slot],
                                      sem.at[1, slot]))

    def start(w, slot):
        for c in copies(w, slot):
            c.start()

    for i in range(N_BUF - 1):
        @pl.when(i < nw)
        def _prime(i=i):
            start(i, i)

    def _bcast(col):
        return jnp.broadcast_to(col, (col.shape[0], _LANES))

    def body(w, carry):
        slot = jax.lax.rem(w, N_BUF)
        ahead = w + (N_BUF - 1)

        # the slot refilled here is the one item w - 1 was scored from
        @pl.when(ahead < nw)
        def _prefetch():
            start(ahead, jax.lax.rem(ahead, N_BUF))

        r, j = row_ref[w], j_ref[w]

        @pl.when(j == 0)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
            l_ref[:] = jnp.zeros_like(l_ref)

        for c in copies(w, slot):
            c.wait()
        k, v = kbuf[slot], vbuf[slot]                  # (P*kv, d)
        s = jax.lax.dot_general(q_ref[r], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = j * page_tokens + col_ref[...]           # (1, P*kv)
        seen = col <= pos_ref[r] + qoff_ref[...]       # (hp, P*kv)
        # bias is 0 on the head's own kv group and -inf elsewhere
        s = jnp.where(seen, s + bias_ref[...], _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a panel with nothing to see (a speculative row's earlier query on
        # its last page) keeps m where it was; while m is still -inf a
        # finite reference point collapses p / alpha to exact zeros instead
        # of exp(-inf - -inf)
        m_ok = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_ok)
        alpha = jnp.exp(m_prev - m_ok)
        l_ref[:] = _bcast(l_prev * alpha + jnp.sum(p, axis=1, keepdims=True))
        m_ref[:] = _bcast(m_new)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(j == live_ref[r] - 1)
        def _finalize():
            o_ref[r] = (acc_ref[:] / l_ref[:, :1]).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, nw, body, 0)


# jitted: a program calls this once a layer with the same shapes, and an
# inner jit is traced and lowered to Mosaic once for all of them (16 plain
# calls cost every process 0.9 s of set-up before its compile-cache lookup)
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k, v, tables, positions, n_tok, *,
                           scale: Optional[float] = None,
                           interpret: bool = False):
    """Attend each row of ``q`` over its live pages of one layer's arenas
    (module docstring)."""
    R, S, h, head_dim = q.shape
    if scale is None:
        scale = 1.0 / (head_dim ** 0.5)
    # the kernel's page: P * kv rows of d lanes, kv of them a token
    merged = k.ndim == 3
    (N, P), kv, d = k.shape[:2], 1 if merged else k.shape[2], k.shape[-1]
    if merged:
        # one "kv head" as wide as the token's row; query head i is zero
        # outside the lanes of its own kv head, i // group
        group = h // (d // head_dim)
        own = (np.arange(h) // group)[:, None] \
            == np.arange(d // head_dim)[None, :]
        q = jnp.where(own[:, :, None], q[:, :, :, None, :], 0) \
            .reshape(R, S, h, d)
    g = h // kv
    hp = _query_rows(S, h)
    n = P * kv
    cdt = k.dtype

    # (query, head) pairs as rows, in the cache dtype (the einsum path
    # casts too); rows past S * h are zero and sliced away
    q3 = q.reshape(R, S * h, d).astype(cdt)
    if hp != S * h:
        q3 = jnp.concatenate(
            [q3, jnp.zeros((R, hp - S * h, d), cdt)], axis=1)
    # page row c is (slot c // kv, kv head c % kv); query row i is (query
    # i // h, head i % h) of kv group (i % h) // g
    rows, qrow = np.arange(n), np.minimum(np.arange(hp), S * h - 1)
    bias = jnp.asarray(np.where(((qrow % h) // g)[:, None]
                                == rows[None, :] % kv, 0.0, _NEG_INF),
                       jnp.float32)
    col = jnp.asarray((rows // kv)[None, :], jnp.int32)
    qoff = jnp.asarray((qrow // h)[:, None], jnp.int32)

    tables = tables.astype(jnp.int32)
    positions = positions.astype(jnp.int32)
    n_work, row, j, page, live = _work_list(tables, positions, n_tok, P)

    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, page_tokens=P),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(1,),
            in_specs=[vmem, vmem, vmem, vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((N_BUF, n, d), cdt),
                pltpu.VMEM((N_BUF, n, d), v.dtype),
                pltpu.SemaphoreType.DMA((2, N_BUF)),
                pltpu.VMEM((hp, d), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((R, hp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(n_work, row, j, page, positions, live, q3, bias, col, qoff,
      k.reshape(N, n, d), v.reshape(N, n, d))
    out = out[:, :S * h].reshape(R, S, h, d)
    if merged:      # each head's own lanes of the wide output
        out = jnp.concatenate(
            [out[:, :, c * group:(c + 1) * group,
                 c * head_dim:(c + 1) * head_dim]
             for c in range(d // head_dim)], axis=2)
    return out
