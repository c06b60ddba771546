"""Grouped matmul as a Pallas TPU kernel: rows sorted by group, each group
multiplied by its own matrix, nothing padded to a capacity.

This is the expert matmul of a dropless mixture-of-experts layer
(:mod:`paddle_tpu.nn.layer.moe`): the token-expert pairs are sorted by
expert, so expert ``g``'s rows are contiguous::

    out[start_g : start_g + sizes_g] = x[start_g : start_g + sizes_g] @ w[g]

Shape contract:

- x            [M, K]    — rows sorted by group; rows past ``sum(sizes)``
  belong to no group (the pairs of experts this chip does not hold)
- w            [G, K, N] — one matrix a group
- group_sizes  [G] int32

Returns ``[M, N]`` in ``x``'s dtype, rows past ``sum(sizes)`` zero.

The rows are cut into tiles of ``tm``; the work list holds one item per
(group, row tile) pair that overlaps, groups in order (the scheme of
MegaBlocks, arXiv:2211.15841): at most ``M / tm + G - 1`` items, and the
grid's middle axis is as long as the list IS this call, so a step with four
rows an expert visits ``G`` items and a tile no group touches is never
visited.  Each item accumulates ``x_tile @ w[g]`` over ``K`` in float32 and
stores the rows of the tile that are the group's.  At a few rows a group
every item streams ``w[g]`` once: the kernel is bound by the weights' bytes,
which is the least an expert layer can do at that load.

No VJP of its own: :func:`paddle_tpu.nn.layer.moe.grouped_matmul` gives it
``jax.lax.ragged_dot``'s.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _LANES, _MIN_SUBLANES, _VMEM_BUDGET, \
    _sublane_rows

KERNEL_NAME = "moe_grouped_matmul"

# row tile, and the largest K / N tiles: a weight tile of 1024 x 1024 bf16 is
# 2 MiB a copy, long enough that the step's fixed cost is small beside it
TILE_M = 128
MAX_TILE = 1024


def _tile(n: int, unit: int) -> Optional[int]:
    """The largest divisor of ``n`` that is a multiple of ``unit`` and at
    most ``MAX_TILE`` (``n`` itself where it is smaller).  A width no
    multiple of ``unit`` divides (1856 = 14.5 lane registers) is one tile,
    the whole axis, which a block may always be, up to ``2 * MAX_TILE``;
    else None."""
    for t in range(min(n, MAX_TILE) // unit * unit, 0, -unit):
        if n % t == 0:
            return t
    return n if n <= 2 * MAX_TILE and n % _MIN_SUBLANES == 0 else None


def _tiles(m: int, k: int, n: int, dtype, interpret: bool
           ) -> Optional[Tuple[int, int, int]]:
    lane = _MIN_SUBLANES if interpret else _LANES
    tm = min(TILE_M, m)
    if m % tm or tm % _sublane_rows(dtype):
        return None
    tk, tn = _tile(k, lane), _tile(n, lane)
    return None if tk is None or tn is None else (tm, tk, tn)


def grouped_matmul_refusal(x_shape, w_shape, dtype, *,
                           interpret: bool = False) -> Optional[str]:
    """None when the kernel takes the call, else the reason it does not
    (the caller's ``kernel_fallback`` reason)."""
    if len(x_shape) != 2 or len(w_shape) != 3:
        return "rank"
    (m, k), (g, kw, n) = x_shape, w_shape
    if k != kw or g < 1:
        return "shape"
    tiles = _tiles(m, k, n, dtype, interpret)
    if tiles is None:
        return "tiling"
    tm, tk, tn = tiles
    item = jnp.dtype(dtype).itemsize
    vmem = 2 * (tm * tk + tk * tn + tm * tn) * item + tm * tn * 4
    if vmem > _VMEM_BUDGET:
        return "vmem"
    return None


def _work_list(group_sizes, m: int, tm: int):
    """The call's (group, row tile) pairs, groups in order and a group's
    tiles in order, padded to ``m / tm + G - 1`` by repeating the last; their
    count; and each group's first row and the row behind its last."""
    G = group_sizes.shape[0]
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    work_ends = jnp.cumsum(tiles)
    n_work = work_ends[-1]
    w = jnp.minimum(jnp.arange(m // tm + G - 1, dtype=jnp.int32),
                    jnp.maximum(n_work - 1, 0))
    group = jnp.minimum(jnp.sum(w[:, None] >= work_ends[None, :], axis=1),
                        G - 1).astype(jnp.int32)
    tile = (first[group] + w - (work_ends - tiles)[group]).astype(jnp.int32)
    return n_work[None].astype(jnp.int32), group, tile, starts, ends


def _gmm_kernel(nw_ref, group_ref, tile_ref, start_ref, end_ref,
                x_ref, w_ref, o_ref, acc_ref, *, tm: int, k_tiles: int,
                transposed: bool):
    i, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # w_ref[0] is [tk, tn], or [tn, tk] where the weights lie transposed
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[0],
        (((1,), (1 if transposed else 0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == k_tiles - 1)
    def _store():
        g, t = group_ref[i], tile_ref[i]
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        mine = (row >= start_ref[g]) & (row < end_ref[g])
        # the tile's block stays in VMEM while consecutive items share it:
        # the first of them finds what the last tile left there
        first = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)
        prev = jnp.where(first, jnp.zeros_like(o_ref), o_ref[...])
        o_ref[...] = jnp.where(mine, acc_ref[...].astype(o_ref.dtype), prev)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(x, w, group_sizes, *, interpret: bool = False):
    """``x [M, K]`` sorted by group times ``w [G, K, N]`` (module
    docstring)."""
    m, k = x.shape
    _, _, n = w.shape
    tm, tk, tn = _tiles(m, k, n, x.dtype, interpret)
    n_work, group, tile, starts, ends = _work_list(group_sizes, m, tm)

    # A width that is not whole lane registers (1856) is no minor axis the
    # chip keeps: it lays ``w`` [G, K, N] out with K minor, so the kernel
    # takes that view as it lies ([G, N, K], the swap is a bitcast) and
    # contracts both operands' last axes, rather than have XLA re-lay the
    # weights out ahead of every call
    transposed = n % _LANES != 0 and k % _LANES == 0
    w = w.astype(x.dtype)
    if transposed:
        w = jnp.swapaxes(w, 1, 2)
        w_spec = pl.BlockSpec(
            (1, tn, tk), lambda j, i, kk, nw, g, t, s, e: (g[i], j, kk))
    else:
        w_spec = pl.BlockSpec(
            (1, tk, tn), lambda j, i, kk, nw, g, t, s, e: (g[i], kk, j))
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, k_tiles=k // tk,
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, n_work[0], k // tk),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, i, kk, nw, g, t, s, e: (t[i], kk)),
                w_spec,
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, i, kk, nw, g, t, s, e: (t[i], j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name=KERNEL_NAME,
        interpret=interpret,
    )(n_work, group, tile, starts, ends, x, w)
    # tiles no group touches were never written
    rows = jnp.arange(m, dtype=jnp.int32)[:, None]
    return jnp.where(rows < ends[-1], out, jnp.zeros_like(out))
