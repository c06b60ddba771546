"""One decode token of the Mamba-2 recurrence as a Pallas TPU kernel that
touches only the LIVE rows' state, in place.

A serving decode step must read and write every live row's recurrent state
``[H, P, N]`` float32 (2 MB a row and layer at 64 x 64 x 128) and nothing
hides that traffic.  A plain ``jax.numpy`` update over the whole
``[max_batch, H, P, N]`` arena pays it for every row, live or not; here the
arena is aliased to the output and the grid visits live rows only::

    S    = exp(dt * A) * S + (dt * x) (outer) B        (written back)
    y    = S C                                          (D * x is added outside)

Shape contract (one state layer):

- state [R, H, P, N] float32 — the arena, aliased to the first output
- live  [R] bool             — rows to update; the others are neither read
  nor written, and their ``y`` comes back zero
- x [R, H, P], dt [R, H] (after the softplus), A [H] (negative), D [H]
- B, C [R, G, N]             — ``G`` groups of ``H / G`` consecutive heads:
  head ``h`` reads row ``h // (H / G)``

Grid ``(R, H / hb)``: a step moves one ``[hb, P, N]`` block (1 MB at hb =
32) in and out, and with it the B / C rows of the groups its heads lie in
(at 8 groups of 8 heads a block spans 4: the block is not shrunk to a
group), so the per-head loop indexes them statically.  The rows are visited
in the order of a list built in the program — live rows first — and every
step past the last live one maps to the block of the step before it, so the
pipeline sees an unchanged block index and moves no data for it.  Per head
the state tile ``[P, N]`` has the head's width on sublanes and the state on
lanes: ``B`` and ``C`` are lane rows, and the per-(head, p) coefficients
arrive transposed (``[P, hb]``) so that a head's column broadcasts along
lanes.

No VJP: decode runs under ``no_grad`` by construction.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _LANES, _MIN_SUBLANES, _VMEM_BUDGET

KERNEL_NAME = "ssm_state_update"

# bytes of state one grid step moves each way: >= 512 KB keeps the 0.35 us a
# grid step costs under a tenth of the block's DMA time
_BLOCK_BYTES = 1 << 20


def _head_block(H: int, P: int, N: int, G: int = 1) -> int:
    """Heads a grid step moves: the most that ``_BLOCK_BYTES`` hold, that
    divide ``H``, and that are whole groups or a whole part of one."""
    hb = max(min(H, _BLOCK_BYTES // (P * N * 4)), 1)
    per_group = H // G
    while H % hb or (hb % per_group and per_group % hb):
        hb -= 1
    return hb


def ssm_state_update_refusal(state_shape, dtype, b_shape) -> Optional[str]:
    """None when the kernel takes the call, else the reason it does not
    (the caller's ``kernel_fallback`` reason)."""
    if len(state_shape) != 4 or len(b_shape) != 3:
        return "rank"
    if jnp.dtype(dtype) != jnp.float32:
        return "state_dtype"
    _, H, P, N = state_shape
    G = b_shape[1]
    if G < 1 or H % G:
        return "head_groups"
    if N % _LANES or P % _MIN_SUBLANES:
        return "state_tile"
    hb = _head_block(H, P, N, G)
    # state in and out, double-buffered, and the small operands
    if 4 * hb * P * N * 4 + 8 * P * max(hb, _LANES) * 4 > _VMEM_BUDGET:
        return "vmem"
    return None


def _kernel(rows_ref, nlive_ref, coef_ref, bc_ref, s_ref, o_ref, y_ref, *,
            hb: int, per_group: int):
    del rows_ref
    nlive = nlive_ref[0]
    visit = pl.program_id(0) < nlive

    @pl.when(visit)
    def _update():
        dA_t = coef_ref[0, 0, 0]                        # (P, hb)
        dtx_t = coef_ref[0, 0, 1]
        for h in range(hb):
            g = h // per_group      # the head's group within the block
            b_row = bc_ref[0, 0, 2 * g:2 * g + 1, :]    # (1, N)
            c_row = bc_ref[0, 0, 2 * g + 1:2 * g + 2, :]
            dA = dA_t[:, h:h + 1]                       # (P, 1)
            dtx = dtx_t[:, h:h + 1]
            s = dA * s_ref[0, h] + dtx * b_row          # (P, N)
            o_ref[0, h] = s
            y_ref[0, 0, :, h:h + 1] = jnp.sum(s * c_row, axis=1,
                                              keepdims=True)

    # a step with no live row at all would write back a block it never
    # filled: hand the state through unchanged
    @pl.when(jnp.logical_and(jnp.logical_not(visit), nlive == 0))
    def _untouched():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


# jitted: a program calls this once a state layer with the same shapes, and
# an inner jit is traced and lowered to Mosaic once for all of them
@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_state_update(state, live, x, dt, A, B, C, D, *,
                     interpret: bool = False):
    """Update the live rows of ``state`` in place by one token and return
    ``(y [R, H, P] in x's dtype, state)`` (module docstring)."""
    R, H, P, N = state.shape
    G = B.shape[1]
    hb = _head_block(H, P, N, G)
    nj = H // hb
    # a head block holds ``gb`` whole groups, or ``hb`` heads of one
    gb = max(hb // (H // G), 1)
    f32 = jnp.float32

    live = live.astype(bool)
    nlive = jnp.sum(live).astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    # past the last live row the list repeats it: no new block to fetch
    rows = order[jnp.minimum(jnp.arange(R), jnp.maximum(nlive - 1, 0))]

    dt = dt.astype(f32)
    xf = x.astype(f32)
    dA = jnp.exp(dt * A.astype(f32))                            # [R, H]
    coef = jnp.stack([jnp.broadcast_to(dA[:, :, None], (R, H, P)),
                      dt[:, :, None] * xf], axis=1)             # [R, 2, H, P]
    # [R, nj, 2, P, hb]: a head block's coefficients, the head on lanes
    coef = coef.reshape(R, 2, nj, hb, P).transpose(0, 2, 1, 4, 3)
    # [R, G / gb, 2 * gb, N]: a head block's groups, B and C of a group
    # on neighbouring rows
    bc = jnp.stack([B, C], axis=2).astype(f32).reshape(R, G // gb, 2 * gb, N)

    def row_block(i, j, rows_ref, nlive_ref):
        return rows_ref[i], jnp.where(i < nlive_ref[0], j, nj - 1)

    def group_block(i, j, rows_ref, nlive_ref):
        r, j = row_block(i, j, rows_ref, nlive_ref)
        return r, j * hb // (gb * (H // G))

    new, y = pl.pallas_call(
        functools.partial(_kernel, hb=hb, per_group=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(R, nj),
            in_specs=[
                pl.BlockSpec((1, 1, 2, P, hb),
                             lambda i, j, r, n: (*row_block(i, j, r, n),
                                                 0, 0, 0)),
                pl.BlockSpec((1, 1, 2 * gb, N),
                             lambda i, j, r, n: (*group_block(i, j, r, n),
                                                 0, 0)),
                pl.BlockSpec((1, hb, P, N),
                             lambda i, j, r, n: (*row_block(i, j, r, n),
                                                 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, P, N),
                             lambda i, j, r, n: (*row_block(i, j, r, n),
                                                 0, 0)),
                pl.BlockSpec((1, 1, P, hb),
                             lambda i, j, r, n: (*row_block(i, j, r, n),
                                                 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((R, nj, P, hb), f32)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=KERNEL_NAME,
        interpret=interpret,
    )(rows, nlive[None], coef, bc, state)
    # rows the grid never visited hold whatever the buffer held
    y = y.transpose(0, 1, 3, 2).reshape(R, H, P)
    y = jnp.where(live[:, None, None],
                  y + D.astype(f32)[None, :, None] * xf, 0.0)
    return y.astype(x.dtype), new
