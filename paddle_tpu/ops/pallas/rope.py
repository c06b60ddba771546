"""Fused rotary position embedding as a Pallas TPU kernel.

Capability parity: reference fused CUDA rope
(`paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu`, python surface
`incubate/nn/functional/fused_rotary_position_embedding.py`). Applies the
rotate-half RoPE to q and k in one VMEM pass per block, avoiding the
intermediate rotate/concat arrays of the unfused path."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import sds_like
from jax.experimental import pallas as pl

_BLOCK_S = 128  # seq rows per block; keeps (Bs, h, d) f32 temps inside VMEM


def _rope_kernel(q_ref, k_ref, cos_ref, sin_ref, oq_ref, ok_ref):
    cos = cos_ref[:].astype(jnp.float32)[:, None, :]   # (Bs, 1, d)
    sin = sin_ref[:].astype(jnp.float32)[:, None, :]

    def rotate_half(v):
        half = v.shape[-1] // 2
        return jnp.concatenate([-v[..., half:], v[..., :half]], axis=-1)

    q = q_ref[0].astype(jnp.float32)                   # (Bs, h, d)
    k = k_ref[0].astype(jnp.float32)
    oq_ref[0] = (q * cos + rotate_half(q) * sin).astype(oq_ref.dtype)
    ok_ref[0] = (k * cos + rotate_half(k) * sin).astype(ok_ref.dtype)


def _rope_raw(q, k, cos_s, sin_s, interpret):
    b, s, hq, d = q.shape
    hk = k.shape[2]
    if s <= _BLOCK_S:
        bs = s
    else:
        bs = _BLOCK_S - _BLOCK_S % 8
        while bs >= 8 and s % bs:
            bs -= 8
        if bs < 8:
            bs = s  # no aligned divisor; single full-seq block
    return pl.pallas_call(
        _rope_kernel,
        grid=(b, s // bs),
        in_specs=[
            pl.BlockSpec((1, bs, hq, d), lambda ib, i: (ib, i, 0, 0)),
            pl.BlockSpec((1, bs, hk, d), lambda ib, i: (ib, i, 0, 0)),
            pl.BlockSpec((bs, d), lambda ib, i: (i, 0)),
            pl.BlockSpec((bs, d), lambda ib, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, hq, d), lambda ib, i: (ib, i, 0, 0)),
            pl.BlockSpec((1, bs, hk, d), lambda ib, i: (ib, i, 0, 0)),
        ],
        out_shape=[
            sds_like(q.shape, q.dtype, q),
            sds_like(k.shape, k.dtype, k),
        ],
        name="fused_rope",
        interpret=interpret,
    )(q, k, cos_s, sin_s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def fused_rope(q, k, cos_s, sin_s, interpret: bool = False):
    """q [b,s,hq,d], k [b,s,hk,d], cos_s/sin_s [s,d] → (q_rot, k_rot).

    The rotation is orthogonal, so the backward is the same kernel with the
    sine table negated (R(θ)ᵀ = R(-θ)) — no residuals besides the tables."""
    return tuple(_rope_raw(q, k, cos_s, sin_s, interpret))


def _rope_fwd(q, k, cos_s, sin_s, interpret):
    return tuple(_rope_raw(q, k, cos_s, sin_s, interpret)), (cos_s, sin_s)


def _rope_bwd(interpret, res, g):
    cos_s, sin_s = res
    dq, dk = g
    dq_in, dk_in = _rope_raw(dq, dk, cos_s, -sin_s, interpret)
    return dq_in, dk_in, None, None


fused_rope.defvjp(_rope_fwd, _rope_bwd)
