"""Fused RMSNorm as a Pallas TPU kernel (forward + backward).

Capability parity: the reference's fused CUDA rms_norm
(`paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu`, python surface
`incubate/nn/functional/fused_rms_norm.py`). One pass over HBM per direction:
the forward saves the per-row reciprocal RMS; the backward fuses dx and the
cross-row dw reduction in a single kernel sweep."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import sds_like
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_BLOCK_ROWS = 512


def _fwd_kernel(x_ref, w_ref, o_ref, rstd_ref, *, eps: float):
    x = x_ref[:].astype(jnp.float32)                       # (Bn, H)
    ms = jnp.mean(jnp.square(x), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    rstd_ref[:] = rstd
    o_ref[:] = (x * rstd * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _bwd_kernel(x_ref, w_ref, rstd_ref, dy_ref, dx_ref, dw_ref, dw_acc):
    i, n = pl.program_id(0), pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)

    x = x_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]                                     # (Bn, 1)
    xhat = x * rstd
    dyw = dy * w
    # dx = rstd * (dy*w - xhat * mean(dy*w*xhat))
    h = x.shape[1]
    m = jnp.sum(dyw * xhat, axis=1, keepdims=True) / h
    dx_ref[:] = (rstd * (dyw - xhat * m)).astype(dx_ref.dtype)
    dw_acc[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _finalize():
        dw_ref[:] = dw_acc[:].astype(dw_ref.dtype)


def _rows(x):
    h = x.shape[-1]
    n = x.size // h
    return x.reshape(n, h), n, h


def _block_rows(n: int, h: int, bytes_per_elem: int = 28) -> int:
    """Largest divisor of n that is sublane-aligned (mult of 8) and keeps the
    kernel's working set (``bytes_per_elem`` per element, double-buffered —
    default 28 fits the norm kernels) inside the ~16M scoped VMEM, or n
    itself for small inputs (full-array blocks are always legal)."""
    cap = min(_BLOCK_ROWS, max(8, (448 * 1024) * 28 // bytes_per_elem // h))
    if n <= cap:
        return n
    b = cap - cap % 8
    while b >= 8:
        if n % b == 0:
            return b
        b -= 8
    return n


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def fused_rms_norm(x, weight, eps: float = 1e-6, interpret: bool = False):
    """RMSNorm over the last axis: x [..., H], weight [H] → [..., H]."""
    out, _ = _rms_fwd(x, weight, eps, interpret)
    return out


def _rms_fwd(x, weight, eps, interpret):
    x2, n, h = _rows(x)
    bn = _block_rows(n, h)
    kernel = functools.partial(_fwd_kernel, eps=eps)
    out, rstd = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            sds_like((n, h), x.dtype, x),
            sds_like((n, 1), jnp.float32, x),
        ],
        name="rms_norm_fwd",
        interpret=interpret,
    )(x2, weight.reshape(1, h))
    return out.reshape(x.shape), (x, weight, rstd)


def _rms_bwd(eps, interpret, res, dy):
    x, weight, rstd = res
    x2, n, h = _rows(x)
    bn = _block_rows(n, h)
    dx, dw = pl.pallas_call(
        _bwd_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            sds_like((n, h), x.dtype, x),
            sds_like((1, h), weight.dtype, x),
        ],
        scratch_shapes=[pltpu.VMEM((1, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="rms_norm_bwd",
        interpret=interpret,
    )(x2, weight.reshape(1, h), rstd, dy.reshape(n, h))
    return dx.reshape(x.shape), dw.reshape(weight.shape)


fused_rms_norm.defvjp(_rms_fwd, _rms_bwd)
