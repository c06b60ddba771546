"""Pallas TPU kernels for the SURVEY §7.8 tail: fused residual-add+LayerNorm
(forward + backward), fused SwiGLU (forward + backward), and the fused AdamW
update.

Capability parity: `paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu:1`
(residual+bias+layernorm in one pass, python surface
`incubate/nn/functional/fused_layernorm.py`),
`fused_bias_act_kernel.cu:1` (gated activations), and the multi-tensor
`paddle/phi/kernels/gpu/adamw_kernel.cu:1`.  On TPU the win is one HBM sweep
per direction instead of separate add/normalize(/activation) passes; for
AdamW, XLA's own fusion of the update chain is already near-optimal — the
kernel exists so the claim is MEASURED, and dispatch stays off unless the
``use_fused_adamw`` flag is set.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import sds_like
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_norm import _block_rows, _rows

# ---------------------------------------------------------------------------
# fused residual-add + LayerNorm
# ---------------------------------------------------------------------------


def _ln_fwd_kernel(x_ref, r_ref, w_ref, b_ref, o_ref, sum_ref, mu_ref,
                   rstd_ref, *, eps: float):
    s = x_ref[:].astype(jnp.float32) + r_ref[:].astype(jnp.float32)  # (Bn, H)
    mu = jnp.mean(s, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(s - mu), axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    sum_ref[:] = s.astype(sum_ref.dtype)
    mu_ref[:] = mu
    rstd_ref[:] = rstd
    o_ref[:] = ((s - mu) * rstd * w_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_bwd_kernel(s_ref, w_ref, mu_ref, rstd_ref, dy_ref, dpre_ref,
                   dx_ref, dw_ref, db_ref, dw_acc, db_acc):
    i, n = pl.program_id(0), pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)
        db_acc[:] = jnp.zeros_like(db_acc)

    s = s_ref[:].astype(jnp.float32)
    w = w_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    mu, rstd = mu_ref[:], rstd_ref[:]
    xhat = (s - mu) * rstd
    dyw = dy * w
    h = s.shape[1]
    c1 = jnp.sum(dyw, axis=1, keepdims=True) / h
    c2 = jnp.sum(dyw * xhat, axis=1, keepdims=True) / h
    # d(pre) = LN backward + the cotangent flowing into the returned sum
    dx_ref[:] = (rstd * (dyw - c1 - xhat * c2)
                 + dpre_ref[:].astype(jnp.float32)).astype(dx_ref.dtype)
    dw_acc[:] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_acc[:] += jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _finalize():
        dw_ref[:] = dw_acc[:].astype(dw_ref.dtype)
        db_ref[:] = db_acc[:].astype(db_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def fused_add_layer_norm(x, residual, weight, bias, eps: float = 1e-5,
                         interpret: bool = False):
    """(LayerNorm(x + residual) * w + b, x + residual) over the last axis —
    the reference fused_layernorm contract: the normed output AND the
    residual sum both come back, each in ONE HBM pass."""
    out, _ = _ln_fwd(x, residual, weight, bias, eps, interpret)
    return out


def _ln_fwd(x, residual, weight, bias, eps, interpret):
    x2, n, h = _rows(x)
    r2 = residual.reshape(n, h)
    bn = _block_rows(n, h)
    kernel = functools.partial(_ln_fwd_kernel, eps=eps)
    out, sum_, mu, rstd = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            sds_like((n, h), x.dtype, x),
            sds_like((n, h), x.dtype, x),
            sds_like((n, 1), jnp.float32, x),
            sds_like((n, 1), jnp.float32, x),
        ],
        interpret=interpret,
    )(x2, r2, weight.reshape(1, h), bias.reshape(1, h))
    res = (sum_, weight, mu, rstd)
    return (out.reshape(x.shape), sum_.reshape(x.shape)), res


def _ln_bwd(eps, interpret, res, cts):
    dy, dpre = cts
    sum_, weight, mu, rstd = res
    s2, n, h = _rows(sum_)
    bn = _block_rows(n, h)
    dx, dw, db = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            sds_like((n, h), sum_.dtype, sum_),
            sds_like((1, h), weight.dtype, sum_),
            sds_like((1, h), weight.dtype, sum_),
        ],
        scratch_shapes=[pltpu.VMEM((1, h), jnp.float32),
                        pltpu.VMEM((1, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(s2, weight.reshape(1, h), mu, rstd, dy.reshape(n, h),
      dpre.reshape(n, h))
    dx = dx.reshape(dy.shape)
    # pre = x + residual: both inputs receive the same cotangent
    return dx, dx, dw.reshape(weight.shape), db.reshape(weight.shape)


fused_add_layer_norm.defvjp(_ln_fwd, _ln_bwd)


# ---------------------------------------------------------------------------
# fused SwiGLU
# ---------------------------------------------------------------------------


def _swiglu_fwd_kernel(g_ref, u_ref, o_ref):
    g = g_ref[:].astype(jnp.float32)
    u = u_ref[:].astype(jnp.float32)
    o_ref[:] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _swiglu_bwd_kernel(g_ref, u_ref, dy_ref, dg_ref, du_ref):
    g = g_ref[:].astype(jnp.float32)
    u = u_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    sig = jax.nn.sigmoid(g)
    silu = g * sig
    dg_ref[:] = (dy * u * (sig + silu * (1.0 - sig))).astype(dg_ref.dtype)
    du_ref[:] = (dy * silu).astype(du_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def fused_swiglu(gate, up, interpret: bool = False):
    """silu(gate) * up in one HBM pass (reference fused_bias_act gated
    path); gate/up: [..., H]."""
    out, _ = _swiglu_fwd(gate, up, interpret)
    return out


def _elementwise_call(kernel, args, n_out, interpret):
    x2, n, h = _rows(args[0])
    rows = [a.reshape(n, h) for a in args]
    bn = _block_rows(n, h)
    outs = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0))] * len(rows),
        out_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0))] * n_out,
        out_shape=[sds_like((n, h), args[0].dtype, args[0])] * n_out,
        interpret=interpret,
    )(*rows)
    outs = outs if isinstance(outs, (list, tuple)) else [outs]
    return [o.reshape(args[0].shape) for o in outs]


def _swiglu_fwd(gate, up, interpret):
    (out,) = _elementwise_call(_swiglu_fwd_kernel, (gate, up), 1, interpret)
    return out, (gate, up)


def _swiglu_bwd(interpret, res, dy):
    gate, up = res
    dg, du = _elementwise_call(_swiglu_bwd_kernel, (gate, up, dy), 2,
                               interpret)
    return dg, du


fused_swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


# ---------------------------------------------------------------------------
# fused AdamW update
# ---------------------------------------------------------------------------


def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                  p_out, m_out, v_out, *, beta1, beta2, eps, weight_decay,
                  decay):
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    lr = sc_ref[0, 0]
    bc1 = sc_ref[0, 1]   # 1 - beta1**t
    bc2 = sc_ref[0, 2]   # 1 - beta2**t
    m = beta1 * m_ref[:] + (1.0 - beta1) * g
    v = beta2 * v_ref[:] + (1.0 - beta2) * jnp.square(g)
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    new_p = p - lr * update
    if decay:
        new_p = new_p - lr * weight_decay * p
    p_out[:] = new_p.astype(p_out.dtype)
    m_out[:] = m
    v_out[:] = v


def _adamw_cols(size: int) -> int:
    return 512 if size % 512 == 0 else 128


def fused_adamw_supported(size: int) -> bool:
    """True when the flat param blocks to a legal Mosaic tiling: 128-aligned
    columns and a sublane-aligned (mult-of-8) row count — without this the
    block-rows fallback would pick a whole-array block beyond VMEM."""
    if size % 128 != 0:
        return False
    h = _adamw_cols(size)
    n = size // h
    return n % 8 == 0 or n <= 8


def fused_adamw(p, g, m, v, lr, t, beta1: float, beta2: float, eps: float,
                weight_decay: float, decay: bool, interpret: bool = False):
    """One-sweep decoupled AdamW update (reference
    `paddle/phi/kernels/gpu/adamw_kernel.cu:1`): returns (new_p, new_m,
    new_v).  ``lr``/``t`` are traced scalars (lr schedules / bias
    correction stay in-graph).  Exact same math as AdamW._update_rule."""
    shape = p.shape
    if not fused_adamw_supported(p.size):
        raise ValueError(f"fused_adamw: size {p.size} does not block to a "
                         "legal tiling (see fused_adamw_supported)")
    h = _adamw_cols(p.size)
    n = p.size // h
    # 4 f32 inputs + 3 f32 outputs, double-buffered ≈ 64 B/element
    bn = _block_rows(n, h, bytes_per_elem=64)
    lr = jnp.asarray(lr, jnp.float32)
    tf = jnp.asarray(t, jnp.float32)
    scalars = jnp.stack([lr, 1.0 - beta1 ** tf,
                         1.0 - beta2 ** tf]).reshape(1, 3)
    kernel = functools.partial(_adamw_kernel, beta1=beta1, beta2=beta2,
                               eps=eps, weight_decay=weight_decay,
                               decay=decay)
    new_p, new_m, new_v = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
            pl.BlockSpec((bn, h), lambda i: (i, 0)),
        ],
        out_shape=[
            sds_like((n, h), p.dtype, p),
            sds_like((n, h), jnp.float32, p),
            sds_like((n, h), jnp.float32, p),
        ],
        interpret=interpret,
    )(p.reshape(n, h), g.reshape(n, h).astype(jnp.float32),
      m.reshape(n, h).astype(jnp.float32),
      v.reshape(n, h).astype(jnp.float32), scalars)
    return (new_p.reshape(shape), new_m.reshape(shape),
            new_v.reshape(shape))
