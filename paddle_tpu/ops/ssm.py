"""State-space (Mamba-2) sequence ops in plain ``jax.numpy``: the chunked
SSD scan, the causal depthwise convolution in front of it, and the
single-token recurrence the decode path takes.

The recurrence, per head ``h`` with state ``S [P, N]`` (``P`` the head's
width, ``N`` the state size), decay ``A[h] < 0`` and step ``dt_t > 0``::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t + D * x_t

``ssd_chunked`` computes it for a whole sequence by the state-space-duality
algorithm (Dao & Gu, "Transformers are SSMs", 2024, listing 1): inside a
chunk every output is a masked, decayed ``C B^T`` product over the chunk's
tokens, between chunks only the ``[H, P, N]`` state is carried.  The chunk
length changes no value, only the shapes of the einsums.  Everything is
float32 at the highest matmul precision: the einsums are a few hundred
MFLOP a layer, small beside the projections around them, and the carried
state must not pick up a bfloat16 rounding per chunk.

Both sequence ops take a carried-in state and ``n_valid`` (tokens of each
row that are real): tokens past it leave the returned state untouched, so
a fixed-size chunk can end in padding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv1d", "ssd_chunked", "ssm_step", "ssm_decode_update"]

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def causal_conv1d(x, w, b, tail0, n_valid):
    """Causal depthwise convolution over time with a carried tail.

    ``x [b, T, C]``; ``w [C, K]`` (tap ``k`` multiplies the input ``K-1-k``
    steps back); ``b [C]`` or None; ``tail0 [b, K-1, C]`` the last ``K-1``
    inputs before ``x``; ``n_valid [b]``.  Returns ``y [b, T, C]`` in
    ``x``'s dtype and the new tail: the last ``K-1`` inputs up to and
    including token ``n_valid - 1`` (``tail0`` itself when ``n_valid`` is
    0)."""
    T, K = x.shape[1], w.shape[1]
    xp = jnp.concatenate([tail0.astype(x.dtype), x], axis=1)
    acc = sum(xp[:, k:k + T].astype(_F32) * w[:, k].astype(_F32)
              for k in range(K))
    if b is not None:
        acc = acc + b.astype(_F32)
    # xp[j] is input j - (K-1): the tail ending at input n_valid - 1 starts
    # at xp index n_valid
    idx = n_valid.astype(jnp.int32)[:, None] + jnp.arange(K - 1)[None, :]
    tail = jnp.take_along_axis(xp, idx[:, :, None], axis=1)
    return acc.astype(x.dtype), tail.astype(tail0.dtype)


def _grouped(v, H):
    """``[..., G, N]`` -> ``[..., H, N]``: every head of a group shares the
    group's B / C."""
    G = v.shape[-2]
    return v if G == H else jnp.repeat(v, H // G, axis=-2)


def ssd_chunked(x, dt, A, B, C, D, state0, n_valid, chunk: int = 256):
    """Chunked SSD scan.  ``x [b, T, H, P]``; ``dt [b, T, H]`` (after the
    softplus); ``A [H]`` (negative); ``B, C [b, T, G, N]``; ``D [H]``;
    ``state0 [b, H, P, N]``; ``n_valid [b]``.  Returns ``y [b, T, H, P]``
    in ``x``'s dtype and the state after token ``n_valid - 1`` in float32.
    """
    b, T, H, P = x.shape
    c = min(int(chunk), T)
    pad = -T % c
    valid = jnp.arange(T)[None, :] < n_valid[:, None]
    # a token past n_valid has dt = 0: decay exp(0) = 1 and nothing added
    dt = jnp.where(valid[..., None], dt.astype(_F32), 0.0)
    xf = x.astype(_F32)
    Bh = _grouped(B.astype(_F32), H)
    Ch = _grouped(C.astype(_F32), H)
    if pad:
        dt, xf, Bh, Ch = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] *
                                  (v.ndim - 2)) for v in (dt, xf, Bh, Ch))
    z = (T + pad) // c
    dt = dt.reshape(b, z, c, H)
    dtx = xf.reshape(b, z, c, H, P) * dt[..., None]
    Bh = Bh.reshape(b, z, c, H, -1)
    Ch = Ch.reshape(b, z, c, H, -1)
    a_cs = jnp.cumsum(dt * A.astype(_F32), axis=2)          # [b, z, c, H]

    # inside a chunk: y_i += sum_{j <= i} exp(a_cs[i] - a_cs[j]) (C_i . B_j)
    # dt_j x_j
    seg = a_cs[:, :, :, None, :] - a_cs[:, :, None, :, :]   # [b, z, i, j, H]
    tri = jnp.tril(jnp.ones((c, c), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    cb = jnp.einsum("bzihn,bzjhn->bzijh", Ch, Bh, precision=_HI)
    y = jnp.einsum("bzijh,bzjhp->bzihp", cb * decay, dtx, precision=_HI)

    # what each chunk adds to the state by its end, and the state carried
    # into each chunk
    to_end = jnp.exp(a_cs[:, :, -1:, :] - a_cs)             # [b, z, c, H]
    added = jnp.einsum("bzjhn,bzjhp->bzhpn", Bh, dtx * to_end[..., None],
                       precision=_HI)
    whole = jnp.exp(a_cs[:, :, -1, :])                      # [b, z, H]

    def carry(S, chunk_terms):
        add, dec = chunk_terms
        return dec[:, :, None, None] * S + add, S

    final, s_in = jax.lax.scan(
        carry, state0.astype(_F32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    s_in = jnp.moveaxis(s_in, 0, 1)                         # [b, z, H, P, N]
    y = y + jnp.einsum("bzihn,bzhpn->bzihp", Ch * jnp.exp(a_cs)[..., None],
                       s_in, precision=_HI)
    y = y.reshape(b, T + pad, H, P)[:, :T] \
        + D.astype(_F32)[None, None, :, None] * x.astype(_F32)
    return y.astype(x.dtype), final


def ssm_step(state, live, x, dt, A, B, C, D):
    """One token of the recurrence for every row, in ``jax.numpy``: the twin
    of the ``ssm_state_update`` kernel.  ``state [R, H, P, N]`` (computed in
    float32 whatever it is kept in); ``live [R]`` bool (a row that is not live keeps its state; its ``y`` is
    zero); ``x [R, H, P]``; ``dt [R, H]``; ``A, D [H]``; ``B, C [R, G, N]``.
    Returns ``y [R, H, P]`` in ``x``'s dtype and the new state in float32."""
    H = x.shape[1]
    dt = dt.astype(_F32)
    xf = x.astype(_F32)
    Bh = _grouped(B.astype(_F32), H)
    Ch = _grouped(C.astype(_F32), H)
    dA = jnp.exp(dt * A.astype(_F32))
    state = state.astype(_F32)
    new = dA[:, :, None, None] * state \
        + (dt[:, :, None] * xf)[..., None] * Bh[:, :, None, :]
    new = jnp.where(live[:, None, None, None], new, state)
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1) \
        + D.astype(_F32)[None, :, None] * xf
    return jnp.where(live[:, None, None], y, 0.0).astype(x.dtype), new


def ssm_decode_update(state, live, x, dt, A, B, C, D):
    """The decode step's state update, dispatched like the other decode
    kernels (``pallas_mode("use_decode_attention")``): the Pallas kernel
    ``ssm_state_update`` where kernels run (a TPU, or the interpreter) and
    its gate takes the shapes — only LIVE rows' state is read and written,
    in place — else :func:`ssm_step` over every row, with a counted
    ``kernel_fallback`` where a kernel could have run.  ``B, C [R, G, N]``:
    any number of groups that divides the heads."""
    from . import pallas_mode
    from .pallas.ssm_state_update import (KERNEL_NAME, ssm_state_update,
                                          ssm_state_update_refusal)

    mode = pallas_mode("use_decode_attention")
    if mode is not None:
        kind, _, interpret = mode
        reason = "hybrid_mesh" if kind != "local" else \
            ssm_state_update_refusal(state.shape, state.dtype, B.shape)
        if reason is None:
            return ssm_state_update(state, live, x, dt, A, B, C, D,
                                    interpret=interpret)
        from ..telemetry import kernel_fallback

        kernel_fallback(KERNEL_NAME, reason, rows=state.shape[0])
    return ssm_step(state, live, x, dt, A, B, C, D)
