"""Quantized KV-cache pages: dtype resolution, per-token int8 scales,
and DTYPE_BYTES-priced page accounting.

Decode is bound by the bytes read a token (what a quantized pool gains on
the chip is not measured on the current tree; see PERF.md); int8 pages
halve the cache bytes AND double how many concurrent users a fixed pool
holds.  Scheme:

- **Storage** — the page arenas become ``int8`` (exactly half the bf16
  itemsize) and a per-(token-slot, kv-head) ``float32`` scale rides in a
  scale arena of shape ``[num_pages, page_tokens, kv_heads]`` alongside
  each k/v arena.  Scales are computed at WRITE time from the token's own
  absmax (``scale = max|x| / 127``) — decode writes one token at a time,
  so per-token scales need no calibration pass and are exact for the
  token they cover (a per-page scale would need the whole page up front).
- **Dequant at the load** — the gather that builds a row's paged view
  multiplies the int8 block by its scale column in the same program
  (``ServingEngine._paged_attention``), so no dequantized copy of the
  ARENAS is ever kept.
- **Calibration seam** — :func:`observe_kv_absmax` runs the PTQ
  :class:`~paddle_tpu.quantization.AbsmaxObserver` over sample KV tensors;
  the per-tensor scale it yields is what a static-scale format (the fp8
  seam below) needs, and tests use it to sanity-bound the per-token scales
  against the observed distribution.
- **fp8 pages** — ``PADDLE_TPU_KV_DTYPE=fp8`` stores ``f8e4m3fn`` pages
  under a STATIC per-tensor scale (``PADDLE_TPU_KV_FP8_SCALE``, the
  calibration :func:`observe_kv_absmax` yields; default 1.0 — e4m3's
  ±448 dynamic range covers typical KV magnitudes raw).  No per-token
  scale planes ride along, so an fp8 page costs EXACTLY half a bf16 page
  — int8's total exceeds half by its f32 scale planes.  Dequant is fused
  at the gather (``f32(q) * scale``), same no-materialized-copy contract
  as int8.

Env: ``PADDLE_TPU_KV_DTYPE=bf16|int8|fp8`` (default ``bf16`` = the
engine's native compute dtype, bit-exact path);
``PADDLE_TPU_KV_FP8_SCALE`` sets the fp8 static scale.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = ["KV_DTYPES", "kv_cache_dtype", "quantize_kv", "dequantize_kv",
           "quantize_kv_fp8", "dequantize_kv_fp8", "default_fp8_scale",
           "observe_kv_absmax", "kv_page_bytes", "kv_scale_page_bytes",
           "layer_page_bytes", "FP8_MAX"]

KV_DTYPES = ("bf16", "int8", "fp8")
_QMAX = 127.0
_SCALE_EPS = 1e-8       # all-zero tokens (trash page writes) quantize to 0
FP8_MAX = 448.0         # f8e4m3fn finite max (no inf encoding in e4m3fn)


def kv_cache_dtype(override: Optional[str] = None) -> str:
    """Resolve the KV page dtype: ``override`` beats ``PADDLE_TPU_KV_DTYPE``
    beats the bit-exact ``bf16`` default.  ``bf16`` means "the engine's
    native compute dtype" (f32 on the CPU smoke); ``fp8`` is a stubbed
    seam and raises."""
    v = (override if override is not None
         else os.environ.get("PADDLE_TPU_KV_DTYPE", "bf16")).strip().lower()
    if v in ("bf16", "bfloat16", "native", "f32", "float32", ""):
        return "bf16"
    if v in ("int8", "s8"):
        return "int8"
    if v in ("fp8", "f8", "f8e4m3fn"):
        return "fp8"
    if v == "f8e5m2":
        raise NotImplementedError(
            "PADDLE_TPU_KV_DTYPE=f8e5m2: only the e4m3fn fp8 flavor is "
            "wired (KV magnitudes want mantissa, not exponent range). "
            f"Supported PADDLE_TPU_KV_DTYPE values: {KV_DTYPES}")
    raise ValueError(
        f"PADDLE_TPU_KV_DTYPE={v!r}: expected one of {KV_DTYPES} "
        "(aliases: bfloat16/native/f32/float32 -> bf16, s8 -> int8, "
        "f8/f8e4m3fn -> fp8)")


def quantize_kv(x):
    """Per-token symmetric int8: ``x`` [..., kv, d] → (int8 values, f32
    scales over the trailing ``d`` axis).  ``dequantize_kv(q, s)`` round-
    trips to within 1/127 of each token's absmax — exact for zeros."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)                     # [..., kv]
    scale = jnp.maximum(amax, _SCALE_EPS) / _QMAX
    q = jnp.clip(jnp.round(xf / scale[..., None]), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q, scale):
    """Inverse of :func:`quantize_kv`: f32 values ``q * scale``."""
    import jax.numpy as jnp

    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def default_fp8_scale() -> float:
    """Static per-tensor fp8 scale (``PADDLE_TPU_KV_FP8_SCALE``, default
    1.0).  Calibrate with :func:`observe_kv_absmax`: ``absmax / FP8_MAX``
    maps the observed range onto e4m3fn's ±448 exactly; the 1.0 default
    stores KV raw, which e4m3fn's range covers for typical magnitudes."""
    s = float(os.environ.get("PADDLE_TPU_KV_FP8_SCALE", "1.0"))
    if not s > 0.0:
        raise ValueError(f"PADDLE_TPU_KV_FP8_SCALE={s}: must be > 0")
    return s


def quantize_kv_fp8(x, scale: float):
    """Static-scale f8e4m3fn: ``clip(x / scale, ±FP8_MAX)`` cast to fp8.
    The clip makes saturation explicit — e4m3fn has no inf, so an
    unclipped overflow would silently wrap to NaN and the decode path's
    non-finite tripwire would fire far from the cause."""
    import jax.numpy as jnp

    xf = x.astype(jnp.float32) / scale
    return jnp.clip(xf, -FP8_MAX, FP8_MAX).astype(jnp.float8_e4m3fn)


def dequantize_kv_fp8(q, scale: float):
    """Inverse of :func:`quantize_kv_fp8`: f32 values ``q * scale``."""
    import jax.numpy as jnp

    return q.astype(jnp.float32) * scale


def observe_kv_absmax(samples) -> float:
    """Run the PTQ :class:`~paddle_tpu.quantization.AbsmaxObserver` over
    sample KV tensors and return the observed per-tensor absmax — the
    static-scale calibration the fp8 seam (and scale sanity checks) use.
    The int8 page path does NOT need this: its per-token scales are
    computed in-program at write time."""
    from ..quantization import AbsmaxObserver

    obs = AbsmaxObserver()._instance(None)
    for x in samples:
        obs(x)
    return float(obs.scales().numpy()[0])


def _dtype_code(kv_dtype: str) -> str:
    return {"bf16": "bf16", "int8": "s8", "fp8": "f8e4m3fn"}[kv_dtype]


def kv_page_bytes(page_tokens: int, kv_heads: int, head_dim: int,
                  kv_dtype: str, *, n_layers: int = 1) -> int:
    """HBM bytes of ONE pool page's k+v arena slices across ``n_layers``,
    priced through ``analysis.program.DTYPE_BYTES`` (the one table every
    byte-accounting rule shares).  Excludes scale buffers — see
    :func:`kv_scale_page_bytes`."""
    from ..analysis.program import DTYPE_BYTES

    per = DTYPE_BYTES[_dtype_code(kv_dtype)]
    return 2 * n_layers * page_tokens * kv_heads * head_dim * per


def layer_page_bytes(layer, page_tokens: int, kv_dtype: str) -> int:
    """HBM bytes of ONE pool page in ONE layer, by the layer's kind
    (:mod:`~paddle_tpu.models.serve_protocol`): K and V of every kv head
    for an ``AttentionLayer``, one padded latent row a token for a
    ``LatentAttentionLayer``.  Excludes scale buffers."""
    from ..analysis.program import DTYPE_BYTES
    from ..models.serve_protocol import LatentAttentionLayer

    if isinstance(layer, LatentAttentionLayer):
        return page_tokens * layer.row_width \
            * DTYPE_BYTES[_dtype_code(kv_dtype)]
    return kv_page_bytes(page_tokens, layer.kv_heads, layer.head_dim,
                         kv_dtype)


def kv_scale_page_bytes(page_tokens: int, kv_heads: int, kv_dtype: str,
                        *, n_layers: int = 1) -> int:
    """Bytes of one page's k+v scale slices (f32 per token-slot per
    kv-head).  Zero for bf16 (no quantization) AND for fp8: its scale is
    a single static scalar baked into the compiled programs, not a
    per-token plane — which is what makes an fp8 page land at exactly
    half the bf16 page bytes while int8's total exceeds half."""
    from ..analysis.program import DTYPE_BYTES

    if kv_dtype in ("bf16", "fp8"):
        return 0
    return 2 * n_layers * page_tokens * kv_heads * DTYPE_BYTES["f32"]
