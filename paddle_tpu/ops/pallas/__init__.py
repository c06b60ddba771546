"""Pallas TPU kernels — the hand-tiled hot set the reference ships as CUDA
fusion kernels (`paddle/phi/kernels/fusion/gpu/`, `flash_attn_kernel.cu`).

Each kernel is a `jax.custom_vjp` function over `pl.pallas_call`, so it works
under the eager vjp tape (apply_op) and inside whole-step jit alike. On
non-TPU backends the functional layer falls back to the XLA reference paths;
tests exercise the kernels in interpreter mode."""

def sds_like(shape, dtype, like):
    """``jax.ShapeDtypeStruct`` for a pallas_call out_shape that PROPAGATES
    the manual-mesh varying axes (vma) of an input operand.

    Inside a manual ``shard_map`` with ``check_vma=True`` — e.g. the
    compiled pipeline engine's tick program (`distributed/pipeline_1f1b.py`)
    — every pallas_call out_shape must declare how it varies across the
    manual axes; a bare ShapeDtypeStruct raises ``vma must not be None``
    (round-5 finding: OneFOneBLayers over attention blocks with the Pallas
    kernels enabled failed on real TPU).  Outside any manual context the
    vma set is empty and this degrades to a plain ShapeDtypeStruct."""
    import jax

    vma = jax.typeof(like).vma
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


from .flash_attention import (flash_attention, flash_attention_supported,
                              flash_attention_varlen,
                              flash_attention_varlen_supported)
from .decode_attention import decode_attention, decode_attention_supported
from .paged_decode_attention import (paged_decode_attention,
                                     paged_decode_attention_refusal)
from .mla_paged_decode_attention import (mla_paged_decode_attention,
                                         mla_paged_decode_attention_refusal)
from .grouped_matmul import grouped_matmul, grouped_matmul_refusal
from .ssm_state_update import ssm_state_update, ssm_state_update_refusal
from .fused_norm import fused_rms_norm
from .rope import fused_rope

__all__ = ["flash_attention", "flash_attention_supported",
           "flash_attention_varlen", "flash_attention_varlen_supported",
           "decode_attention", "decode_attention_supported",
           "paged_decode_attention", "paged_decode_attention_refusal",
           "mla_paged_decode_attention",
           "mla_paged_decode_attention_refusal",
           "grouped_matmul", "grouped_matmul_refusal",
           "ssm_state_update", "ssm_state_update_refusal",
           "fused_rms_norm", "fused_rope"]
