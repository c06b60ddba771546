"""Bytes and operations of absorbed multi-head latent attention in a decode
step, computed from the PUBLISHED keys of a configuration file and nothing
from the program (the rule of ``lib/flops.py``).

An MLA layer caches one latent row a token: ``kv_lora_rank`` lanes of
``c_kv`` and ``qk_rope_head_dim`` of the rotated key part, in bfloat16.  A
decode step must read every cached row its queries can see, once a layer,
and per (query token, cached token) every head takes one dot product over
the row's ``kv_lora_rank + qk_rope_head_dim`` lanes for the score and one
multiply-add over ``kv_lora_rank`` lanes for the value.  The zero lanes that
pad a row to whole registers are the layout's, not the algorithm's: they
count neither as bytes nor as operations, so the share cannot be flattered
by them."""

from __future__ import annotations

ITEMSIZE = 2        # bfloat16


def row_lanes(cfg: dict) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def row_bytes(cfg: dict) -> int:
    """One cached token in one layer, as the algorithm needs it."""
    return row_lanes(cfg) * ITEMSIZE


def flops_per_query_cached_token(cfg: dict) -> int:
    """Score and value of ONE query token against ONE cached token, all
    heads, one layer: ``2 * heads * ((rank + rope) + rank)``."""
    return 2 * cfg["num_attention_heads"] \
        * (row_lanes(cfg) + cfg["kv_lora_rank"])


def least_seconds(cfg: dict, peaks: dict, cached_tokens: int) -> dict:
    """The least time a chip could take to attend one query token a row over
    ``cached_tokens`` cached tokens (summed over rows and layers): the
    larger of the bytes over the HBM peak and the operations over the MXU
    peak, and which of the two bounds it."""
    by_bytes = cached_tokens * row_bytes(cfg) / peaks["hbm_bytes_per_s"]
    by_flops = cached_tokens * flops_per_query_cached_token(cfg) \
        / peaks["flops_bf16"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "hbm" if by_bytes >= by_flops else "mxu"}
