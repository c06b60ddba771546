"""Bytes and operations of what an Ouro (looped LM) decode step must move,
computed from the PUBLISHED keys of an ``ouro`` configuration file and
nothing from the program (the rule of ``lib/flops.py``).

**The weights.**  A decode step runs the ``num_hidden_layers`` layers
``total_ut_steps`` times with the same weights, and a step's rows share each
read: the least a step must move is every layer's matrices once a pass and
the head once, in bfloat16.  A layer is the query, key, value and output
projections, the three SwiGLU matrices and four norm vectors.  The
embedding rows of the step's tokens and the early-exit gate (unused at
threshold 1.0) are left out.

**The cache.**  Each (pass, layer) keeps K and V of every kv head, in
bfloat16: ``2 * num_key_value_heads * head_dim * 2`` bytes a token.  A query
must read every cached token it can see, once a layer and pass, and takes a
score and a value multiply-add over ``head_dim`` lanes a head per cached
token.  The counts are the same whatever implements the step."""

from __future__ import annotations

ITEMSIZE = 2        # bfloat16


def layer_params(cfg: dict) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q = cfg["num_attention_heads"] * d
    kv = cfg["num_key_value_heads"] * d
    return h * q + 2 * h * kv + q * h + 3 * h * cfg["intermediate_size"] \
        + 4 * h


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def weight_bytes_per_step(cfg: dict) -> int:
    """Every layer once a pass and the head once."""
    return (cfg["total_ut_steps"] * cfg["num_hidden_layers"]
            * layer_params(cfg) + head_params(cfg)) * ITEMSIZE


def kv_bytes_per_token(cfg: dict) -> int:
    """One cached token in one layer of one pass: K and V."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEMSIZE


def flops_per_query_cached_token(cfg: dict) -> int:
    """Score and value of ONE query token against ONE cached token, all
    heads, one layer of one pass."""
    return 4 * cfg["num_attention_heads"] * cfg["head_dim"]


def decode_least_seconds(cfg: dict, peaks: dict, steps: int,
                         kv_tokens: int) -> float:
    """The least time ``steps`` decode steps could take by HBM bandwidth:
    their weight reads and the ``kv_tokens`` cached tokens their queries
    read (summed over rows, layers and passes)."""
    return (steps * weight_bytes_per_step(cfg)
            + kv_tokens * kv_bytes_per_token(cfg)) / peaks["hbm_bytes_per_s"]


def attention_least_seconds(cfg: dict, peaks: dict, kv_tokens: int) -> dict:
    """The least time the page walk could take over ``kv_tokens`` cached
    tokens (one query token each): the larger of their K/V bytes over the
    HBM peak and their operations over the MXU peak, and which bounds it."""
    by_bytes = kv_tokens * kv_bytes_per_token(cfg) / peaks["hbm_bytes_per_s"]
    by_flops = kv_tokens * flops_per_query_cached_token(cfg) \
        / peaks["flops_bf16"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "hbm" if by_bytes >= by_flops else "mxu"}
