"""Bytes and operations of the routed experts a chip holds, computed from the
PUBLISHED keys of a configuration file and nothing from the program (the
rule of ``lib/flops.py``).

One routed expert is a SwiGLU of three matrices ``hidden_size x
moe_intermediate_size`` in bfloat16.  A program launch must read the weights
of every held expert that got at least one token, once an expert layer, and
does ``2 * 3 * hidden * width`` operations a (token, expert) pair.  The
tokens' own rows (a few hundred KB) are left out, so the share cannot be
flattered by bytes the weights dwarf."""

from __future__ import annotations

ITEMSIZE = 2        # bfloat16


def expert_bytes(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * ITEMSIZE


def flops_per_pair(cfg: dict) -> int:
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def held_experts(cfg: dict) -> int:
    """Routed experts whose weights this chip holds, a layer."""
    return cfg["experts_held"][1]


def least_seconds(cfg: dict, peaks: dict, experts_hit: int,
                  pairs: int) -> dict:
    """The least time a chip could take for ``pairs`` (token, expert) pairs
    over ``experts_hit`` (expert, layer, launch) weight reads: the larger of
    the weights' bytes over the HBM peak and the operations over the MXU
    peak, and which of the two bounds it."""
    by_bytes = experts_hit * expert_bytes(cfg) / peaks["hbm_bytes_per_s"]
    by_flops = pairs * flops_per_pair(cfg) / peaks["flops_bf16"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "hbm" if by_bytes >= by_flops else "mxu"}
