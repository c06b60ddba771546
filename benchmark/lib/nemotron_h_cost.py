"""Bytes and operations of what a Nemotron-H decode step must move, computed
from the PUBLISHED keys of a ``nemotron_h`` configuration file and nothing
from the program (the rule of ``lib/flops.py``).  ``lib/ssm_bytes.py`` and
``lib/moe_cost.py`` count the same things under Granite-4.0-H's and
DeepSeek-V3's key names (and three matrices an expert).

**The recurrent state.**  One ``M`` block keeps, per request, a state
``[mamba_num_heads, mamba_head_dim, ssm_state_size]`` in float32 (the
configuration's ``assumed.ssm_state_dtype``).  A decode step reads it and
writes it back for every LIVE row: that is what ``ssm_state_update`` has to
touch, and all that its roofline share counts; the small operands (the
coefficients, the groups' B and C, y: under 1 % of the state) and the
convolution's tail, which XLA updates, are left out.

**The routed experts.**  One routed expert is two matrices ``hidden_size x
moe_intermediate_size`` in bfloat16, ``W_down relu(W_up x) ** 2``.  A launch
must read the weights of every held expert that got at least one token,
once an expert block, and does ``2 * 2 * hidden * width`` operations a
(token, expert) pair.  The tokens' own rows are left out.  The counts are
the same whatever implements the kernel."""

from __future__ import annotations

STATE_ITEMSIZE = 4          # float32
WEIGHT_ITEMSIZE = 2         # bfloat16


def blocks(cfg: dict, letter: str) -> int:
    """Blocks of one kind: ``M`` Mamba-2, ``E`` experts, ``*`` attention."""
    return cfg["hybrid_override_pattern"].count(letter)


def state_bytes_per_row_layer(cfg: dict) -> int:
    """One request's recurrent state in one ``M`` block."""
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"] \
        * cfg["ssm_state_size"] * STATE_ITEMSIZE


def update_bytes_per_row(cfg: dict) -> int:
    """Read + write of one live row's state over every ``M`` block: what one
    decode step costs the kernel for that row."""
    return 2 * blocks(cfg, "M") * state_bytes_per_row_layer(cfg)


def expert_bytes(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * WEIGHT_ITEMSIZE


def flops_per_pair(cfg: dict) -> int:
    return 4 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_experts(cfg: dict) -> int:
    """Routed experts whose weights this chip holds, a block."""
    return cfg["experts_held"][1]


def experts_least_seconds(cfg: dict, peaks: dict, experts_hit: int,
                          pairs: int) -> dict:
    """The least time a chip could take for ``pairs`` (token, expert) pairs
    over ``experts_hit`` (expert, block, launch) weight reads: the larger of
    the weights' bytes over the HBM peak and the operations over the MXU
    peak, and which of the two bounds it."""
    by_bytes = experts_hit * expert_bytes(cfg) / peaks["hbm_bytes_per_s"]
    by_flops = pairs * flops_per_pair(cfg) / peaks["flops_bf16"]
    return {"seconds": max(by_bytes, by_flops),
            "bound": "hbm" if by_bytes >= by_flops else "mxu"}
