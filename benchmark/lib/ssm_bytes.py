"""Bytes of recurrent state a decode step has to move, computed from the
PUBLISHED keys of a configuration file and nothing from the program (the
rule of ``lib/flops.py``).

One Mamba-2 layer keeps, per request, a state ``[n_heads, d_head, d_state]``
in float32 (the configuration's ``assumed.ssm_state_dtype``).  A decode step
reads it and writes it back for every LIVE row; that is what the
``ssm_state_update`` kernel has to touch, and all that its roofline share
counts: the kernel's small operands (coefficients, B, C, y: under 1 % of the
state) and the convolution's tail, which XLA updates, are left out, so the
share cannot be flattered by bytes the kernel never moves."""

from __future__ import annotations

STATE_ITEMSIZE = 4          # float32


def state_layers(cfg: dict) -> int:
    return sum(kind == "mamba" for kind in cfg["layer_types"])


def state_bytes_per_row_layer(cfg: dict) -> int:
    """One request's recurrent state in one layer."""
    return cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"] \
        * STATE_ITEMSIZE


def update_bytes_per_row(cfg: dict) -> int:
    """Read + write of one live row's state over every state layer: what
    one decode step costs the kernel for that row."""
    return 2 * state_layers(cfg) * state_bytes_per_row_layer(cfg)
