"""Fixed-size per-request state beside the paged KV pool.

A model's :class:`~paddle_tpu.models.serve_protocol.StateLayer` entries (a
state-space / recurrent mixer: for Mamba-2 the convolution's tail and the
``[H, P, N]`` recurrent state) keep arrays of a FIXED shape per request.
:class:`RowStatePool` describes them: per state layer and array one arena
``[max_batch, *shape]`` indexed by the DECODE ROW.  A request's slot is the
row it was admitted to, so there is no second allocator: admission,
retirement, eviction and slot reuse copy nothing, and the slots in use are
the engine's active rows.  The arenas ride the engine's two compiled
programs exactly like the page arenas (donated in, updated in place,
returned); the prefill program zeroes a request's state when its first
launch runs, carries it from launch to launch in the slot, and decode updates
it in place.
"""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from ..models.serve_protocol import StateLayer

__all__ = ["RowStatePool", "StateLayersUnsupported"]


class StateLayersUnsupported(NotImplementedError):
    """A serving feature that cannot yet be right for a model with state
    layers was asked for: it would need the recurrent state snapshotted,
    moved or rolled back, which nothing does yet."""

    def __init__(self, feature: str, why: str):
        self.feature = feature
        super().__init__(
            f"{feature} is not supported for a model with state layers: "
            f"{why}")


class RowStatePool:
    """The shapes, first value and bytes of the row-state arenas (module
    docstring)."""

    def __init__(self, max_batch: int, layers: Sequence[StateLayer]):
        self.max_batch = int(max_batch)
        self.layers = list(layers)
        names = {n for layer in self.layers for n, _ in layer.arrays}
        for layer in self.layers:
            if {n for n, _ in layer.arrays} != names:
                raise ValueError("every state layer must keep the same "
                                 "named arrays")
        self.names = sorted(names)
        self.bytes_per_row = sum(
            int(np.prod(shape)) * np.dtype(dtype).itemsize
            for layer in self.layers for _, (shape, dtype) in layer.arrays)

    def zeros(self) -> Dict[str, list]:
        """The arenas' first value: ``{name: [one [max_batch, *shape] array
        per state layer]}``."""
        import jax.numpy as jnp

        return {name: [jnp.zeros((self.max_batch, *shape), dtype)
                       for layer in self.layers
                       for n, (shape, dtype) in layer.arrays if n == name]
                for name in self.names}

    @property
    def nbytes(self) -> int:
        return self.bytes_per_row * self.max_batch
