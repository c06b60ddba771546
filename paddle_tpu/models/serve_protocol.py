"""How a causal LM tells ``ServingEngine`` what each of its layers keeps.

A served model describes itself layer by layer (``serve_layers()``): an
:class:`AttentionLayer` keeps K/V that grow by a token a step, in pages of
the engine's ``PagedKVPool``; a :class:`StateLayer` (a state-space /
recurrent mixer) keeps arrays of a FIXED shape per request — for Mamba-2 the
last ``d_conv - 1`` convolution inputs and the ``[H, P, N]`` recurrent state
— however long the request is (``serving/state_pool.py`` holds those).

The engine walks ``serve_layers()`` once per program and owns pages, tables
and state slots; the model owns its block math::

    model.serve_layers()                       -> [AttentionLayer | StateLayer]
    model.serve_begin(tokens, positions)       -> (x, shared)
    model.serve_layer(i, x, shared, io)        -> x
    model.serve_end(x)                         -> logits

``io`` is the engine's side of layer ``i``: ``io.attend(q, k, v)`` scatters
this step's K/V into the layer's pages and attends each row over its pages;
``io.read_state(name)`` / ``io.write_state(name, value)`` read and write the
rows' slots of one state array; ``io.n_valid [R]`` says how many of a row's
tokens are real and ``io.live [R]`` which rows step at all.

It lives under ``models/`` so that a model need not import ``serving/``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

__all__ = ["AttentionLayer", "StateLayer"]


@dataclasses.dataclass(frozen=True)
class AttentionLayer:
    """A layer whose cache is K/V pages.  ``scale``: what the scores are
    multiplied by; None is ``1 / sqrt(head_dim)``."""
    heads: int
    kv_heads: int
    head_dim: int
    scale: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class StateLayer:
    """A layer that keeps arrays of a fixed shape per request:
    ``arrays[name] = (shape of one request's array, dtype)``."""
    arrays: Tuple[Tuple[str, Tuple[Tuple[int, ...], str]], ...]

    @staticmethod
    def of(**arrays) -> "StateLayer":
        return StateLayer(tuple(
            (name, (tuple(int(s) for s in shape), str(np.dtype(dtype))))
            for name, (shape, dtype) in sorted(arrays.items())))
