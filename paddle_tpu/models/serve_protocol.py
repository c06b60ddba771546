"""How a causal LM tells ``ServingEngine`` what each of its layers keeps.

A served model describes itself layer by layer (``serve_layers()``), and a
layer is one of four kinds: an :class:`AttentionLayer` keeps K/V that grow
by a token a step, in pages of the engine's ``PagedKVPool``; a
:class:`LatentAttentionLayer` (multi-head latent attention) keeps ONE latent
row a token in the same pages, ``[c_kv | k_rope]``, shared by every head and
never expanded to per-head K/V in the cache; a :class:`StateLayer` (a
state-space / recurrent mixer) keeps arrays of a FIXED shape per request —
for Mamba-2 the last ``d_conv - 1`` convolution inputs and the ``[H, P, N]``
recurrent state — however long the request is (``serving/state_pool.py``
holds those); a :class:`StatelessLayer` keeps NOTHING per request (a
feed-forward or expert block that is a layer of its own, as in Nemotron-H,
where a block is one mixer OR one feed-forward part): the engine gives it no
arena, and it sees only which tokens are real.

The engine walks ``serve_layers()`` once per program and owns pages, tables
and state slots; the model owns its block math::

    model.serve_layers()     -> [AttentionLayer | LatentAttentionLayer
                                 | StateLayer | StatelessLayer]
    model.serve_begin(tokens, positions)       -> (x, shared)
    model.serve_layer(i, x, shared, io)        -> x
    model.serve_end(x)                         -> logits

A model whose layers run several times a step (a looped model, Ouro)
says so with two more methods; a model without them is walked once::

    model.serve_passes()                       -> P (1 where absent)
    model.serve_pass_end(x)                    -> x, after every pass

The engine then walks ``serve_layers()`` P times inside ONE compiled loop
(the programs hold each layer once), calls ``serve_pass_end`` after each
pass, the last included, and hands the result to ``serve_end``.  Each
(pass, layer) keeps K/V of its own: pass ``t`` of a layer reads and writes
pages ``t * num_pages + page`` of that layer's arena, which holds
``P * num_pages`` pages, while the pool and the tables count
``num_pages``.  What does not compose with that is refused by name
(``serving.PassesUnsupported``), as is ``io.note`` / ``io.keep`` in a
walk that repeats.

``io`` is the engine's side of layer ``i``: ``io.attend(q, k, v)`` scatters
this step's K/V into the layer's pages and attends each row over its pages;
``io.attend_latent(q_nope, q_rope, c_kv, k_rope, w_uk, w_uv)`` does the same
for a latent layer's rows, in the absorbed form in the decode program and
the expanded form in the prefill program; ``io.note(name, value)`` hands the
engine a count made inside the program (it rides the step's one fetch);
``io.keep(name, value)`` leaves an array on the device beside the decode
step's logits, for a tolerance harness to fetch (no step does);
``io.read_state(name)`` / ``io.write_state(name, value)`` read and write the
rows' slots of one state array; ``io.valid [R, s]`` says which tokens are
real, ``io.n_valid [R]`` how many of a row's tokens are real and
``io.live [R]`` which rows step at all.  A layer may
ask only what its kind keeps: ``attend`` is an :class:`AttentionLayer`'s,
``attend_latent`` a :class:`LatentAttentionLayer`'s, the state calls a
:class:`StateLayer`'s, and any of them from another kind raises a
``TypeError`` that names the call and the kind; ``note``, ``keep``,
``valid``, ``n_valid`` and ``live`` are every kind's.

Where no layer is a :class:`StateLayer`, the engine runs a step's decode
rows inside its last prefill launch where that launch is of the narrowest
width (``ServingEngine.rides_prefill``): the model then sees ONE row, the
prompt's tokens followed by the decode rows' ``[R, S]`` tokens, and
``serve_begin`` gets ``positions`` [1, s], every
token's own, in place of [R] row starts.  ``attend`` / ``attend_latent``
split the row by part themselves; a layer that keeps nothing reads
``io.valid`` there (``io.n_valid`` and ``io.live`` are None: the real
tokens of that row are no prefix).

It lives under ``models/`` so that a model need not import ``serving/``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np

__all__ = ["AttentionLayer", "LatentAttentionLayer", "StateLayer",
           "StatelessLayer"]


@dataclasses.dataclass(frozen=True)
class AttentionLayer:
    """A layer whose cache is K/V pages.  ``scale``: what the scores are
    multiplied by; None is ``1 / sqrt(head_dim)``."""
    heads: int
    kv_heads: int
    head_dim: int
    scale: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class LatentAttentionLayer:
    """A layer whose cache is one latent row a token (MLA, DeepSeek-V2):
    ``latent_dim`` lanes of the normalised compressed K/V ``c_kv`` — the
    value too — then ``rope_dim`` lanes of the one rotated key part all
    heads share.  Head ``i``'s key is ``[W_UK,i c_kv | k_rope]``
    (``nope_dim + rope_dim`` wide), its value ``W_UV,i c_kv`` (``v_dim``);
    ``scale`` multiplies the scores."""
    heads: int
    latent_dim: int
    rope_dim: int
    nope_dim: int
    v_dim: int
    scale: float

    @property
    def row_width(self) -> int:
        """Lanes of a cached row: ``latent_dim + rope_dim`` rounded up to
        whole 128-lane registers.  The chip tiles the minor axis by 128 in
        HBM whatever is asked (576 lanes occupy 640), and Mosaic refuses a
        copy of a 576-wide slice, so the padding is stated, zero, and costs
        nothing more."""
        return -(-(self.latent_dim + self.rope_dim) // 128) * 128


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


@dataclasses.dataclass(frozen=True)
class StatelessLayer:
    """A layer that keeps nothing per request: no pages, no state slot.
    The engine walks it like the others and hands it ``io.n_valid`` /
    ``io.live`` / ``io.note`` / ``io.keep`` only."""
