"""Continuous-batching serving engine over a paged KV pool.

Reference capability: the serving half of the fusion set —
`masked_multihead_attention_kernel.cu` (single-token cached attention, here
the Pallas decode kernel / grouped einsum), the paged
`block_multi_head_attention_kernel.cu` cache (here the page arenas +
:class:`PagedKVPool` tables) and the `fused_multi_transformer` serving loop
(here a decode and a prefill XLA program, compiled once each — the prefill
at a few fixed widths — and reused across the whole request stream).

Design (TPU-first: *nothing* recompiles as traffic changes shape):

- **Physical cache** — per layer, ``k_pages``/``v_pages`` arenas of shape
  ``[num_pages, page_tokens, kv_heads, head_dim]`` (a token's heads merged,
  ``[num_pages, page_tokens, kv_heads * head_dim]``, where a head is
  narrower than the chip's 128 lanes); for a model with latent-attention
  layers (MLA) ONE arena a layer, ``[num_pages, page_tokens, row_width]``:
  a latent row a token and nothing per head.  Both compiled
  programs take the arenas DONATED, update them with scatter-writes, and
  return them; XLA aliases the buffers so the cache never copies (the
  donation lint below enforces exactly this).  A model with state-space
  layers keeps, beside the pages, FIXED-size state per request (a Mamba-2
  layer: the convolution's tail and the ``[H, P, N]`` recurrent state):
  per state layer an arena ``[max_batch, ...]`` indexed by the decode row
  (:class:`~paddle_tpu.serving.state_pool.RowStatePool`), donated and
  returned by both programs with the page arenas.  The prefill program
  zeroes a request's slot when its first launch runs and carries the state
  from launch to launch; decode updates the live rows' slots in place.
- **One decode program** per ``(max_batch, pages_per_seq)`` signature:
  every active request is a row; a row's block table gathers its pages
  into a ``[rows, pages_per_seq * page_tokens, kv, d]`` view, masked by
  the row's position.  Idle rows point at the reserved trash page, so
  admit/finish/evict never changes the compiled shape.
- **One prefill program at a few fixed widths** (``PREFILL_WIDTHS``
  pages a launch): a prompt's uncached pages stream through in launches
  of those widths (:func:`prefill_plan`), so ragged prompt lengths share a
  handful of compiled signatures instead of one per length, and a launch
  of several pages reads the weights once for all of them.  Junk tail slots of the
  prompt's last page are overwritten by the first decode steps before
  the position mask ever exposes them; what a wide launch holds past
  that page scatters to the trash page.
- **Scheduler** — FIFO admission gated on free page count, eviction under
  pool pressure (youngest-admitted victim, or the most-slack victim when
  deadlines are attached; the evictee requeues at the front and recomputes
  from its prompt — deterministic greedy decode makes the replay
  byte-identical), per-request SLO milestones through :class:`SLOMeter`
  and the flight recorder.
- **Resilience** (ISSUE 10) — the front door is an
  :class:`~paddle_tpu.serving.admission.AdmissionController`: bounded
  queue + circuit breaker reject at ``submit`` with ``Overloaded`` and a
  measured retry-after hint, deadline-dead queued requests are shed each
  step, long prompts defer under pool pressure (bounded bypass so the
  head cannot starve).  A :class:`~paddle_tpu.serving.journal.
  ServingJournal` makes accepted work durable (admission records +
  delivered-token high-water marks, flushed through the checkpoint
  storage seam every step), tokens surface to the client sink only AFTER
  the covering flush, and :meth:`ServingEngine.recover` replays the
  journal into a relaunched engine with every delivered token emitted
  exactly once.  ``run()`` can arm a decode-loop watchdog whose expiry
  exits 101 into the :class:`~paddle_tpu.distributed.fleet.elastic.
  supervisor.Supervisor` relaunch path, and transient step failures
  (``serve`` fault family, storage flake) are absorbed with the breaker
  counting them.

Env knobs: ``PADDLE_TPU_SERVE_MAX_BATCH`` (decode rows, default 4),
``PADDLE_TPU_PAGE_TOKENS`` (page size, default 16),
``PADDLE_TPU_SERVE_PAGES`` (arena pages incl. trash page, default 64),
``PADDLE_TPU_SERVE_MAX_PAGES_PER_SEQ`` (per-request budget, default 8),
``PADDLE_TPU_SERVE_LINT`` (=0 skips the decode-program donation gate),
``PADDLE_TPU_SERVE_MAX_QUEUE`` (admission queue bound, default 64),
``PADDLE_TPU_SERVE_BREAKER_THRESHOLD`` / ``_COOLDOWN`` (circuit breaker),
``PADDLE_TPU_SERVE_WATCHDOG_S`` (decode-loop watchdog, 0 = off),
``PADDLE_TPU_SERVE_MAX_STEP_FAILURES`` (consecutive absorbed step
failures before the error propagates, default 8),
``PADDLE_TPU_SERVE_DEFER_LOOKAHEAD`` / ``_DEFER_MAX`` (long-prompt
deferral window / starvation cap).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from ..distributed.checkpoint import faults as _faults
from ..distributed.checkpoint.replicator import env_int as _env_int
from ..distributed.fleet.fault_domain import _env_float
from ..framework.jax_compat import default_layout, persistent_cache_off
from ..jit import named_program
from ..profiler import span as _span
from ..telemetry import record_event as _event
from ..telemetry import tracing
from ..telemetry.runtime import bump as _bump
from .admission import AdmissionController, Deadline, Overloaded
from .journal import ServingJournal
from .kv_pool import LatentLayersUnsupported, OffloadPool, PagedKVPool, \
    PassesUnsupported, PoolExhausted, TRASH_PAGE, default_page_tokens
from .kv_quant import (default_fp8_scale, dequantize_kv, dequantize_kv_fp8,
                       kv_cache_dtype, kv_scale_page_bytes, layer_page_bytes,
                       quantize_kv, quantize_kv_fp8)
from .metrics import Cycle, SLOMeter
from .prefix_cache import PrefixCache
from ..models.serve_protocol import AttentionLayer, LatentAttentionLayer, \
    StateLayer, StatelessLayer
from .state_pool import RowStatePool, StateLayersUnsupported

__all__ = ["Request", "ServingEngine", "check_decode_donation"]

QUEUED, RUNNING, FINISHED, SHED = "queued", "running", "finished", "shed"

# The engine's compiled programs under names of their own: XLA calls the
# module ``jit_<name>``, and that is what a device trace's ``XLA Modules``
# line (and the benchmark's ``_decode_fn`` / ``_prefill_fn`` patterns) shows.
DECODE_PROGRAM = "serve_decode_fn"
PREFILL_PROGRAM = "serve_prefill_fn"
CP_PREFILL_PROGRAM = "serve_cp_prefill_fn"
# Pages a launch of the prefill program may hold: one executable a width,
# every one the module ``jit_serve_prefill_fn``.  A launch of one page is
# bound by the weight read like a decode step; wider ones read the weights
# once for all their pages (the sweep on the chip: PERF.md section 6).
PREFILL_WIDTHS = (1, 4)


def prefill_plan(pages: int, widths=PREFILL_WIDTHS) -> List[int]:
    """The widths of the launches that cover ``pages`` pages: the fewest
    launches the ladder allows in which every launch is more than half full.
    The widest width while more than it holds is left; then the narrowest
    that covers the rest, unless half of it or more would be junk (a launch
    costs what its width costs: the sweep in PERF.md section 6), where full
    narrower launches take the rest.  Only a prompt's last launch can run
    past its pages."""
    out = []
    while pages > 0:
        w = next((w for w in widths if w >= pages), None)
        if w is None or (2 * pages <= w and w != widths[0]):
            w = max((w for w in widths if w <= pages), default=widths[0])
        out.append(w)
        pages -= w
    return out


# What the decode program writes for a position whose logits hold a NaN or an
# infinity, in place of a token id.
NON_FINITE = -1


def greedy_choice(logits):
    """The greedy token of every position of ``logits`` [..., V], chosen on
    the device from the values as they are (no cast): int32 [...], the first
    index of the maximum as ``np.argmax`` picks it, or :data:`NON_FINITE`
    where the position holds a NaN or an infinity."""
    import jax.numpy as jnp

    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(jnp.all(jnp.isfinite(logits), axis=-1), tok,
                     jnp.int32(NON_FINITE))


# Tracing a program swaps tracers into the model's param Tensors
# (``_StateSwap`` in ``_forward``), so two engines sharing one model object
# — an in-process fleet scaling out while the incumbent serves — must never
# overlap a trace with a ``_param_arrays`` read: the reader would capture a
# tracer and feed it to its already-compiled executable.  One process-wide
# lock serialises swap-reads against trace/compile; compiled calls take
# materialised arrays and run outside it.
_SWAP_LOCK = threading.Lock()


class Request:
    """One generation request riding the engine."""

    _next_rid = 0

    def __init__(self, prompt, max_new_tokens: int,
                 eos_token_id: Optional[int],
                 rid: Optional[int] = None,
                 trace_id: Optional[str] = None):
        if rid is None:
            rid = Request._next_rid
            Request._next_rid += 1
        else:
            rid = int(rid)
            Request._next_rid = max(Request._next_rid, rid + 1)
        self.rid = rid
        self.trace_id = trace_id
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.eos_token_id = None if eos_token_id is None else int(eos_token_id)
        self.state = QUEUED
        self.generated: List[int] = []
        self.row: Optional[int] = None
        self.evictions = 0
        self.deadline: Optional[Deadline] = None
        self.delivered = 0                    # client-visible high-water mark
        self.delivered_tokens: List[int] = []
        self.defers = 0                       # FIFO-head bypasses suffered
        self.drafter = None                   # speculative proposer (or None)
        self.cached_tokens = 0                # prompt tokens adopted from the
        # prefix cache at the LAST admission (reset on eviction: the pages
        # go back, the re-admission re-matches)
        self.kv_import = None                 # (first_token, frames) from a
        # prefill-tier worker, or None: set at submit, consumed instead of
        # the local prefill (disagg.py)
        self.offloads = 0                     # host-RAM swap-outs suffered

    @property
    def pos(self) -> int:
        """Cache position the NEXT decode step writes (the position of the
        last generated token)."""
        return len(self.prompt) + len(self.generated) - 1

    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens or (
            self.eos_token_id is not None and bool(self.generated)
            and self.generated[-1] == self.eos_token_id)


def check_decode_donation(compiled, arena_bytes: int,
                          name: str = "serving_decode", *,
                          scale_bytes: int = 0, shards: int = 1,
                          state_bytes: int = 0):
    """Shardlint gate for the serving path: run the ``donation`` rule over
    the compiled decode program and additionally require the KV arenas to
    be ALIASED (donated in, updated in place) — an unaliased arena means
    the program copies the whole cache every step, the exact defect the
    subsystem exists to delete.  With int8 pages the f32 ``scale_bytes``
    buffers ride the same donation: an unaliased scale arena silently
    copies ``2 * layers * pages * page_tokens * kv_heads`` floats per
    step, so the gate requires ``arena_bytes + scale_bytes`` aliased.
    ``arena_bytes`` is the page planes as the engine allocated them, whatever
    a page's row holds: K and V of every kv head, or a latent layer's one
    latent row a token.
    ``state_bytes``: the row-state arenas of a model with state layers
    (:class:`RowStatePool`), which must be aliased all the same — an
    unaliased one copies every row's recurrent state every step.
    Under a ``shards``-way TP mesh (ISSUE 19) the compiled memory
    analysis is PER DEVICE and the arenas shard evenly over the kv-head
    axis, so each shard must alias its ``1/shards`` slice — the gate
    scales its floor accordingly (the donation-dropped failure mode still
    reads as alias_bytes ~ 0, far below any per-shard floor).
    Returns the :class:`LintReport`; raises ``RuntimeError`` when the
    arenas (or scales) are not aliased or an unexempted donation error
    fires."""
    from ..analysis import lint

    report = lint(compiled, rules=["donation"], name=name)
    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = {"alias_bytes": int(ma.alias_size_in_bytes),
               "argument_bytes": int(ma.argument_size_in_bytes)}
    except Exception:
        pass
    need = (int(arena_bytes) + int(scale_bytes)) // max(int(shards), 1) \
        + int(state_bytes)
    if mem is not None and mem["alias_bytes"] < need:
        what = "KV arenas" if not scale_bytes else \
            "KV arenas + int8 scale buffers"
        if state_bytes:
            what += f" + row-state arenas ({state_bytes} bytes)"
        raise RuntimeError(
            f"serving decode program does not alias its {what}: "
            f"{mem['alias_bytes']} bytes aliased < {need} required "
            f"({arena_bytes} arena + {scale_bytes} scale over {shards} "
            f"shard(s)) — the cache is being copied every step (donation "
            f"dropped; check donate_argnums and that arena/scale "
            f"shapes/dtypes are unchanged between input and output)")
    if not report.ok:
        raise RuntimeError(
            "serving decode program failed the donation lint:\n" +
            "\n".join(f.format() for f in report.failures()))
    return report


class _Ride(NamedTuple):
    """The decode part of a riding prefill launch
    (:meth:`ServingEngine._prefill_fn`).  The model sees ONE row: the
    prompt's ``split`` tokens, then the decode rows' ``[R, width]`` tokens
    token-major; ``positions`` / ``valid`` [1, split + R * width] are every
    token's position and whether it is real, ``head`` the tokens whose
    logits are wanted (the prompt's last, then every decode token).
    ``tables`` [R, MP], ``starts`` [R] and ``n_tok`` [R] are the decode
    program's own inputs."""
    split: int
    width: int
    tables: object
    starts: object
    n_tok: object
    positions: object
    valid: object
    head: object


class _LayerIO:
    """The engine's side of ONE layer inside a traced program: what
    ``model.serve_layer(i, x, shared, io)`` may touch.  An attention layer
    calls :meth:`attend` (a latent one :meth:`attend_latent`); a state
    layer reads and writes its rows' slots (:meth:`read_state` /
    :meth:`write_state`); every kind, a layer that keeps nothing too, may
    look at ``valid`` [R, s] (tokens that are real), ``n_valid`` [R]
    (tokens of each row that are real) and ``live`` [R] (rows that step at
    all) and call :meth:`note` / :meth:`keep`.  In a riding launch
    (``ride``: :class:`_Ride`) the one row's real tokens are no prefix, so
    ``n_valid`` and ``live`` are None there.  A call that is another
    kind's raises a ``TypeError`` that names it.  The updated arenas land
    in the program's result."""

    def __init__(self, engine, spec, arenas, index, tables, positions,
                 n_tok, n_valid, row, fresh, notes, kept, valid=None,
                 ride=None):
        self._eng, self._spec, self._arenas = engine, spec, arenas
        self._index, self._notes, self._kept = index, notes, kept
        self._tables, self._positions, self._n_tok = tables, positions, n_tok
        self._row, self._fresh, self._ride = row, fresh, ride
        self.valid = valid
        self.n_valid = None if ride is not None else n_valid
        self.live = None if ride is not None else n_valid > 0

    def _only(self, kind, call: str) -> None:
        if not isinstance(self._spec, kind):
            raise TypeError(
                f"io.{call} is a {kind.__name__}'s call; this layer "
                f"described itself as {type(self._spec).__name__}, which "
                f"keeps no such memory")

    def _parts(self, *xs):
        """Each of ``xs`` [1, split + R * width, ...] of a riding launch as
        its prompt part [1, split, ...] and its decode part [R, width,
        ...]."""
        ride = self._ride
        rows = ride.n_tok.shape[0]
        return [(x[:, :ride.split],
                 x[0, ride.split:].reshape((rows, ride.width) + x.shape[2:]))
                for x in xs]

    @staticmethod
    def _join(prompt, decode):
        """The two parts' outputs as the one row again."""
        import jax.numpy as jnp

        return jnp.concatenate(
            [prompt, decode.reshape((1, -1) + decode.shape[2:])], axis=1)

    def attend(self, q, k, v):
        """Scatter this step's ``k`` / ``v`` [R, s, kv, d] into the layer's
        pages and attend ``q`` [R, s, h, d] over each row's pages.  A
        riding launch attends its prompt part as the prefill program does
        and its decode part as the decode program does."""
        self._only(AttentionLayer, "attend")
        pages = {key: self._arenas[key][self._index]
                 for key in self._eng._planes}
        if self._ride is None:
            out, pages = self._attend(q, k, v, pages, self._tables,
                                      self._positions, self._n_tok,
                                      decode=self._row is None)
        else:
            ride = self._ride
            (qp, qd), (kp, kd), (vp, vd) = self._parts(q, k, v)
            out_p, pages = self._attend(qp, kp, vp, pages, self._tables,
                                        self._positions, self._n_tok,
                                        decode=False)
            out_d, pages = self._attend(qd, kd, vd, pages, ride.tables,
                                        ride.starts, ride.n_tok, decode=True)
            out = self._join(out_p, out_d)
        for key, arena in pages.items():
            self._arenas[key][self._index] = arena
        return out

    def _attend(self, q, k, v, pages, tables, positions, n_tok, *, decode):
        eng, spec = self._eng, self._spec
        walk = eng._page_walk(q.shape[0], q.shape[1], spec) \
            if decode else None
        out, new = eng._attend(
            q, k, v, pages, tables, positions, n_tok,
            walk=None if walk is None else tuple(walk.items()),
            scale=spec.scale)
        return out, dict(pages, **new)

    def attend_latent(self, q_nope, q_rope, c_kv, k_rope, w_uk, w_uv):
        """Scatter this step's latent rows (``c_kv`` [R, s, latent],
        ``k_rope`` [R, s, rope], already rotated) into the layer's pages
        and attend ``q_nope`` [R, s, h, nope] / ``q_rope`` [R, s, h, rope]
        over each row's pages.  ``w_uk`` [latent, h, nope] and ``w_uv``
        [latent, h, v] are the layer's up-projections: the decode program
        absorbs them into the query and the output and walks the latent
        rows as they lie; the prefill program expands the row's pages to
        per-head K/V inside the program; a riding launch, each part as its
        own program does.  Returns [R, s, h, v]."""
        self._only(LatentAttentionLayer, "attend_latent")
        pages = self._arenas["c"][self._index]
        if self._ride is None:
            out, pages = self._attend_latent(
                q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, pages, self._tables,
                self._positions, self._n_tok, decode=self._row is None)
        else:
            ride = self._ride
            (qn, qn_d), (qr, qr_d), (c, c_d), (kr, kr_d) = self._parts(
                q_nope, q_rope, c_kv, k_rope)
            out_p, pages = self._attend_latent(
                qn, qr, c, kr, w_uk, w_uv, pages, self._tables,
                self._positions, self._n_tok, decode=False)
            out_d, pages = self._attend_latent(
                qn_d, qr_d, c_d, kr_d, w_uk, w_uv, pages, ride.tables,
                ride.starts, ride.n_tok, decode=True)
            out = self._join(out_p, out_d)
        self._arenas["c"][self._index] = pages
        return out

    def _attend_latent(self, q_nope, q_rope, c_kv, k_rope, w_uk, w_uv,
                       pages, tables, positions, n_tok, *, decode):
        eng, spec = self._eng, self._spec
        walk = eng._latent_walk(q_nope.shape[0], q_nope.shape[1], spec) \
            if decode else None
        return eng._attend_latent(
            q_nope, q_rope, c_kv, k_rope, w_uk, w_uv, pages, tables,
            positions, n_tok, spec=spec, absorbed=decode,
            walk=None if walk is None else tuple(walk.items()))

    def note(self, name: str, value, reduce: str = "sum") -> None:
        """A count made inside the program, for the step's span: summed
        (``reduce="max"``: the largest taken) over the layers that note it
        and over a prompt's launches, and fetched with the step's token
        ids."""
        import jax.numpy as jnp

        value = jnp.asarray(value, jnp.int32)
        if name in self._notes:
            prev = self._notes[name][0]
            value = prev + value if reduce == "sum" \
                else jnp.maximum(prev, value)
        self._notes[name] = (value, reduce)

    def keep(self, name: str, value) -> None:
        """An array ``[R, s, ...]`` this layer leaves ON THE DEVICE beside
        the program's logits, stacked over the layers that keep it
        (:attr:`ServingEngine.last_decode_kept`, ``last_prefill_kept``):
        fetched on request, by a tolerance harness; no step fetches it."""
        self._kept.setdefault(name, []).append(value)

    def read_state(self, name: str):
        """The rows' slots of state array ``name``, ``[R, *shape]``: in
        decode the arena itself (row r is slot r); in prefill the
        request's slot, zero where its first launch is running."""
        import jax
        import jax.numpy as jnp

        self._only(StateLayer, "read_state")
        arena = self._arenas[name][self._index]
        if self._row is None:
            return arena
        cur = jax.lax.dynamic_index_in_dim(arena, self._row, 0,
                                           keepdims=True)
        return jnp.where(self._fresh, jnp.zeros_like(cur), cur)

    def write_state(self, name: str, value) -> None:
        import jax

        self._only(StateLayer, "write_state")
        arena = self._arenas[name][self._index]
        value = value.astype(arena.dtype)
        self._arenas[name][self._index] = value if self._row is None else \
            jax.lax.dynamic_update_index_in_dim(arena, value[0], self._row,
                                                0)


class ServingEngine:
    """Continuous batching over a causal LM that describes itself layer by
    layer (``serve_layers`` / ``serve_begin`` / ``serve_layer`` /
    ``serve_end``: :mod:`~paddle_tpu.models.serve_protocol`).
    The model owns its block math; the engine owns pages, tables, state
    slots and the two things a layer may ask of it: attend over this
    layer's pages, read and write this row's state.  ``LlamaForCausalLM``
    (attention layers only: every feature below),
    ``GraniteHybridForCausalLM`` (Mamba-2 state layers beside attention
    layers: what would need the state snapshotted, moved or rolled back —
    prefix cache, offload, speculation, TP / CP meshes, quantized pages,
    disaggregated prefill — raises :class:`StateLayersUnsupported`) and
    ``DeepseekV3ForCausalLM`` (latent-attention layers: the prefix cache,
    speculation and fp8 pages work; int8 pages, TP / CP meshes, offload
    and disaggregated prefill raise :class:`LatentLayersUnsupported`) and
    ``NemotronHForCausalLM`` (a block is ONE part: a Mamba-2 state layer, an
    attention layer, or an expert layer that keeps nothing per request; it
    has state layers, so what they refuse it refuses) are served.  Greedy
    decoding — determinism is what makes eviction-replay byte-exact."""

    def __init__(self, model, *, max_batch: Optional[int] = None,
                 page_tokens: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 max_pages_per_seq: Optional[int] = None,
                 lint: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 admission: Optional[AdmissionController] = None,
                 journal=None, journal_ship=None, on_token=None, now=None,
                 kv_dtype: Optional[str] = None, speculative=None,
                 tp: Optional[int] = None, prefix_cache=None,
                 cp: Optional[int] = None, offload=None):
        import jax as _jax
        import jax.numpy as jnp

        from ..generation.speculative import AdaptiveK, SpecConfig

        if not all(hasattr(model, m) for m in (
                "serve_layers", "serve_begin", "serve_layer", "serve_end")):
            raise TypeError(
                "ServingEngine serves causal LMs that describe their layers "
                "to it (serve_layers / serve_begin / serve_layer / "
                "serve_end, see models/serve_protocol.py: LlamaForCausalLM, "
                "GraniteHybridForCausalLM, DeepseekV3ForCausalLM, "
                "NemotronHForCausalLM, OuroForCausalLM); got "
                + type(model).__name__)
        self.model = model
        # the model's layers as the engine sees them, and each layer's
        # index within its own family of arenas
        self._layers = list(model.serve_layers())
        # the layers that keep pages: K/V rows or latent rows, one kind a
        # model (one page arena shape serves them all)
        paged = [sp for sp in self._layers
                 if isinstance(sp, (AttentionLayer, LatentAttentionLayer))]
        stl = [sp for sp in self._layers if isinstance(sp, StateLayer)]
        # layers that keep nothing per request: walked, given no arena
        bare = [sp for sp in self._layers if isinstance(sp, StatelessLayer)]
        if len(paged) + len(stl) + len(bare) != len(self._layers) \
                or not paged:
            raise TypeError(
                "serve_layers() must name AttentionLayer / "
                "LatentAttentionLayer / StateLayer / StatelessLayer "
                "entries, at least one of them a layer that keeps pages")
        if len({(a.kv_heads, a.head_dim) if isinstance(a, AttentionLayer)
                else a for a in paged}) != 1:
            raise ValueError("every layer that keeps pages must keep rows "
                             "of one shape ((kv_heads, head_dim), or one "
                             "latent layer's): one page arena shape serves "
                             "them all")
        self._latent: Optional[LatentAttentionLayer] = \
            paged[0] if isinstance(paged[0], LatentAttentionLayer) else None
        att = paged
        # how many times a step walks the layers (serve_protocol: a looped
        # model's passes); each pass keeps its K/V in pages of its own
        self.passes = int(model.serve_passes()) \
            if hasattr(model, "serve_passes") else 1
        if self.passes < 1:
            raise ValueError(f"serve_passes() is {self.passes}: a step "
                             f"walks the layers at least once")
        if self.passes > 1 and (stl or self._latent is not None):
            raise PassesUnsupported(
                "state or latent layers", "a pass keeps pages of K/V rows "
                "of its own; a state slot or a latent row has no pass yet")
        # the layers whose K/V a step's attention reads, over every pass
        self._kv_layer_passes = self.passes * sum(
            isinstance(sp, AttentionLayer) for sp in self._layers)
        counts = {StateLayer: 0, "paged": 0}
        self._family_index = []
        for sp in self._layers:
            if isinstance(sp, StatelessLayer):      # in no family of arenas
                self._family_index.append(None)
                continue
            family = StateLayer if isinstance(sp, StateLayer) else "paged"
            self._family_index.append(counts[family])
            counts[family] += 1
        self.max_batch = max_batch if max_batch is not None else \
            _env_int("PADDLE_TPU_SERVE_MAX_BATCH", 4)
        P = page_tokens if page_tokens is not None else default_page_tokens()
        N = num_pages if num_pages is not None else \
            _env_int("PADDLE_TPU_SERVE_PAGES", 64)
        MP = max_pages_per_seq if max_pages_per_seq is not None else \
            _env_int("PADDLE_TPU_SERVE_MAX_PAGES_PER_SEQ", 8)
        max_pos = model.config.max_position_embeddings
        if MP * P > max_pos:
            MP = max(1, max_pos // P)
        self.page_tokens, self.num_pages, self.max_pages_per_seq = P, N, MP
        self.pool = PagedKVPool(N, P)
        self._now = now if now is not None else time.monotonic
        self.meter = SLOMeter(now=self._now)
        self.meter.passes = self.passes
        # the cycle in progress (metrics.Cycle), on the meter's clock, and
        # the requests the last delivering flush handed a token
        self._cycle = Cycle(time.monotonic_ns if now is None else
                            (lambda: int(round(now() * 1e9))))
        self._cycle_rids: frozenset = frozenset()
        self.admission = admission if admission is not None else \
            AdmissionController(max_queue=max_queue, now=self._now)
        # journal_ship: optional ``ship(seq, data)`` — a fleet replica
        # wires the depot put here so segments replicate off-host at the
        # same flush boundary that gates token emission (fleet.py)
        self.journal: Optional[ServingJournal] = \
            ServingJournal(journal, ship=journal_ship) \
            if isinstance(journal, str) else journal
        self._on_token = on_token
        # per-replica chaos scope for the "slow_serve" seam: the fleet
        # layer stamps the replica name here so a degraded-hardware fault
        # can target ONE replica even when several share the process
        self.fault_scope = ""
        self._lint = (os.environ.get("PADDLE_TPU_SERVE_LINT", "1") != "0"
                      if lint is None else bool(lint))

        self._params = [p for _, p in model.named_parameters()]
        self._buffers = [b for _, b in model.named_buffers()]
        cdt = next((p._value.dtype for p in self._params
                    if jnp.issubdtype(p._value.dtype, jnp.floating)),
                   jnp.float32)
        self._cdt = cdt
        n_layers = len(att)
        # a latent row is one "head" as wide as the padded row
        kv_heads, head_dim = (1, self._latent.row_width) \
            if self._latent is not None \
            else (att[0].kv_heads, att[0].head_dim)
        # fixed-size state per decode row, for the model's state layers
        self.state: Optional[RowStatePool] = \
            RowStatePool(self.max_batch, stl) if stl else None
        # TP-sharded decode (ISSUE 19 leg 1): tp > 1 compiles BOTH
        # programs under a 1-D "model" mesh — params Megatron-sharded in
        # place (q/k/v/gate/up out-dim, o/down in-dim), arenas sharded
        # over the kv-head axis and STILL donated (each shard aliases its
        # slice), step inputs replicated.  The page tables / scheduler /
        # journal are untouched: sharding is a compile-time property of
        # the two programs, not a scheduling concern.
        self.tp = int(tp if tp is not None
                      else _env_int("PADDLE_TPU_SERVE_TP", 1))
        self._mesh = None
        if self.tp > 1:
            self._refuse_with_state(
                "tp > 1", "the state layers' heads are not sharded over a "
                "model mesh yet")
            self._refuse_with_latent(
                "tp > 1", "every head reads the one latent row: the heads "
                "and the experts need a mesh of their own (two axes)")
            self._refuse_with_passes(
                "tp > 1", "the looped walk is not partitioned over a model "
                "mesh yet")
            from .disagg import decode_mesh, shard_llama_params

            h_att = att[0].heads
            if kv_heads % self.tp or h_att % self.tp:
                raise ValueError(
                    f"PADDLE_TPU_SERVE_TP={self.tp} must divide both "
                    f"kv_heads ({kv_heads}) and attention heads ({h_att}) "
                    f"— a ragged shard would change the q-group geometry")
            self._mesh = decode_mesh(self.tp)
            shard_llama_params(model, self._mesh)
        # context-parallel prefill (long-context ladder): cp > 1 builds a
        # 1-D "sep" mesh and compiles ONE extra prefill program per padded
        # prompt signature that shards the prompt's seq dim over the ring
        # (ops/pallas/ring_flash.py / the jnp ppermute ring).  Params,
        # buffers, arenas and step inputs commit REPLICATED on the mesh so
        # the two standard programs keep their shapes (and their donation);
        # only the CP program's interior is seq-sharded.
        self.cp = int(cp if cp is not None
                      else _env_int("PADDLE_TPU_SERVE_CP", 1))
        if self.cp > 1:
            self._refuse_with_state(
                "cp > 1", "the ring prefill carries no recurrent state "
                "between its shards")
            self._refuse_with_latent(
                "cp > 1", "the ring prefill unrolls the Llama block and "
                "rotates per-head K/V")
            self._refuse_with_passes(
                "cp > 1", "the ring prefill walks the layers once")
            from jax.sharding import Mesh as _Mesh

            if self.tp > 1:
                raise ValueError(
                    f"PADDLE_TPU_SERVE_CP={self.cp} cannot combine with "
                    f"PADDLE_TPU_SERVE_TP={self.tp}: the serving mesh is "
                    f"one axis (shard prompts OR heads, not both yet)")
            devs = _jax.devices()
            if len(devs) < self.cp:
                raise ValueError(
                    f"PADDLE_TPU_SERVE_CP={self.cp} needs {self.cp} "
                    f"devices, have {len(devs)}")
            self._mesh = _Mesh(np.array(devs[:self.cp]), ("sep",))
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(self._mesh, PartitionSpec())
            for p in self._params:
                p._value = _jax.device_put(p._value, rep)
            for bb in self._buffers:
                bb._value = _jax.device_put(bb._value, rep)
        self._cp_execs: Dict[int, object] = {}
        self.cp_lint_reports: Dict[int, object] = {}
        # KV page dtype (ISSUE 13 + long-context ladder): "bf16" = the
        # native compute dtype, bit-exact; "int8" stores quantized pages +
        # f32 per-(slot, head) scale arenas, dequantized at the gather
        # inside the same program; "fp8" stores f8e4m3fn pages under ONE
        # static scale baked into the programs (no scale arenas — exactly
        # half the bf16 page bytes)
        # a page is [P, kv_heads, head_dim]; where a head is narrower than
        # a lane register (128) the arena keeps a token's heads merged,
        # [P, kv_heads * head_dim]: the chip tiles the two minor axes, and
        # around every scatter and gather the compiler re-laid a 64-wide
        # arena out, a copy of the whole pool each way (PERF.md, PR 26).
        # The decode kernel reads a merged page as it lies.  Under the TP
        # mesh the kv-head axis stays an axis: it is sharded.
        # A latent layer's page is [P, row_width]: one row a token.
        self._flat_pages = self._latent is not None or \
            (head_dim % 128 != 0 and self.tp == 1)
        self._page_shape = (P, kv_heads, head_dim)
        # a pass's pages lie at ``t * N`` in the arena of P * N pages
        self._arena_shape = (self.passes * N, P, kv_heads * head_dim) \
            if self._flat_pages else (self.passes * N,) + self._page_shape
        self.kv_dtype = kv_cache_dtype(kv_dtype)
        if self.kv_dtype != "bf16":
            self._refuse_with_state(
                f"kv_dtype={self.kv_dtype!r}", "quantized pages beside a "
                "float32 recurrent state have no measured tolerance yet")
            self._refuse_with_passes(
                f"kv_dtype={self.kv_dtype!r}", "quantized pages of every "
                "pass have no measured tolerance yet")
        if self.kv_dtype == "int8":
            self._refuse_with_latent(
                "kv_dtype='int8'", "the scales are one a kv head, and a "
                "latent row mixes a normalised latent with a rotated key")
        self._fp8_scale = default_fp8_scale() \
            if self.kv_dtype == "fp8" else None
        adt = (jnp.int8 if self.kv_dtype == "int8"
               else jnp.float8_e4m3fn if self.kv_dtype == "fp8" else cdt)
        # what a page holds, by plane: K and V rows, or latent rows ("c")
        arenas = {plane: [jnp.zeros(self._arena_shape, adt)
                          for _ in range(n_layers)]
                  for plane in (("c",) if self._latent is not None
                                else ("k", "v"))}
        self._scale_bytes = 0
        if self.kv_dtype == "int8":
            sshape = (N, P, kv_heads)
            arenas["ks"] = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(n_layers)]
            arenas["vs"] = [jnp.zeros(sshape, jnp.float32)
                            for _ in range(n_layers)]
            self._scale_bytes = 2 * n_layers * int(np.prod(sshape)) * 4
        if self._mesh is not None and self.tp > 1:
            from .disagg import shard_arenas

            arenas = shard_arenas(arenas, self._mesh)
        elif self._mesh is not None:
            # cp mesh: arenas replicate — each device aliases its full
            # copy, so the donation lint floors are unchanged (shards=1)
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(self._mesh, PartitionSpec())
            arenas = {key: [_jax.device_put(a, rep) for a in arrs]
                      for key, arrs in arenas.items()}
        # what the donation gate holds the decode program to: the page
        # planes as they are allocated, whatever kind of row they keep
        self._arena_bytes = sum(
            int(np.prod(a.shape)) * a.dtype.itemsize
            for plane in ("k", "v", "c") for a in arenas.get(plane, ()))
        self._planes = tuple(arenas)    # what a layer's page holds: k, v
        # and an int8 pool's scales, or latent rows
        self._attend = _jax.jit(self._paged_attention,
                                static_argnames=("walk", "scale"))
        self._attend_latent = _jax.jit(
            self._latent_attention,
            static_argnames=("spec", "walk", "absorbed"))
        if self.state is not None:
            arenas.update(self.state.zeros())
        self._arenas = arenas
        # a page is priced by its layer's kind, over every layer and pass
        self.pool.set_page_bytes(
            self.passes * sum(layer_page_bytes(sp, P, self.kv_dtype)
                              for sp in att),
            kv_scale_page_bytes(P, kv_heads, self.kv_dtype,
                                n_layers=n_layers),
            self.kv_dtype)
        self.meter.set_kv_bytes_per_token(self.pool.bytes_per_token())

        # prefix cache (ISSUE 19 leg 3): True/env "1" = trie under the
        # PADDLE_TPU_PREFIX_PAGES budget; an int = explicit page budget;
        # a PrefixCache = caller-owned (tests share one across engines)
        if prefix_cache is None:
            prefix_cache = \
                os.environ.get("PADDLE_TPU_PREFIX_CACHE", "0") == "1"
        if prefix_cache is True:
            prefix_cache = PrefixCache(self.pool)
        elif isinstance(prefix_cache, int) and not isinstance(
                prefix_cache, bool) and prefix_cache > 0:
            prefix_cache = PrefixCache(self.pool, max_pages=prefix_cache)
        self.prefix: Optional[PrefixCache] = \
            prefix_cache if isinstance(prefix_cache, PrefixCache) else None
        if self.prefix is not None:
            self._refuse_with_state(
                "prefix_cache", "a cached prefix's pages would be adopted "
                "without the recurrent state at their end (no state "
                "snapshots yet)")
            self._refuse_with_passes(
                "prefix_cache", "adopting a page across passes is not "
                "measured yet")

        # host-RAM KV offload (long-context ladder): preemption swaps a
        # victim's private pages to the OffloadPool instead of discarding
        # them — its generated tokens SURVIVE and decode resumes
        # token-exact after the recall scatter.  Shared (prefix-trie)
        # pages never copy: the park keeps the victim's reference so the
        # one resident copy stays in HBM.  True/env "1" = tier under the
        # PADDLE_TPU_KV_OFFLOAD_PAGES budget; an OffloadPool = caller-owned
        if offload is None:
            offload = os.environ.get("PADDLE_TPU_KV_OFFLOAD", "0") == "1"
        if offload is True:
            offload = OffloadPool()
        elif isinstance(offload, int) and not isinstance(offload, bool) \
                and offload > 0:
            offload = OffloadPool(max_pages=offload)
        self.offload: Optional[OffloadPool] = \
            offload if isinstance(offload, OffloadPool) else None
        if self.offload is not None:
            self._refuse_with_state(
                "offload", "a swapped-out request's recurrent state is not "
                "spilled with its pages yet")
            self._refuse_with_latent(
                "offload", "the host frames are K/V frames [layers, P, kv, "
                "d]; latent rows have no frame format yet")
            self._refuse_with_passes(
                "offload", "a host frame holds one pass's page")
        self._offload_lost: set = set()   # parked rids whose host frames
        # were LRU-dropped: recall is impossible, re-admission downgrades
        # them to the eviction-replay re-prefill path (the README failure
        # matrix's "offload stall" row)

        # speculative decoding (ISSUE 13): the decode program widens to a
        # fixed [R, k_max+1] verify signature; a per-row dynamic valid
        # count carries the adaptive draft length, so k changes never
        # recompile.  None/0 = plain serial decode (S = 1).
        if speculative is None:
            env_k = _env_int("PADDLE_TPU_SPEC_K", 0)
            speculative = SpecConfig(k=env_k) if env_k > 0 else None
        elif isinstance(speculative, int):
            speculative = SpecConfig(k=speculative) \
                if speculative > 0 else None
        elif not isinstance(speculative, SpecConfig):
            raise TypeError("speculative must be None, an int draft "
                            "length, or a generation.SpecConfig")
        self.spec: Optional[SpecConfig] = speculative
        if self.spec is not None:
            self._refuse_with_state(
                "speculative", "a rejected draft would have to roll the "
                "recurrent state back")
            self._refuse_with_passes(
                "speculative", "verifying drafts through every pass is not "
                "measured yet")
        self._spec_width = 1 + (self.spec.k if self.spec else 0)
        self._adapt = AdaptiveK(self.spec.k, self.spec.adaptive,
                                decay=self.spec.ema_decay) \
            if self.spec else None
        # a step that prefills carries its decode rows on its LAST prefill
        # launch (one weight read and one launch path for both;
        # :meth:`_step_inner`) where every layer can split its work by
        # part: attention by its own tables, a latent layer absorbed for
        # the decode part, a layer that keeps nothing by ``io.valid``.  A
        # state layer would have to split its conv tail and scan, and the
        # TP / CP meshes run programs of their own: those keep the two
        # programs apart.  Only the narrowest launch of the ladder carries
        # a decode part (:meth:`_carries_rows`).  Set False before the
        # first step to keep the programs apart anyway (they are built for
        # what it says then).
        refusal = "state_layers" if self.state is not None else \
            "mesh" if self._mesh is not None else None
        self.rides_prefill = refusal is None
        self.rides_prefill_refusal: Optional[str] = refusal
        if refusal is not None:
            _event("serve_rides_prefill", refusal, rides_prefill=False)
        # the decode program, compiled before any other, chooses the layouts
        # the weights lie in, and they move there once (:meth:`_compile`):
        # a weight laid out otherwise is copied on every launch.  A mesh's
        # programs are partitioned by GSPMD and keep the layouts as they
        # are; so does an engine whose model already lies in layouts
        # another engine's programs chose ("laid_out": moving them again
        # would break that engine's programs).  Both are named here.
        self.param_layout_refusal: Optional[str] = \
            "mesh" if self._mesh is not None else None
        if self._mesh is not None:
            _event("serve_param_layouts", "mesh", relaid=0)
        self._idle_ride = None         # the decode part of a launch that
        # carries no rows (:meth:`_ride_args`)
        self._ride_choice = None        # the riding launch's [R, S] choice

        self._queue: deque = deque()
        self._active: Dict[int, Request] = {}          # row -> Request
        self._results: Dict[int, np.ndarray] = {}
        self.shed: Dict[int, str] = {}                 # rid -> reason
        self._decode_exec = None
        # a launch wider than a row's table could never be full of pages
        self._prefill_widths = tuple(
            w for w in PREFILL_WIDTHS
            if w <= max(self.max_pages_per_seq, PREFILL_WIDTHS[0]))
        self._prefill_exec: Dict[int, object] = {}     # width -> program
        self._decode_compiles = 0
        self.lint_report = None
        self._note_reduce: Dict[str, str] = {}   # what the model's layers
        # note (``io.note``) and how it adds up, set when a program is traced
        self._prefill_notes: list = []   # a prompt's launches' vectors
        self._decode_logits = None       # the latest step's logits
        # [R, S, V], left on the device (:attr:`last_decode_logits`)
        self._decode_kept: Dict[str, object] = {}   # and what its layers
        # kept there (:attr:`last_decode_kept`)
        self._decode_noted = None        # what they noted: fetched with the
        # step's token ids
        self._prefill_kept: list = []    # the same of the latest prompt's
        # launches (:attr:`last_prefill_kept`)
        self.steps_total = 0
        self.first_step_wall: Optional[float] = None   # WARMING until set:
        # a replica advertises warming=True on its lease until its first
        # completed work step, so the fleet router never spills a
        # deadline-bound request onto a cold (uncompiled/unloaded) engine
        self._pending_delivery: List[tuple] = []       # (rid, idx, token)
        self._work = threading.Event()
        self._stop_flag = False
        self._step_failures = 0
        self._max_step_failures = _env_int(
            "PADDLE_TPU_SERVE_MAX_STEP_FAILURES", 8)
        self._defer_lookahead = _env_int(
            "PADDLE_TPU_SERVE_DEFER_LOOKAHEAD", 4)
        self._defer_max = _env_int("PADDLE_TPU_SERVE_DEFER_MAX", 8)

    def _refuse_with_state(self, feature: str, why: str) -> None:
        """What cannot be right yet for a model with state layers is
        refused by name, never run wrong."""
        if self.state is not None:
            raise StateLayersUnsupported(feature, why)

    def _refuse_with_latent(self, feature: str, why: str) -> None:
        """The same for pages of latent rows."""
        if self._latent is not None:
            raise LatentLayersUnsupported(feature, why)

    def _refuse_with_passes(self, feature: str, why: str) -> None:
        """The same for a model whose layers run several times a step."""
        if self.passes > 1:
            raise PassesUnsupported(feature, why)

    # -- public API --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 64,
               eos_token_id: Optional[int] = None, *,
               deadline: Optional[Deadline] = None,
               rid: Optional[int] = None,
               delivered_tokens: Optional[List[int]] = None,
               age_s: float = 0.0,
               trace_id: Optional[str] = None,
               kv_import=None) -> int:
        """Admit a request or refuse it.  Raises ``ValueError`` for a
        request the engine could NEVER serve (malformed, or worst-case
        page demand beyond the whole pool), :class:`Overloaded` for a
        request it cannot serve NOW (bounded queue full, circuit breaker
        open) — the latter carries ``retry_after_s``.

        ``delivered_tokens`` / ``age_s`` are the fleet failover hooks: a
        request replayed from a dead replica arrives with the tokens its
        client already saw (delivered high-water mark — regenerated but
        not re-emitted) and the wall-clock age it accrued there (deadlines
        keep aging across the failover).  ``trace_id`` is its
        distributed-trace id (minted here for edge submits, passed
        through for fleet/replay submits) — one trace spans the request's
        whole life, across any number of replicas."""
        trace_id = tracing.mint(trace_id)
        r = Request(prompt, max_new_tokens, eos_token_id, rid=rid,
                    trace_id=trace_id)
        # disagg import (see submit_prefilled): set BEFORE the request is
        # visible to the scheduler so admission never races the flag —
        # an imported request takes a full private allocation (its frames
        # cover every prompt page) and skips prefix matching
        r.kv_import = kv_import
        if rid is not None and (
                rid in self._results or rid in self.shed or
                any(q.rid == rid for q in list(self._queue)) or
                any(a.rid == rid for a in list(self._active.values()))):
            raise ValueError(f"rid {rid} already known to this engine")
        if deadline is not None and not isinstance(deadline, Deadline):
            raise TypeError("deadline must be a serving.Deadline")
        r.deadline = deadline
        budget = self.max_pages_per_seq * self.page_tokens
        if len(r.prompt) + r.max_new_tokens > budget:
            raise ValueError(
                f"prompt ({len(r.prompt)}) + max_new_tokens "
                f"({r.max_new_tokens}) exceeds the per-request page budget "
                f"{budget} (= {self.max_pages_per_seq} pages x "
                f"{self.page_tokens} tokens)")
        need_max = self.pool.pages_for(len(r.prompt) + r.max_new_tokens)
        if need_max > self.pool.capacity:
            # an unservable request must be rejected HERE: admitted, it
            # would either block the FIFO head forever (never enough free
            # pages) or evict everyone and still starve mid-decode,
            # crashing run() and discarding other requests' work
            raise ValueError(
                f"request needs up to {need_max} pages but the pool only "
                f"has {self.pool.capacity} — raise PADDLE_TPU_SERVE_PAGES "
                f"or lower max_new_tokens")
        try:
            self.admission.check(len(self._queue), self.meter)
        except Overloaded as e:
            self.meter.reject(reason=e.reason,
                              retry_after_s=e.retry_after_s)
            raise
        if delivered_tokens:
            r.delivered = len(delivered_tokens)
            r.delivered_tokens = [int(t) for t in delivered_tokens]
        if self.journal is not None:
            # accepted work becomes durable at the admission boundary —
            # BEFORE the request is queued, so a flush failure leaves
            # neither a phantom queue entry (served despite the client
            # seeing an error) nor a ghost journal record (replayed after
            # a crash despite never being accepted)
            self.journal.submit_durable(r.rid, r.prompt, r.max_new_tokens,
                                        r.eos_token_id, r.deadline,
                                        primed=r.delivered_tokens or None,
                                        age_s=age_s, trace_id=trace_id)
        self._queue.append(r)
        self.meter.submit(r.rid, age_s=age_s, trace_id=trace_id)
        self.meter.set_queue_depth(len(self._queue))
        self._work.set()
        return r.rid

    def submit_prefilled(self, prompt, first_token: int, kv_frames, *,
                         max_new_tokens: int = 64,
                         eos_token_id: Optional[int] = None,
                         deadline: Optional[Deadline] = None,
                         rid: Optional[int] = None,
                         age_s: float = 0.0,
                         trace_id: Optional[str] = None) -> int:
        """Admit a request whose prefill already ran on a prefill-tier
        worker (ISSUE 19 leg 2): ``kv_frames`` holds one host dict per
        prompt page (the :meth:`prefill_export` format, streamed through
        the depot) and ``first_token`` the token that prefill's logits
        chose.  Instead of running the prefill program, admission scatters
        the frames into the arenas and delivery starts at
        ``first_token``.

        The journal records the FULL prompt, exactly as a local submit
        would: crash replay re-prefills locally — deterministic greedy
        makes that token-exact even when the frames are long gone, and the
        delivered high-water mark keeps emission exactly-once."""
        self._refuse_with_state(
            "submit_prefilled", "the frames carry K/V pages and no "
            "recurrent state")
        self._refuse_with_latent(
            "submit_prefilled", "the frames are K/V frames; latent rows "
            "have no frame format yet")
        self._refuse_with_passes(
            "submit_prefilled", "a frame holds one pass's page")
        frames = list(kv_frames)
        p = np.asarray(prompt, np.int32).reshape(-1)
        need = self.pool.pages_for(len(p))
        if len(frames) != need:
            raise ValueError(
                f"kv_frames covers {len(frames)} pages but the prompt "
                f"needs {need} (page_tokens={self.page_tokens})")
        return self.submit(prompt, max_new_tokens, eos_token_id,
                           deadline=deadline, rid=rid, age_s=age_s,
                           trace_id=trace_id,
                           kv_import=(int(first_token), frames))

    def handback_queued(self) -> List[dict]:
        """Drain hook: remove every queued-but-UNSTARTED request (nothing
        delivered yet, not holding pool pages) and return its descriptor
        so a fleet frontend can re-submit it on another replica.  Each
        handed-back rid is journaled as shed(``drained``): if THIS replica
        later dies, its journal fold must not resurrect work that already
        moved elsewhere.  Active requests are untouched — a draining
        replica finishes what it started."""
        out: List[dict] = []
        for r in list(self._queue):
            if r.delivered > 0:
                continue   # an evictee mid-replay: its pages/tokens live
                # here, let the drain finish it locally
            try:
                self._queue.remove(r)
            except ValueError:
                continue   # the serve thread admitted it meanwhile
            # read the clock BEFORE shedding: meter.shed retires it
            age_s = max(0.0, self._now() - self.meter.clock(r.rid).submit_t)
            self._shed(r, "drained")
            out.append({"rid": r.rid,
                        "prompt": [int(x) for x in r.prompt],
                        "max_new_tokens": r.max_new_tokens,
                        "eos_token_id": r.eos_token_id,
                        "deadline": (None if r.deadline is None
                                     else r.deadline.to_doc()),
                        "age_s": age_s,
                        "trace_id": r.trace_id})
        if out and self.journal is not None:
            try:
                self.journal.flush()
            except OSError:
                pass   # shed records stay pending; next step retries
        self.meter.set_queue_depth(len(self._queue))
        return out

    def run(self, max_steps: int = 100000, *, forever: bool = False,
            watchdog_s: Optional[float] = None,
            on_wedge=None) -> Dict[int, np.ndarray]:
        """Drive the scheduler; returns {rid: generated token array}.

        ``forever=False`` (default) returns once every submitted request
        finished (or was shed) and verifies the pool quiesced with zero
        leaked pages.  ``forever=True`` keeps serving: an idle engine
        blocks on an event ``submit`` sets (no busy-spin, the step counter
        stays flat) until :meth:`stop` is called — it still drains to idle
        before returning, and still leak-checks.

        ``watchdog_s`` (default env ``PADDLE_TPU_SERVE_WATCHDOG_S``, 0 =
        off) arms a :class:`~paddle_tpu.distributed.CommWatchdog` around
        every step: a wedged compiled program (or a scheduler livelock)
        dumps the flight recorder and invokes ``on_wedge`` — by default
        ``os._exit(101)`` so a Supervisor relaunches into
        :meth:`recover`.  The journal is flushed every step, so the exit
        loses no accepted work and no delivered token."""
        if watchdog_s is None:
            watchdog_s = _env_float("PADDLE_TPU_SERVE_WATCHDOG_S", 0.0)
        wd = None
        if watchdog_s and watchdog_s > 0:
            from ..distributed.watchdog import CommWatchdog

            wd = CommWatchdog(timeout=watchdog_s,
                              poll_interval=min(0.5, watchdog_s / 4),
                              on_timeout=on_wedge or self._wedge_handler)
        steps = 0
        self._stop_flag = False
        try:
            while True:
                if not self._queue and not self._active:
                    if self._undelivered():
                        # a transient flush failure on the FINAL step left
                        # journal records / sink tokens pending — they are
                        # remaining work: step() retries the flush (and
                        # still escalates after MAX_STEP_FAILURES) before
                        # the loop may declare quiescence or park idle
                        if wd is not None:
                            with wd.watch("serve_step", timeout=watchdog_s):
                                self.step()
                        else:
                            self.step()
                        continue
                    if not forever or self._stop_flag:
                        break
                    self._work.wait()        # event-gated idle: no spin
                    self._work.clear()
                    continue
                if wd is not None:
                    with wd.watch("serve_step", timeout=watchdog_s):
                        self.step()
                else:
                    self.step()
                steps += 1
                # the quiesce guard bounds the BATCH mode (a finite trace
                # that stops draining is a livelock); a forever server
                # legitimately steps without bound — its hang guard is
                # the watchdog
                if not forever and steps > max_steps:
                    raise RuntimeError(f"serving loop did not quiesce in "
                                       f"{max_steps} steps")
        finally:
            if wd is not None:
                wd.stop()
        # with a live prefix cache the trie legitimately pins pages at
        # quiesce; the partition invariant (free ⊎ referenced = all
        # pages, shared counted once) still holds and is still checked
        self.pool.check_leaks(allow_shared=self.prefix is not None)
        return dict(self._results)

    def serve_forever(self, **kw) -> Dict[int, np.ndarray]:
        """``run(forever=True)``: serve until :meth:`stop`."""
        return self.run(forever=True, **kw)

    def stop(self) -> None:
        """Ask a ``forever`` loop to return once it drains to idle."""
        self._stop_flag = True
        self._work.set()

    @property
    def last_decode_logits(self) -> Optional[np.ndarray]:
        """Host copy of the latest decode step's logits ``[R, S, V]`` in the
        program's own dtype, None before the first step.  FETCHED ON
        REQUEST: the array stays on the device and a step pulls only its
        token ids, so every read costs a transfer of the whole array and is
        counted (``SLOMeter.summary()["decode_logits_fetches"]``).  The
        tolerance harnesses read it; the serving loop never does."""
        if self._decode_logits is None:
            return None
        self.meter.decode_logits_fetched()
        return np.asarray(self._decode_logits)

    @property
    def last_decode_kept(self) -> Dict[str, np.ndarray]:
        """Host copies of what the latest decode step's layers kept on the
        device (``io.keep``): per name one array ``[layers that kept it, R,
        S, ...]``.  Fetched on request, as :attr:`last_decode_logits` is."""
        return {name: np.asarray(a) for name, a in self._decode_kept.items()}

    @property
    def last_prefill_kept(self) -> Dict[str, np.ndarray]:
        """The same of the latest prompt's prefill: per name one array
        ``[layers that kept it, tokens, ...]`` over the tokens of the pages
        its launches ran, padding included (a prefix-cached page ran in no
        launch)."""
        launches = self._prefill_kept
        return {name: np.concatenate([np.asarray(k[name])[:, 0]
                                      for k in launches], axis=1)
                for name in (launches[0] if launches else ())}

    def row_state(self, rid: int) -> Dict[str, np.ndarray]:
        """Host copy of a RUNNING request's fixed-size state: per name one
        array ``[state layers, *shape]``, as the last program left it (what
        ``last_decode_logits`` is to the logits: the tolerance harness
        holds the recurrent state itself to the reference, because a state
        kept in too few bits hides in the logits under the activations' own
        rounding)."""
        row = next((row for row, r in self._active.items()
                    if r.rid == rid), None)
        if self.state is None or row is None:
            raise KeyError(f"request {rid} holds no row state")
        return {name: np.stack([np.asarray(a[row])
                                for a in self._arenas[name]])
                for name in self.state.names}

    def step(self) -> None:
        """One scheduler iteration: shed what cannot meet its deadline,
        admit what fits, prefill the newly admitted, take one decode step
        for every row that holds a token, retire finished rows, then flush
        the journal and surface newly delivered tokens to the sink.  Where
        the engine rides (:attr:`rides_prefill`) a step that prefills runs
        no decode program: its decode rows ride the last prompt's last
        launch, and that prompt's row steps from the next step on
        (:meth:`_step_inner`).

        Transient (``OSError``-class) failures — storage flake on the
        journal, injected ``serve`` faults — are absorbed: request state
        is untouched (faults fire before the mutation they guard), the
        circuit breaker counts the failure, and the next step retries.
        After ``PADDLE_TPU_SERVE_MAX_STEP_FAILURES`` consecutive failures
        the error propagates."""
        self.steps_total += 1
        self._cycle.enter("admit")      # what ends here lay outside step()
        try:
            with _span("serve.step", step=self.steps_total,
                       active=len(self._active), queued=len(self._queue)):
                try:
                    did_work = self._step_inner()
                except OSError as e:
                    self._step_failures += 1
                    self.admission.breaker.note_failure()
                    _event("serve_step_error", type(e).__name__,
                           error=repr(e)[:200],
                           consecutive=self._step_failures)
                    _bump("serving.step_failures_total")
                    if self._step_failures >= self._max_step_failures:
                        raise
                    return
                if did_work:
                    self._step_failures = 0
                    self.admission.breaker.note_success()
                    if self.first_step_wall is None:
                        self.first_step_wall = time.time()
        finally:
            self._cycle.enter("outside")

    def _undelivered(self) -> bool:
        """Tokens or journal records still awaiting a successful flush."""
        return bool(self._pending_delivery) or (
            self.journal is not None and self.journal.pending > 0)

    def _step_inner(self) -> bool:
        """The phases of :meth:`step`, in order: shed, admit; the fresh
        prompts' prefills; the decode step over every row that holds a
        token; the flush.  Where the engine rides, the last fresh prompt
        whose prefill ends in a launch that carries a decode part
        (:meth:`_rides_last`; the rider) is prefilled after the decode
        step's preparation, and its last launch carries the decode rows:
        ``_decode_step`` then launches nothing, the rider's one fetch
        brings its first token and the rows' choice, and both are flushed
        at the end of the step.  The rider's row steps from the next step
        on; a prompt prefilled before it already holds its token and
        rides as a decode row.  A step with no rider, or whose rider the
        preparation evicted, runs the decode program."""
        with _span("serve.shed_scan"):
            self._shed_scan()
        with _span("serve.admit") as sp:
            occupied = len(self._active)
            self._admit()
            sp.note(admitted=len(self._active) - occupied)
        did_work = self._undelivered()   # a retried flush is real work:
        # succeeding must reset the failure streak and close the breaker
        cy = self._cycle
        fresh = [r for r in self._active.values() if not r.generated]
        rider = next((r for r in reversed(fresh) if self._rides_last(r)),
                     None) if self.rides_prefill else None
        if fresh:
            cy.enter("prefill")
        for r in fresh:
            if r is not rider:
                self._prefill_request(r)
                did_work = True
        ride = None
        if any(r.generated for r in self._active.values()):
            cy.enter("decode")
            ride = self._decode_step(rider)
            did_work = True
        if rider is not None and rider.state == RUNNING:
            cy.enter("prefill")
            self._prefill_request(rider, ride)
            did_work = True
        self._flush_delivery()
        self.meter.set_queue_depth(len(self._queue))
        self.meter.set_occupancy(self.pool.occupancy())
        return did_work

    def _pages_to_run(self, r: Request):
        """``r``'s prompt pages and the first one its prefill runs: pages
        ``[0, c0)`` were adopted already filled from the prefix cache, and
        the match cap guarantees ``c0 < n_chunks`` (the last prompt
        token's logits are always computed fresh)."""
        n_chunks = -(-len(r.prompt) // self.page_tokens)
        return n_chunks, min(r.cached_tokens // self.page_tokens,
                             n_chunks - 1)

    def _carries_rows(self, width: int) -> bool:
        """Whether a prefill launch of ``width`` pages carries a decode
        part: only the narrowest of the ladder, the launch whose rows lie
        under the chip's ridge, where the rows beside it ride the weight
        read it makes anyway.  A wider launch is bound by its matmuls, and
        its idle decode slots (every launch but a step's last) would cost
        it rows: it keeps the plain program."""
        return self.rides_prefill and width == self._prefill_widths[0]

    def _rides_last(self, r: Request) -> bool:
        """Whether ``r``'s prefill ends in a launch that carries a decode
        part (imported pages launch nothing)."""
        if r.kv_import is not None:
            return False
        n_chunks, c0 = self._pages_to_run(r)
        return self._carries_rows(
            prefill_plan(n_chunks - c0, self._prefill_widths)[-1])

    def _prefill_request(self, r: Request, ride=None) -> None:
        """``r``'s prefill under its span, then its retirement if its first
        token was its last.  ``ride``: the decode batch its last launch
        carries (:meth:`_decode_prep`), or None."""
        self._cycle.prefill_requests += 1
        with _span("serve.prefill", rid=r.rid, trace=r.trace_id or "",
                   prompt_tokens=len(r.prompt),
                   cached_tokens=r.cached_tokens) as sp:
            chunks, launches, noted = self._prefill(r, ride)
            sp.note(chunks=chunks, launches=launches, **noted)
        self._retire_if_done(r)

    # -- scheduling --------------------------------------------------------
    def _free_rows(self) -> List[int]:
        return [i for i in range(self.max_batch) if i not in self._active]

    def _shed_scan(self) -> None:
        """Drop queued requests whose deadline can no longer be met —
        serving them would burn pool pages on output nobody is waiting
        for.  Active requests are never shed (they are producing; a miss
        is counted at finish)."""
        # snapshot + in-place removal: submit() may append from another
        # thread while a forever-mode engine steps — never rebind or
        # iterate the live deque here (a rebind would silently strand a
        # concurrent append on the orphaned deque)
        for r in list(self._queue):
            reason = self.admission.shed_reason(
                submit_t=self.meter.clock(r.rid).submit_t,
                deadline=r.deadline, first_token_out=r.delivered > 0,
                meter=self.meter)
            if reason is not None:
                self._queue.remove(r)
                self._shed(r, reason)

    def _shed(self, r: Request, reason: str) -> None:
        r.state = SHED
        self.shed[r.rid] = reason
        if self.journal is not None:
            self.journal.shed(r.rid, reason)
        self.meter.shed(r.rid, reason=reason)

    def _admit_need(self, r: Request):
        """``(pages to NEWLY allocate, cached prefix pages to adopt)`` for
        admitting ``r``.  With a prefix cache, the trie's longest match
        shrinks the fresh-page demand (the match cap guarantees at least
        ONE private page: the last prompt token always re-prefills, and
        decode writes land past the shared prefix).  Imported requests
        (``kv_import``) carry frames for every page and skip matching."""
        total = self.pool.pages_for(len(r.prompt) + 1)
        if self.prefix is None or r.kv_import is not None:
            return total, []
        pages, _n_tok = self.prefix.match(r.prompt)
        return total - len(pages), pages

    def _admit(self) -> None:
        rows = self._free_rows()
        while self._queue and rows:
            r = self._queue[0]
            if self.pool.is_parked(r.rid):
                # swapped-out request at the head: restore its KV from the
                # host tier (or downgrade to an eviction-style re-prefill
                # if the frames were LRU-dropped) instead of re-allocating
                if self._recall(r, rows) == "wait":
                    break
                continue
            need, cached = self._admit_need(r)
            if not self.pool.can_alloc(need):
                # pool pressure: a long prompt at the head must not wedge
                # admission — try ONE shorter request from the lookahead
                # window (bounded per-head bypass budget, no starvation)
                if not self._admit_bypass(r, need, rows):
                    break
                continue
            self._admit_one(r, need, rows, from_head=True, cached=cached)

    def _admit_one(self, r: Request, need: int, rows: List[int],
                   *, from_head: bool, cached=()) -> None:
        _faults.fire("serve_pool", f"admit_rid{r.rid}")
        if from_head:
            self._queue.popleft()
        else:
            self._queue.remove(r)
        if cached:
            # prefix hit: adopt the trie's pages (COW refcount++) and
            # allocate only the uncached tail — prefill resumes at the
            # first uncached chunk (see _prefill)
            self.pool.adopt(r.rid, cached)
            r.cached_tokens = len(cached) * self.page_tokens
        else:
            r.cached_tokens = 0
        if self.prefix is not None and r.kv_import is None:
            self.prefix.note(bool(cached), n_tokens=r.cached_tokens)
        self.pool.alloc(r.rid, need)
        r.row = rows.pop(0)
        r.state = RUNNING
        self._active[r.row] = r
        if self.state is not None:
            # a state slot is the row; the prefill program zeroes it when
            # the request's first page runs
            self.meter.set_state_slots(len(self._active) / self.max_batch)
        self.meter.admit(r.rid, queue_depth=len(self._queue), pages=need)
        self.meter.set_occupancy(self.pool.occupancy())

    def _admit_bypass(self, head: Request, head_need: int,
                      rows: List[int]) -> bool:
        """Pool-pressure deferral of long prompts: when the FIFO head does
        not fit, admit one STRICTLY smaller request from the next
        ``PADDLE_TPU_SERVE_DEFER_LOOKAHEAD`` queue slots instead of
        wedging.  The head keeps its place and can only be bypassed
        ``PADDLE_TPU_SERVE_DEFER_MAX`` times — after that admission holds
        strictly FIFO until the head fits.  Demand is compared on FRESH
        pages (post prefix-cache match): a long prompt that is mostly
        cached is cheap, not long."""
        if head.defers >= self._defer_max:
            return False
        window = min(len(self._queue), self._defer_lookahead + 1)
        for i in range(1, window):
            c = self._queue[i]
            if self.pool.is_parked(c.rid):
                continue   # parked requests re-enter only through _recall
            need, cached = self._admit_need(c)
            if need < head_need and self.pool.can_alloc(need):
                head.defers += 1
                self.meter.defer(head.rid, defers=head.defers,
                                 need=head_need, free=self.pool.pages_free)
                self._admit_one(c, need, rows, from_head=False,
                                cached=cached)
                return True
        return False

    def _evict(self, victim: Request) -> None:
        """Preempt ``victim``: free its pages, requeue it at the front; the
        deterministic greedy replay regenerates the same tokens (tokens
        the client already saw are NOT re-delivered — ``delivered`` is the
        high-water mark)."""
        freed = self.pool.free(victim.rid)
        self._release_row(victim)
        victim.state = QUEUED
        victim.generated = []        # replayed from the prompt on re-admit
        victim.cached_tokens = 0     # pages went back (trie-pinned ones
        # survive there); the re-admission re-matches the prefix cache
        victim.drafter = None        # rebuilt at re-prefill; proposals only
        # ever influence WHICH positions get verified, never the tokens,
        # so a drafter reset cannot perturb the deterministic replay
        victim.evictions += 1
        self._queue.appendleft(victim)
        self.meter.evict(victim.rid, reason="pool_pressure",
                         pages_freed=freed)

    def _release_row(self, r: Request) -> None:
        """``r`` leaves its decode row (retired or evicted).  Its state slot
        goes with the row and is not cleared: the next request's prefill
        zeroes it, and a replay recomputes from the prompt."""
        del self._active[r.row]
        r.row = None

    def _preempt(self, victim: Request) -> None:
        """Route a pool-pressure preemption: with a host-RAM offload tier
        the victim's KV pages spill and the request resumes WITHOUT
        recompute; without one it falls back to the eviction replay."""
        if self.offload is not None:
            self._offload(victim)
        else:
            self._evict(victim)

    def _offload(self, victim: Request) -> None:
        """Swap ``victim`` out to the host tier: its PRIVATE pages'
        contents are exported to :class:`OffloadPool` frames and the HBM
        pages freed; SHARED pages (prefix-cache COW) keep the victim's
        pool reference and never copy — one resident HBM copy serves
        every holder, so a shared page "offloads" for free.  The request
        keeps its generated tokens and drafter (the whole point: recall
        resumes decode with zero recompute) and requeues at the front.
        If the put LRU-drops frames of ANY parked request (including this
        one), that owner is marked lost and downgrades to an
        eviction-style re-prefill at recall time."""
        pages = self.pool.table(victim.rid)
        spill = [(j, p) for j, p in enumerate(pages)
                 if self.pool.refcount(p) <= 1]
        frames = [(j, self._export_page(p)) for j, p in spill]
        self.pool.swap_out(victim.rid)
        del self._active[victim.row]
        victim.row = None
        victim.state = QUEUED
        victim.offloads += 1
        self._queue.appendleft(victim)
        nbytes = 0
        lost = set()
        for j, fr in frames:
            nbytes += sum(int(v.nbytes) for v in fr.values())
            for rid_lost, _slot in self.offload.put(victim.rid, j, fr):
                lost.add(rid_lost)
        for rid_lost in lost:
            # partial frame sets are useless: drop the survivors too and
            # let _recall downgrade the owner to a re-prefill
            self._offload_lost.add(rid_lost)
            self.offload.drop(rid_lost)
        self.meter.offload(victim.rid, pages=len(frames),
                           shared_pages=len(pages) - len(frames),
                           bytes_out=nbytes)

    def _recall(self, r: Request, rows: List[int]) -> str:
        """Re-admit a parked request from the head of the queue.  Returns
        ``"recalled"`` (row active again, KV restored), ``"downgraded"``
        (host frames were dropped — request reset to a fresh re-prefill,
        still queued), or ``"wait"`` (frames intact but HBM pages are
        short; the admit loop breaks and retries next step)."""
        import jax.numpy as jnp

        if r.rid in self._offload_lost or self.offload is None:
            self._downgrade(r)
            return "downgraded"
        plan = self.pool.parked_plan(r.rid)
        missing = [j for j, p in enumerate(plan) if p is None]
        if not all(self.offload.holds(r.rid, j) for j in missing):
            self._downgrade(r)
            return "downgraded"
        if not self.pool.can_alloc(len(missing)):
            # nearing the head of the queue: refresh this request's frames
            # so the LRU trims colder parked requests first
            # (distance-to-next-use approximated by queue position)
            self.offload.touch(r.rid)
            return "wait"
        table, refill = self.pool.swap_in(r.rid)
        nbytes = 0
        for j, pid in refill:
            frame = self.offload.get(r.rid, j)
            nbytes += sum(int(v.nbytes) for v in frame.values())
            idx = jnp.asarray(np.asarray([pid], np.int32))
            for key, arrs in self._arenas.items():
                vals = np.asarray(frame[key])[:, None]  # [layers, 1, ...]
                for li in range(len(arrs)):
                    arrs[li] = self._page_write(arrs[li], idx, vals[li])
        self._queue.popleft()
        r.row = rows.pop(0)
        r.state = RUNNING
        self._active[r.row] = r
        self.meter.recall(r.rid, pages=len(refill), bytes_in=nbytes,
                          n_tokens=len(r.generated))
        self.meter.set_occupancy(self.pool.occupancy())
        return "recalled"

    def _downgrade(self, r: Request) -> None:
        """Offload-stall fallback: the parked request's host frames are
        gone (LRU-dropped, or the tier vanished), so release its retained
        pool refs and reset it to eviction-replay semantics — re-prefill
        from the journaled prompt, with the ``delivered`` high-water mark
        suppressing re-emission.  The request keeps its queue position
        and re-enters through the normal admit path."""
        self.pool.drop_parked(r.rid)
        if self.offload is not None:
            self.offload.drop(r.rid)
        self._offload_lost.discard(r.rid)
        r.generated = []
        r.cached_tokens = 0
        r.drafter = None
        r.evictions += 1
        self.meter.offload_stall(r.rid)

    def _victim_key(self, x: Request):
        """Eviction preference under pool pressure, largest key loses.

        No-deadline requests are preempted before any deadline-carrying
        one (their sort group compares higher), youngest-admitted first —
        the original policy.  Among deadline-carrying requests the victim
        is the one with the MOST remaining slack: it has the best chance
        of still making its SLO after the eviction replay."""
        c = self.meter.clock(x.rid)
        budgets = []
        if x.deadline is not None:
            if x.deadline.total_s is not None:
                budgets.append(c.submit_t + x.deadline.total_s)
            if x.deadline.ttft_s is not None and x.delivered == 0:
                budgets.append(c.submit_t + x.deadline.ttft_s)
        if not budgets:
            return (1, c.admit_t or 0.0, x.rid)
        return (0, min(budgets) - self._now(), x.rid)

    def _ensure_page(self, r: Request, n_tok: int = 1) -> bool:
        """Make sure pages covering ``r.pos .. r.pos + n_tok - 1`` exist
        (``n_tok > 1`` when a verify step writes draft positions too).
        Under pool pressure an active request is preempted (see
        :meth:`_victim_key`: youngest-admitted without deadlines,
        most-slack with); when ``r`` itself is chosen it self-preempts
        (returns False) and waits in the queue for pages to free up."""
        need = (r.pos + max(int(n_tok), 1) - 1) // self.page_tokens + 1
        while len(self.pool.table(r.rid)) < need:
            if self.pool.can_alloc(1):
                _faults.fire("serve_pool", f"page_rid{r.rid}")
                self.pool.alloc(r.rid, 1)
                continue
            live = [x for x in self._active.values() if x.state == RUNNING]
            if live == [r]:  # r alone owns the pool and still starves:
                # no amount of preemption can ever satisfy it
                raise PoolExhausted(
                    f"request {r.rid} needs page {need} but the pool is "
                    f"exhausted — raise PADDLE_TPU_SERVE_PAGES or lower "
                    f"the per-request budget")
            victim = max(live, key=self._victim_key)
            self._preempt(victim)
            if victim is r:
                return False
        return True

    def _retire_if_done(self, r: Request) -> None:
        if r.state != RUNNING or not r.done():
            return
        freed = self.pool.free(r.rid)
        self._release_row(r)
        r.state = FINISHED
        self._results[r.rid] = np.asarray(r.generated, np.int32)
        if self.journal is not None:
            self.journal.finish(r.rid)
        self.meter.finish(r.rid, n_tokens=len(r.generated),
                          deadline=r.deadline)
        self.meter.set_occupancy(self.pool.occupancy())
        del freed

    # -- compiled programs -------------------------------------------------
    def _padded_table(self, rid) -> np.ndarray:
        t = np.full((self.max_pages_per_seq,), TRASH_PAGE, np.int32)
        pages = self.pool.table(rid)
        t[:len(pages)] = pages
        return t

    def _prefill_chunks(self, prompt, table, c0: int = 0, row: int = 0,
                        ride=None):
        """Drive the compiled prefill program over ``prompt``'s pages
        ``[c0, n_chunks)`` in the launches of :func:`prefill_plan`.
        Returns the last-prompt-token logits and the number of launches.
        Shared by scheduled prefills (:meth:`_prefill`, where ``c0`` skips
        prefix-cached pages) and the standalone :meth:`prefill_export`
        path.  ``row``: the decode row whose state slot the state layers
        carry the prompt through; each launch is told how many of its
        tokens are real.  ``ride``: the step's decode batch
        (:meth:`_decode_prep`), which the LAST launch carries (one of the
        width that carries a decode part: :meth:`_carries_rows`); the
        other launches of that width carry idle decode slots."""
        import jax.numpy as jnp

        P = self.page_tokens
        n_chunks = -(-len(prompt) // P)
        widths = prefill_plan(n_chunks - c0, self._prefill_widths)
        logits, c = None, c0
        self._prefill_notes, self._prefill_kept = [], []
        for i, w in enumerate(widths):
            part = prompt[c * P:(c + w) * P]
            chunk = np.zeros((1, w * P), np.int32)
            chunk[0, :len(part)] = part
            # the prompt's last token lies in its last launch
            take = len(prompt) - 1 - c * P if c + w >= n_chunks else 0
            logits = self._run_prefill(
                jnp.asarray(chunk), jnp.int32(c * P), table,
                jnp.int32(take), jnp.int32(row), jnp.int32(len(part)),
                ride=ride if i == len(widths) - 1 else None)
            self._cycle.prefill_launches += 1
            self._cycle.prefill_tokens += len(part)
            c += w
        return logits, len(widths)

    def _prefill(self, r: Request, ride=None):
        """Fill ``r``'s pages and deliver its first token.  Returns the
        pages run, the program launches that took (both 0 where the pages
        were imported) and what the launches' layers noted, for the
        span.  ``ride``: the step's decode batch, carried by the last
        launch; its choice comes with the logits in the one fetch, and
        its rows' tokens are booked after ``r``'s."""
        import jax
        import jax.numpy as jnp

        if r.kv_import is not None:
            if self.cp > 1:
                from ..telemetry import kernel_fallback
                kernel_fallback("serving_cp_prefill", "kv_import",
                                rid=str(r.rid))
            self._import_kv(r)
            return 0, 0, {}
        _faults.fire("serve_prefill", f"rid{r.rid}")
        prompt = r.prompt
        # a prefix-cache hit resumes at the first uncached page
        n_chunks, c0 = self._pages_to_run(r)
        with _span("serve.prefill.dispatch"):
            if self._cp_accepts(len(prompt), cached_tokens=r.cached_tokens):
                logits = self._cp_prefill_run(prompt, self.pool.table(r.rid))
                launches = 1
            else:
                table = jnp.asarray(self._padded_table(r.rid)[None])
                logits, launches = self._prefill_chunks(prompt, table, c0,
                                                        r.row, ride)
            self.meter.prefill_launched(launches)
        with _span("serve.prefill.to_host"):
            # the launches' notes come with the logits, and the riding
            # rows' choice: one sync point
            logits, noted, choice = jax.device_get((
                logits, self._prefill_notes,
                None if ride is None else self._ride_choice))
            self._prefill_notes, self._ride_choice = [], None
        with _span("serve.prefill.sample"):
            tok = int(np.argmax(logits))
            r.generated.append(tok)
            self.meter.first_token(r.rid)
            self._deliver(r, tok)
            if self.prefix is not None:
                # register this prompt's FULL pages for future requests
                # (the chunks matched at admission just get their LRU
                # refreshed)
                self.prefix.insert(r.prompt, self.pool.table(r.rid))
            if self.spec is not None:
                # (re)build the drafter here so eviction replay and crash
                # recovery get a fresh one primed with exactly the tokens
                # a first-admission drafter would have seen
                r.drafter = self.spec.make_drafter()
                r.drafter.begin([int(t) for t in r.prompt])
                r.drafter.observe([tok])
            if ride is not None:
                stepped, _, n_tok, _, _, drafts = ride
                self._decode_sample(stepped, choice, n_tok, drafts)
        # a launch reads the prompt's pages so far, its own included
        ends = c0 + np.cumsum(prefill_plan(n_chunks - c0,
                                           self._prefill_widths))
        pages = 0 if self._latent is None else int(ends.sum())
        facts = self._step_facts(noted, pages)
        facts.update(passes=self.passes, kv_tokens=int(np.minimum(
            ends * self.page_tokens, len(prompt)).sum())
            * self._kv_layer_passes)
        if ride is not None:
            # the riding rows' reads, as a decode step would note them
            _, _, n_tok, positions, _, _ = ride
            facts["kv_tokens_decode"] = int(np.where(
                n_tok > 0, positions + n_tok, 0).sum()) \
                * self._kv_layer_passes
        return n_chunks - c0, launches, facts

    def _import_kv(self, r: Request) -> None:
        """Disaggregated admission (ISSUE 19 leg 2): instead of running
        the prefill program, scatter the KV page frames a prefill-tier
        worker streamed through the depot into this engine's arenas, then
        deliver the first token that worker's prefill chose.
        Deterministic prefill makes the imported pages bit-identical to a
        local prefill, so eviction replay (re-import, ``kv_import`` stays
        on the request) and crash replay (local re-prefill from the
        journaled prompt) are both token-exact."""
        import jax.numpy as jnp

        _faults.fire("serve_prefill", f"rid{r.rid}")
        first_tok, frames = r.kv_import
        pids = self.pool.table(r.rid)[:len(frames)]
        idx = jnp.asarray(np.asarray(pids, np.int32))
        for key, arrs in self._arenas.items():
            # frame[key] is [layers, page_tokens, ...] for ONE page;
            # stack to [layers, n_pages, page_tokens, ...]
            stacked = np.stack([np.asarray(f[key]) for f in frames],
                               axis=1)
            for li in range(len(arrs)):
                arrs[li] = self._page_write(arrs[li], idx, stacked[li])
        tok = int(first_tok)
        r.generated.append(tok)
        self.meter.first_token(r.rid)
        self._deliver(r, tok)
        if self.spec is not None:
            r.drafter = self.spec.make_drafter()
            r.drafter.begin([int(t) for t in r.prompt])
            r.drafter.observe([tok])
        _event("serve_kv_import", str(r.rid), pages=len(frames),
               trace=r.trace_id)

    def _page_write(self, arena, idx, vals):
        """Host-side page scatter (the KV-import path): writes whole
        pages at ``idx`` and keeps the arena's sharding committed so the
        next compiled call sees the exact signature it lowered for."""
        import jax
        import jax.numpy as jnp

        # frames are ``[pages, P, kv, d]`` whatever this engine's arenas
        # keep a token as (:meth:`_export_page`)
        vals = jnp.asarray(vals).reshape((-1,) + arena.shape[1:])
        out = arena.at[idx].set(vals.astype(arena.dtype))
        if self._mesh is not None:
            out = jax.device_put(out, arena.sharding)
        return out

    def prefill_export(self, prompt):
        """Run a standalone prefill and EXPORT the finished pages instead
        of scheduling decode: returns ``(first_token, frames)`` where
        ``frames`` holds one host dict per prompt page (``k``/``v`` and,
        for int8 pools, ``ks``/``vs`` planes, each ``[layers,
        page_tokens, ...]``).  This is the prefill-tier workhorse
        (:class:`~paddle_tpu.serving.disagg.PrefillWorker`): pages are
        allocated, filled by the SAME compiled prefill program a local
        admission would use, copied out, and freed — nothing stays
        scheduled on this engine."""
        import jax.numpy as jnp

        self._refuse_with_state(
            "prefill_export", "the exported frames carry K/V pages and no "
            "recurrent state")
        self._refuse_with_latent(
            "prefill_export", "the frames are K/V frames; latent rows have "
            "no frame format yet")
        self._refuse_with_passes(
            "prefill_export", "a frame holds one pass's page")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        need = self.pool.pages_for(len(prompt))
        if need > min(self.pool.capacity, self.max_pages_per_seq):
            raise ValueError(
                f"prompt needs {need} pages; this prefill engine takes "
                f"at most {min(self.pool.capacity, self.max_pages_per_seq)}")
        self._export_seq = getattr(self, "_export_seq", 0) + 1
        key = ("__prefill_export__", self._export_seq)
        self.pool.alloc(key, need)
        try:
            t = np.full((self.max_pages_per_seq,), TRASH_PAGE, np.int32)
            pages = self.pool.table(key)
            t[:len(pages)] = pages
            if self._cp_accepts(len(prompt)):
                logits, launches = self._cp_prefill_run(prompt, pages), 1
            else:
                logits, launches = self._prefill_chunks(
                    prompt, jnp.asarray(t[None]))
            self.meter.prefill_launched(launches)
            first = int(np.argmax(np.asarray(logits)))
            frames = [self._export_page(p) for p in pages]
            return first, frames
        finally:
            self.pool.free(key)

    def _export_page(self, pid: int) -> dict:
        """Host copy of one physical page across every layer and plane.
        ONE wire format: a K / V frame is ``[layers, P, kv, d]`` also where
        the arena keeps a token's heads merged, so frames move between
        engines of either layout (a tp=1 prefill tier into a TP decode
        tier, a journal read back under another tp)."""
        return {key: np.stack([
            np.asarray(a[pid]).reshape(self._page_shape)
            if key in ("k", "v") else np.asarray(a[pid]) for a in arrs])
            for key, arrs in self._arenas.items()}

    def _decode_step(self, rider: Optional[Request] = None):
        """One verify-wide decode step.  With a ``rider`` still running
        after the preparation, the step launches nothing: it returns the
        batch for the rider's last prefill launch to carry
        (:meth:`_step_inner`), and its span says ``rode=1``; else it runs
        the decode program and returns None.  Serial mode (spec off) is the
        degenerate S=1 case: every row carries n_tok=1 and the program
        trace is value-identical to the old single-token decode.  With
        speculation, each row drafts k_r tokens host-side, the ONE
        compiled program scores positions ``pos..pos+k_r`` in a single
        weight read, and the greedy acceptance loop emits the longest
        prefix whose drafts match the target's own argmax — followed by
        the target's correction token, so every step emits >= 1 token and
        the stream is token-exact vs serial by construction.  Rejected
        drafts leave stale cache slots AT OR PAST the next write position;
        the next step's scatter overwrites them before its gather (same
        program), and the causal mask hides anything beyond its window."""
        import jax
        import jax.numpy as jnp

        with _span("serve.decode") as sp:
            with _span("serve.decode.prep"):
                batch = self._decode_prep()
            if batch is None:
                sp.note(rows=0, n_tok=0, live_pages=0, table_pages=0,
                        state_rows=0, rode=0, passes=self.passes,
                        kv_tokens=0)
                for r in list(self._active.values()):
                    self._retire_if_done(r)
                return None
            stepped, tokens, n_tok, positions, tables, drafts = batch
            rode = rider is not None and rider.state == RUNNING
            self._cycle.decode_rows += len(stepped)
            # live_pages: what the rows' queries can see (idle rows: none),
            # the decode kernel's own rule; table_pages: what the padded
            # tables hold
            live = np.where(n_tok > 0, -(-(positions + n_tok)
                                         // self.page_tokens), 0)
            seen = int(np.where(n_tok > 0, positions + n_tok, 0).sum())
            # state_rows: rows whose recurrent state the step updates
            sp.note(rows=len(stepped), n_tok=int(n_tok.sum()),
                    live_pages=int(live.sum()), table_pages=tables.size,
                    state_rows=len(stepped) if self.state is not None
                    else 0, rode=int(rode), passes=self.passes)
            _faults.fire("serve_decode", f"step{self.steps_total}")
            _faults.fire("slow_serve", f"{self.fault_scope}/decode")
            self.meter.decode_step(rode=rode)
            if rode:
                # what the rows will see is counted here; what the riding
                # launch's layers note and read, on that launch's own span
                if self._latent is not None:
                    sp.note(**self._step_facts([], int(live.sum()), seen))
                return batch
            # kv_tokens: the cached tokens the rows' queries read, over
            # every attention layer and pass
            sp.note(kv_tokens=seen * self._kv_layer_passes)
            with _span("serve.decode.dispatch"):
                choice = self._run_decode(jnp.asarray(tokens),
                                          jnp.asarray(positions),
                                          jnp.asarray(tables),
                                          jnp.asarray(n_tok))
            # the step's one sync point: the chosen ids [R, S] int32 and
            # what the layers noted, not the logits
            with _span("serve.decode.to_host") as to_host:
                choice, noted = jax.device_get((choice, self._decode_noted))
                to_host.note(bytes=choice.nbytes
                             + (0 if noted is None else noted.nbytes))
            if self._latent is not None or noted is not None:
                sp.note(**self._step_facts(
                    [] if noted is None else [noted], int(live.sum()), seen))
            with _span("serve.decode.sample"):
                self._decode_sample(stepped, choice, n_tok, drafts)
        return None

    def _step_facts(self, noted, pages: int, tokens: int = 0) -> dict:
        """A ``serve.decode`` / ``serve.prefill`` span's facts from what
        the layers of its launches noted (``noted``: one vector a launch,
        in ``_note_reduce``'s order), and for a model with latent layers
        ``latent_pages``: the pages of latent rows its programs read, over
        every layer (``pages`` a layer: the live pages a decode step walks,
        the prompt's pages so far at each prefill launch), and
        ``latent_tokens``: the cached tokens a decode step's queries see,
        over every layer."""
        facts = {}
        if self._latent is not None:
            layers = sum(isinstance(sp, LatentAttentionLayer)
                         for sp in self._layers)
            facts.update(latent_pages=pages * layers,
                         latent_tokens=tokens * layers)
        if len(noted):
            noted = np.asarray(noted, np.int64).reshape(len(noted), -1)
            for i, (name, how) in enumerate(self._note_reduce.items()):
                col = noted[:, i]
                facts[name] = int(col.max() if how == "max" else col.sum())
        return facts

    def _decode_prep(self):
        """Host-side inputs of one decode step: ``(stepped rows, tokens,
        n_tok, positions, tables, drafts)``, or None when no row steps."""
        R, MP, S = self.max_batch, self.max_pages_per_seq, self._spec_width
        tokens = np.zeros((R, S), np.int32)
        positions = np.zeros((R,), np.int32)
        n_tok = np.zeros((R,), np.int32)
        tables = np.full((R, MP), TRASH_PAGE, np.int32)
        drafts: Dict[int, List[int]] = {}
        stepped: List[Request] = []
        for r in [self._active[row] for row in sorted(self._active)]:
            # _ensure_page can evict LATER snapshot entries; skip anything
            # no longer running so an evictee never allocates while queued;
            # a row without a token is a prompt not prefilled yet (a rider)
            if r.state != RUNNING or r.row is None or r.done() \
                    or not r.generated:
                continue
            d: List[int] = []
            if self.spec is not None and r.drafter is not None:
                # never draft past the output budget: the last budgeted
                # token needs no verification slot (nothing follows it)
                k_r = min(self._adapt.k(),
                          r.max_new_tokens - len(r.generated) - 1)
                if k_r > 0:
                    d = [int(t) for t in r.drafter.propose(k_r)]
            drafts[r.rid] = d
            self._ensure_page(r, 1 + len(d))
        # _ensure_page may have evicted rows; rebuild the live view
        for row, r in sorted(self._active.items()):
            if r.done() or not r.generated:
                continue
            d = drafts.get(r.rid, [])
            seq = [r.generated[-1]] + d
            tokens[row, :len(seq)] = seq
            n_tok[row] = len(seq)
            positions[row] = r.pos
            tables[row] = self._padded_table(r.rid)
            stepped.append(r)
        if not stepped:
            return None
        return stepped, tokens, n_tok, positions, tables, drafts

    def _decode_sample(self, stepped, choice, n_tok, drafts) -> None:
        """Book one step's tokens.  ``choice`` [R, S]: what the program
        chose at each position (:func:`greedy_choice`).  Only a stepped
        row's live positions ``[:n_tok[row]]`` are looked at: idle rows and
        the junk past a row's drafts hold whatever the program made of
        them."""
        proposed_total = accepted_total = emitted_total = 0
        choice, n_tok = choice.tolist(), n_tok.tolist()
        for r in stepped:
            nv = n_tok[r.row]
            chosen = choice[r.row][:nv]
            if min(chosen) == NON_FINITE:
                # a corrupted int8 scale (or any cache poisoning) surfaces
                # as NaN/inf logits — fail LOUDLY instead of emitting junk
                raise RuntimeError(
                    f"non-finite decode logits for rid {r.rid} "
                    f"(kv_dtype={self.kv_dtype}): corrupted KV page or "
                    f"scale buffer")
            d = drafts.get(r.rid, [])
            emitted: List[int] = []
            for i in range(nv):
                tok = chosen[i]
                r.generated.append(tok)
                self.meter.token(r.rid)
                self._deliver(r, tok)
                emitted.append(tok)
                if r.done():
                    break
                if i < nv - 1 and tok != d[i]:
                    break            # first mismatch: rest of the draft is
                    # conditioned on a token the target rejected
            if self.spec is not None:
                accepted = len(emitted) - 1
                proposed_total += len(d)
                accepted_total += accepted
                emitted_total += len(emitted)
                self._adapt.update(accepted, len(d))
                if r.drafter is not None and not r.done():
                    r.drafter.observe(emitted)
        if self.spec is not None:
            self.meter.spec_step(proposed=proposed_total,
                                 accepted=accepted_total,
                                 emitted=emitted_total, rows=len(stepped))
        for r in list(self._active.values()):
            self._retire_if_done(r)

    # -- delivery / crash recovery ----------------------------------------
    def _deliver(self, r: Request, tok: int) -> None:
        """Token bookkeeping right after ``r.generated.append(tok)``.  New
        tokens advance the journaled high-water mark and queue for the
        sink (emitted only after the covering journal flush); replayed
        tokens (eviction or crash recovery) are suppressed and verified
        against what the client already saw — greedy decode is
        deterministic, a divergence is an engine bug."""
        idx = len(r.generated) - 1
        if idx < r.delivered:
            if r.delivered_tokens[idx] != tok:
                raise RuntimeError(
                    f"replay divergence for rid {r.rid} at token {idx}: "
                    f"regenerated {tok}, client saw "
                    f"{r.delivered_tokens[idx]}")
            return
        r.delivered_tokens.append(tok)
        r.delivered = idx + 1
        if self.journal is not None:
            self.journal.deliver(r.rid, idx, tok)
        self._pending_delivery.append((r.rid, idx, tok))

    def _flush_delivery(self) -> None:
        """Durability barrier, then client emission: journal records hit
        disk BEFORE any of the tokens they cover reach the sink.  On a
        flush failure everything stays pending — the step-failure path
        retries, and a crash instead re-generates the tokens exactly.

        A flush that delivers tokens ends the cycle (:class:`Cycle`): WHEN
        tokens became client-visible and what stood between this flush and
        the last is on the ``serve.deliver`` span (counts only: its stamps
        are the profiler's) and, for the longest cycles, in the meter; the
        journal holds the per-token detail."""
        cy = self._cycle
        cy.enter("deliver")
        per_rid: Dict[int, int] = {}    # the lowest index a request is handed
        for rid, idx, _tok in self._pending_delivery:
            per_rid.setdefault(rid, idx)
        with _span("serve.deliver", requests=len(per_rid),
                   tokens=len(self._pending_delivery)) as sp:
            if self.journal is not None:
                self.journal.flush()
            if self._on_token is not None:
                for rid, idx, tok in self._pending_delivery:
                    self._on_token(rid, idx, tok)
            self._pending_delivery.clear()
            if not per_rid:
                sp.note(gaps=0)         # nothing delivered: the cycle goes on
                return
            waited = [rid for rid, idx in per_rid.items() if idx >= 1]
            cy.gaps = len(waited)
            cy.gaps_long = sum(rid not in self._cycle_rids for rid in waited)
            cy.first_tokens = len(per_rid) - len(waited)
            self._cycle_rids = frozenset(per_rid)
            sp.note(seq=cy.seq, compiled=int(cy.compiled), **cy.counts())
            cy.enter("deliver")         # the cycle ends here
            self.meter.cycle_closed(cy, step=self.steps_total)
            cy.begin()

    def recover(self) -> dict:
        """Replay the journal into this (fresh) engine after a crash:
        re-submit every accepted-but-unfinished request with its original
        rid and delivered high-water mark (tokens the client already saw
        are regenerated but not re-delivered), restore finished results
        and shed records, and re-offer every journaled token to the sink
        (which deduplicates) — closing the flush→emit crash window.
        Returns ``{"replayed", "finished", "shed", "truncated"}`` and
        writes the supervisor resume report (``PADDLE_TPU_RESUME_REPORT``
        protocol) when there was anything to recover."""
        if self.journal is None:
            raise RuntimeError("recover() needs a journal-backed engine")
        st = self.journal.load_state()
        replayed = 0
        for rid in st.open_rids():
            rec = st.requests[rid]
            r = Request(np.asarray(rec["prompt"], np.int32),
                        rec["max_new_tokens"], rec["eos_token_id"], rid=rid,
                        trace_id=rec.get("trace_id"))
            r.deadline = Deadline.from_doc(rec.get("deadline"))
            toks = st.delivered.get(rid, [])
            r.delivered = len(toks)
            r.delivered_tokens = list(toks)
            self._queue.append(r)
            # deadlines keep aging across the crash: backdate the clock
            # by the wall time already spent, so a budget that died while
            # the process was down sheds here instead of being served to
            # a client that gave up long ago
            age = max(0.0, time.time() - rec.get("submit_wall",
                                                 time.time()))
            self.meter.submit(r.rid, age_s=age, trace_id=r.trace_id)
            replayed += 1
        # re-offer BEFORE restoring _results: a status poll must never see
        # a rid finished while its journaled tokens are still on their way
        # back to the sink
        if self._on_token is not None:
            for rid in sorted(st.delivered):
                if rid in st.shed:
                    continue
                for idx, tok in enumerate(st.delivered[rid]):
                    self._on_token(rid, idx, tok)
        for rid in st.finished:
            self._results[rid] = np.asarray(st.delivered.get(rid, []),
                                            np.int32)
        for rid, reason in st.shed.items():
            self.shed[rid] = reason
        info = {"replayed": replayed, "finished": len(st.finished),
                "shed": len(st.shed), "truncated": st.truncated,
                "known_rids": sorted(st.requests)}
        if st.requests:
            _event("serve_replay", str(self.journal.root), **info)
            _bump("serving.requests_replayed_total", replayed)
            self._write_resume_report(info)
        if self._queue:
            self.meter.set_queue_depth(len(self._queue))
            self._work.set()
        return info

    @staticmethod
    def _write_resume_report(info: dict) -> None:
        """Same stamp-file protocol the snapshot resume ladder uses: the
        Supervisor reads it back and narrates ``resume_source=journal`` +
        ``resume_replayed`` in its restart events."""
        base = os.environ.get("PADDLE_TPU_RESUME_REPORT")
        if not base:
            return
        try:
            import json

            with open(f"{base}.0", "w") as f:
                json.dump({"rank": 0, "source": "journal",
                           "replayed": info["replayed"]}, f)
        except OSError:
            pass

    def _wedge_handler(self, info: dict) -> None:
        """Watchdog expiry: the flight recorder is already dumped; the
        journal was flushed at the end of the last completed step, so
        exiting loses nothing the client saw.  Exit 101 hands control to
        the Supervisor relaunch → :meth:`recover`."""
        _event("serve_wedged", str(info.get("name")),
                    elapsed_s=round(float(info.get("elapsed", 0.0)), 3))
        try:
            from ..distributed.fleet.elastic import ELASTIC_EXIT_CODE
        except Exception:
            ELASTIC_EXIT_CODE = 101
        os._exit(ELASTIC_EXIT_CODE)



    # -- traced functions --------------------------------------------------
    @property
    def _ks(self):
        return self._arenas["k"]

    @property
    def _vs(self):
        return self._arenas["v"]

    def _paged_attention(self, q, k_new, v_new, arenas, tables,
                         positions, n_tok, walk=None, scale=None):
        """Scatter this step's k/v into one layer's page arenas
        (``arenas``: that layer's array by plane, ``k`` / ``v`` and an int8
        pool's ``ks`` / ``vs``) and attend each row over its pages: gathered
        by the whole padded table
        for the einsum below (prefill; decode where no kernel runs), or,
        with ``walk`` (:meth:`_page_walk`'s items: the decode program on a
        TPU), only the live ones, read in place by
        ``paged_decode_attention`` from the arena as the engine keeps it,
        heads merged or not.  Called through ``self._attend``, a
        ``jit`` of it: the layers of a program that share a shape share
        one trace and one lowering of this body.
        ``scale`` multiplies the scores on either path (None:
        ``1 / sqrt(d)``).  ``n_tok`` [R] is the
        per-row count of VALID tokens in the s-window (speculative verify
        rows carry 1 + k_r; idle rows 0) — invalid slots scatter to the
        trash page.  Mirrors ``generation.cached_attention``'s grouped
        einsum (cache dtype multiplies, f32 accumulation, no cache cast)
        so bf16 outputs are bit-identical to the contiguous-cache path —
        junk cols (trash page, unwritten slots, positions past a row's
        valid window) mask to exact zeros.  int8 pages quantize on the
        scatter (per-token scales into the scale arenas) and dequantize
        at the gather, fused into the same program."""
        import jax
        import jax.numpy as jnp

        R, s, h, d = q.shape
        kv = k_new.shape[2]
        P = self.page_tokens
        MP = tables.shape[1]
        kp, vp = arenas["k"], arenas["v"]
        quant = self.kv_dtype == "int8"
        fp8 = self.kv_dtype == "fp8"

        def rows(x):        # [R, s, kv, d] as the arena keeps a token
            return x.reshape(R, s, kv * d) if self._flat_pages else x

        pos_js = positions[:, None] + jnp.arange(s)[None, :]      # [R, s]
        valid = jnp.arange(s)[None, :] < n_tok[:, None]           # [R, s]
        page = jnp.take_along_axis(tables,
                                   jnp.clip(pos_js // P, 0, MP - 1), axis=1)
        page = jnp.where(valid, page, TRASH_PAGE)
        slot = jnp.where(valid, pos_js % P, 0)
        if quant:
            kq, ksc = quantize_kv(k_new)        # [R,s,kv] scales
            vq, vsc = quantize_kv(v_new)
            kp = kp.at[page, slot].set(rows(kq))
            vp = vp.at[page, slot].set(rows(vq))
            ksp = arenas["ks"].at[page, slot].set(ksc)
            vsp = arenas["vs"].at[page, slot].set(vsc)
        elif fp8:
            # static scale: quantize on the scatter, no scale planes
            kp = kp.at[page, slot].set(
                rows(quantize_kv_fp8(k_new, self._fp8_scale)))
            vp = vp.at[page, slot].set(
                rows(quantize_kv_fp8(v_new, self._fp8_scale)))
        else:
            kp = kp.at[page, slot].set(rows(k_new).astype(kp.dtype))
            vp = vp.at[page, slot].set(rows(v_new).astype(vp.dtype))
        if walk is not None:
            from ..ops.pallas.paged_decode_attention import \
                paged_decode_attention

            out = paged_decode_attention(q, kp, vp, tables, positions,
                                         n_tok, scale=scale, **dict(walk))
            return out, {"k": kp, "v": vp}
        C = MP * P
        if quant:
            kk = dequantize_kv(kp[tables].reshape(R, C, kv, d),
                               ksp[tables].reshape(R, C, kv)).astype(
                                   self._cdt)
            vv = dequantize_kv(vp[tables].reshape(R, C, kv, d),
                               vsp[tables].reshape(R, C, kv)).astype(
                                   self._cdt)
        elif fp8:
            kk = dequantize_kv_fp8(kp[tables].reshape(R, C, kv, d),
                                   self._fp8_scale).astype(self._cdt)
            vv = dequantize_kv_fp8(vp[tables].reshape(R, C, kv, d),
                                   self._fp8_scale).astype(self._cdt)
        else:
            kk = kp[tables].reshape(R, C, kv, d)
            vv = vp[tables].reshape(R, C, kv, d)
        g = h // kv
        q5 = q.reshape(R, s, kv, g, d).astype(kk.dtype)

        def attend(q5, pos_js):     # queries [R, b, kv, g, d] at pos_js
            scores = jnp.einsum("bskgd,bckd->bkgsc", q5, kk,
                                preferred_element_type=jnp.float32)
            scores = scores / jnp.sqrt(float(d)) if scale is None \
                else scores * float(scale)
            col = jnp.arange(C)[None, None, None, None, :]
            row_pos = pos_js[:, None, None, :, None]
            scores = jnp.where(col <= row_pos, scores,
                               jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("bkgsc,bckd->bskgd", probs.astype(vv.dtype),
                              vv, preferred_element_type=jnp.float32)

        if s > P and s % P == 0:
            # a launch of several pages attends a page of queries at a
            # time, so the float32 scores stay the size one page's are
            def blocks(x):      # [R, s, ...] -> [s / P, R, P, ...]
                return jnp.moveaxis(
                    x.reshape((R, s // P, P) + x.shape[2:]), 1, 0)

            out = jax.lax.map(lambda b: attend(*b),
                              (blocks(q5), blocks(pos_js)))
            out = jnp.moveaxis(out, 0, 1)
        else:
            out = attend(q5, pos_js)
        out = out.reshape(R, s, h, d).astype(q.dtype)
        new = {"k": kp, "v": vp}
        if quant:
            new["ks"], new["vs"] = ksp, vsp
        return out, new

    def _latent_attention(self, q_nope, q_rope, c_new, r_new, w_uk, w_uv,
                          pages, tables, positions, n_tok, *, spec,
                          absorbed: bool, walk=None):
        """Scatter this step's latent rows into one latent layer's page
        arena ``pages`` [N, P, row_width] and attend each row over its
        pages (:meth:`_LayerIO.attend_latent` has the arguments).  What is
        stored is ``[c_kv | k_rope | 0]`` a token and nothing per head.

        ``absorbed`` (the decode program): ``W_UK`` goes into the query and
        ``W_UV`` onto the output, so every head attends over the latent
        rows as they lie — read in place by ``mla_paged_decode_attention``
        with ``walk`` (:meth:`_latent_walk`'s items), else gathered by the
        whole padded table.  Expanded (the prefill program: one row, pages
        of queries): the row's live pages are gathered a block at a time
        and expanded to per-head K/V inside the program (a third of the
        absorbed form's operations a score), under an online softmax.  fp8
        pages quantize on the
        scatter and dequantize at the gather.  Scores and softmax are
        float32; junk columns mask to exact zeros.  Called through
        ``self._attend_latent``, a ``jit`` of it."""
        import jax
        import jax.numpy as jnp

        R, s, h, _ = q_nope.shape
        L, rd, W = spec.latent_dim, spec.rope_dim, spec.row_width
        P, MP = self.page_tokens, tables.shape[1]
        fp8 = self.kv_dtype == "fp8"
        cdt = self._cdt

        def padded(*parts):     # [..., L | rd | zeros] to the row's width
            parts = [x.astype(cdt) for x in parts]
            return jnp.concatenate(
                parts + [jnp.zeros(parts[0].shape[:-1] + (W - L - rd,),
                                   cdt)], axis=-1)

        pos_js = positions[:, None] + jnp.arange(s)[None, :]      # [R, s]
        valid = jnp.arange(s)[None, :] < n_tok[:, None]
        page = jnp.take_along_axis(tables,
                                   jnp.clip(pos_js // P, 0, MP - 1), axis=1)
        page = jnp.where(valid, page, TRASH_PAGE)
        slot = jnp.where(valid, pos_js % P, 0)
        rows = padded(c_new, r_new)
        pages = pages.at[page, slot].set(
            quantize_kv_fp8(rows, self._fp8_scale) if fp8 else rows)

        def latent_rows(ids):       # pages ``ids`` [R, n] as [R, n * P, W]
            ctx = pages[ids].reshape(R, -1, W)
            return dequantize_kv_fp8(ctx, self._fp8_scale).astype(cdt) \
                if fp8 else ctx

        low = jnp.finfo(jnp.float32).min
        if absorbed:
            q_abs = jnp.einsum("rshn,lhn->rshl", q_nope.astype(cdt), w_uk,
                               preferred_element_type=jnp.float32)
            q_cat = padded(q_abs, q_rope)
            if walk is not None:
                from ..ops.pallas.mla_paged_decode_attention import \
                    mla_paged_decode_attention

                o_lat = mla_paged_decode_attention(
                    q_cat, pages, tables, positions, n_tok, latent=L,
                    scale=spec.scale, **dict(walk))
            else:
                ctx = latent_rows(tables)       # the whole padded tables
                scores = jnp.einsum("rshw,rcw->rhsc", q_cat, ctx,
                                    preferred_element_type=jnp.float32)
                col = jnp.arange(MP * P)[None, None, None, :]
                probs = jax.nn.softmax(jnp.where(
                    col <= pos_js[:, None, :, None], scores * spec.scale,
                    low), axis=-1).astype(cdt)
                o_lat = jnp.einsum("rhsc,rcl->rshl", probs, ctx[..., :L],
                                   preferred_element_type=jnp.float32)
            out = jnp.einsum("rshl,lhv->rshv", o_lat.astype(cdt), w_uv,
                             preferred_element_type=jnp.float32)
            return out.astype(q_nope.dtype), pages

        # the live context, a few pages at a time (flash-style): what a
        # launch costs follows the tokens the prompt has so far, not the
        # table's width, and the float32 scores stay a block's size.  (A
        # softmax over the whole padded table cost 19 ms a layer a page of
        # queries here, 120 x this loop: PERF.md section 6, PR 30.)
        B = 2 if MP % 2 == 0 else 1             # pages a block
        n_blocks = jnp.minimum(
            (jnp.max(pos_js) + B * P) // (B * P), MP // B)
        q = jnp.concatenate([q_nope, q_rope], axis=-1).astype(cdt)

        def block(i, carry):
            m, l, acc = carry       # [R, h, s, 1] x 2, [R, h, s, v]
            ctx = latent_rows(
                jax.lax.dynamic_slice_in_dim(tables, i * B, B, axis=1))
            c_ctx = ctx[..., :L]
            k = jnp.concatenate([
                jnp.einsum("rcl,lhn->rchn", c_ctx, w_uk,
                           preferred_element_type=jnp.float32).astype(cdt),
                jnp.broadcast_to(ctx[:, :, None, L:L + rd],
                                 (R, B * P, h, rd))], axis=-1)
            v = jnp.einsum("rcl,lhv->rchv", c_ctx, w_uv,
                           preferred_element_type=jnp.float32).astype(cdt)
            scores = jnp.einsum("rbhd,rchd->rhbc", q, k,
                                preferred_element_type=jnp.float32)
            col = i * B * P + jnp.arange(B * P)[None, None, None, :]
            scores = jnp.where(col <= pos_js[:, None, :, None],
                               scores * spec.scale, low)
            m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
            # a query that has seen nothing yet keeps exact zeros
            m_ok = jnp.where(m_new == low, 0.0, m_new)
            p = jnp.exp(scores - m_ok)
            alpha = jnp.exp(m - m_ok)
            pv = jnp.einsum("rhbc,rchv->rhbv", p.astype(cdt), v,
                            preferred_element_type=jnp.float32)
            return (m_new, l * alpha + jnp.sum(p, -1, keepdims=True),
                    acc * alpha + pv)

        _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
            jnp.full((R, h, s, 1), low, jnp.float32),
            jnp.zeros((R, h, s, 1), jnp.float32),
            jnp.zeros((R, h, s, spec.v_dim), jnp.float32)))
        out = jnp.moveaxis(acc / jnp.where(l > 0.0, l, 1.0), 1, 2)
        return out.astype(q_nope.dtype), pages

    def _latent_walk(self, rows: int, width: int,
                     layer: LatentAttentionLayer):
        """:meth:`_page_walk` for a latent layer: the keyword arguments of
        ``mla_paged_decode_attention`` where a kernel can run and its gate
        takes the shapes, else None (the gather + einsum) with a counted
        ``kernel_fallback``."""
        from ..ops import pallas_mode
        from ..ops.pallas.mla_paged_decode_attention import (
            KERNEL_NAME, mla_paged_decode_attention_refusal)

        mode = pallas_mode("use_decode_attention")
        if mode is None:
            return None
        kind, _, interpret = mode
        if kind != "local":
            reason = "hybrid_mesh"
        elif self.kv_dtype != "bf16":
            reason = "kv_dtype"
        else:
            reason = mla_paged_decode_attention_refusal(
                (rows, width, layer.heads, layer.row_width),
                self._arena_shape, (rows, self.max_pages_per_seq),
                self._arenas["c"][0].dtype, layer.latent_dim,
                interpret=interpret)
        if reason is None:
            return {"interpret": interpret}
        from ..telemetry import kernel_fallback

        kernel_fallback(KERNEL_NAME, reason, kv_dtype=self.kv_dtype,
                        rows=rows, width=width)
        return None

    def _forward(self, param_arrays, buffer_arrays, arenas, tokens,
                 positions, tables, n_tok, *, n_valid=None, row=None,
                 fresh=None, ride: Optional[_Ride] = None):
        """Shared model step for both programs: ONE walk over the model's
        layers, each given its :class:`_LayerIO`.  ``tokens`` [R, s]
        (decode/verify: s=spec width; prefill: R=1, s=a width of
        ``PREFILL_WIDTHS`` x page_tokens);
        ``positions`` [R] absolute position of each row's first token;
        ``n_tok`` [R] tokens per row whose K/V are kept (rest scatter to
        trash); ``n_valid`` [R] tokens per row that are real (None:
        ``n_tok``) — what a state layer may let into its state; ``row``
        (prefill): the decode row whose state slot the one prompt row uses,
        zeroed first where ``fresh``; None (decode): row r is slot r.
        ``ride`` (a riding launch): ``tokens`` is the one row of the prompt
        part and the decode part, which the model sees at
        ``ride.positions``; only ``ride.head``'s tokens reach the head.
        A looped model's walk (``passes`` > 1) runs inside one
        ``fori_loop`` with the arenas in its carry."""
        import jax
        import jax.numpy as jnp

        from ..autograd import no_grad
        from ..jit import _StateSwap
        from ..tensor.tensor import Tensor

        model = self.model
        new_arenas = {key: list(arrs) for key, arrs in arenas.items()}
        notes: Dict[str, object] = {}
        kept: Dict[str, list] = {}
        n_valid = n_tok if n_valid is None else n_valid
        if ride is None:
            at = positions
            valid = jnp.arange(tokens.shape[1])[None, :] < n_valid[:, None]
        else:
            at, valid = ride.positions, ride.valid

        def walk(x, arenas, tables, ride, notes, kept):
            for li, spec in enumerate(self._layers):
                io = _LayerIO(self, spec, arenas, self._family_index[li],
                              tables, positions, n_tok, n_valid, row, fresh,
                              notes, kept, valid, ride)
                x = model.serve_layer(li, x, shared, io)
            return x

        def one_pass(t, carry):
            """Pass ``t`` of a looped model: every layer once, over pages
            ``t * num_pages + page`` (both parts' tables of a riding
            launch), then the model's step between passes."""
            xv, arenas = carry
            arenas = {key: list(arrs) for key, arrs in arenas.items()}
            off = t * self.num_pages
            inner: Dict[str, object] = {}
            y = walk(Tensor(xv), arenas, tables + off,
                     None if ride is None
                     else ride._replace(tables=ride.tables + off),
                     inner, inner)
            if inner:
                raise TypeError(
                    f"io.note / io.keep ({sorted(inner)}) inside a walk "
                    f"that repeats ({self.passes} passes): what a layer "
                    f"counts or keeps is not carried out of the loop")
            y = model.serve_pass_end(y)
            return y._value.astype(xv.dtype), arenas

        with _StateSwap(self._params, param_arrays), \
                _StateSwap(self._buffers, buffer_arrays), no_grad():
            x, shared = model.serve_begin(Tensor(tokens), at)
            if self.passes == 1:
                x = walk(x, new_arenas, tables, ride, notes, kept)
                if hasattr(model, "serve_pass_end"):
                    x = model.serve_pass_end(x)
            else:
                # ONE copy of the layers in the program, the arenas carried
                xv, new_arenas = jax.lax.fori_loop(
                    0, self.passes, one_pass, (x._value, new_arenas))
                x = Tensor(xv)
            if ride is not None:
                x = Tensor(jnp.take(x._value, ride.head, axis=1))
            logits = model.serve_end(x)
            self._note_reduce = {name: notes[name][1]
                                 for name in sorted(notes)}
            # what the layers noted: one small vector, in that order
            noted = jnp.stack([notes[name][0] for name in self._note_reduce]) \
                if notes else None
            return logits._value, new_arenas, noted, \
                {name: jnp.stack(vals) for name, vals in kept.items()}

    def _decode_fn(self, param_arrays, buffer_arrays, arenas, tokens,
                   positions, tables, n_tok):
        """ONE compiled decode signature: ``tokens`` [R, S] where S is the
        fixed speculative width (1 + k_max; 1 when speculation is off) and
        ``n_tok`` carries each row's live width — adapting k never
        recompiles.  Returns what a step fetches — the greedy choice [R, S]
        (:func:`greedy_choice`) and the vector of what the layers noted
        (``io.note``; None where none did) — then what stays on the device
        — the logits [R, S, V] the choice was made from and what the layers
        kept (``io.keep``) — and the arenas."""
        logits, arenas, noted, kept = self._forward(
            param_arrays, buffer_arrays, arenas, tokens, positions, tables,
            n_tok)
        return (greedy_choice(logits), noted), (logits, kept), arenas

    def _page_walk(self, rows: int, width: int, layer: AttentionLayer):
        """How one attention layer of the decode program attends, decided at
        trace time by what the engine can observe: the keyword arguments of
        ``paged_decode_attention`` where the Pallas dispatch is local (a
        TPU, or the interpreter), the pages hold the compute dtype and the
        kernel's gate takes the arena's shape (4-D, or a token's heads
        merged); else None, the gather + einsum — with a
        ``kernel_fallback`` event wherever a kernel could have run.  Many
        short rows against the prefill's one row of one to a few pages of
        queries: the two programs share the mask rule and nothing else, so
        prefill keeps the einsum, a page of queries at a time."""
        from ..ops import pallas_mode
        from ..ops.pallas.paged_decode_attention import (
            KERNEL_NAME, paged_decode_attention_refusal)

        mode = pallas_mode("use_decode_attention")
        if mode is None:
            return None
        kind, _, interpret = mode
        pages = self._arenas["k"][0]
        if kind != "local":
            reason = "hybrid_mesh"
        elif self.kv_dtype != "bf16":
            reason = "kv_dtype"
        else:
            reason = paged_decode_attention_refusal(
                (rows, width, layer.heads, layer.head_dim),
                pages.shape, (rows, self.max_pages_per_seq), pages.dtype,
                interpret=interpret)
        if reason is None:
            return {"interpret": interpret}
        from ..telemetry import kernel_fallback

        kernel_fallback(KERNEL_NAME, reason, kv_dtype=self.kv_dtype,
                        rows=rows, width=width)
        return None

    def _prefill_fn(self, param_arrays, buffer_arrays, arenas, tokens,
                    chunk_start, tables, take_idx, row=None, n_valid=None,
                    ride=None):
        """The prefill signature, compiled once a width of
        ``PREFILL_WIDTHS``: ``tokens`` [1, w * page_tokens] of one prompt
        from position ``chunk_start`` on.  ``row`` / ``n_valid``: the
        request's decode row and the launch's real tokens.  K/V are kept up
        to the end of the page that holds the last real token (its junk
        tail is harmless: decode overwrites it before the position mask
        exposes it); whole junk pages behind it, which only a launch wider
        than the prompt's rest has, scatter to the trash page.  State
        layers let only the real tokens into the state: a recurrence would
        swallow the junk.

        ``ride`` (the riding form, the narrowest width where the engine
        rides: :meth:`_carries_rows`): the decode program's four inputs
        ``(tokens [R, S], positions, tables, n_tok)``, idle (``n_tok`` 0)
        on every launch of that width but a step's last.
        The model sees the prompt's tokens and the decode rows' as one
        row, so every weight is read once for both; only the prompt's last
        token and the decode tokens reach the head.  Returns, beside the
        logits row and the notes, the rows' choice [R, S]; beside what the
        prompt's tokens kept, the rows' logits [R, S, V] and what their
        tokens kept, as the decode program leaves them."""
        import jax.numpy as jnp

        positions = chunk_start[None]                 # [1]
        s, P = tokens.shape[1], self.page_tokens
        # n_valid <= s, a whole number of pages
        keep = s if n_valid is None else -(-n_valid // P) * P
        n_tok = jnp.full((1,), keep, jnp.int32)
        row = jnp.int32(0) if row is None else row
        if ride is None:
            logits, arenas, noted, kept = self._forward(
                param_arrays, buffer_arrays, arenas, tokens, positions,
                tables, n_tok,
                n_valid=None if n_valid is None else n_valid[None], row=row,
                fresh=chunk_start == 0)
            # what the layers noted rides beside the logits row, and what
            # they kept stays on the device, as in decode
            return (jnp.take(logits[0], take_idx, axis=0), noted), kept, \
                arenas
        d_tokens, d_starts, d_tables, d_n_tok = ride
        R, S = d_tokens.shape
        offs = jnp.arange(S)
        real = s if n_valid is None else n_valid
        ride = _Ride(
            split=s, width=S, tables=d_tables, starts=d_starts, n_tok=d_n_tok,
            positions=jnp.concatenate([
                chunk_start + jnp.arange(s),
                (d_starts[:, None] + offs).reshape(-1)])[None],
            valid=jnp.concatenate([
                jnp.arange(s) < real,
                (offs[None, :] < d_n_tok[:, None]).reshape(-1)])[None],
            head=jnp.concatenate([take_idx[None], s + jnp.arange(R * S)]))
        logits, arenas, noted, kept = self._forward(
            param_arrays, buffer_arrays, arenas,
            jnp.concatenate([tokens[0], d_tokens.reshape(-1)])[None],
            positions, tables, n_tok, row=row, fresh=chunk_start == 0,
            ride=ride)
        d_logits = logits[0, 1:].reshape(R, S, -1)
        kept_prompt = {name: v[:, :, :s] for name, v in kept.items()}
        kept_rows = {name: v[:, 0, s:].reshape((v.shape[0], R, S)
                                               + v.shape[3:])
                     for name, v in kept.items()}
        return (logits[0, 0], noted, greedy_choice(d_logits)), \
            (kept_prompt, d_logits, kept_rows), arenas

    def _param_arrays(self):
        with _SWAP_LOCK:
            return ([p._value for p in self._params],
                    [b._value for b in self._buffers])

    def _repl(self, x):
        """Committed-replicated copy of a step input under the TP mesh
        (no-op unsharded).  Compiled signatures are sharding-sensitive:
        an uncommitted host array could lower with a different layout
        than the one the executable was built for."""
        if self._mesh is None:
            return x
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(x, NamedSharding(self._mesh,
                                               PartitionSpec()))

    def _compile(self, fn, args, name: str, *, choose_layouts: bool = False):
        """Lower and compile one engine program (arenas donated) as the
        module ``jit_<name>``.  Under a TP / CP mesh GSPMD partitions it
        over a mesh the kernel wrappers do not know, which the Pallas
        dispatchers are told.  Every argument is taken in the layout it
        lies in; with ``choose_layouts`` the compiler picks the layout of
        each parameter of ``args[0]`` (``Layout.AUTO``) and the engine's
        arrays move there (:meth:`_relay_params`): the span notes how many
        moved (``relaid``) and their bytes (``relaid_bytes``)."""
        import contextlib

        import jax

        from ..ops import gspmd_program

        scope = gspmd_program() if self._mesh is not None \
            else contextlib.nullcontext()
        jit_kw = {"donate_argnums": (2,)}
        pa = args[0]
        if choose_layouts:
            jit_kw["in_shardings"] = (self._param_formats(pa),) \
                + (None,) * (len(args) - 1)
            args = ([jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pa],) \
                + tuple(args[1:])
        self._cycle.compiled = True
        with _span("serve.compile", program=name) as sp, _SWAP_LOCK, scope:
            compiled = jax.jit(named_program(fn, name), **jit_kw) \
                .lower(*args).compile()
            if choose_layouts:
                moved, nbytes = self._relay_params(
                    pa, compiled.input_formats[0][0])
                sp.note(relaid=moved, relaid_bytes=nbytes)
                self.meter.params_relaid += moved
                self.meter.params_relaid_bytes += nbytes
            return compiled

    @staticmethod
    def _param_formats(pa):
        """What a program that chooses the layouts asks of each parameter
        of ``pa``: the compiler's choice, on the parameter's own
        sharding."""
        from jax.experimental.layout import Format, Layout

        return [Format(Layout.AUTO, a.sharding) for a in pa]

    def _relay_params(self, pa, formats):
        """Move each parameter whose array lies otherwise into the layout
        of ``formats`` (one a parameter of ``pa``, as a compiled program
        takes them), one at a time: the new array replaces the old in the
        ``Parameter`` and in ``pa``, so the old one is gone before the next
        moves, and the weights are never held twice.  Entries that are no
        arrays (a compile for a described chip) stay as they are.  Returns
        the parameters moved and their bytes.  The moves compile afresh
        (``persistent_cache_off``): a move loaded from the persistent cache
        leaves its result where it was."""
        import jax

        moved = nbytes = 0
        with persistent_cache_off():
            for i, (p, fmt) in enumerate(zip(self._params, formats)):
                old = pa[i]
                # a parameter the program does not read has no layout there
                if not isinstance(old, jax.Array) or fmt.layout is None or \
                        old.format.layout == fmt.layout:
                    continue
                new = jax.device_put(old, fmt)
                if new.format.layout != fmt.layout:
                    raise RuntimeError(
                        f"parameter {i} {old.shape} did not move into the "
                        f"layout its program takes: {new.format.layout}, "
                        f"not {fmt.layout}")
                pa[i] = p._value = new
                moved += 1
                nbytes += old.nbytes
                del old, new
        return moved, nbytes

    def _decode_args(self, tokens, positions, tables, n_tok):
        pa, ba = self._param_arrays()
        return (pa, ba, self._arenas, self._repl(tokens),
                self._repl(positions), self._repl(tables),
                self._repl(n_tok))

    def _decode_program(self, args):
        """The decode program, compiled (and held to the donation gate) the
        first time it is asked for.  It chooses the parameters' layouts
        unless :attr:`param_layout_refusal` names why not; the parameters
        in ``args`` are then the moved ones."""
        if self._decode_exec is None:
            self._decode_compiles += 1
            if self.param_layout_refusal is None and not all(
                    default_layout(p._value) for p in self._params):
                self.param_layout_refusal = "laid_out"
                _event("serve_param_layouts", "laid_out", relaid=0)
            self._decode_exec = self._compile(
                self._decode_fn, args, DECODE_PROGRAM,
                choose_layouts=self.param_layout_refusal is None)
            if self._lint:
                self.lint_report = check_decode_donation(
                    self._decode_exec, self._arena_bytes,
                    scale_bytes=self._scale_bytes, shards=self.tp,
                    state_bytes=self.state.nbytes if self.state else 0)
        return self._decode_exec

    def _run_decode(self, tokens, positions, tables, n_tok):
        args = self._decode_args(tokens, positions, tables, n_tok)
        # the previous step's logits are dropped here, never fetched unless
        # someone asked (:attr:`last_decode_logits`)
        (choice, self._decode_noted), \
            (self._decode_logits, self._decode_kept), self._arenas = \
            self._decode_program(args)(*args)
        return choice

    def _ride_args(self, ride=None):
        """The decode part of a riding launch, on the device: the four
        inputs of ``ride`` (a batch of :meth:`_decode_prep`), or the idle
        slots that a launch of the riding width carries where it is not a
        step's last (kept, not copied again)."""
        import jax.numpy as jnp

        if ride is not None:
            _, tokens, n_tok, positions, tables, _ = ride
            return tuple(self._repl(jnp.asarray(a))
                         for a in (tokens, positions, tables, n_tok))
        if self._idle_ride is None:
            self._idle_ride = self._idle_rows()
        return self._idle_ride

    def _idle_rows(self):
        """The decode program's four inputs with every row idle: tokens,
        positions, tables (the trash page) and ``n_tok`` (0)."""
        import jax.numpy as jnp

        R, S = self.max_batch, self._spec_width
        return tuple(self._repl(a) for a in (
            jnp.zeros((R, S), jnp.int32), jnp.zeros((R,), jnp.int32),
            jnp.full((R, self.max_pages_per_seq), TRASH_PAGE, jnp.int32),
            jnp.zeros((R,), jnp.int32)))

    def _run_prefill(self, tokens, chunk_start, tables, take_idx, row,
                     n_valid, ride=None):
        """Launch the prefill program of ``tokens``' width.  The first
        launch compiles the decode program, which chooses the weights'
        layouts, then every width of the ladder, so none compiles once
        requests are being served.  A launch of the width that carries a
        decode part (:meth:`_carries_rows`) carries ``ride``'s rows, or
        idle slots; one that carried rows leaves their choice for the
        step's one fetch and their logits where the decode program's would
        be."""
        if self._decode_exec is None:
            self._decode_program(self._decode_args(*self._idle_rows()))
        pa, ba = self._param_arrays()
        args = (pa, ba, self._arenas, self._repl(tokens),
                self._repl(chunk_start), self._repl(tables),
                self._repl(take_idx), self._repl(row), self._repl(n_valid))
        if not self._prefill_exec:
            import jax.numpy as jnp

            for w in self._prefill_widths:
                wide = self._repl(jnp.zeros((1, w * self.page_tokens),
                                            tokens.dtype))
                self._prefill_exec[w] = self._compile(
                    self._prefill_fn, args[:3] + (wide,) + args[4:] + (
                        (self._ride_args(),) if self._carries_rows(w)
                        else ()), PREFILL_PROGRAM)
        width = tokens.shape[1] // self.page_tokens
        carries = self._carries_rows(width)
        if carries:
            args += (self._ride_args(ride),)
        out, kept, self._arenas = self._prefill_exec[width](*args)
        logits, noted = out[:2]
        if carries:
            kept, d_logits, d_kept = kept
            if ride is not None:
                self._ride_choice = out[2]
                self._decode_logits, self._decode_kept = d_logits, d_kept
        if noted is not None:
            self._prefill_notes.append(noted)   # fetched with the logits
        if kept:
            self._prefill_kept.append(kept)
        return logits

    # -- context-parallel prefill (ISSUE 20 leg 1) -------------------------
    def _cp_accepts(self, n_prompt: int, *, cached_tokens: int = 0) -> bool:
        """Gate for the context-parallel prefill program.  Every rejection
        emits a ``kernel_fallback("serving_cp_prefill", reason)`` event so
        telemetry shows WHY a long-prompt engine fell back to the chunked
        path: ``prefix_cached`` (the CP program refills every page — a
        cached prefix would be recomputed, losing the cache win) and
        ``short_prompt`` (fewer page-chunks than ring devices: some shards
        would be all-padding and the ring overhead can't amortize)."""
        if self.cp <= 1:
            return False
        from ..telemetry import kernel_fallback

        n_chunks = -(-n_prompt // self.page_tokens)
        if cached_tokens > 0:
            kernel_fallback("serving_cp_prefill", "prefix_cached",
                            cached_tokens=cached_tokens)
            return False
        if n_chunks < self.cp:
            kernel_fallback("serving_cp_prefill", "short_prompt",
                            n_chunks=n_chunks, cp=self.cp)
            return False
        return True

    def _cp_prefill_fn(self, param_arrays, buffer_arrays, arenas, tokens,
                       tables, take_idx):
        """Context-parallel prefill program: ONE forward over the whole
        zero-padded prompt ``tokens`` [1, nc_pad * page_tokens] with the
        sequence dim ring-sharded over the ``sep`` mesh axis
        (:func:`ring_attention` — the same ring the training side uses).
        KV lands in the page arenas exactly where the chunked program
        would put it (``tables`` [1, nc_pad] routes pad chunks to the
        trash page), and the one needed hidden row is sliced at
        ``take_idx`` BEFORE the lm_head so no full-sequence logits
        [s, V] ever materializes."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from ..autograd import no_grad
        from ..distributed.meta_parallel.context_parallel import \
            ring_attention
        from ..jit import _StateSwap
        from ..models.llama import rotate_half_apply
        from ..nn import functional as F
        from ..tensor.manipulation import reshape
        from ..tensor.tensor import Tensor

        model = self.model
        with _StateSwap(self._params, param_arrays), \
                _StateSwap(self._buffers, buffer_arrays), no_grad():
            base = model.llama
            R, s = tokens.shape                       # R == 1
            cfg = model.config
            h, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
            cos = base.rope_cos._value
            sin = base.rope_sin._value
            pos_ids = jnp.clip(jnp.arange(s)[None, :], 0,
                               cos.shape[0] - 1)                  # [1, s]
            cos_s = jnp.take(cos, pos_ids, axis=0)[:, :, None, :]
            sin_s = jnp.take(sin, pos_ids, axis=0)[:, :, None, :]
            x = base.embed_tokens(Tensor(tokens))
            # seed GSPMD: the hidden stream is seq-sharded over the ring;
            # the projections stay local per-shard and only the ring
            # rotates K/V between devices
            seq_sh = NamedSharding(self._mesh,
                                   PartitionSpec(None, "sep", None))
            x = Tensor(jax.lax.with_sharding_constraint(x._value, seq_sh))
            P = self.page_tokens
            pos = jnp.arange(s)
            page_idx = jnp.take(tables[0], pos // P)              # [s]
            slot = pos % P
            new_arenas = {key: [] for key in arenas}

            def page_rows(x):   # the one row's [s, kv, d] as the arena
                return x[0].reshape(s, kvh * d) if self._flat_pages \
                    else x[0]   # keeps a token (see __init__)

            for li, layer in enumerate(base.layers):
                xin = layer.input_layernorm(x)
                q = reshape(layer.self_attn.q_proj(xin), [R, s, h, d])
                k = reshape(layer.self_attn.k_proj(xin), [R, s, kvh, d])
                v = reshape(layer.self_attn.v_proj(xin), [R, s, kvh, d])
                qv, kv_ = rotate_half_apply(q._value, k._value, cos_s,
                                            sin_s)
                vv = v._value
                kp, vp = arenas["k"][li], arenas["v"][li]
                # quantize-then-dequantize BEFORE the ring for quantized
                # pools: the chunked oracle reads even its own chunk's KV
                # back from the arena, so CP must attend over the same
                # rounded values to stay token-exact
                if self.kv_dtype == "int8":
                    kq, ksc = quantize_kv(kv_)
                    vq, vsc = quantize_kv(vv)
                    kp = kp.at[page_idx, slot].set(page_rows(kq))
                    vp = vp.at[page_idx, slot].set(page_rows(vq))
                    new_arenas["ks"].append(
                        arenas["ks"][li].at[page_idx, slot].set(ksc[0]))
                    new_arenas["vs"].append(
                        arenas["vs"][li].at[page_idx, slot].set(vsc[0]))
                    k_att = dequantize_kv(kq, ksc).astype(self._cdt)
                    v_att = dequantize_kv(vq, vsc).astype(self._cdt)
                elif self.kv_dtype == "fp8":
                    kq = quantize_kv_fp8(kv_, self._fp8_scale)
                    vq = quantize_kv_fp8(vv, self._fp8_scale)
                    kp = kp.at[page_idx, slot].set(page_rows(kq))
                    vp = vp.at[page_idx, slot].set(page_rows(vq))
                    k_att = dequantize_kv_fp8(
                        kq, self._fp8_scale).astype(self._cdt)
                    v_att = dequantize_kv_fp8(
                        vq, self._fp8_scale).astype(self._cdt)
                else:
                    kp = kp.at[page_idx, slot].set(
                        page_rows(kv_).astype(kp.dtype))
                    vp = vp.at[page_idx, slot].set(
                        page_rows(vv).astype(vp.dtype))
                    k_att = kv_.astype(kp.dtype)
                    v_att = vv.astype(vp.dtype)
                new_arenas["k"].append(kp)
                new_arenas["v"].append(vp)
                out = ring_attention(qv, k_att, v_att, mesh=self._mesh,
                                     sep_axis="sep", causal=True)
                x = x + layer.self_attn.o_proj(
                    Tensor(out._value.astype(qv.dtype).reshape(R, s,
                                                               h * d)))
                x = x + layer.mlp(layer.post_attention_layernorm(x))
            hidden = base.norm(x)
            # ONE row of hidden state, then the vocab projection — the
            # full-seq [s, V] logits never exist
            hrow = Tensor(jnp.take(hidden._value[0], take_idx[None],
                                   axis=0)[None])                # [1,1,D]
            if model.lm_head is not None:
                logits = model.lm_head(hrow)
            else:
                logits = F.linear(hrow, base.embed_tokens.weight.T)
            return logits._value[0, 0], new_arenas

    def _run_cp_prefill(self, tokens, tables, take_idx):
        """Compile-and-run for the CP program, one executable per padded
        prompt length (``nc_pad`` chunks — prompts that pad to the same
        multiple of ``cp`` share an executable; ``take_idx`` is traced,
        so the exact prompt length never recompiles)."""
        pa, ba = self._param_arrays()
        args = (pa, ba, self._arenas, self._repl(tokens),
                self._repl(tables), self._repl(take_idx))
        sig = int(tokens.shape[1])
        exec_ = self._cp_execs.get(sig)
        if exec_ is None:
            exec_ = self._compile(self._cp_prefill_fn, args,
                                  CP_PREFILL_PROGRAM)
            self._cp_execs[sig] = exec_
            if self._lint:
                # arenas are replicated over the ring (shards=1: every
                # device aliases the full arena bytes)
                self.cp_lint_reports[sig] = check_decode_donation(
                    exec_, self._arena_bytes,
                    name=f"serving_cp_prefill_{sig}",
                    scale_bytes=self._scale_bytes)
        logits, self._arenas = exec_(*args)
        return logits

    def _cp_prefill_run(self, prompt, pages):
        """Build the padded CP inputs for ``prompt`` over its allocated
        ``pages`` and run the CP program; returns last-token logits [V].
        The chunk count pads up to a multiple of ``cp`` so the ring
        divides evenly — pad chunks carry zero tokens and scatter to the
        trash page."""
        import jax.numpy as jnp

        P = self.page_tokens
        n_chunks = -(-len(prompt) // P)
        nc_pad = -(-n_chunks // self.cp) * self.cp
        tokens = np.zeros((1, nc_pad * P), np.int32)
        tokens[0, :len(prompt)] = np.asarray(prompt, np.int32)
        tbl = np.full((1, nc_pad), TRASH_PAGE, np.int32)
        tbl[0, :n_chunks] = np.asarray(pages[:n_chunks], np.int32)
        self._cycle.prefill_launches += 1
        self._cycle.prefill_tokens += len(prompt)
        return self._run_cp_prefill(jnp.asarray(tokens), jnp.asarray(tbl),
                                    jnp.int32(len(prompt) - 1))
