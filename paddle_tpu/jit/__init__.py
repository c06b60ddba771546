"""Whole-graph compilation (reference capability: `python/paddle/jit` to_static
+ SOT, `program_translator.py:325`, `sot/translate.py:99`).

TPU-first design: instead of bytecode capture + graph-break fallback, the
tracer IS ``jax.jit`` — python control flow runs at trace time, and anything
un-traceable simply stays eager (call the layer directly). Two entry points:

- :func:`to_static` — compile a Layer (or function over Layers) into one XLA
  computation. Stateful semantics are preserved by functionalizing: params
  and buffers are swapped to traced values during trace, buffer mutations
  (BN running stats) are returned as outputs and written back, RNG draws go
  through a per-call traced key (`framework.random.key_scope`). Gradients
  work: the compiled forward is recorded on the eager tape as ONE node whose
  vjp is a compiled (rematerializing) backward.

- :class:`TrainStep` — the performance path: forward + backward + optimizer
  update fused into a single jitted, donated-buffer step (the analogue of
  the reference's static-graph executor running a whole Program per step).
"""

from __future__ import annotations

import collections
import functools
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..autograd import no_grad
from ..autograd.tape import TapeNode, is_grad_enabled
from ..framework.random import key_scope, next_key
from ..nn.clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue
from ..nn.layer.layers import Layer
from ..profiler import span as _span
from ..tensor.tensor import Tensor

__all__ = ["to_static", "TrainStep", "not_to_static", "ignore_module", "save",
           "load", "InputSpec", "TranslatedLayer"]


def _is_tensor(x) -> bool:
    return isinstance(x, Tensor)


class _CompileCache:
    """Bounded per-process compile cache (LRU): the KernelKey-style dict
    every StaticFunction / AOTFunction keys compiled programs by, capped
    at ``PADDLE_TPU_JIT_CACHE_MAX`` entries (default 64) so shape churn —
    ragged batches, sweep loops — cannot grow it without limit. Evictions
    bump the ``compile_cache_evictions`` telemetry counter: a hot loop
    that keeps evicting (cache thrash = recompile storm) is visible in
    prometheus instead of silent.

    ``persistent`` optionally names an on-disk
    :class:`~paddle_tpu.compile.cache.ExecutableCache` backing layer —
    the in-memory cache is the first level of the AOT compile service's
    lookup (:class:`~paddle_tpu.compile.AOTFunction` consults it before
    the disk store)."""

    _DEFAULT_MAX = 64

    def __init__(self, max_entries: Optional[int] = None, persistent=None):
        if max_entries is None:
            try:
                max_entries = int(os.environ.get("PADDLE_TPU_JIT_CACHE_MAX",
                                                 self._DEFAULT_MAX))
            except ValueError:
                max_entries = self._DEFAULT_MAX
        self.max_entries = max(1, max_entries)
        self.persistent = persistent
        self.evictions = 0
        self._entries: "collections.OrderedDict[Any, Any]" = \
            collections.OrderedDict()

    def get(self, key, default=None):
        try:
            value = self._entries[key]
        except KeyError:
            return default
        self._entries.move_to_end(key)
        return value

    def put(self, key, value) -> None:
        self._entries[key] = value
        self._entries.move_to_end(key)
        while len(self._entries) > self.max_entries:
            self._entries.popitem(last=False)
            self.evictions += 1
            try:
                from .. import telemetry

                telemetry.bump("compile_cache_evictions")
            except Exception:
                pass

    __setitem__ = put

    def __getitem__(self, key):
        value = self.get(key, default=_MISSING)
        if value is _MISSING:
            raise KeyError(key)
        return value

    def __contains__(self, key) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()


_MISSING = object()


_stamped_paths: set = set()
_fleet_fd_mod = None
_last_fleet_step_t: Optional[float] = None


def _note_fleet_step(step: int) -> None:
    """Fleet fault domain probe: stamp per-step progress AND inter-step
    wall time into this rank's heartbeat lease, so the lease monitor can
    tell alive-but-stuck-in-step (straggler) from dead and a chronically
    slow rank from the gang median. No-op (one global read) without an
    active domain — must stay free on the hot path; the wall-time delta
    is two perf_counter reads, no device sync (async dispatch means the
    inter-call gap reflects device pace once the pipeline saturates)."""
    global _fleet_fd_mod, _last_fleet_step_t
    if _fleet_fd_mod is None:
        try:
            from ..distributed.fleet import fault_domain as _fleet_fd_mod
        except Exception:
            _fleet_fd_mod = False
    if _fleet_fd_mod:
        now = time.perf_counter()
        dt = None if _last_fleet_step_t is None \
            else now - _last_fleet_step_t
        _last_fleet_step_t = now
        try:
            _fleet_fd_mod.note_step_current(step, dt=dt)
        except TypeError:
            try:
                _fleet_fd_mod.note_step_current(step)
            except Exception:
                pass
        except Exception:
            pass


def _stamp_first_step() -> None:
    """Goodput probe for the restart supervisor: the first COMPLETED train
    step of this process writes a wall-clock stamp to the path named by
    ``PADDLE_TPU_FIRST_STEP_STAMP`` (the Supervisor sets a fresh path per
    launch and reads it back as ``time_to_first_step_s``). One write per
    stamp path, nothing without the env var."""
    path = os.environ.get("PADDLE_TPU_FIRST_STEP_STAMP")
    if not path or path in _stamped_paths:
        return
    _stamped_paths.add(path)
    try:
        with open(path, "w") as f:
            f.write(repr(time.time()))
    except OSError:
        pass


def named_program(fn: Callable, name: str, **fixed) -> Callable:
    """``fn`` (with ``fixed`` keyword arguments bound) under a ``__name__``
    of its own.  ``jax.jit`` names the compiled module ``jit_<__name__>``:
    a bound method lends its Python name and a ``functools.partial`` has
    none (``jit__unknown``), while a device trace's readers find a program
    by that name — so it is a constant of the program, pinned by tests.
    A named ``partial`` and no wrapper function: every frame between
    ``jit`` and the traced body is paid per traced operation (tracebacks)."""
    program = functools.partial(fn, **fixed)
    program.__name__ = program.__qualname__ = name
    return program


class _StateSwap:
    """Temporarily swap the arrays held by a list of Tensors (trace-time)."""

    def __init__(self, tensors: Sequence[Tensor], arrays):
        self.tensors = tensors
        self.arrays = arrays
        self._saved = None

    def __enter__(self):
        self._saved = [t._value for t in self.tensors]
        for t, a in zip(self.tensors, self.arrays):
            t._value = a
        return self

    def __exit__(self, *exc):
        for t, v in zip(self.tensors, self._saved):
            t._value = v


class StaticFunction:
    """One compiled graph per (input structure, shapes) — the KernelKey-style
    compile cache (reference `sot/symbolic/compile_cache.py` capability)."""

    def __init__(self, fn: Callable, layer: Optional[Layer] = None, input_spec=None,
                 full_graph: bool = True, backend=None):
        self._fn = fn
        self._layer = layer
        self._cache = _CompileCache()  # bounded: shape churn can't leak
        try:
            functools.update_wrapper(self, fn)
        except Exception:
            pass

    def _discover_layers(self):
        """Layers owning the state this function touches: the bound layer,
        any Layer in the function's closure/defaults, and any Layer the
        function references as a GLOBAL (``to_static(lambda x: model(x))``
        at module level / in a REPL has ``model`` in __globals__, not the
        closure — missing it left mutated buffers un-swapped and leaked
        tracers out of the trace)."""
        layers = []
        if self._layer is not None:
            layers.append(self._layer)
        closure = getattr(self._fn, "__closure__", None) or ()
        for cell in closure:
            try:
                v = cell.cell_contents
            except ValueError:
                continue
            if isinstance(v, Layer):
                layers.append(v)
        for v in (getattr(self._fn, "__defaults__", None) or ()):
            if isinstance(v, Layer):
                layers.append(v)
        code = getattr(self._fn, "__code__", None)
        fglobals = getattr(self._fn, "__globals__", None)
        if code is not None and fglobals is not None:
            import dis

            # walk LOAD_GLOBAL/LOAD_NAME instructions specifically:
            # co_names also lists ATTRIBUTE names, which would falsely
            # capture an unrelated global Layer that happens to share a
            # name with e.g. an `obj.model` access.  LOAD_NAME is what
            # class-body / exec / some REPL scopes emit instead of
            # LOAD_GLOBAL (advisor round 4).  Two documented gaps remain:
            # (a) Layers reached only through attribute access on a
            # container (``holder.model``) are NOT discoverable; (b) a
            # LOAD_NAME that actually binds a class-body LOCAL resolves
            # here against __globals__, so a same-named module-level
            # Layer would be captured instead of the local one (which
            # stays missed).  In both cases pass the Layer explicitly or
            # bind it via closure/defaults.
            for ins in dis.get_instructions(code):
                if ins.opname in ("LOAD_GLOBAL", "LOAD_NAME"):
                    v = fglobals.get(ins.argval)
                    if isinstance(v, Layer):
                        layers.append(v)
        return layers

    def _state(self):
        params, buffers, seen = [], [], set()
        for layer in self._discover_layers():
            for _, p in layer.named_parameters():
                if id(p) not in seen:
                    seen.add(id(p))
                    params.append(p)
            for _, b in layer.named_buffers():
                if id(b) not in seen:
                    seen.add(id(b))
                    buffers.append(b)
        return params, buffers

    def __call__(self, *args, **kwargs):
        params, buffers = self._state()
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_is_tensor)
        mask = tuple(isinstance(l, Tensor) for l in leaves)
        tensor_leaves = [l for l, m in zip(leaves, mask) if m]
        static_leaves = [l for l, m in zip(leaves, mask) if not m]
        t_arrays = [t._value for t in tensor_leaves]

        cache_key = (treedef, mask, tuple(repr(s) for s in static_leaves),
                     tuple((tuple(a.shape), str(a.dtype)) for a in t_arrays),
                     len(params), len(buffers))
        entry = self._cache.get(cache_key)
        if entry is None:
            entry = self._build(treedef, mask, static_leaves, params, buffers, t_arrays)
            self._cache[cache_key] = entry

        b_arrays = [b._value for b in buffers]
        p_arrays = [p._value for p in params]
        rng = next_key()

        record = is_grad_enabled() and (
            any(not p.stop_gradient for p in params) or
            any(not t.stop_gradient for t in tensor_leaves))

        out_arrays, new_buf = entry["fwd"](p_arrays, b_arrays, rng, t_arrays)
        for b, nv in zip(buffers, new_buf):
            b._value = nv
            b._producer = None

        out_tensors = [Tensor(a, stop_gradient=not record) for a in out_arrays]
        if record:
            node_inputs = params + tensor_leaves
            bwd = entry["bwd"]

            def node_vjp(cts, _p=p_arrays, _b=b_arrays, _r=rng, _t=t_arrays):
                cts = cts if isinstance(cts, tuple) else (cts,)
                gp, gt = bwd(_p, _b, _r, _t, tuple(cts))
                return tuple(list(gp) + list(gt))

            node = TapeNode(getattr(self._fn, "__name__", "to_static"), node_vjp,
                            node_inputs, out_tensors)
            for i, o in enumerate(out_tensors):
                o._producer = (node, i)

        it = iter(out_tensors)
        rebuilt_leaves = [next(it) if m else s
                         for m, s in zip(entry["out_mask"], entry["out_static"])]
        return jax.tree_util.tree_unflatten(entry["out_treedef"], rebuilt_leaves)

    def _build(self, treedef, mask, static_leaves, params, buffers, t_arrays):
        fn = self._fn

        def pure(p_arr, b_arr, rng, t_arr):
            it_t = iter(t_arr)
            it_s = iter(static_leaves)
            leaves2 = [Tensor(next(it_t)) if m else next(it_s) for m in mask]
            args2, kwargs2 = jax.tree_util.tree_unflatten(treedef, leaves2)
            with _StateSwap(params, p_arr), _StateSwap(buffers, b_arr), \
                    key_scope(rng), no_grad():
                out = fn(*args2, **kwargs2)
                new_buf = [b._value for b in buffers]
            out_leaves, out_treedef = jax.tree_util.tree_flatten(out, is_leaf=_is_tensor)
            out_mask = tuple(isinstance(o, Tensor) for o in out_leaves)
            out_arrays = tuple(o._value for o, m in zip(out_leaves, out_mask) if m)
            meta = (out_treedef, out_mask,
                    [None if m else o for o, m in zip(out_leaves, out_mask)])
            return out_arrays, new_buf, meta

        # learn the output structure with one abstract evaluation (no compile)
        meta_holder = {}

        def probe(p_arr, b_arr, rng, t_arr):
            out_arrays, new_buf, meta = pure(p_arr, b_arr, rng, t_arr)
            meta_holder["meta"] = meta
            return out_arrays, new_buf

        jax.eval_shape(probe, [p._value for p in params], [b._value for b in buffers],
                       jax.random.PRNGKey(0), list(t_arrays))
        out_treedef, out_mask, out_static = meta_holder["meta"]

        fwd = jax.jit(lambda p, b, r, t: pure(p, b, r, t)[:2])

        def bwd(p_arr, b_arr, rng, t_arr, cts):
            _, vjp_fn = jax.vjp(lambda p, t: pure(p, b_arr, rng, t)[0], p_arr, t_arr)
            return vjp_fn(cts)

        return {"fwd": fwd, "bwd": jax.jit(bwd), "out_treedef": out_treedef,
                "out_mask": out_mask, "out_static": out_static}


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              full_graph: bool = True, **kwargs):
    """Compile a Layer or a function into one XLA computation (paddle
    jit.api.to_static parity, reference `jit/api.py:171`)."""

    def decorate(fn):
        if isinstance(fn, Layer):
            sf = StaticFunction(fn.forward, layer=fn, input_spec=input_spec)
            fn.forward = sf
            return fn
        layer = None
        if hasattr(fn, "__self__") and isinstance(fn.__self__, Layer):
            layer = fn.__self__
        return StaticFunction(fn, layer=layer, input_spec=input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


# The step's compiled variants under names of their own (``named_program``):
# the plain step, the one with the fused health probe (HealthGuard /
# SDCMonitor armed) and the ``check_nan_inf`` diagnosis variant.
STEP_PROGRAM = "train_step"
GUARDED_STEP_PROGRAM = "train_step_guarded"
CHECKED_STEP_PROGRAM = "train_step_checked"


class TrainStep:
    """Fused train step: grads + clip + optimizer update in ONE compiled XLA
    program with donated state (the TPU answer to the reference's static
    executor; also the unit that pjit shards for hybrid parallel).

    usage::

        step = TrainStep(model, lambda model, x, y: loss_fn(model(x), y), opt)
        loss = step(x, y)   # Tensor; model/optimizer state updated in place

    ``health_guard=`` (a :class:`~paddle_tpu.distributed.health.HealthGuard`)
    arms the fused anomaly probe: one in-program isfinite + grad-norm
    reduction, and a non-finite step is SKIPPED in-program (old params /
    opt-state / buffers selected back) instead of applied — the detect
    layer of the detect → skip → rewind loop.

    ``persistent_cache=`` routes compilation through the AOT compile
    service (:mod:`paddle_tpu.compile`): True for the default on-disk
    executable cache (``PADDLE_TPU_COMPILE_CACHE``), a path, or an
    :class:`~paddle_tpu.compile.ExecutableCache`. The first process to
    compile this step serializes the executable; a supervisor relaunch
    (or a fresh bench run) with the same program fingerprint warm-loads
    it instead of re-invoking XLA — ``compile_info`` reports what
    happened (``mode`` cold|warm, seconds, fingerprint, cost FLOPs).
    """

    def __init__(self, model: Layer, loss_fn: Callable, optimizer, donate: bool = True,
                 gradient_merge: Optional[int] = None, health_guard=None,
                 persistent_cache=None, snapshotter=None):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self._donate = donate
        self._health_guard = health_guard
        self._snapshotter = snapshotter
        self._sdc_monitor = None
        if persistent_cache is not None:
            from ..compile import resolve_cache

            self._persistent_cache = resolve_cache(persistent_cache)
        else:
            self._persistent_cache = None
        # AOT bookkeeping: compile_info = the FIRST compile of this step
        # (the expensive one a warm restart amortizes); compile_events =
        # every (mode, seconds, fingerprint, flops) the service reported —
        # re-traces (e.g. an optimizer counter going python-int → int32
        # after step 1) land here too, typically as warm loads
        self.compile_info: Optional[Dict[str, Any]] = None
        self.compile_events: List[Dict[str, Any]] = []
        # gradient merge (reference `auto_parallel_gradient_merge.py`): run k
        # micro-steps accumulating grads IN-JIT, update once; k defaults from
        # the fleet strategy tag stamped by distributed_optimizer
        if gradient_merge is None:
            gradient_merge = getattr(optimizer, "_gradient_merge_k", 1)
        self._merge_k = max(1, int(gradient_merge or 1))
        self._merge_avg = bool(getattr(optimizer, "_gradient_merge_avg", True))
        self._param_names = [n for n, _ in model.named_parameters()]
        self._params = [p for _, p in model.named_parameters()]
        self._trainable = [not p.stop_gradient for p in self._params]
        self._buffers = [b for _, b in model.named_buffers()]
        self._lr_mults = [getattr(p, "optimize_attr", {}).get("learning_rate", 1.0)
                          for p in self._params]
        # ASP (incubate.asp): pruned params carry n:m masks that must be
        # re-applied after every update — the eager path does it via the
        # decorated optimizer.step, which this fused step never calls
        from ..incubate.asp import ASPHelper

        self._asp_masks = [ASPHelper._masks.get(id(p)) for p in self._params]
        self._compiled = self._maybe_aot(
            jax.jit(named_program(self._step, STEP_PROGRAM),
                    donate_argnums=(0, 1) if donate else ()),
            "step")
        # FLAGS_check_nan_inf variant: same step + per-grad finite flags
        # (covers the compiled path the eager apply_op hook can't see —
        # reference nan_inf_utils_detail checks inside every kernel launch).
        # NO donation: on a detected NaN we raise BEFORE rebinding state, and
        # the old params/opt-state must still be alive.
        self._compiled_checked = jax.jit(named_program(
            self._step, CHECKED_STEP_PROGRAM, check_numerics=True))

    # -- health guard ------------------------------------------------------
    def attach_health_guard(self, guard) -> None:
        """Arm a :class:`~paddle_tpu.distributed.health.HealthGuard` on an
        already-built step (the ``health_guard=`` ctor arg is equivalent).
        The next call traces the guarded program variant."""
        self._health_guard = guard

    # -- in-memory snapshots -----------------------------------------------
    def attach_snapshotter(self, snapshotter) -> None:
        """Arm a :class:`~paddle_tpu.distributed.checkpoint.Snapshotter`
        (``snapshotter=`` ctor arg is equivalent): every
        ``PADDLE_TPU_SNAP_EVERY``-th completed step triggers a host-RAM
        snapshot + peer replication.  Pure host-side hook AFTER the state
        rebind — the compiled program, its fingerprint, and the trace are
        untouched, so attaching/detaching never recompiles."""
        self._snapshotter = snapshotter

    # -- SDC monitor -------------------------------------------------------
    def attach_sdc_monitor(self, monitor) -> None:
        """Arm a :class:`~paddle_tpu.distributed.health.SDCMonitor`: the
        guarded program's probe grows deterministic step-fingerprint lanes
        (per-bucket pre-reduce, post-allreduce grad, parameter tree) that
        the monitor resolves ``max_lag`` late and votes across replicas.
        The lanes are traced into the guarded variant, which compiles
        lazily on first use — attach BEFORE the first guarded call and the
        run still pays exactly one guarded trace (no added recompile);
        attaching (or detaching) later drops the cached guarded executable
        for one documented retrace, never a silent stale program."""
        self._sdc_monitor = monitor
        self._compiled_guarded = None

    def _make_guarded_jit(self):
        """Compiled variant with the fused health probe. Donation is safe:
        a skipped step's old state feeds the in-program select, never a
        post-hoc host decision (DistributedTrainStep pins shardings)."""
        return self._maybe_aot(
            jax.jit(named_program(self._step, GUARDED_STEP_PROGRAM,
                                  health_probe=True),
                    donate_argnums=(0, 1) if self._donate else ()),
            "guarded_step")

    # -- AOT compile service ----------------------------------------------
    def _maybe_aot(self, jitted, tag: str):
        """Route a compiled variant through the persistent executable cache
        when one is configured (ctor ``persistent_cache=``); otherwise the
        plain jit object. The checked (``check_nan_inf``) debug variant
        stays un-cached on purpose — it is a diagnosis path, not a restart
        hot path."""
        if self._persistent_cache is None:
            return jitted
        from ..compile import AOTFunction

        # extras resolve lazily (at first compile): DistributedTrainStep's
        # sharding pins are placed after the base ctor builds this wrapper
        return AOTFunction(jitted, cache=self._persistent_cache,
                           name=f"{type(self).__name__}.{tag}",
                           extras=lambda: self._fingerprint_extras(tag),
                           on_compile=self._note_compile)

    def _fingerprint_extras(self, tag: str) -> Dict[str, Any]:
        """Program identity beyond the StableHLO text: anything that could
        make the 'same' HLO compile to an incompatible executable must be
        in here (DistributedTrainStep adds mesh + sharding pins). The
        overlap config (TP decomposition, grad buckets, scheduler flags)
        rides along so toggling PADDLE_TPU_TP_OVERLAP / bucket size can
        never warm-load a stale decomposition."""
        extras = {"tag": tag, "donate": bool(self._donate),
                  "merge_k": self._merge_k}
        try:
            from ..distributed.overlap import overlap_fingerprint

            extras["overlap"] = overlap_fingerprint()
        except Exception:
            pass
        try:
            # SP changes the between-region activation layout (ag/rs vs
            # all-reduce): same model source, different program — the flag
            # must split the executable cache the same way overlap does
            from ..distributed.meta_parallel import sp_fingerprint

            extras["sp"] = sp_fingerprint()
        except Exception:
            pass
        mon = getattr(self, "_sdc_monitor", None)
        if mon is not None and mon.active:
            # fingerprint lanes change the guarded program's output arity:
            # an AOT executable traced without (or with a different) SDC
            # layout must never warm-load for this configuration
            extras["sdc"] = mon.trace_signature()
        return extras

    def _note_compile(self, info: Dict[str, Any]) -> None:
        self.compile_events.append(info)
        if self.compile_info is None:
            self.compile_info = info

    def _get_guarded(self):
        c = getattr(self, "_compiled_guarded", None)
        if c is None:
            c = self._compiled_guarded = self._make_guarded_jit()
        return c

    # -- functional pieces -------------------------------------------------
    def _clip_grads(self, grads):
        clip = self.optimizer._grad_clip
        if clip is None:
            return grads
        if isinstance(clip, ClipGradByGlobalNorm):
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g, p in zip(grads, self._params) if getattr(p, "need_clip", True))
            gnorm = jnp.sqrt(sq)
            scale = clip.clip_norm / jnp.maximum(gnorm, clip.clip_norm)
            return [g * scale.astype(g.dtype) if getattr(p, "need_clip", True) else g
                    for g, p in zip(grads, self._params)]
        if isinstance(clip, ClipGradByNorm):
            out = []
            for g, p in zip(grads, self._params):
                if not getattr(p, "need_clip", True):
                    out.append(g)
                    continue
                n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
                s = jnp.minimum(clip.clip_norm / jnp.maximum(n, 1e-12), 1.0)
                out.append(g * s.astype(g.dtype))
            return out
        if isinstance(clip, ClipGradByValue):
            return [jnp.clip(g, clip.min, clip.max) for g in grads]
        raise NotImplementedError(f"clip {type(clip)} in TrainStep")

    def _constrain_micro(self, arrays):
        """Hook: re-pin shardings after the [B] → [k, B/k] micro-batch
        reshape (DistributedTrainStep overrides to keep the batch axes on
        the data mesh dims)."""
        return arrays

    def _comm_grads(self, grads):
        """Hook: gradient-communication shaping between backward and clip
        (value-identity). DistributedTrainStep overrides to route grads
        through reverse-topological comm buckets so XLA emits one
        reduce-scatter per bucket instead of a monolithic one."""
        return grads

    def _sdc_pre_reduce_groups(self, grads):
        """Hook: ``(labels, groups)`` of PRE-reduce grad groups for the SDC
        fingerprint's rank-local diagnostic lanes. The base step has no
        comm buckets — no lanes; DistributedTrainStep taps each
        reverse-topological grad bucket so a suspect's divergence is
        localized to a bucket in the post-mortem."""
        return [], []

    def _constrain_compute(self, arrays):
        """Hook: pin the COMPUTE layout of the params entering the forward
        (value-identity). DistributedTrainStep overrides to constrain each
        param to its compute spec (storage spec minus the ZeRO "sharding"
        axis) so the storage sharding never propagates into activation
        layouts — see the spec-policy section in distributed/engine.py."""
        return arrays

    def _step(self, param_arrays, opt_states, buffer_arrays, key, lr, batch_arrays,
              sdc_vote=None, check_numerics: bool = False,
              health_probe: bool = False):
        if getattr(self, "offload", False):
            # offloaded states arrive in host memory; TPU arithmetic cannot
            # mix memory spaces, so stream them to device here — the update's
            # out_shardings (pinned_host) stream the new states back
            opt_states = [
                {k: (jax.device_put(v, jax.memory.Space.Device)
                     if hasattr(v, "ndim") else v) for k, v in st.items()}
                for st in opt_states]
        masters = [st.pop("@master", None) for st in opt_states]
        compute_params = [m if m is not None else p
                          for m, p in zip(masters, param_arrays)]

        def loss_of(p_arr, bufs, batch_mb, key_):
            run_p = [p.astype(orig.dtype) for p, orig in zip(p_arr, param_arrays)]
            run_p = self._constrain_compute(run_p)
            with _StateSwap(self._params, run_p), \
                    _StateSwap(self._buffers, bufs), key_scope(key_), no_grad():
                loss_t = self.loss_fn(self.model, *[Tensor(a) for a in batch_mb])
                new_buf = [b._value for b in self._buffers]
            return loss_t._value.astype(jnp.float32), new_buf

        k = self._merge_k
        if k == 1:
            (loss, new_buf), grads = jax.value_and_grad(loss_of, has_aux=True)(
                compute_params, buffer_arrays, batch_arrays, key)
        else:
            micro = tuple(self._constrain_micro(
                [a.reshape((k, a.shape[0] // k) + a.shape[1:])
                 for a in batch_arrays]))
            keys = jax.random.split(key, k)
            zeros = [jnp.zeros_like(p) for p in compute_params]

            def body(carry, xs):
                acc, bufs, loss_sum = carry
                mb, key_i = xs
                (loss_i, nb), g = jax.value_and_grad(loss_of, has_aux=True)(
                    compute_params, bufs, list(mb), key_i)
                acc = [a + gi.astype(a.dtype) for a, gi in zip(acc, g)]
                return (acc, nb, loss_sum + loss_i), None

            (grads, new_buf, loss_sum), _ = jax.lax.scan(
                body, (zeros, list(buffer_arrays), jnp.zeros((), jnp.float32)),
                (micro, keys))
            loss = loss_sum / k
            if self._merge_avg:
                grads = [g / k for g in grads]
        finite = None
        if check_numerics:
            finite = jnp.stack([jnp.isfinite(loss)] +
                               [jnp.all(jnp.isfinite(g)) for g in grads])
        ok = gnorm = None
        if health_probe:
            # fused device-side anomaly probe (health guard): ONE isfinite
            # reduction over loss + raw (pre-clip) grads, plus the global
            # grad norm the host-side SpikeDetector consumes — all inside
            # this program, no host sync added
            ok = jnp.isfinite(loss)
            for g in grads:
                ok &= jnp.all(jnp.isfinite(g))
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in grads))
        sdc_on = health_probe and self._sdc_monitor is not None \
            and self._sdc_monitor.active
        sdc_labels, sdc_groups = self._sdc_pre_reduce_groups(grads) \
            if sdc_on else ([], [])
        grads = self._comm_grads(grads)
        if sdc_on:
            # post-allreduce global grad: bitwise-identical across DP
            # replicas (same reduction, same order) — the first VOTED
            # fingerprint pair; earlier bucket pairs are rank-local
            sdc_labels = list(sdc_labels) + ["grad"]
            sdc_groups = list(sdc_groups) + [list(grads)]
        grads = self._clip_grads(grads)
        new_params, new_states = [], []
        for i, (p_arr, g, st) in enumerate(zip(compute_params, grads, opt_states)):
            if not self._trainable[i]:
                if masters[i] is not None:
                    # frozen low-precision param: restore the popped master
                    # slot so the state pytree keeps its structure (pjit
                    # out_shardings include @master for every bf16 param)
                    st = dict(st)
                    st["@master"] = masters[i]
                new_params.append(param_arrays[i])
                new_states.append(st)
                continue
            np_, ns = self.optimizer._update_rule(
                p_arr, g.astype(p_arr.dtype), st, lr * self._lr_mults[i],
                param_meta=self._params[i])
            ns = {**st, **ns}  # keep untouched slots: stable state pytree
            if self._asp_masks[i] is not None:
                np_ = np_ * self._asp_masks[i].astype(np_.dtype)
            if masters[i] is not None:
                ns = dict(ns)
                ns["@master"] = np_
                np_ = np_.astype(param_arrays[i].dtype)
            new_params.append(np_)
            new_states.append(ns)
        if check_numerics:
            return loss, new_params, new_states, new_buf, finite
        if health_probe:
            # skip-and-count: a non-finite step must not poison ANY state —
            # select old params/opt-states/buffers in-program (scalar-pred
            # selects fuse to ~free); the probe rides back as 3 floats
            def _sel(new, old):
                return jnp.where(ok, new, old)

            new_params = [_sel(n, o) for n, o in zip(new_params, param_arrays)]
            sel_states = []
            for st_new, st_old, m in zip(new_states, opt_states, masters):
                old = dict(st_old)
                if m is not None:
                    old["@master"] = m
                sel_states.append({k: _sel(v, old[k])
                                   for k, v in st_new.items()})
            new_states = sel_states
            new_buf = [_sel(n, o) for n, o in zip(new_buf, buffer_arrays)]
            probe_vals = [loss.astype(jnp.float32),
                          ok.astype(jnp.float32), gnorm]
            probe = jnp.stack(probe_vals)
            if sdc_on:
                # parameter tree AFTER the update + skip-select: the second
                # voted pair — replicas applying the same reduced grad to
                # the same params must land bitwise-identical
                from ..distributed.health.sdc import fingerprint_lanes

                sdc_labels.append("params")
                sdc_groups.append(list(new_params))
                seed = self._sdc_monitor.policy.seed

                def _lanes():
                    return jnp.stack(fingerprint_lanes(sdc_groups, seed))

                if sdc_vote is None:
                    lanes = _lanes()
                else:
                    # cadence gate INSIDE the program: the projection work
                    # runs only on vote steps (the host passes the flag as
                    # a dynamic scalar — both values share one trace), so
                    # at production cadence the defense is ~free
                    lanes = jax.lax.cond(
                        jnp.asarray(sdc_vote, bool), _lanes,
                        lambda: jnp.zeros((2 * len(sdc_groups),),
                                          jnp.float32))
                probe = jnp.concatenate([probe, lanes])
                # trace-time bookkeeping: the monitor learns the lane
                # layout it will resolve (host-side list write, no tracer)
                self._sdc_monitor.set_lane_labels(sdc_labels)
            return loss, new_params, new_states, new_buf, probe
        return loss, new_params, new_states, new_buf

    # -- state marshalling -------------------------------------------------
    def _opt_states(self):
        states = []
        for p in self._params:
            st = dict(self.optimizer._state_for(p))
            if self.optimizer._multi_precision and p._value.dtype in (jnp.bfloat16, jnp.float16):
                st["@master"] = self.optimizer._master(p)
            states.append(st)
        return states

    def _prepare_batch(self, batch) -> List:
        """Batch Tensors/arrays → raw arrays; the hook
        DistributedTrainStep overrides to pin mesh shardings via
        device_put. One home for the marshalling __call__ and lower()
        share."""
        return [b._value if isinstance(b, Tensor) else jnp.asarray(b)
                for b in batch]

    def _marshal_args(self, batch, key=None):
        """The full argument tuple of one compiled-step invocation —
        exactly what ``self._compiled`` is called (or lowered) with."""
        states = self._opt_states()
        param_arrays = [p._value for p in self._params]
        buffer_arrays = [b._value for b in self._buffers]
        batch_arrays = self._prepare_batch(batch)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        if key is None:
            key = next_key()
        return (param_arrays, states, buffer_arrays, key, lr, batch_arrays)

    def lower(self, *batch, lowering_platforms=None):
        """AOT-lower the fused step program at these example batch
        shapes WITHOUT executing or compiling it — the entry point the
        static linter (:mod:`paddle_tpu.analysis`) and ahead-of-time
        inspection use. The lowered object carries the exact donation
        and sharding pins of the step's own compiled variant (it IS the
        same jit object), so what the linter sees is what runs. Uses a
        fixed PRNG key (key VALUES never affect lowering) so a lint/
        inspection pass does not advance the training RNG stream.

        ``lowering_platforms=("tpu",)`` cross-lowers from a host without
        the chip: the Pallas -> Mosaic lowering runs and refuses a block
        spec the TPU cannot take (tests/test_tpu_lowering.py)."""
        args = self._marshal_args(batch, key=jax.random.PRNGKey(0))
        target = self._compiled
        # unwrap the AOT service: AOTFunction.lower delegates, but going
        # straight to the jit object keeps this free of cache effects
        jitted = getattr(target, "_jitted", target)
        if lowering_platforms is None:
            return jitted.lower(*args)
        return jitted.trace(*args).lower(
            lowering_platforms=tuple(lowering_platforms))

    def __call__(self, *batch) -> Tensor:
        with _span("train.step", step=self.optimizer._step_count + 1):
            with _span("train.marshal"):
                args = self._checked_args(batch)
            # launched from this frame, not a helper's: the first call traces
            # the step, and tracing pays for every frame above it
            variant, args = self._variant(args)
            with _span("train.launch", program=variant):
                if variant == GUARDED_STEP_PROGRAM:
                    loss, new_params, new_states, new_buf, probe = \
                        self._get_guarded()(*args)
                elif variant == CHECKED_STEP_PROGRAM:
                    loss, new_params, new_states, new_buf, finite = \
                        self._compiled_checked(*args)
                    self._raise_on_non_finite(finite)
                    probe = None
                else:
                    loss, new_params, new_states, new_buf = \
                        self._compiled(*args)
                    probe = None
            with _span("train.rebind"):
                for p, arr, st in zip(self._params, new_params, new_states):
                    mw = st.pop("@master", None)
                    if mw is not None:
                        self.optimizer._master_weights[id(p)] = mw
                    p._value = arr
                    p._producer = None
                    self.optimizer._accumulators[id(p)] = st
                for b, arr in zip(self._buffers, new_buf):
                    b._value = arr
                    b._producer = None
                self.optimizer._step_count += 1
            with _span("train.guard"):
                self._after_step(probe)
            # supervisor goodput probe: first completed step of this process
            # (relaunch → here is time_to_first_step_s in restart events)
            _stamp_first_step()
            # fleet fault domain: per-step heartbeat stamp (straggler
            # detection)
            _note_fleet_step(self.optimizer._step_count)
            try:  # telemetry: step event for the flight recorder +
                # prometheus.  No host sync here — loss stays a device value.
                from .. import telemetry

                if telemetry.enabled():
                    telemetry.bump("train_step_calls_total")
                    telemetry.record_event(
                        "step", type(self).__name__,
                        step=self.optimizer._step_count)
            except Exception:
                pass
            return Tensor(loss)

    def _checked_args(self, batch):
        """``_marshal_args`` behind the checks every call makes first."""
        from ..incubate.asp import ASPHelper

        # ASP masks are baked into the compiled program as constants; a
        # prune_model/decorate AFTER construction would otherwise train
        # dense silently (advisor round 3) — detect and refuse
        for i, p in enumerate(self._params):
            if ASPHelper._masks.get(id(p)) is not self._asp_masks[i]:
                raise RuntimeError(
                    f"ASP mask for parameter {self._param_names[i]!r} "
                    "changed after this TrainStep was compiled; call "
                    "asp.prune_model BEFORE building the TrainStep (or "
                    "rebuild it)")
        args = self._marshal_args(batch)
        if self._merge_k > 1:
            for a in args[-1]:
                if a.ndim == 0 or a.shape[0] % self._merge_k:
                    raise ValueError(
                        f"gradient_merge k={self._merge_k} needs every batch "
                        f"arg's dim0 divisible by k, got shape {a.shape}")
        return args

    def _variant(self, args):
        """Which compiled variant this step runs, and its arguments."""
        from ..framework.flags import get_flags

        guard = self._health_guard
        mon = self._sdc_monitor
        if (guard is not None and guard.active) or \
                (mon is not None and mon.active):
            # guarded path wins over check_nan_inf: it subsumes the check
            # (detects the same non-finites) and recovers instead of raising
            if mon is not None and mon.active:
                # this step's number (post-increment) against the vote
                # cadence: off-cadence steps skip the fingerprint work
                # in-program (lax.cond on this dynamic flag — no retrace)
                nxt = self.optimizer._step_count + 1
                args = args + (nxt % max(1, mon.policy.every) == 0,)
            return GUARDED_STEP_PROGRAM, args
        if get_flags("check_nan_inf")["check_nan_inf"]:
            return CHECKED_STEP_PROGRAM, args
        return STEP_PROGRAM, args

    def _raise_on_non_finite(self, finite) -> None:
        flags = list(map(bool, finite))
        if not all(flags):
            bad = (["loss"] if not flags[0] else []) + [
                self._param_names[i] for i, ok in enumerate(flags[1:]) if not ok]
            raise RuntimeError(
                "check_nan_inf: non-finite values in compiled train step "
                f"(gradients of: {', '.join(bad)})")

    def _after_step(self, probe) -> None:
        """The host-side hooks behind a completed step: the guards resolve
        their probe, the snapshotter keeps its cadence."""
        guard = self._health_guard
        mon = self._sdc_monitor
        if probe is not None:
            # state is already rebound (skips selected in-program); the
            # guard resolves the probe max_lag steps late and may raise
            # SystemExit(101) here to hand control to the Supervisor
            if guard is not None and guard.active:
                guard.on_step(probe, step=self.optimizer._step_count)
            if mon is not None and mon.active:
                # same late-resolve discipline over the fingerprint lanes;
                # a sticky-confirmed suspect exits 101 here too (the
                # supervisor answers with an exclude-list relaunch)
                mon.on_step(probe, step=self.optimizer._step_count)
        # in-memory snapshot cadence: the capture device-gets the JUST
        # REBOUND state synchronously (the next step donates these arrays,
        # so a lazy capture would read invalidated buffers); serialization
        # + peer replication leave on the snapshotter's background thread
        if self._snapshotter is not None:
            try:
                if self._snapshotter.on_step(self.optimizer._step_count) \
                        and mon is not None:
                    # the SDC rewind anchor only advances to generations
                    # that actually exist — a suspect verdict rewinds to
                    # the newest snapshot at or before the last
                    # fingerprint-clean step
                    mon.note_checkpoint(self.optimizer._step_count)
            except Exception:
                pass  # degraded RPO must never kill the step


class InputSpec:
    """Shape/dtype signature of one model input (reference
    `python/paddle/static/input.py` InputSpec). ``None``/``-1`` dims are
    DYNAMIC: the exported program is shape-polymorphic in them (jax.export
    symbolic dimensions). A ``str`` dim names its symbol, and equal names
    share one symbol ACROSS specs (e.g. two inputs with a shared dynamic
    batch: ``InputSpec(["b", 128]), InputSpec(["b"])``); anonymous dynamic
    dims at position 0 also share one batch symbol, other anonymous dims
    vary independently."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = tuple(
            s if isinstance(s, str)
            else None if s is None or int(s) == -1 else int(s)
            for s in shape)
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype!r}, name={self.name!r})"


def _specs_to_sds(specs):
    """[InputSpec | Tensor | ShapeDtypeStruct] → ShapeDtypeStructs, with
    dynamic InputSpec dims lowered to jax.export symbolic dimensions (one
    shared scope). Named (str) dims and anonymous dim-0 dims share symbols
    across specs — the common multi-input case where every input carries the
    same dynamic batch; other anonymous dims vary independently."""
    from jax import export as jax_export
    from ..framework import dtype as _dtype_mod

    out = []
    scope = jax_export.SymbolicScope()
    counter = [0]
    named = {}

    def dyn(key=None):
        if key is not None and key in named:
            return named[key]
        counter[0] += 1
        # anonymous symbols live in a reserved "_…" namespace so they can
        # never alias a user-provided dim name in the shared scope
        name = key if isinstance(key, str) else (
            "_dbatch" if key == 0 else f"_d{counter[0]}")
        sym = jax_export.symbolic_shape(name, scope=scope)[0]
        if key is not None:
            named[key] = sym
        return sym

    for spec in specs:
        if isinstance(spec, InputSpec):
            shape = tuple(
                dyn(s) if isinstance(s, str)
                else dyn(0) if s is None and i == 0
                else dyn() if s is None else s
                for i, s in enumerate(spec.shape))
            out.append(jax.ShapeDtypeStruct(
                shape, _dtype_mod.canonical_dtype(spec.dtype)))
        elif isinstance(spec, Tensor):
            out.append(jax.ShapeDtypeStruct(tuple(spec.shape), spec._value.dtype))
        elif isinstance(spec, jax.ShapeDtypeStruct):
            out.append(spec)
        else:
            arr = jnp.asarray(spec)
            out.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
    return out


def save(layer, path: str, input_spec=None, **configs) -> None:
    """jit.save (reference `python/paddle/jit/api.py` save): persist

    - ``{path}.pdiparams`` — the state_dict (always), and
    - ``{path}.pdmodel`` — a serialized StableHLO program of the inference
      forward with parameters frozen in (requires ``input_spec``; the
      reference likewise needs specs or prior example inputs to concretize
      the graph). The artifact is loadable WITHOUT the python model class —
      `jit.load` runs it directly, the predictor-export contract.
    """
    from ..framework.io import save as _save

    target = layer._fn if isinstance(layer, StaticFunction) else layer
    base_layer = layer._layer if isinstance(layer, StaticFunction) else \
        (layer if isinstance(layer, Layer) else None)
    if base_layer is not None:
        _save(base_layer.state_dict(), path + ".pdiparams")
    elif not callable(target):
        _save(target, path + ".pdiparams")
        return

    if input_spec is None:
        if base_layer is None:
            raise ValueError(
                "jit.save of a plain function requires input_spec — there are "
                "no parameters to persist and no signature to trace a graph from")
        return  # params-only save; no graph without an input signature

    from jax import export as jax_export

    sds = _specs_to_sds(input_spec)
    fwd = base_layer.forward if base_layer is not None else target
    params, buffers = ([], [])
    if base_layer is not None:
        params = [p for _, p in base_layer.named_parameters()]
        buffers = [b for _, b in base_layer.named_buffers()]
    p_arrays = [p._value for p in params]
    b_arrays = [b._value for b in buffers]
    was_training = base_layer.training if base_layer is not None else False
    if base_layer is not None:
        base_layer.eval()
    try:
        def pure(*in_arrays):
            with _StateSwap(params, p_arrays), _StateSwap(buffers, b_arrays), \
                    key_scope(jax.random.PRNGKey(0)), no_grad():
                out = fwd(*[Tensor(a) for a in in_arrays])
            leaves, _ = jax.tree_util.tree_flatten(out, is_leaf=_is_tensor)
            return tuple(l._value if isinstance(l, Tensor) else l for l in leaves)

        exported = jax_export.export(jax.jit(pure))(*sds)
        with open(path + ".pdmodel", "wb") as f:
            f.write(exported.serialize())
    finally:
        if base_layer is not None and was_training:
            base_layer.train()


class TranslatedLayer(Layer):
    """A loaded ``.pdmodel`` StableHLO program, callable like the original
    layer (reference `translated_layer.py` TranslatedLayer). Parameters are
    frozen inside the program; ``state_dict`` exposes the sidecar params."""

    def __init__(self, exported, params: Optional[dict] = None):
        super().__init__()
        self._exported = exported
        self._params_dict = params or {}
        self.training = False

    def forward(self, *args):
        arrays = [a._value if isinstance(a, Tensor) else jnp.asarray(a) for a in args]
        out = self._exported.call(*arrays)
        outs = tuple(Tensor(o) for o in out)
        return outs[0] if len(outs) == 1 else outs

    def state_dict(self, *a, **k):
        return dict(self._params_dict)


def load(path: str, **configs):
    """jit.load: a ``.pdmodel`` becomes a runnable TranslatedLayer; with only
    ``.pdiparams`` present, returns the state_dict (params-only artifact)."""
    import os

    from ..framework.io import load as _load

    params = _load(path + ".pdiparams") if os.path.exists(path + ".pdiparams") else None
    if os.path.exists(path + ".pdmodel"):
        from jax import export as jax_export

        with open(path + ".pdmodel", "rb") as f:
            exported = jax_export.deserialize(f.read())
        return TranslatedLayer(exported, params)
    if params is None:
        raise FileNotFoundError(
            f"jit.load: neither {path}.pdmodel nor {path}.pdiparams exists")
    return params
