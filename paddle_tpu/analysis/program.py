"""Program artifact collection: normalize anything the ``compile/``
subsystem can lower into the bundle of evidence the lint rules read.

One :class:`ProgramArtifacts` holds, best-effort (every field degrades to
None/empty rather than raising — a rule that needs a missing artifact
simply reports nothing):

- ``stablehlo_text`` — the lowered (pre-optimization) module text;
- ``hlo_text``       — the OPTIMIZED post-SPMD HLO (``compiled.as_text``),
  where the partitioner's inserted collectives and the
  ``input_output_alias`` donation header are visible;
- ``diagnostics``    — the XLA compile-time stderr captured around
  ``.compile()`` (:func:`capture_compile_diagnostics`): the
  ``spmd_partitioner`` "Involuntary full rematerialization" warnings are
  C++ glog lines on fd 2 that no python logging hook sees;
- ``memory``         — ``compiled.memory_analysis()`` argument/output/
  alias/temp byte sizes (per-device HBM accounting);
- ``jaxpr_prims``    — a recursive walk of the jaxpr collecting
  ``(primitive_name, params)`` pairs (host-callback and ppermute rules);
- ``source_fns``     — python callables whose SOURCE the host-sync rule
  AST-walks (the user's loss/step functions — a ``float()`` on a traced
  value is visible in source before it ever becomes a trace error).

Target normalization (:func:`collect`) accepts a
:class:`~paddle_tpu.jit.TrainStep` /
:class:`~paddle_tpu.distributed.engine.DistributedTrainStep` (example
batch in ``args``), a :class:`~paddle_tpu.compile.AOTFunction`, a
``jax.jit`` wrapper or plain callable (example args), an already-lowered
or already-compiled object, or a pre-built :class:`ProgramArtifacts`.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import sys
import tempfile
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["ProgramArtifacts", "collect", "capture_compile_diagnostics",
           "jaxpr_primitives", "DTYPE_BYTES", "shape_bytes"]

# ONE HLO dtype→itemsize table for every rule that parses shapes out of
# module text (remat pricing, replication sizing) — a rule-local copy
# that misses fp8/s16 silently under-prices exactly the tensors it
# exists to flag
DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8,
               "f32": 4, "s32": 4, "u32": 4,
               "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
               "pred": 1, "s8": 1, "u8": 1,
               "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
               "f8e4m3fnuz": 1, "f8e5m2fnuz": 1}


def shape_bytes(dtype: str, dims: str) -> int:
    """Byte size of one HLO shape ``dtype[dims]``; unknown dtypes assume
    4 bytes (over-reporting beats a silent false negative in an
    error-severity rule)."""
    size = DTYPE_BYTES.get(dtype, 4)
    for d in dims.split(","):
        if d.strip():
            size *= int(d)
    return size


_capture_lock = threading.Lock()


class _Diagnostics:
    """Mutable holder filled when the capture context exits."""

    def __init__(self) -> None:
        self.text: str = ""


@contextlib.contextmanager
def capture_compile_diagnostics():
    """Capture fd-level stderr for the duration of the block — the only
    way to see XLA's C++ compile diagnostics (glog writes to fd 2
    directly, bypassing ``sys.stderr`` and python logging).  Yields a
    holder whose ``.text`` is populated on exit.  Serialized under a
    process-wide lock (fd 2 is global state); ``PADDLE_TPU_LINT_CAPTURE=0``
    turns it into a no-op for environments where fd games are unsafe."""
    diag = _Diagnostics()
    if os.environ.get("PADDLE_TPU_LINT_CAPTURE", "1") in ("0", "false"):
        yield diag
        return
    with _capture_lock:
        cap = tempfile.TemporaryFile(mode="w+", errors="replace")
        try:
            sys.stderr.flush()
        except Exception:
            pass
        saved = os.dup(2)
        os.dup2(cap.fileno(), 2)
        try:
            yield diag
        finally:
            try:
                sys.stderr.flush()
            except Exception:
                pass
            os.dup2(saved, 2)
            os.close(saved)
            try:
                cap.seek(0)
                diag.text = cap.read()
            finally:
                cap.close()
            # re-emit non-lint noise? No: compile diagnostics belong to the
            # report now; the raw text is kept verbatim on the artifacts.


@dataclasses.dataclass
class ProgramArtifacts:
    """Everything a lint rule may read about one compiled program."""

    name: str = "program"
    stablehlo_text: Optional[str] = None
    hlo_text: Optional[str] = None
    diagnostics: str = ""
    memory: Optional[Dict[str, int]] = None
    jaxpr_prims: List[Tuple[str, dict]] = dataclasses.field(
        default_factory=list)
    source_fns: List[Callable] = dataclasses.field(default_factory=list)
    n_devices: int = 1
    mesh_shape: Optional[Dict[str, int]] = None
    donate_expected: Optional[bool] = None
    input_shardings: Optional[Sequence[Any]] = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)


def jaxpr_primitives(jaxpr) -> List[Tuple[str, dict]]:
    """Recursive (primitive name, eqn params) walk over a (Closed)Jaxpr,
    descending into every sub-jaxpr an eqn carries (scan bodies, cond
    branches, pjit/shard_map calls, custom_vjp closures)."""
    out: List[Tuple[str, dict]] = []
    seen: set = set()

    def walk(j) -> None:
        j = getattr(j, "jaxpr", j)  # ClosedJaxpr → Jaxpr
        if j is None or id(j) in seen:
            return
        seen.add(id(j))
        for eqn in getattr(j, "eqns", ()):
            out.append((eqn.primitive.name, dict(eqn.params)))
            for v in eqn.params.values():
                for sub in _subjaxprs(v):
                    walk(sub)

    walk(jaxpr)
    return out


def _subjaxprs(v):
    from jax.extend.core import ClosedJaxpr, Jaxpr  # local: keep import cheap

    if isinstance(v, (Jaxpr, ClosedJaxpr)):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _subjaxprs(x)
    elif callable(v):
        # custom_jvp/vjp store callables wrapping jaxprs; don't descend
        return


def _memory_dict(compiled) -> Optional[Dict[str, int]]:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
        }
    except Exception:
        return None


def _n_devices() -> int:
    try:
        import jax

        return len(jax.devices())
    except Exception:
        return 1


def _is_train_step(target) -> bool:
    return hasattr(target, "_compiled") and hasattr(target, "loss_fn") \
        and hasattr(target, "lower")


def _is_aot_function(target) -> bool:
    return hasattr(target, "_jitted") and hasattr(target, "lower") \
        and not hasattr(target, "loss_fn")


def collect(target, args: Sequence[Any] = (), name: Optional[str] = None,
            compile: bool = True, jaxpr: Optional[bool] = None,
            extra_source_fns: Sequence[Callable] = ()) -> ProgramArtifacts:
    """Normalize ``target`` (+ example ``args``) into
    :class:`ProgramArtifacts`.  ``compile=False`` stops at the lowered
    module (no optimized HLO / diagnostics / memory — rules that read
    those stay silent).  ``jaxpr`` defaults to True for plain callables
    and False for TrainStep-sized programs (a second full trace)."""
    import jax

    art = ProgramArtifacts(name=name or _default_name(target),
                           n_devices=_n_devices())
    art.source_fns = list(extra_source_fns)
    lowered = compiled = None
    jaxpr_fn_args: Optional[Tuple[Callable, tuple]] = None

    if isinstance(target, ProgramArtifacts):
        return target
    if _is_train_step(target):
        art.donate_expected = bool(getattr(target, "_donate", True))
        mesh = getattr(target, "mesh", None)
        if mesh is not None:
            art.mesh_shape = {k: int(v) for k, v in mesh.shape.items()}
        if getattr(target, "loss_fn", None) is not None:
            art.source_fns.append(target.loss_fn)
        lowered = target.lower(*args)
        if jaxpr is None:
            jaxpr = False
    elif _is_aot_function(target):
        lowered = target.lower(*args)
        if jaxpr is None:
            jaxpr = False
    elif hasattr(target, "lower") and callable(getattr(target, "lower")):
        # a jax.jit wrapper
        lowered = target.lower(*args)
        fn = getattr(target, "__wrapped__", None)
        if fn is not None:
            art.source_fns.append(fn)
            jaxpr_fn_args = (fn, tuple(args))
    elif hasattr(target, "compile") and hasattr(target, "as_text"):
        lowered = target  # already lowered
    elif hasattr(target, "as_text") and hasattr(target, "memory_analysis"):
        compiled = target  # already compiled
    elif callable(target):
        art.source_fns.append(target)
        jaxpr_fn_args = (target, tuple(args))
        lowered = jax.jit(target).lower(*args)
    else:
        raise TypeError(
            f"cannot lint {type(target).__name__}: expected a TrainStep, "
            "AOTFunction, jitted/plain callable, lowered or compiled "
            "object, or ProgramArtifacts")

    if lowered is not None:
        try:
            art.stablehlo_text = lowered.as_text()
        except Exception:
            art.stablehlo_text = None
        if compile:
            with capture_compile_diagnostics() as diag:
                compiled = lowered.compile()
            art.diagnostics = diag.text
    if compiled is not None:
        try:
            art.hlo_text = compiled.as_text()
        except Exception:
            art.hlo_text = None
        art.memory = _memory_dict(compiled)
        try:
            art.input_shardings = compiled.input_shardings
        except Exception:
            art.input_shardings = None

    if (jaxpr is None or jaxpr) and jaxpr_fn_args is not None:
        fn, fa = jaxpr_fn_args
        # the same fn/args just lowered, so a failure here is a broken
        # walker, not an untraceable program: let it raise
        art.jaxpr_prims = jaxpr_primitives(jax.make_jaxpr(fn)(*fa))
    return art


def _default_name(target) -> str:
    for attr in ("__name__", "_name"):
        n = getattr(target, attr, None)
        if isinstance(n, str):
            return n
    return type(target).__name__
