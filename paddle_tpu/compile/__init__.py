"""paddle_tpu.compile — the ahead-of-time compile service.

Compile time is recoverable wall-clock: a supervisor relaunch (exit 101)
or a cold bench run re-traces and re-compiles the fused train step that an
earlier process already paid XLA for. This subsystem amortizes it to disk:

- :mod:`.aot` — :class:`AOTFunction` wraps ``jax.jit(...)`` with the
  ``lower() → fingerprint → (deserialize | compile + serialize)``
  pipeline; :func:`fingerprint` keys programs by StableHLO text + mesh +
  device kind/count + jax/jaxlib versions + donation/sharding spec.
- :mod:`.cache` — :class:`ExecutableCache`, the corruption-safe on-disk
  store (payload + CRC32 sidecar committed last, checkpoint-storage retry
  seam, LRU keep-N): any corrupt/stale/unreadable entry degrades to a
  clean recompile, never a crash.
- :mod:`.metrics` — ``compile_begin``/``compile_end`` flight-recorder
  events (cold|warm, seconds, fingerprint), prometheus counters/gauges,
  and the ``cost_analysis()`` FLOP cross-check against StepMeter's
  analytic MFU model.

Wired through ``jit.TrainStep(persistent_cache=...)`` /
``DistributedTrainStep`` and ``fleet.elastic.Supervisor(compile_cache=...)``
so a relaunched child's first step deserializes its executable instead of
re-invoking XLA (checkpoint load + trace time, not compile time).

One resolver places every cache (:func:`cache_dir`):
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.compile_cache/`` in the
checkout.  :func:`enable_persistent_cache` points JAX's own persistent
compilation cache there, so the serving and ``generate()`` programs are
cached too; the AOT store sits in ``aot/`` beneath it.

Env: ``PADDLE_TPU_COMPILE_CACHE`` (explicit AOT root),
``PADDLE_TPU_COMPILE_CACHE_MAX`` (disk LRU entries, default 32),
``PADDLE_TPU_JIT_CACHE_MAX`` (in-process LRU entries, default 64).
"""

from .aot import AOTFunction, fingerprint, resolve_cache  # noqa: F401
from .cache import (ExecutableCache, PersistentCacheStats,  # noqa: F401
                    cache_dir, default_root, enable_persistent_cache)
from .metrics import compile_begin, compile_end, flops_of  # noqa: F401

__all__ = [
    "AOTFunction", "fingerprint", "resolve_cache",
    "ExecutableCache", "default_root", "cache_dir",
    "enable_persistent_cache", "PersistentCacheStats",
    "flops_of", "compile_begin", "compile_end",
]
