"""The few places where the benchmark touches the program beyond its entry
points: making a model's weights on the device, the persistent compile
cache, and the kernel-fallback counters."""

from __future__ import annotations

from typing import Callable, Dict

from .trace import SPAN_PREFIX



def seed_key(seed: int):
    """A PRNG key from any whole number (``--seed`` may pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def construct(factory: Callable, seed: int):
    """``factory()`` builds a ``paddle_tpu`` Layer by the program's own
    constructors and initializers (and casts it, e.g. ``amp.decorate``).
    Run eagerly that is one device call per leaf, in float32 first: 15 GB
    for the 16-layer Mistral cut, which does not fit the chip.  Run inside
    ONE jitted call under ``framework.key_scope`` — the program's own
    trace-safe RNG — the same code makes every leaf on the device from the
    seed, directly in the type it is served or trained in."""
    import jax

    from paddle_tpu.framework import key_scope

    box = {}

    def make(key):
        with key_scope(key):
            box["model"] = m = factory()
        # buffers too: a constant made inside the trace (the rope tables)
        # is a tracer until the call returns it
        return ([p.value for p in m.parameters()],
                [b.value for b in m.buffers()])

    params, buffers = jax.jit(make)(seed_key(seed))
    model = box["model"]
    for t, v in zip(list(model.parameters()) + list(model.buffers()),
                    params + buffers):
        t.set_value(v)
    return model


def static_forward(model, fn: Callable):
    """``fn(model, *tensors)`` as one compiled program whose weights are
    ARGUMENTS.  ``paddle.jit.to_static`` of a bare function closes over the
    model and bakes its weights into the executable as constants (a 3.8 GB
    program for a 0.7 B model, first chip run of PR 23); of a ``Layer`` it
    swaps the state in, so the function is wrapped in one."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    class Wrapped(nn.Layer):
        def __init__(self):
            super().__init__()
            self.inner = model

        def forward(self, *tensors):
            return fn(self.inner, *tensors)

    return paddle.jit.to_static(Wrapped())


def enable_compile_cache():
    """JAX's persistent cache where the program places it
    (``compile.cache_dir()``: ``JAX_COMPILATION_CACHE_DIR`` when set, else
    ``.compile_cache/`` inside the checkout)."""
    from paddle_tpu.compile import enable_persistent_cache

    return enable_persistent_cache()


def fallbacks() -> Dict[str, float]:
    """``kernel_fallback.*``: a Pallas gate that took the XLA path counts
    here when the program is traced."""
    import paddle_tpu.telemetry as telemetry

    return {k: v for k, v in telemetry.counters().items()
            if k.startswith("kernel_fallback.")}


def span(name: str, **facts):
    """A host span in the profiler's own trace, on the device's clock."""
    import jax

    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **facts)


class Tracer:
    """The profiler around the last ``length_s`` seconds of a window."""

    def __init__(self, directory: str, on: bool):
        self.dir, self.on = directory, on
        self.started_at = None
        self.stopped_at = None
        self._window = None

    def start(self, now: float) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # our spans, not every frame
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = span("window")
        self._window.__enter__()
        self.started_at = now

    def stop(self, now: float) -> None:
        import jax

        self._window.__exit__(None, None, None)
        self.stopped_at = now
        jax.profiler.stop_trace()

    @property
    def running(self) -> bool:
        return self.started_at is not None and self.stopped_at is None


def use_kernels(rehearsal: bool) -> None:
    """On the chip the kernels must be Mosaic calls; the tests' rehearsal on
    the CPU interprets them."""
    import paddle_tpu as paddle

    paddle.set_flags({"pallas_interpret": bool(rehearsal)})
